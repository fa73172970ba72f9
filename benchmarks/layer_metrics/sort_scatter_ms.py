"""tables: device time per block in sort, scatter and gather ops."""
from readers import per_block_ms


def read(run):
    return per_block_ms(run, "sort_scatter_s")
