"""kernels: device time per block in the Pallas ``tpu_custom_call``s."""
from readers import per_block_ms


def read(run):
    return per_block_ms(run, "pallas_s")
