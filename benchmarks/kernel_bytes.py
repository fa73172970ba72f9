"""Bytes each Pallas kernel of ``parmmg_tpu/ops/pallas_kernels.py`` must
move between HBM and the core, from its shapes.

Every kernel works on ``[rows, 128]`` float32 or int32 views of vectors
of ``n`` elements (``rows`` = n / 128 rounded up to a multiple of 8) and
reads each operand and writes each result exactly once, so its least
traffic is (operands + results) x rows x 128 x 4 bytes.  The packing of
the operands into those views (gathers, transposes, zero padding) is
XLA's work around the kernel and is not counted: this is the kernel's
own roofline, and none of them does enough arithmetic per byte to be
compute-bound on a chip with 197 TFLOP/s over 819 GB/s.

``n`` is read from the trace (the kernel's first operand); the call
sites hand it the edge table (n = 6 capT), the face pairs or the tets of
one group.
"""
from __future__ import annotations

_LANE, _SUB, _WORD = 128, 8, 4

# kernel name -> (operands read, results written), each [rows, 128] x 4 B
_ARRAYS = {
    # x0 y0 z0 x1 y1 z1 h0 h1 -> length
    "edge_length_iso": (8, 1),
    # edge vector (3) + two packed tensors (6 + 6) -> length
    "edge_length_ani": (15, 1),
    # 4 corners x 3 coordinates + 6 tensor rows (zeros when iso: one
    # shared block, still read once per grid step) -> quality
    "quality_iso": (18, 1),
    "quality_ani": (18, 1),
    # mask, value -> score (+ a 4-byte count in SMEM)
    "score_count": (2, 1),
    # mask, three values -> score (+ count)
    "score3_count": (4, 1),
    # flags -> inclusive prefix sums
    "merge_prefix": (1, 1),
}


def padded_rows(n: int) -> int:
    rows = -(-n // _LANE)
    return -(-rows // _SUB) * _SUB


def kernel_bytes(name: str, n: int) -> int:
    """Least HBM bytes of one call of kernel ``name`` on ``n`` elements."""
    if name not in _ARRAYS:
        raise KeyError(f"no bytes function for Pallas kernel {name!r}: add "
                       "it to benchmarks/kernel_bytes.py")
    reads, writes = _ARRAYS[name]
    return (reads + writes) * padded_rows(n) * _LANE * _WORD
