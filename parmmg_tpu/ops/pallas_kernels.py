"""Fused Pallas TPU kernels for the per-entity hot math.

The adaptation waves evaluate metric edge lengths and tet qualities for
every entity every cycle (the vectorized analogue of Mmg's ``MMG5_lenedg``
/ ``MMG5_caltet`` calls inside ``MMG5_mmg3d1_delone``, which the reference
invokes per group at /root/reference/src/libparmmg1.c:737-739).  In pure
XLA each formula materializes a chain of [capE]/[capT] intermediates in
HBM; these kernels fuse the whole formula into one VMEM pass per block —
one HBM read per operand, one write per result, all math on the VPU.

Layout: 1-D entity arrays are padded and viewed as [R, 128] (lane dim =
128), blocked (8, 128) per grid step — the float32 min tile.  Gathers
(vertex coords by index) stay outside in XLA, which already batches them;
the kernels are pure elementwise fusion, so they are exact drop-ins.

On non-TPU backends the same kernels run with ``interpret=True`` in tests
(parity is asserted against the jnp reference in tests/test_pallas.py);
production dispatch (ops/quality.py, ops/edges.py) uses them only on TPU.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..core.constants import ALPHA_TET, EPSD

try:  # pallas is part of jax, but guard exotic builds
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    HAVE_PALLAS = True
except Exception:  # pragma: no cover
    HAVE_PALLAS = False

_LANE = 128
_SUB = 8


def use_pallas() -> bool:
    """Are the Pallas kernels ALLOWED (pallas importable, not disabled)?

    The actual TPU-vs-other choice is made at LOWERING time by
    ``jax.lax.platform_dependent`` at the call sites — deciding from
    ``jax.default_backend()`` here was wrong whenever a TPU plugin is
    registered as the process default while a computation lowers for CPU
    devices (e.g. the multichip dry run on the virtual CPU mesh), which
    crashed with 'Only interpret mode is supported on CPU backend'.
    """
    env = os.environ.get("PARMMG_TPU_PALLAS", "")
    if env == "0":
        return False
    return HAVE_PALLAS


def pallas_forced() -> bool:
    """PARMMG_TPU_PALLAS=1: call the Pallas kernels UNCONDITIONALLY
    (interpret mode off-TPU) — lets CPU verification runs exercise the
    production kernel numerics instead of the jnp formulas."""
    return HAVE_PALLAS and os.environ.get("PARMMG_TPU_PALLAS", "") == "1"


def pallas_score_enabled() -> bool:
    """PARMMG_PALLAS_SCORE gate for the candidate-scoring kernels
    (score_count_pallas / score3_count_pallas): default on — the
    production dispatch in ops/edges.topk_prep is TPU-only either way,
    so CPU runs are unaffected; =0 falls back to the jnp reference on
    every backend."""
    return os.environ.get("PARMMG_PALLAS_SCORE", "") != "0"


def _pad_rows(n: int) -> int:
    """Rows of a [R,128] view holding n elements, R a multiple of 8."""
    r = -(-n // _LANE)
    return -(-r // _SUB) * _SUB


def _to_blocks(a: jax.Array, rows: int) -> jax.Array:
    """[n] -> [rows,128] zero-padded float32 view."""
    n = a.shape[0]
    flat = jnp.zeros(rows * _LANE, jnp.float32).at[:n].set(
        a.astype(jnp.float32))
    return flat.reshape(rows, _LANE)


def _from_blocks(b: jax.Array, n: int, dtype) -> jax.Array:
    return b.reshape(-1)[:n].astype(dtype)


# ---------------------------------------------------------------------------
# Edge length (iso): exact log-mean integral of 1/h along the edge
# (numerics identical to ops/quality.py:edge_length_iso)
# ---------------------------------------------------------------------------
def _len_iso_kernel(x0, y0, z0, x1, y1, z1, h0, h1, out):
    dx = x1[:] - x0[:]
    dy = y1[:] - y0[:]
    dz = z1[:] - z0[:]
    d = jnp.sqrt(jnp.maximum(dx * dx + dy * dy + dz * dz, 0.0))
    ha = jnp.maximum(h0[:], EPSD)
    hb = jnp.maximum(h1[:], EPSD)
    r0 = 1.0 / ha
    r1 = 1.0 / hb
    close = jnp.abs(r0 - r1) < 1e-6 * jnp.maximum(r0, r1)
    ratio = jnp.where(close, 1.0, ha / hb)
    logr = jnp.log(jnp.maximum(ratio, EPSD))
    lm = jnp.where(close, 0.5 * (r0 + r1),
                   (r1 - r0) / jnp.where(close, 1.0, logr))
    out[:] = d * lm


def _auto_interpret(interpret: bool | None) -> bool:
    """interpret=None -> run compiled on TPU, interpreted elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def edge_length_iso_pallas(p0: jax.Array, p1: jax.Array,
                           h0: jax.Array, h1: jax.Array,
                           interpret: bool | None = None) -> jax.Array:
    """Fused iso edge length. p0,p1: [N,3]; h0,h1: [N] -> [N]."""
    n = p0.shape[0]
    rows = _pad_rows(n)
    args = [_to_blocks(p0[:, 0], rows), _to_blocks(p0[:, 1], rows),
            _to_blocks(p0[:, 2], rows), _to_blocks(p1[:, 0], rows),
            _to_blocks(p1[:, 1], rows), _to_blocks(p1[:, 2], rows),
            _to_blocks(h0, rows), _to_blocks(h1, rows)]
    spec = pl.BlockSpec((_SUB, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        _len_iso_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
        grid=(rows // _SUB,),
        in_specs=[spec] * 8,
        out_specs=spec,
        name="edge_length_iso",
        interpret=_auto_interpret(interpret),
    )(*args)
    return _from_blocks(out, n, p0.dtype)


# ---------------------------------------------------------------------------
# Edge length (aniso): endpoint quadratic forms + simpson-like average
# (numerics identical to ops/quality.py:edge_length_ani)
# ---------------------------------------------------------------------------
def _len_ani_kernel(ex, ey, ez, a11, a12, a13, a22, a23, a33,
                    b11, b12, b13, b22, b23, b33, out):
    x, y, z = ex[:], ey[:], ez[:]

    def quad(m11, m12, m13, m22, m23, m33):
        return (m11[:] * x * x + m22[:] * y * y + m33[:] * z * z
                + 2.0 * (m12[:] * x * y + m13[:] * x * z + m23[:] * y * z))

    q0 = quad(a11, a12, a13, a22, a23, a33)
    q1 = quad(b11, b12, b13, b22, b23, b33)
    l0 = jnp.sqrt(jnp.maximum(q0, 0.0))
    l1 = jnp.sqrt(jnp.maximum(q1, 0.0))
    s = jnp.maximum(l0 + l1, EPSD)
    out[:] = (2.0 / 3.0) * (l0 * l0 + l0 * l1 + l1 * l1) / s


def edge_length_ani_pallas(p0: jax.Array, p1: jax.Array,
                           m0: jax.Array, m1: jax.Array,
                           interpret: bool | None = None) -> jax.Array:
    """Fused aniso edge length. p0,p1: [N,3]; m0,m1: [N,6] -> [N]."""
    n = p0.shape[0]
    rows = _pad_rows(n)
    e = p1 - p0
    args = [_to_blocks(e[:, k], rows) for k in range(3)]
    args += [_to_blocks(m0[:, k], rows) for k in range(6)]
    args += [_to_blocks(m1[:, k], rows) for k in range(6)]
    spec = pl.BlockSpec((_SUB, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        _len_ani_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
        grid=(rows // _SUB,),
        in_specs=[spec] * 15,
        out_specs=spec,
        name="edge_length_ani",
        interpret=_auto_interpret(interpret),
    )(*args)
    return _from_blocks(out, n, p0.dtype)


# ---------------------------------------------------------------------------
# Candidate scoring + top-k budget prep: the wave selection preamble
# (numerics identical to the jnp reference in ops/edges.py:topk_prep).
# First non-elementwise kernels in this file: the candidate COUNT (the
# defer/budget scalar every wave computes before lax.top_k) is reduced
# across the sequential TPU grid into a (1,1) int32 SMEM output — one
# pass produces both the masked-negated score vector and the reduction.
# ---------------------------------------------------------------------------
def _score_kernel(m, v, out, cnt):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt[0, 0] = 0

    sel = m[:] > 0.0
    out[:] = jnp.where(sel, -v[:], -jnp.inf)
    cnt[0, 0] += jnp.sum(sel.astype(jnp.int32))


def _score_min3_kernel(m, v0, v1, v2, out, cnt):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt[0, 0] = 0

    sel = m[:] > 0.0
    v = jnp.minimum(v0[:], jnp.minimum(v1[:], v2[:]))
    out[:] = jnp.where(sel, -v, -jnp.inf)
    cnt[0, 0] += jnp.sum(sel.astype(jnp.int32))


def _score_call(kernel, name, args, rows, interpret):
    """One pass over [rows,128] operands -> (score block, count).  The
    count is a (1,1) int32 output kept whole in SMEM (Mosaic stores no
    scalar to VMEM): the TPU grid is sequential, so += across steps is
    a legal reduction."""
    spec = pl.BlockSpec((_SUB, _LANE), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        grid=(rows // _SUB,),
        in_specs=[spec] * len(args),
        out_specs=(spec, pl.BlockSpec(memory_space=pltpu.SMEM)),
        name=name,
        interpret=_auto_interpret(interpret),
    )(*args)


def score_count_pallas(mask: jax.Array, val: jax.Array,
                       interpret: bool | None = None):
    """Fused top-k prep: (where(mask, -val, -inf) [N], sum(mask) int32)."""
    n = mask.shape[0]
    rows = _pad_rows(n)
    out, cnt = _score_call(
        _score_kernel, "score_count",
        [_to_blocks(mask, rows), _to_blocks(val, rows)], rows, interpret)
    return _from_blocks(out, n, val.dtype), cnt[0, 0]


def score3_count_pallas(mask: jax.Array, v0: jax.Array, v1: jax.Array,
                        v2: jax.Array, interpret: bool | None = None):
    """Fused shell-score top-k prep: min3 + mask + negate + count.

    (where(mask, -min(v0,min(v1,v2)), -inf) [N], sum(mask) int32) — the
    exact minimum chain order of the swap_edges_wave reference, so f32
    results are bit-identical."""
    n = mask.shape[0]
    rows = _pad_rows(n)
    out, cnt = _score_call(
        _score_min3_kernel, "score3_count",
        [_to_blocks(mask, rows), _to_blocks(v0, rows),
         _to_blocks(v1, rows), _to_blocks(v2, rows)], rows, interpret)
    return _from_blocks(out, n, v0.dtype), cnt[0, 0]


# ---------------------------------------------------------------------------
# Tet quality: volume + 6 edge lengths + normalization in one pass
# (numerics identical to ops/quality.py:quality_from_points)
# ---------------------------------------------------------------------------
def _qual_kernel(x0, y0, z0, x1, y1, z1, x2, y2, z2, x3, y3, z3,
                 m11, m12, m13, m22, m23, m33, out, *, aniso: bool):
    d1x = x1[:] - x0[:]
    d1y = y1[:] - y0[:]
    d1z = z1[:] - z0[:]
    d2x = x2[:] - x0[:]
    d2y = y2[:] - y0[:]
    d2z = z2[:] - z0[:]
    d3x = x3[:] - x0[:]
    d3y = y3[:] - y0[:]
    d3z = z3[:] - z0[:]
    cx = d2y * d3z - d2z * d3y
    cy = d2z * d3x - d2x * d3z
    cz = d2x * d3y - d2y * d3x
    vol = (d1x * cx + d1y * cy + d1z * cz) / 6.0

    xs = (x0[:], x1[:], x2[:], x3[:])
    ys = (y0[:], y1[:], y2[:], y3[:])
    zs = (z0[:], z1[:], z2[:], z3[:])
    if aniso:
        M11, M12, M13 = m11[:], m12[:], m13[:]
        M22, M23, M33 = m22[:], m23[:], m33[:]
    rap = jnp.zeros_like(vol)
    # IARE order: (0,1)(0,2)(0,3)(1,2)(1,3)(2,3)
    for (i, j) in ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)):
        ex = xs[j] - xs[i]
        ey = ys[j] - ys[i]
        ez = zs[j] - zs[i]
        if aniso:
            rap = rap + (M11 * ex * ex + M22 * ey * ey + M33 * ez * ez
                         + 2.0 * (M12 * ex * ey + M13 * ex * ez
                                  + M23 * ey * ez))
        else:
            rap = rap + ex * ex + ey * ey + ez * ez
    if aniso:
        det = (M11 * (M22 * M33 - M23 * M23)
               - M12 * (M12 * M33 - M23 * M13)
               + M13 * (M12 * M23 - M22 * M13))
        num = ALPHA_TET * vol * jnp.sqrt(jnp.maximum(det, 0.0))
    else:
        num = ALPHA_TET * vol
    q = num / jnp.maximum(rap, EPSD) ** 1.5
    out[:] = jnp.where(vol > 0, jnp.minimum(q, 1.0), jnp.minimum(q, 0.0))


# ---------------------------------------------------------------------------
# Inclusive int32 prefix sum: the scan backbone of the incremental
# topology merge (ops/topo_incr.merge_sorted_band) — survivor ranks and
# band insertion shifts are both prefix sums over [6*capT]/[4*capT] flag
# vectors.  Within a block, a log-step scan along lanes then across
# sublanes; the running block total is carried across the sequential
# grid in SMEM.
# Integer adds are associative, so this is bit-identical to jnp.cumsum.
# ---------------------------------------------------------------------------
def _scan_steps(x, axis):
    """Inclusive Hillis-Steele scan of an (8,128) int32 block along
    ``axis`` by log-step rotate-and-add (Mosaic lowers no cumsum)."""
    io = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < x.shape[axis]:
        x = x + jnp.where(io >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _prefix_kernel(x_ref, o_ref, carry):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry[0] = 0

    x = x_ref[:]
    c1 = _scan_steps(x, 1)                          # within-row inclusive
    rt = jnp.broadcast_to(c1[:, _LANE - 1:_LANE], x.shape)  # row totals
    roff = _scan_steps(rt, 0) - rt                  # exclusive row offsets
    o_ref[:] = c1 + roff + carry[0]
    carry[0] = carry[0] + jnp.sum(x)


def _to_blocks_i32(a: jax.Array, rows: int) -> jax.Array:
    """[n] -> [rows,128] zero-padded int32 view."""
    n = a.shape[0]
    flat = jnp.zeros(rows * _LANE, jnp.int32).at[:n].set(
        a.astype(jnp.int32))
    return flat.reshape(rows, _LANE)


def merge_prefix_pallas(x: jax.Array,
                        interpret: bool | None = None) -> jax.Array:
    """Inclusive prefix sum of an int32 vector: [n] -> [n].

    Zero padding at the tail only feeds positions >= n, which are
    discarded, so the result equals ``jnp.cumsum(x)`` exactly."""
    n = x.shape[0]
    rows = _pad_rows(n)
    spec = pl.BlockSpec((_SUB, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        _prefix_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.int32),
        grid=(rows // _SUB,),
        in_specs=[spec],
        out_specs=spec,
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        name="merge_prefix",
        interpret=_auto_interpret(interpret),
    )(_to_blocks_i32(x, rows))
    return out.reshape(-1)[:n]


def quality_pallas(p: jax.Array, m6bar: jax.Array | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """Fused tet quality. p: [N,4,3]; m6bar: optional [N,6] mean metric."""
    n = p.shape[0]
    rows = _pad_rows(n)
    args = []
    for c in range(4):
        for k in range(3):
            args.append(_to_blocks(p[:, c, k], rows))
    aniso = m6bar is not None
    if aniso:
        for k in range(6):
            args.append(_to_blocks(m6bar[:, k], rows))
    else:
        zero = jnp.zeros((rows, _LANE), jnp.float32)
        args += [zero] * 6
    spec = pl.BlockSpec((_SUB, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_qual_kernel, aniso=aniso),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
        grid=(rows // _SUB,),
        in_specs=[spec] * 18,
        out_specs=spec,
        name="quality_ani" if aniso else "quality_iso",
        interpret=_auto_interpret(interpret),
    )(*args)
    return _from_blocks(out, n, p.dtype)
