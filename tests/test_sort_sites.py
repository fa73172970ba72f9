"""The sort/segment/scan legs of the edge, face and band tables against
independent numpy oracles, and interpret-mode parity of the two Pallas
kernels whose bodies were rewritten for Mosaic (SMEM count output,
log-step scan).  Everything here asserts BIT equality.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from parmmg_tpu.ops import pallas_kernels as pk

I32_MAX = 2147483647
# deliberately awkward lengths: 1, sub-lane, lane-1/lane/lane+1, odd,
# crossing the (8,128) block boundary, multi-block prime
SIZES = (1, 2, 127, 128, 129, 777, 1025, 4099)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


@pytest.mark.parametrize("n", SIZES)
def test_merge_prefix_vs_cumsum(rng, n):
    x = rng.integers(-5, 50, n).astype(np.int32)
    got = np.asarray(pk.merge_prefix_pallas(jnp.asarray(x), interpret=True))
    assert np.array_equal(np.cumsum(x, dtype=np.int32), got)


def test_merge_prefix_wraps_like_int32(rng):
    # int32 adds wrap identically in the scan and in cumsum
    x = np.full(3000, 2 ** 30, np.int32)
    got = np.asarray(pk.merge_prefix_pallas(jnp.asarray(x), interpret=True))
    assert np.array_equal(np.cumsum(x, dtype=np.int32), got)


@pytest.mark.parametrize("n", SIZES)
def test_score_count_vs_numpy(rng, n):
    mask = rng.random(n) < 0.6
    val = rng.standard_normal(n).astype(np.float32)
    out, cnt = pk.score_count_pallas(jnp.asarray(mask), jnp.asarray(val),
                                     interpret=True)
    assert np.array_equal(np.where(mask, -val, -np.inf), np.asarray(out))
    assert int(cnt) == int(mask.sum())


@pytest.mark.parametrize("n", SIZES)
def test_score3_count_vs_numpy(rng, n):
    mask = rng.random(n) < 0.6
    v = rng.standard_normal((3, n)).astype(np.float32)
    out, cnt = pk.score3_count_pallas(
        jnp.asarray(mask), *(jnp.asarray(c) for c in v), interpret=True)
    ref = np.where(mask, -np.minimum(v[0], np.minimum(v[1], v[2])), -np.inf)
    assert np.array_equal(ref, np.asarray(out))
    assert int(cnt) == int(mask.sum())


@pytest.mark.parametrize("n", SIZES)
def test_segment_first_single_word(rng, n):
    from parmmg_tpu.ops.edges import segment_first
    k = np.sort(rng.integers(0, max(2, n // 4), n).astype(np.int32))
    ref = np.concatenate([[True], k[1:] != k[:-1]])
    assert np.array_equal(ref, np.asarray(segment_first((jnp.asarray(k),))))


def test_segment_first_multi_word(rng):
    from parmmg_tpu.ops.edges import segment_first
    n = 2051
    a = rng.integers(0, 6, n).astype(np.int32)
    b = rng.integers(0, 6, n).astype(np.int32)
    o = np.lexsort((b, a))
    aa, bb = a[o], b[o]
    ref = np.concatenate(
        [[True], (aa[1:] != aa[:-1]) | (bb[1:] != bb[:-1])])
    got = segment_first((jnp.asarray(aa), jnp.asarray(bb)))
    assert np.array_equal(ref, np.asarray(got))


@pytest.mark.parametrize("packed", [True, False])
def test_sort_pairs_vs_numpy(rng, packed):
    from parmmg_tpu.ops.edges import PACK_LIMIT, sort_pairs
    n = 700
    a = rng.integers(0, 40, n).astype(np.int32)
    b = rng.integers(0, 40, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    order, ka, kb, first, _ = sort_pairs(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(valid),
        40 if packed else PACK_LIMIT + 1)
    aa = np.where(valid, a, I32_MAX)
    bb = np.where(valid, b, I32_MAX)
    ref = np.lexsort((bb, aa))                  # stable, invalid last
    assert np.array_equal(ref, np.asarray(order))
    assert np.array_equal(aa[ref], np.asarray(ka))
    assert np.array_equal(bb[ref], np.asarray(kb))
    head = np.concatenate([[True], (aa[ref][1:] != aa[ref][:-1])
                           | (bb[ref][1:] != bb[ref][:-1])])
    assert np.array_equal(head, np.asarray(first))


def test_unique_priority_ties_break_by_lane(rng):
    from parmmg_tpu.ops.edges import unique_priority
    n = 600
    score = (np.round(rng.random(n) * 8) / 8).astype(np.float32)
    mask = rng.random(n) < 0.7
    pri = np.asarray(unique_priority(jnp.asarray(score), jnp.asarray(mask)))
    assert np.all(pri[~mask] == 0)
    live = pri[mask]
    assert len(np.unique(live)) == len(live) and live.min() >= 1
    # higher score -> higher priority; equal scores -> lower lane wins
    idx = np.flatnonzero(mask)
    by_pri = idx[np.argsort(-live, kind="stable")]
    ref = idx[np.lexsort((idx, -score[idx]))]
    assert np.array_equal(ref, by_pri)


def test_band_order_vs_numpy(rng):
    from parmmg_tpu.ops.topo_incr import band_order
    m = 300
    bk = rng.integers(0, 50, m).astype(np.int32)
    bk = np.where(rng.random(m) < 0.3, I32_MAX, bk).astype(np.int32)
    bs = rng.permutation(m).astype(np.int32)
    got = np.asarray(band_order((jnp.asarray(bk),), jnp.asarray(bs)))
    assert np.array_equal(np.lexsort((bs, bk)), got)


def test_face_sort_pairs_every_interior_face():
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops import adjacency as adj
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    adja = np.asarray(adj.build_adjacency(m).adja)[:len(tet)]
    # oracle: faces keyed by their sorted vertex triple
    seen = {}
    for t, tv in enumerate(np.asarray(tet)):
        for f in range(4):
            key = tuple(sorted(np.delete(tv, f)))
            seen.setdefault(key, []).append((t, f))
    for pairs in seen.values():
        if len(pairs) == 2:
            (t0, f0), (t1, f1) = pairs
            assert adja[t0, f0] == 4 * t1 + f1
            assert adja[t1, f1] == 4 * t0 + f0
        else:
            (t0, f0), = pairs
            assert adja[t0, f0] < 0
