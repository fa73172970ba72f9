"""Ask the v5e compiler, without a chip: every Pallas kernel left in
``ops/pallas_kernels.py`` and one ``groups.adapt_block`` program are
compiled ahead of time for a DESCRIBED ``v5e:2x2`` topology and must
carry ``tpu_custom_call``.  What Mosaic refuses (a scalar store to VMEM,
an unimplemented primitive, a block shape off the (8,128) tiling) fails
here, at no chip time; interpret mode cannot see any of it.

Only this file touches libtpu, and only from inside the fixtures below
(never at import, in a skipif or in parametrize): the worker that is
handed this file loads the library, every other worker stays clear.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.ops import pallas_kernels as pk

# widths of the smoke run (chip_smoke.py: cube_mesh(32), -mesh-size
# 16384): one group holds capT = 64678 tet slots, its edge table 6*capT
CAPT = 64678
CAPE = 6 * CAPT


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(*shape):
    return (shape, jnp.float32)


def _i32(*shape):
    return (shape, jnp.int32)


KERNEL_CASES = {
    "edge_length_iso": (
        lambda a, b, c, d: pk.edge_length_iso_pallas(a, b, c, d,
                                                     interpret=False),
        [_f32(CAPE, 3), _f32(CAPE, 3), _f32(CAPE), _f32(CAPE)]),
    "edge_length_ani": (
        lambda a, b, c, d: pk.edge_length_ani_pallas(a, b, c, d,
                                                     interpret=False),
        [_f32(CAPE, 3), _f32(CAPE, 3), _f32(CAPE, 6), _f32(CAPE, 6)]),
    "quality_iso": (
        lambda p: pk.quality_pallas(p, interpret=False),
        [_f32(CAPT, 4, 3)]),
    "quality_ani": (
        lambda p, m: pk.quality_pallas(p, m, interpret=False),
        [_f32(CAPT, 4, 3), _f32(CAPT, 6)]),
    "score_count": (
        lambda m, v: pk.score_count_pallas(m, v, interpret=False),
        [_f32(CAPE), _f32(CAPE)]),
    "score3_count": (
        lambda m, a, b, c: pk.score3_count_pallas(m, a, b, c,
                                                  interpret=False),
        [_f32(CAPE), _f32(CAPE), _f32(CAPE), _f32(CAPE)]),
    "merge_prefix": (
        lambda x: pk.merge_prefix_pallas(x, interpret=False),
        [_i32(CAPE)]),
}


def test_every_pallas_call_has_a_case():
    """A kernel added to ops/pallas_kernels.py must be asked here too:
    five call sites (the two score kernels share one) carry the seven
    names compiled below."""
    with open(pk.__file__) as f:
        src = f.read()
    assert src.count("pl.pallas_call(") == 5
    for name in KERNEL_CASES:
        assert f'"{name}"' in src, name


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_cache):
    fn, shapes = KERNEL_CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    assert name in txt                   # the kernel's stable name


def test_adapt_block_compiles_for_v5e(one_chip, no_cache, monkeypatch):
    """THE grouped cycle block (one program: the swap arm and the split
    prescreen are run-time switches) from ShapeDtypeStructs: the program
    chip_smoke.py runs, at a small group shape."""
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel import groups
    from parmmg_tpu.parallel.distribute import split_to_shards
    from parmmg_tpu.utils.fixtures import cube_mesh
    # code that asks jax.default_backend() sees the CPU here; the
    # trace-time defaults on this path are steered to their tpu values:
    # swap23 pairs off the face sort, the surface scatters run over
    # lists (ops/surflist), and the table makers' sorts carry their
    # payloads (ops/edges.sort_carry)
    from parmmg_tpu.utils import placement
    monkeypatch.setenv("PARMMG_SWAP_FACESORT", "1")
    monkeypatch.setattr(groups, "placed_on_tpu", lambda: True)
    monkeypatch.setattr(placement, "placed_on_tpu", lambda: True)
    vert, tet = cube_mesh(2)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    part = (vert[tet].mean(axis=1)[:, 0] > 0.5).astype(np.int32)
    stacked, met_s = split_to_shards(
        m, jnp.full(m.capP, 0.3, m.vert.dtype), part, 2)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    monkeypatch.setattr(groups, "_GROUP_BLOCK_CACHE", {})
    fn = groups._group_block_program(False, False, 0.01).__wrapped__
    compiled = fn.lower(
        jax.tree.map(sds, stacked), sds(met_s), arr((), jnp.int32),
        arr((2,), jnp.bool_), arr((), jnp.bool_), arr((), jnp.bool_),
    ).compile()
    txt = compiled.as_text()
    for kernel in ("edge_length_iso", "score_count", "score3_count"):
        assert kernel in txt, kernel
    # the prefix sum is the host tail's merge's (ops/topo_incr): a block
    # sorts its tables in full
    assert "merge_prefix" not in txt
    assert "tpu_custom_call" in txt
