"""A vertex's scalars ride in one row (PR 42, ``ops/rowpack``).

The collapse, the split and the smoother read their per-vertex flags,
tags, scores and sizes through ROW gathers of packed tables where they
read each through a 1-D gather of its own.  A gather copies bits, so
nothing may move: the primitive is held to the columns' own gathers for
every carrier mix, and two cycles of the block program at each of the
five grouped cells' kinds of input, and of the SPMD block on four virtual
devices, are held to the digests the parent's code gave (seed 21).
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.core.mesh import make_mesh, mesh_to_host
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.rowpack import FLAGS_PER_WORD, pack, take
from parmmg_tpu.parallel import dist, groups
from parmmg_tpu.parallel.distribute import split_to_shards
from parmmg_tpu.utils.fixtures import (analytic_ani_metric,
                                       analytic_iso_metric, cube_mesh,
                                       sphere_mesh, torus_mesh)

N = 257
HAUSD = 0.01
SEED = 21


# ---- the primitive ----------------------------------------------------------

def columns(rng):
    nasty = [np.nan, -np.nan, np.inf, -np.inf, -0.0, 0.0, 1e-45, -1e-40,
             np.float32(3.4e38)]
    f = rng.standard_normal(N).astype(np.float32)
    f[:len(nasty)] = nasty
    # a NaN with a payload: no arithmetic would keep it
    f[len(nasty)] = np.array([0x7fc12345], np.uint32).view(np.float32)[0]
    s = rng.integers(-2 ** 31, 2 ** 31, N).astype(np.int32)
    s[:2] = [-2 ** 31, -1]
    u = rng.integers(0, 2 ** 32, N, dtype=np.uint64).astype(np.uint32)
    u[:2] = [0x80000000, 0xFFFFFFFF]
    flags = [rng.integers(0, 2, N).astype(bool)
             for _ in range(FLAGS_PER_WORD + 3)]
    return {"f32": f, "s32": s, "u32": u,
            "f32x3": rng.standard_normal((N, 3)).astype(np.float32),
            "s32x4": rng.integers(-9, 9, (N, 4)).astype(np.int32),
            "flags": flags}


MIXES = {
    "f32": ["f32"],
    "s32": ["s32"],
    "u32": ["u32"],
    "eight-flags": ["flags8"],
    "a-flag-and-a-tag": ["flag", "u32"],
    "rows-and-scalars": ["f32x3", "u32", "flag", "f32", "s32"],
    "flags-between": ["flag", "f32", "flag", "s32x4", "flag"],
    "two-flag-words": ["u32", "flags35", "f32x3"],
    "every-kind": ["f32", "s32", "u32", "f32x3", "s32x4", "flags8"],
}


def mix(names, rng):
    c = columns(rng)
    flags = iter(c["flags"])
    out = []
    for name in names:
        if name == "flag":
            out.append(next(flags))
        elif name.startswith("flags"):
            out += [next(flags) for _ in range(int(name[5:]))]
        else:
            out.append(c[name])
    return [jnp.asarray(a) for a in out]


def bits(a):
    return np.asarray(a).tobytes()


@pytest.mark.parametrize("shape", [(500,), (120, 4)], ids=["1d", "tet"])
@pytest.mark.parametrize("name", list(MIXES))
def test_a_packed_row_gather_is_each_columns_own_gather(name, shape):
    """Every column back in its own type and shape, equal to its own 1-D
    (or row) gather to the bit: NaNs with their payloads, infinities,
    -0.0, denormals, negative ``s32``, ``u32`` with the top bit set,
    flags in one word and in two; the indices hold the last row (the
    drop row of a ``capP + 1`` table) and repeat."""
    rng = np.random.default_rng(len(name))
    cols = mix(MIXES[name], rng)
    idx = rng.integers(0, N, shape).astype(np.int32)
    idx.reshape(-1)[:3] = [N - 1, 0, N - 1]
    idx = jnp.asarray(idx)
    cols = {f"c{i}": col for i, col in enumerate(cols)}
    got = jax.jit(lambda cols, idx: pack(**cols).take(idx))(cols, idx)
    assert set(got) == set(cols)
    for name, col in cols.items():
        want, out = col[idx], got[name]
        assert out.dtype == want.dtype and out.shape == want.shape
        assert bits(out) == bits(want)


def test_the_table_is_one_word_a_scalar_and_a_word_a_32_flags():
    rng = np.random.default_rng(0)

    def table(names):
        return pack(**{f"c{i}": col
                       for i, col in enumerate(mix(names, rng))}).table
    assert table(["f32x3", "u32", "flag", "f32"]).shape == (N, 6)
    assert table(["flags35", "s32"]).shape == (N, 3)
    # a lone word rides beside its own copy: the chip fetches a row of
    # one word at the scalar's price
    assert table(["flags8"]).shape == (N, 2)
    assert table(["u32"]).shape == (N, 2)
    assert table(["u32"]).dtype == jnp.uint32


@pytest.mark.parametrize("kind", ["f32", "s32", "u32", "flag"])
def test_take_is_one_columns_gather(kind):
    rng = np.random.default_rng(7)
    col, = mix([kind], rng)
    idx = jnp.asarray(rng.integers(0, N, (90, 4)).astype(np.int32))
    out = jax.jit(take)(col, idx)
    assert out.dtype == col.dtype and bits(out) == bits(col[idx])


@pytest.mark.parametrize("col", [
    np.zeros(N, np.float64), np.zeros(N, np.int8), np.zeros(N, np.float16),
    np.zeros((N, 2), bool), np.zeros((N, 2, 2), np.float32)],
    ids=["f64", "s8", "f16", "flag-rows", "rank-3"])
def test_a_column_that_is_no_32_bit_word_is_refused(col):
    with pytest.raises(TypeError):
        pack(col=col)


# ---- two cycles of a block against the parent's -----------------------------

def grouped(vert, tet, met, ngroups):
    """The fixture's tets shuffled (seed 21), analysed and cut into
    ``ngroups`` by the pass's own cut; the stacked groups, their metric."""
    rng = np.random.default_rng(SEED)
    tet = tet[rng.permutation(len(tet))]
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    met = jnp.asarray(met(vert), mesh.vert.dtype)
    met = jnp.concatenate(
        [met, jnp.broadcast_to(met[-1:], (mesh.capP - len(vert),)
                               + met.shape[1:])])
    vert_h, tet_h, _, _, _ = mesh_to_host(mesh)
    part = groups.fresh_cut(vert_h, tet_h, ngroups)
    return split_to_shards(mesh, met, part, ngroups)


def iso_shock(at, h):
    return lambda v: analytic_iso_metric(
        v - np.array([at - 0.5, 0, 0]), "shock", h)


def ani_shock(h, h_tan):
    return lambda v: analytic_ani_metric(v, "shock", h, h_tan)


# the five grouped cells' kinds of input at toy size, and the SPMD cell's:
# (fixture, metric, groups)
CELLS = {
    "iso-growth": (cube_mesh(5), iso_shock(0.5, 0.2), 2),
    "aniso-coarsen": (cube_mesh(5), ani_shock(0.3, 0.6), 2),
    "sphere-growth": (sphere_mesh(5),
                      lambda v: 0.12 + 0.6 * np.abs(v[:, 0]), 2),
    "torus-coarsen": (torus_mesh(14, 4), ani_shock(0.35, 0.7), 2),
    "iso-readapt": (cube_mesh(5), iso_shock(0.55, 0.2), 3),
    "spmd4-iso-growth": (cube_mesh(6), iso_shock(0.5, 0.17), 8),
}

# what the parent's waves gave: the operations of the two cycles and the
# digest of everything they leave but the edge tags (thirteen leaves of
# the stacked state, the metric, columns 0-10 of the counts rows).
# Commit 34e868d (PR 47's parent), my CPU run, PR 47; its whole-state
# digests were the ones PRs 42 and 46 pinned here (cb0d304, 69c1c2e).
# The edge tags are apart because PR 47 changed the INPUT's: a seam edge
# is frozen in every slot of its shell (``split_to_shards`` tagged the
# slots of the tets that own a seam face), so that leaf holds more set
# bits going in and coming out, and nothing else moved by a byte
PARENT = {
    "iso-growth": {"ops": [114, 19, 17, 19], "sha256":
        "f6db3678dab43f47c3aeb5c8f983f61513b4c5096b7e99fa7f7eeb678b01e1df"},
    "aniso-coarsen": {"ops": [0, 27, 20, 16], "sha256":
        "3acb34d3d048386d0f5c9c156f7070b131fa355e5c2df46edc2c85ea7eb62e75"},
    "sphere-growth": {"ops": [98, 0, 55, 54], "sha256":
        "4a1a910fd7ee4fb5bd0b3d0418e9fd8df92bbc74fc48c494d4824ff3a6cf504f"},
    "torus-coarsen": {"ops": [40, 6, 79, 59], "sha256":
        "90a48cd3e921fca47d6f9f533e057b569e648b135f37c7bf25d5aea02410a0e8"},
    "iso-readapt": {"ops": [66, 20, 19, 37], "sha256":
        "5f67a9589696f930e47a64559511d37de4a60d41f318702af614384800058d1d"},
    "spmd4-iso-growth": {"ops": [290, 20, 15, 101], "sha256":
        "5a7c72fe4183bb6733a6a4e9c342bb25f2dc65fe097ebfa3a7965180151546e0"},
}

# the edge tags two cycles later, under the freeze of every slot: PR 47's
# own, my CPU run, PR 47 (34e868d's differ in all six cells)
ETAG = {
    "iso-growth":
        "876df3d01ee5db95e20937437084d23951fe443a85f6b563feec8d023b28d0f8",
    "aniso-coarsen":
        "82f5a3d7437b3ad6f8b64b3d464221db9954ac0d0c49af7bcbc67413bca4b180",
    "sphere-growth":
        "d84868d5fcb6f676701d4bd55d5024e2bbe105c2122197b5c189185ce7427cde",
    "torus-coarsen":
        "9432c2ee824ec2c1f6ace8f07122e461e807be26bd64d942e624f1c507823ccf",
    "iso-readapt":
        "ab5698ab946e982120cddb518766cb217dfd2eccfb9c4eddf416051893dcf0a0",
    "spmd4-iso-growth":
        "15fc6a9a36d5b933e7cb2679c2dccec0df30cc584710741f66738471131eac5f",
}


def digest(stacked, met_s, counts):
    h, etag = hashlib.sha256(), hashlib.sha256()
    for f in dataclasses.fields(stacked):
        (etag if f.name == "etag" else h).update(np.ascontiguousarray(
            np.asarray(getattr(stacked, f.name))).tobytes())
    for a in [met_s] + list(counts):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    ops = np.sum(np.asarray(counts)[:, :4], axis=0).tolist()
    return {"ops": ops, "sha256": h.hexdigest()}, etag.hexdigest()


def two_cycles(cell):
    (vert, tet), met, ngroups = CELLS[cell]
    stacked, met_s = grouped(vert, tet, met, ngroups)
    counts = []
    if cell.startswith("spmd4"):
        dmesh = dist.make_device_mesh(4)
        stacked, met_s = dist.shard_stacked((stacked, met_s), dmesh)
        lvl = dist.shard_stacked(jnp.zeros(ngroups, jnp.int8), dmesh)
        step = dist.dist_adapt_block(dmesh, swap=True, hausd=HAUSD,
                                     G=ngroups // 4)
        for c in range(2):
            stacked, met_s, cs, _, _, lvl = step(
                stacked, met_s, jnp.asarray(c, jnp.int32), lvl)
            counts.append(np.asarray(cs)[:11])
        return digest(stacked, met_s, counts)
    step = groups._group_block(True, True, False, False, HAUSD)
    for c in range(2):
        stacked, met_s, cs = step(
            stacked, met_s, jnp.asarray(c, jnp.int32),
            jnp.ones(ngroups, bool))
        counts.append(np.asarray(cs).sum(axis=0)[:11])
    return digest(stacked, met_s, counts)


@pytest.mark.parametrize("cell", list(CELLS))
def test_two_cycles_of_a_block_equal_the_parents(cell):
    got, etag = two_cycles(cell)
    # the comparison is of meshes that changed: splits or collapses, and
    # moves
    assert got["ops"][0] + got["ops"][1] > 0 and got["ops"][3] > 0
    assert got == PARENT[cell]
    assert etag == ETAG[cell]


# ---- the host's programs: the polish (sliver collapse, the smoother's
# ---- optimal-position mode) and a fem round (the split in fem mode) ---------

PARENT_HOST = {
    "polish": {"ops": [2, 121, 36, 1509], "sha256":
        "bfa774bd46a2e6236058549f16e6347c4b513e9aececa832a07554a5d9660e62"},
    "fem": {"ops": [18, 0, 0], "sha256":
        "0da94c6037f324706bd35ef9a59e24be58b48452b0ecb2ab5e98248ce73f6588"},
}


def host_program(name):
    from parmmg_tpu.ops import adapt
    rng = np.random.default_rng(SEED)
    vert, tet = sphere_mesh(5) if name == "polish" else cube_mesh(4)
    mesh = analyze_mesh(make_mesh(vert, tet)).mesh
    if name == "fem":
        mesh, met, counts = adapt.fem_pass(
            mesh, jnp.full(mesh.capP, 0.3, mesh.vert.dtype))
    else:
        # interior vertices far off their lattice: slivers to polish
        interior = np.asarray(mesh.vtag)[: len(vert)] == 0
        vert = vert + 0.11 * interior[:, None] * rng.uniform(
            -1, 1, vert.shape)
        mesh = analyze_mesh(make_mesh(vert, tet)).mesh
        met = jnp.full(mesh.capP, 0.4, mesh.vert.dtype)
        counts = 0
        for wave in range(2):
            mesh, cs = adapt.sliver_polish(
                mesh, met, jnp.asarray(wave, jnp.int32), hausd=HAUSD)
            counts = counts + np.asarray(cs)[:4]
    h = hashlib.sha256()
    for a in jax.tree.leaves((mesh, met)):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return {"ops": np.asarray(counts)[:4].tolist(), "sha256": h.hexdigest()}


@pytest.mark.parametrize("name", ["polish", "fem"])
def test_a_host_program_equals_the_parents(name):
    got = host_program(name)
    assert sum(got["ops"]) > 0
    assert got == PARENT_HOST[name]


if __name__ == "__main__":
    import sys
    for name in ("polish", "fem"):
        print(f'    "{name}": {host_program(name)},', flush=True)
    for cell in CELLS if "cells" in sys.argv else ():
        print(f'    "{cell}": {two_cycles(cell)},', flush=True)
