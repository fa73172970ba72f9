"""Incremental shard migration tests (parallel/migrate.py).

The reference migrates only moving groups between ranks with communicator
repair (distributegrps_pmmg.c:1631-1841); the shard-resident outer loop
(dist.distributed_adapt_multi) must do the same: between outer iterations
no whole-mesh merge happens — only the displaced interface band moves.
These tests assert exactly that (a merge-call counter), plus the usual
conformity/quality/volume gates and the comm-table ordering contract on
the migrated state.  Runs on the virtual 8-device CPU mesh
(tests/conftest.py), the analogue of the reference NP matrix
(cmake/testing/pmmg_tests.cmake:30-63).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import make_mesh, tet_volumes
from parmmg_tpu.core import constants as C
from parmmg_tpu.ops.adjacency import build_adjacency, check_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import tet_quality
from parmmg_tpu.parallel import dist
from parmmg_tpu.parallel import distribute
from parmmg_tpu.utils.fixtures import cube_mesh

# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
pytestmark = pytest.mark.slow


def _setup(n=3, capmul=4):
    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=capmul * len(vert),
                  capT=capmul * len(tet))
    m = analyze_mesh(m).mesh
    return m, jnp.full(m.capP, 0.3, m.vert.dtype)


def test_flood_labels_advance_into_smaller():
    """The bigger shard's color must invade the smaller across the
    interface (PMMG_get_ifcDirection priority, moveinterfaces_pmmg.c:77)."""
    from parmmg_tpu.parallel.migrate import flood_labels
    from parmmg_tpu.parallel.distribute import split_to_shards
    from parmmg_tpu.parallel.comms import build_interface_comms
    from parmmg_tpu.core.mesh import mesh_to_host

    m, met = _setup(6)
    vert_h, tet_h, _, _, _ = mesh_to_host(m)
    # equal halves: the size tie breaks toward the higher shard id, whose
    # front advances 2 tet-ball layers into shard 0 — but not all of it
    cent = vert_h[tet_h].mean(axis=1)
    part = (cent[:, 0] > 0.5).astype(np.int32)
    s, ms, l2g = split_to_shards(m, met, part, 2, return_l2g=True)
    g2l = []
    for s_ in range(2):
        mm = np.full(len(vert_h), -1, np.int64)
        mm[l2g[s_]] = np.arange(len(l2g[s_]))
        g2l.append(mm)
    comms = build_interface_comms(tet_h, part, 2, l2g, g2l)
    sizes = jnp.asarray(np.asarray(s.tmask).sum(axis=1).astype(np.int32))
    labels, depth = flood_labels(
        s, jnp.asarray(comms.node_idx), jnp.asarray(comms.nbr),
        sizes, 2, nlayers=2)
    labels, depth = np.asarray(labels), np.asarray(depth)
    tm = np.asarray(s.tmask)
    # flood depth: every flipped tet records its wave (1 or 2); kept
    # tets record 0 (consumed by enforce_ne_min's front-ordered revert)
    flipped = tm[0] & (labels[0] != 0)
    assert set(np.unique(depth[0][flipped])) <= {1, 2}
    assert (depth[0][tm[0] & ~flipped] == 0).all()
    # the big shard (1) keeps everything; the small shard (0) donates a
    # band to shard 1
    assert (labels[1][tm[1]] == 1).all()
    moved = (labels[0][tm[0]] == 1).sum()
    assert 0 < moved < tm[0].sum()


# NOTE (slow-tier burn-down): the two heaviest tests this module
# carried — test_multi_iteration_no_intermediate_merge and
# test_migration_moves_interface_band — now live in
# tests/test_compile_ledger.py at tier-1 size, asserted on the shared
# steady_state_migration_scenario fixture (one compile for the whole
# scenario family instead of a multi-minute 8-shard build here).


def test_driver_uses_shard_resident_path():
    """The API path with the default ifc-displacement mode must route
    through distributed_adapt_multi and produce a valid mesh."""
    from parmmg_tpu.api import ParMesh, IParam
    calls = {"n": 0}
    orig = distribute.merge_shards

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    distribute.merge_shards = counting
    try:
        vert, tet = cube_mesh(2)
        pm = ParMesh()
        pm.set_mesh_size(np_=len(vert), ne=len(tet))
        pm.set_vertices(vert)
        pm.set_tetrahedra(tet + 1)
        pm.set_met_size(1, len(vert))
        pm.set_scalar_mets(np.full(len(vert), 0.35))
        pm.set_iparameter(IParam.niter, 2)
        pm.set_iparameter(IParam.nDevices, 4)
        assert pm.run() == C.PMMG_SUCCESS
    finally:
        distribute.merge_shards = orig
    assert calls["n"] == 1
    v, _ = pm.get_vertices()
    t, _ = pm.get_tetrahedra()
    p = v[t - 1]
    vol = np.einsum("ti,ti->t", p[:, 1] - p[:, 0],
                    np.cross(p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) / 6
    assert (vol > 0).all()
    assert np.isclose(vol.sum(), 1.0, rtol=1e-4)


def test_graph_mode_one_merge_and_rebalance():
    """VERDICT r2 #7 'Done' gate: graph-balancing mode runs niter=3 with
    exactly ONE merge (the final output), labels realized through the
    band machinery (migrate.graph_repartition_labels)."""
    calls = {"n": 0}
    orig = distribute.merge_shards

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    distribute.merge_shards = counting
    try:
        m, met = _setup(3)
        out, met2, part = dist.distributed_adapt_multi(
            m, met, 4, niter=3, cycles=3, mode="graph")
    finally:
        distribute.merge_shards = orig
    assert calls["n"] == 1, "graph mode must not merge between iterations"
    out = build_adjacency(out)
    assert check_adjacency(out) == {"asymmetric": 0, "face_mismatch": 0}
    vols = np.asarray(tet_volumes(out))[np.asarray(out.tmask)]
    assert (vols > 0).all()
    assert np.isclose(vols.sum(), 1.0, rtol=1e-4)
    # the repartition balances the shard loads: final part sizes within
    # a generous band of the mean
    sizes = np.bincount(part, minlength=4)
    assert sizes.min() > 0.25 * sizes.mean()
