"""Distributed (multi-device shard_map) adaptation tests on the virtual
8-device CPU mesh — the analogue of the reference's NP in {1,2,4,8} CI
matrix (cmake/testing/pmmg_tests.cmake:30-63), with quality/conformity
assertions instead of exit codes.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from parmmg_tpu.core.mesh import make_mesh, tet_volumes, mesh_to_host
from parmmg_tpu.core import constants as C
from parmmg_tpu.ops.adjacency import build_adjacency, check_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import tet_quality
from parmmg_tpu.parallel.dist import distributed_adapt
from parmmg_tpu.parallel.partition import move_interfaces
from parmmg_tpu.utils.fixtures import cube_mesh

# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
pytestmark = pytest.mark.slow


def _setup(n=3, capmul=4):
    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=capmul * len(vert), capT=capmul * len(tet))
    m = analyze_mesh(m).mesh
    return m, jnp.full(m.capP, 0.3, m.vert.dtype)


@pytest.mark.parametrize("ndev", [2, 8])
def test_distributed_adapt_conforming(ndev):
    # ndev=4 is covered by the iterated + API tests below; the 1-core CI
    # host makes each extra (ndev, shape) combo cost minutes of wall clock
    m, met = _setup(3)
    out, met2, part = distributed_adapt(m, met, ndev, cycles=4)
    out = build_adjacency(out)
    assert check_adjacency(out) == {"asymmetric": 0, "face_mismatch": 0}
    vols = np.asarray(tet_volumes(out))[np.asarray(out.tmask)]
    assert (vols > 0).all()
    assert np.isclose(vols.sum(), 1.0, rtol=1e-4)
    assert len(part) == int(np.asarray(out.tmask).sum())
    assert part.min() >= 0 and part.max() < ndev


def test_iterated_with_interface_displacement():
    m, met = _setup(3)
    part = None
    for it in range(2):
        m, met, part = distributed_adapt(m, met, 4, cycles=3, part=part)
        m = analyze_mesh(m).mesh
        _, tet_h, _, _, _ = mesh_to_host(m)
        part = move_interfaces(tet_h, part, 4, nlayers=2)
    vols = np.asarray(tet_volumes(m))[np.asarray(m.tmask)]
    assert (vols > 0).all()
    assert np.isclose(vols.sum(), 1.0, rtol=1e-4)
    q = np.asarray(tet_quality(m, met))[np.asarray(m.tmask)]
    assert q.min() > 0.05


def test_api_multidevice():
    from parmmg_tpu.api import ParMesh, IParam
    vert, tet = cube_mesh(2)
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(1, len(vert))
    pm.set_scalar_mets(np.full(len(vert), 0.3))
    pm.set_iparameter(IParam.niter, 2)
    pm.set_iparameter(IParam.nDevices, 4)
    assert pm.run() == C.PMMG_SUCCESS
    v, _ = pm.get_vertices()
    t, _ = pm.get_tetrahedra()
    p = v[t - 1]
    vol = np.einsum("ti,ti->t", p[:, 1] - p[:, 0],
                    np.cross(p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) / 6
    assert (vol > 0).all()
    assert np.isclose(vol.sum(), 1.0, rtol=1e-4)
