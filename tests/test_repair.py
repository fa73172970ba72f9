"""Sequential last-resort repair (ops/repair.py) — unit tests.

The batched waves deadlock on tangled sliver clusters; the sequential
pass reproduces the reference remesher's one-op-at-a-time freedom
(MMG3D_opttyp cascade).  The boundary path (plain-MG_BDY vertex sliding
along a boundary edge with sequential tag routing) is the fix for the
'boundary caps' that capped distributed qmin at ~1e-5.

Fixture: squash a vertex toward a neighbor along the largest step that
keeps every incident tet positive (no inversions — repair fixes
degeneracy, not tangling), leaving a genuinely flat sliver.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp

from parmmg_tpu.core import constants as C
from parmmg_tpu.core.mesh import make_mesh, tet_volumes
from parmmg_tpu.ops.adjacency import build_adjacency, check_adjacency
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.quality import tet_quality
from parmmg_tpu.ops.repair import repair_mesh
from parmmg_tpu.utils.fixtures import cube_mesh


def _squash(m, a, b, frac=0.9995):
    """Move vertex a toward b by the largest inversion-free step."""
    vh = np.asarray(m.vert).copy()
    tm = np.asarray(m.tmask)
    tet = np.asarray(m.tet)[tm]
    ball = tet[(tet == a).any(axis=1)]

    def minvol(p):
        vv = vh.copy()
        vv[a] = p
        q = vv[ball]
        d1 = q[:, 1] - q[:, 0]
        d2 = q[:, 2] - q[:, 0]
        d3 = q[:, 3] - q[:, 0]
        return np.einsum("ti,ti->t", d1, np.cross(d2, d3)).min()

    lo, hi = 0.0, 1.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        p = vh[a] + mid * (vh[b] - vh[a])
        if minvol(p) > 0:
            lo = mid
        else:
            hi = mid
    vh[a] = vh[a] + frac * lo * (vh[b] - vh[a])
    return dataclasses.replace(m, vert=jnp.asarray(vh, m.vert.dtype))


def _run(m, a, b):
    m = _squash(m, a, b)
    m = build_adjacency(m)
    q0 = np.asarray(tet_quality(m))[np.asarray(m.tmask)]
    assert q0.min() < 1e-2              # genuinely degenerate
    vols0 = np.asarray(tet_volumes(m))[np.asarray(m.tmask)]
    assert (vols0 > 0).all()            # but NOT inverted
    vol0 = float(vols0.sum())
    m2, nfixed = repair_mesh(m, jnp.full(m.capP, 0.3, m.vert.dtype),
                             q_floor=1e-2)
    assert nfixed > 0
    q1 = np.asarray(tet_quality(m2))[np.asarray(m2.tmask)]
    assert q1.min() > 1e-2
    m2 = build_adjacency(m2)
    assert check_adjacency(m2) == {"asymmetric": 0, "face_mismatch": 0}
    vols = np.asarray(tet_volumes(m2))[np.asarray(m2.tmask)]
    assert (vols > 0).all()
    assert abs(vols.sum() - vol0) < 1e-3 * vol0
    return m2


def test_repair_boundary_cap():
    """A flat sliver pressed onto the domain surface (plain-MG_BDY
    vertices) must be repaired by the boundary-edge collapse with tag
    routing — the old all-untagged guard refused the whole cavity."""
    vert, tet = cube_mesh(3)
    m = make_mesh(vert, tet, capP=2 * len(vert), capT=2 * len(tet))
    m = analyze_mesh(m).mesh
    vtag = np.asarray(m.vtag)
    vm = np.asarray(m.vmask)
    vh = np.asarray(m.vert)
    plain = vm & (vtag == C.MG_BDY)
    face = plain & (np.abs(vh[:, 2]) < 1e-9)     # inner z=0 face verts
    ids = np.where(face)[0]
    assert len(ids) >= 2
    d = np.linalg.norm(vh[ids][:, None] - vh[ids][None], axis=-1)
    d[d == 0] = np.inf
    i, j = np.unravel_index(np.argmin(d), d.shape)
    _run(m, int(ids[i]), int(ids[j]))


def test_repair_interior_cluster():
    """Interior flat sliver: the pre-existing untagged path."""
    vert, tet = cube_mesh(3)
    m = make_mesh(vert, tet, capP=2 * len(vert), capT=2 * len(tet))
    m = analyze_mesh(m).mesh
    vtag = np.asarray(m.vtag)
    vm = np.asarray(m.vmask)
    interior = np.where(vm & (vtag == 0))[0]
    assert len(interior) >= 2
    _run(m, int(interior[0]), int(interior[1]))


def test_repair_rounds_are_spans():
    """Each round of the sequential loop is a ``repair round`` span
    (obs/trace.py) carrying what it found and fixed."""
    from parmmg_tpu.obs import trace as otrace
    vert, tet = cube_mesh(3)
    m = make_mesh(vert, tet, capP=2 * len(vert), capT=2 * len(tet))
    m = analyze_mesh(m).mesh
    interior = np.where(np.asarray(m.vmask) & (np.asarray(m.vtag) == 0))[0]
    otrace.TRACER.reset()
    with otrace.span("sequential repair") as tail:
        _run(m, int(interior[0]), int(interior[1]))
    rounds = [r for r in otrace.TRACER.ring
              if r.get("name") == "repair round"]
    assert rounds and all(r["parent"] == tail.id for r in rounds)
    assert rounds[0]["bad"] > 0 and sum(r["fixed"] for r in rounds) > 0
