"""A small solid torus through the grouped path (the path the chip
runs) under a tensor that is COARSE along its surface: ``ParMesh.run``
in three groups or more, judged by a float64 numpy oracle written here.

The user's tensor asks for 0.5 along the surface and 0.12 across the
plane x = 0.3; the surface allows a chord of sqrt(8 hausd r) = 0.18
round the tube.  What keeps the output's surface on the torus is the
curvature's tensor (``ops/metric.hausd_metric_bound``), the collapse's
test of the faces it leaves (``ops/collapse``) and the slide that
follows its direction (``ops/smooth``): without the first two a job
like this one hands back surface triangles three and four times
``hausd`` under the torus (issue 34's rehearsal on the parent).
"""
import numpy as np
import pytest

from parmmg_tpu.api.params import DParam, IParam
from parmmg_tpu.api.parmesh import ParMesh
from parmmg_tpu.core import constants as C
from parmmg_tpu.obs import trace as otrace
from parmmg_tpu.obs.metrics import REGISTRY
from parmmg_tpu.utils.fixtures import torus_mesh

R, r = 1.0, 0.4
HAUSD = 0.01
# 9,720 tets, cells of 0.105 round the tube and 0.084 (inner equator) to
# 0.195 (outer) along the ring: the input's own surface triangles stand
# 0.006 under the torus, inside hausd (at 30 stations they stand 0.0097
# under it, and nothing in a job refines a surface for hausd alone)
NU, NC = 45, 6
# a surface vertex the job places (a lifted midpoint, a slide) stands
# off the torus by the cubic patch's own error: readings of 3e-4 to
# 7e-4 at the cell's full size; a bare chord midpoint of an edge of 0.2
# round the tube stands 0.2^2 / (8 r) = 1.25e-2 under it
VERTEX_LIMIT = 2e-3
TORUS = 2.0 * np.pi ** 2 * R * r * r


def shock_tensor(vert):
    m = np.zeros((len(vert), 6))
    m[:, 0] = 1.0 / (0.12 + 0.8 * np.abs(vert[:, 0] - 0.3)) ** 2
    m[:, 3] = m[:, 5] = 1.0 / 0.5 ** 2
    return m


@pytest.fixture(scope="module")
def job():
    vert, tet = torus_mesh(NU, NC, R, r)
    pm = ParMesh()
    pm.set_mesh_size(np_=len(vert), ne=len(tet))
    pm.set_vertices(vert)
    pm.set_tetrahedra(tet + 1)
    pm.set_met_size(3, len(vert))
    pm.set_tensor_mets(shock_tensor(vert))
    pm.set_iparameter(IParam.meshSize, 3300)
    pm.set_iparameter(IParam.niter, 2)
    pm.set_iparameter(IParam.verbose, 0)
    pm.set_dparameter(DParam.hausd, HAUSD)
    otrace.TRACER.reset()
    before = dict(REGISTRY.snapshot()["counters"])
    assert pm.run() == C.PMMG_SUCCESS
    after = dict(REGISTRY.snapshot()["counters"])
    v, _ = pm.get_vertices()
    t, _ = pm.get_tetrahedra()
    return {"vert": np.asarray(v, np.float64),
            "tet": np.asarray(t, np.int64) - 1, "ntets_in": len(tet),
            "counters": {k: after[k] - before.get(k, 0.0) for k in after},
            "spans": [rec for rec in otrace.TRACER.ring
                      if rec.get("kind") == "span"]}


def torus_distance(p):
    return np.abs(np.hypot(np.hypot(p[..., 0], p[..., 1]) - R,
                           p[..., 2]) - r)


def oracle(vert, tet):
    """What a user can check of a solid torus's mesh, in float64."""
    p = vert[tet]
    vol = np.einsum("ij,ij->i", p[:, 1] - p[:, 0], np.cross(
        p[:, 2] - p[:, 0], p[:, 3] - p[:, 0])) / 6.0
    faces = np.sort(np.concatenate(
        [tet[:, [1, 2, 3]], tet[:, [0, 2, 3]], tet[:, [0, 1, 3]],
         tet[:, [0, 1, 2]]]), axis=1)
    uniq, cnt = np.unique(faces, axis=0, return_counts=True)
    skin = uniq[cnt == 1]
    rim = np.sort(np.concatenate(
        [skin[:, [0, 1]], skin[:, [1, 2]], skin[:, [0, 2]]]), axis=1)
    rim_uniq, rim_cnt = np.unique(rim, axis=0, return_counts=True)
    on = np.unique(skin)
    return {"inverted": int((vol <= 0).sum()),
            "overfull": int((cnt > 2).sum()),
            "open_rim": int((rim_cnt != 2).sum()),
            # a closed surface of genus 1
            "euler": len(on) - len(rim_uniq) + len(skin),
            "vertex_dev": float(torus_distance(vert[on]).max()),
            "chord_dev": float(torus_distance(
                vert[skin].mean(axis=1)).max()),
            "volume": float(vol.sum()), "n_surface": len(on)}


def test_the_grouped_torus_is_conforming_and_within_hausd(job):
    spans = {}
    for rec in job["spans"]:
        spans.setdefault(rec["name"].split("/")[-1], []).append(rec)
    assert all(rec["groups"] >= 3 for rec in spans["grp split"])
    assert job["counters"]["groups.dispatches"] > 0
    o = oracle(job["vert"], job["tet"])
    assert o["inverted"] == 0 and o["overfull"] == 0, o
    assert o["open_rim"] == 0 and o["euler"] == 0, o
    # every surface triangle's centroid within the hausd the job states
    assert o["chord_dev"] <= HAUSD, o
    assert o["vertex_dev"] < VERTEX_LIMIT, o
    # the inscribed polyhedron lacks about area x mean sag of the volume
    assert TORUS * (1 - 0.03) < o["volume"] < TORUS * (1 + 1e-3), o
    # the tensor was coarse along the surface: the job coarsened
    assert len(job["tet"]) < job["ntets_in"] and o["n_surface"] > 300, o


def test_the_bound_and_the_veto_reach_spans_and_counters(job):
    c = job["counters"]
    # every regular surface vertex was examined and, the user's tensor
    # being coarser than the curvature allows, changed
    assert c["surf.bdy_verts"] == 4 * NC * NU
    assert 0 < c["surf.bound_verts"] <= c["surf.bdy_verts"]
    bound, = [rec for rec in job["spans"]
              if rec["name"].split("/")[-1] == "hausd bound"]
    assert bound["bound_verts"] == c["surf.bound_verts"]
    assert bound["bdy_verts"] == c["surf.bdy_verts"]
    assert abs(bound["kappa_max"] - 1.0 / r) < 0.1 / r
    metric, = [rec for rec in job["spans"]
               if rec["name"].split("/")[-1] == "metric"]
    assert bound["parent"] == metric["id"]
    assert metric["bound_verts"] == c["surf.bound_verts"]
    # with the bound in the tensor the hausd test is no longer the
    # surface's only guard: it refuses one collapse in ten here (74 of
    # 702 wanted), where the unbounded map of issue 34's rehearsal left
    # it two in five
    assert c["adapt.ncollapse"] > 100
    assert 0 < c["surf.hveto"] < 0.25 * c["adapt.ncollapse"]
    assert c["surf.bmoved"] > 0
