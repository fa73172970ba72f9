"""ParMesh: the public mesh-adaptation object (PMMG_ParMesh analogue).

Mirrors the reference's public API surface (libparmmg.h; implementation
API_functions_pmmg.c) in pythonic form: every ``PMMG_Set_*``/``PMMG_Get_*``
pair becomes a ``set_*``/``get_*`` method operating on numpy staging
arrays; the adaptation entries (``PMMG_parmmglib_centralized``
libparmmg.c:1444, ``_distributed`` :1519) become :meth:`run`.

Design note (TPU-first): the reference keeps per-rank groups of Mmg meshes
and remeshes them sequentially; here the staging arrays become ONE flat
device Mesh (core.mesh) adapted by batched waves, and the multi-device
path shards it over a ``jax.sharding.Mesh`` with frozen interfaces
(parallel/).  Groups survive only as shards — the migration quantum — so
the "two-level rank→group decomposition" (SURVEY §2.8) maps to
device→shard.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ..core import constants as C
from .params import Info, IParam, DParam


def _grow(a: np.ndarray | None, n: int, width: int | None, dtype):
    shape = (n,) if width is None else (n, width)
    out = np.zeros(shape, dtype)
    if a is not None:
        k = min(len(a), n)
        out[:k] = a[:k]
    return out


class ParMesh:
    """Staged mesh + solutions + parameters + (optional) interface comms."""

    def __init__(self, nprocs: int = 1, myrank: int = 0):
        self.info = Info()
        self.nprocs = nprocs
        self.myrank = myrank
        self.comm = None            # plugged by parallel runs
        # mesh staging (1-based API ids are converted to 0-based rows)
        self.np_ = 0
        self.ne_ = 0
        self.nt_ = 0
        self.na_ = 0
        self.nprism_ = 0
        self.nquad_ = 0
        self.vert: np.ndarray | None = None
        self.vref: np.ndarray | None = None
        self.vreq: np.ndarray | None = None     # bool required
        self.vcrn: np.ndarray | None = None     # bool corner
        self.vnormal: np.ndarray | None = None
        self.tetra: np.ndarray | None = None
        self.tref: np.ndarray | None = None
        self.tetra_req: np.ndarray | None = None
        self.tria: np.ndarray | None = None
        self.triaref: np.ndarray | None = None
        self.tria_req: np.ndarray | None = None
        self.edge: np.ndarray | None = None
        self.edgeref: np.ndarray | None = None
        self.edge_ridge: np.ndarray | None = None
        self.edge_req: np.ndarray | None = None
        self.prism: np.ndarray | None = None
        self.quad: np.ndarray | None = None
        # metric / ls / displacement / user fields
        self.met: np.ndarray | None = None      # [np] or [np,6]
        self.met_type: int = 0                  # 0 none,1 scalar,3 tensor
        self.ls: np.ndarray | None = None
        self.disp: np.ndarray | None = None
        self.fields: list[np.ndarray] = []
        self.field_types: list[int] = []
        # distributed-API communicators (Set_ith*Communicator*)
        self.n_node_comm = 0
        self.n_face_comm = 0
        self.node_comms: list[dict] = []
        self.face_comms: list[dict] = []
        # outputs (+ caches, invalidated by run())
        self._out = None                        # core Mesh after run()
        self._out_met = None
        self._out_stats = None
        self._glonum = None
        self._out_vn = None
        self._out_host_cache = None
        self._out_edges_cache = None
        self._out_tria_cache = None

    # ------------------------------------------------------------------
    # sizes
    # ------------------------------------------------------------------
    def set_mesh_size(self, np_: int, ne: int, nprism: int = 0, nt: int = 0,
                      nquad: int = 0, na: int = 0) -> None:
        """PMMG_Set_meshSize (libparmmg.h:348)."""
        self.np_, self.ne_, self.nt_, self.na_ = np_, ne, nt, na
        self.nprism_, self.nquad_ = nprism, nquad
        self.vert = _grow(self.vert, np_, 3, np.float64)
        self.vref = _grow(self.vref, np_, None, np.int32)
        self.vreq = _grow(self.vreq, np_, None, bool)
        self.vcrn = _grow(self.vcrn, np_, None, bool)
        self.tetra = _grow(self.tetra, ne, 4, np.int64)
        self.tref = _grow(self.tref, ne, None, np.int32)
        self.tetra_req = _grow(self.tetra_req, ne, None, bool)
        self.tria = _grow(self.tria, nt, 3, np.int64)
        self.triaref = _grow(self.triaref, nt, None, np.int32)
        self.tria_req = _grow(self.tria_req, nt, None, bool)
        self.edge = _grow(self.edge, na, 2, np.int64)
        self.edgeref = _grow(self.edgeref, na, None, np.int32)
        self.edge_ridge = _grow(self.edge_ridge, na, None, bool)
        self.edge_req = _grow(self.edge_req, na, None, bool)
        self.prism = _grow(self.prism, nprism, 6, np.int64)
        self.prism_ref = _grow(getattr(self, "prism_ref", None), nprism,
                               None, np.int32)
        self.quad = _grow(self.quad, nquad, 4, np.int64)
        self.quad_ref = _grow(getattr(self, "quad_ref", None), nquad,
                              None, np.int32)

    def get_mesh_size(self):
        """PMMG_Get_meshSize: sizes of the CURRENT mesh — after run() the
        adapted output (incl. the rebuilt feature-edge count, so
        ``for i in 1..na: get_edge(i)`` walks the output edges)."""
        if self._out is not None:
            vert, tet, _, _, _ = self._out_host()
            return len(vert), len(tet), self.nprism_, self._out_ntria(), \
                self.nquad_, len(self.get_edges()[0])
        return self.np_, self.ne_, self.nprism_, self.nt_, self.nquad_, \
            self.na_

    # ------------------------------------------------------------------
    # entities (1-based ids, like the reference API)
    # ------------------------------------------------------------------
    def set_vertex(self, x, y, z, ref: int, pos: int) -> None:
        self.vert[pos - 1] = (x, y, z)
        self.vref[pos - 1] = ref

    def set_vertices(self, coords: np.ndarray, refs=None) -> None:
        coords = np.asarray(coords, np.float64).reshape(self.np_, 3)
        self.vert[:] = coords
        if refs is not None:
            self.vref[:] = np.asarray(refs, np.int32).reshape(self.np_)

    def set_tetrahedron(self, v0, v1, v2, v3, ref: int, pos: int) -> None:
        self.tetra[pos - 1] = (v0, v1, v2, v3)
        self.tref[pos - 1] = ref

    def set_tetrahedra(self, tets: np.ndarray, refs=None) -> None:
        self.tetra[:] = np.asarray(tets, np.int64).reshape(self.ne_, 4)
        if refs is not None:
            self.tref[:] = np.asarray(refs, np.int32).reshape(self.ne_)

    def set_triangle(self, v0, v1, v2, ref: int, pos: int) -> None:
        self.tria[pos - 1] = (v0, v1, v2)
        self.triaref[pos - 1] = ref

    def set_triangles(self, tris: np.ndarray, refs=None) -> None:
        self.tria[:] = np.asarray(tris, np.int64).reshape(self.nt_, 3)
        if refs is not None:
            self.triaref[:] = np.asarray(refs, np.int32).reshape(self.nt_)

    def set_edge(self, v0, v1, ref: int, pos: int) -> None:
        self.edge[pos - 1] = (v0, v1)
        self.edgeref[pos - 1] = ref

    def set_edges(self, edges: np.ndarray, refs=None) -> None:
        self.edge[:] = np.asarray(edges, np.int64).reshape(self.na_, 2)
        if refs is not None:
            self.edgeref[:] = np.asarray(refs, np.int32).reshape(self.na_)

    def set_prism(self, vs, ref: int, pos: int) -> None:
        self.prism[pos - 1] = vs
        self.prism_ref[pos - 1] = ref

    def set_quadrilateral(self, vs, ref: int, pos: int) -> None:
        self.quad[pos - 1] = vs
        self.quad_ref[pos - 1] = ref

    def set_corner(self, pos: int) -> None:
        self.vcrn[pos - 1] = True

    def set_required_vertex(self, pos: int) -> None:
        self.vreq[pos - 1] = True

    def set_required_tetrahedron(self, pos: int) -> None:
        self.tetra_req[pos - 1] = True

    def set_required_triangle(self, pos: int) -> None:
        self.tria_req[pos - 1] = True

    def set_required_edge(self, pos: int) -> None:
        self.edge_req[pos - 1] = True

    def set_ridge(self, pos: int) -> None:
        self.edge_ridge[pos - 1] = True

    def set_normal_at_vertex(self, pos: int, nx, ny, nz) -> None:
        if self.vnormal is None:
            self.vnormal = np.zeros((self.np_, 3))
        self.vnormal[pos - 1] = (nx, ny, nz)

    # ------------------------------------------------------------------
    # metric & solutions
    # ------------------------------------------------------------------
    def set_met_size(self, typ: int, np_: int) -> None:
        """typ: 1=scalar, 3=tensor (MMG5_Scalar/MMG5_Tensor)."""
        if np_ != self.np_:
            raise ValueError("metric size must match vertex count")
        self.met_type = typ
        width = None if typ == 1 else 6
        self.met = _grow(None, np_, width, np.float64)

    def set_scalar_met(self, m: float, pos: int) -> None:
        self.met[pos - 1] = m

    def set_scalar_mets(self, m: np.ndarray) -> None:
        self.met[:] = np.asarray(m, np.float64).reshape(self.np_)

    def set_tensor_met(self, m11, m12, m13, m22, m23, m33, pos: int) -> None:
        self.met[pos - 1] = (m11, m12, m13, m22, m23, m33)

    def set_tensor_mets(self, m: np.ndarray) -> None:
        self.met[:] = np.asarray(m, np.float64).reshape(self.np_, 6)

    def set_sols_at_vertices_size(self, nsols: int, types: list[int]) -> None:
        """PMMG_Set_solsAtVerticesSize: declare user fields."""
        self.fields = []
        self.field_types = list(types)
        for t in types:
            width = {1: None, 2: 3, 3: 6}[t]
            self.fields.append(_grow(None, self.np_, width, np.float64))

    def set_ith_sol_in_sols_at_vertices(self, i: int, vals: np.ndarray)\
            -> None:
        f = self.fields[i - 1]
        self.fields[i - 1] = np.asarray(vals, np.float64).reshape(f.shape)

    def get_ith_sol_in_sols_at_vertices(self, i: int) -> np.ndarray:
        return self.fields[i - 1]

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def set_local_parameter(self, typ: int, ref: int, hmin: float,
                            hmax: float, hausd: float) -> None:
        """MMG3D_Set_localParameter analogue: size bounds applying only to
        entities carrying surface reference ``ref``.  ``typ``: 1=triangle
        (the only type the reference's parameter files use for 3D)."""
        self.info.local_params.append(
            (int(typ), int(ref), float(hmin), float(hmax), float(hausd)))

    def set_iparameter(self, key: IParam, val: int) -> None:
        self.info.set_iparameter(key, val)

    def set_dparameter(self, key: DParam, val: float) -> None:
        self.info.set_dparameter(key, val)

    # ------------------------------------------------------------------
    # distributed-API communicators (libparmmg.h Set_ith*Communicator*)
    # ------------------------------------------------------------------
    def set_number_of_node_communicators(self, n: int) -> None:
        self.n_node_comm = n
        self.node_comms = [dict(color_out=-1, local=None, global_=None)
                           for _ in range(n)]

    def set_number_of_face_communicators(self, n: int) -> None:
        self.n_face_comm = n
        self.face_comms = [dict(color_out=-1, local=None, global_=None)
                           for _ in range(n)]

    def set_ith_node_communicator_size(self, i: int, color_out: int,
                                       nitem: int) -> None:
        c = self.node_comms[i]
        c["color_out"] = color_out
        c["local"] = np.zeros(nitem, np.int64)
        c["global_"] = np.zeros(nitem, np.int64)

    def set_ith_face_communicator_size(self, i: int, color_out: int,
                                       nitem: int) -> None:
        c = self.face_comms[i]
        c["color_out"] = color_out
        c["local"] = np.zeros(nitem, np.int64)
        c["global_"] = np.zeros(nitem, np.int64)

    def set_ith_node_communicator_nodes(self, i: int, local_ids, global_ids,
                                        is_not_ordered: bool = True) -> None:
        """Items must appear in the same order on both sides of a rank
        pair; with ``is_not_ordered`` they are sorted by global id (the
        ordering contract, reference API_functions_pmmg.c:1295-1330)."""
        c = self.node_comms[i]
        lo = np.asarray(local_ids, np.int64)
        gl = np.asarray(global_ids, np.int64)
        if is_not_ordered:
            o = np.argsort(gl, kind="stable")
            lo, gl = lo[o], gl[o]
        c["local"], c["global_"] = lo, gl

    def set_ith_face_communicator_faces(self, i: int, local_ids, global_ids,
                                        is_not_ordered: bool = True) -> None:
        c = self.face_comms[i]
        lo = np.asarray(local_ids, np.int64)
        gl = np.asarray(global_ids, np.int64)
        if is_not_ordered:
            o = np.argsort(gl, kind="stable")
            lo, gl = lo[o], gl[o]
        c["local"], c["global_"] = lo, gl

    def get_number_of_node_communicators(self) -> int:
        return self.n_node_comm

    def get_number_of_face_communicators(self) -> int:
        return self.n_face_comm

    def get_ith_node_communicator_size(self, i: int):
        c = self.node_comms[i]
        return c["color_out"], len(c["local"])

    def get_ith_face_communicator_size(self, i: int):
        c = self.face_comms[i]
        return c["color_out"], len(c["local"])

    def get_ith_node_communicator_nodes(self, i: int):
        return self.node_comms[i]["local"]

    def get_ith_face_communicator_faces(self, i: int):
        return self.face_comms[i]["local"]

    def check_set_node_communicators(self) -> bool:
        """Coordinate-based sanity check of the user comms
        (PMMG_Check_Set_NodeCommunicators, chkcomm oracle flavor).
        Single-process form: verify ids are in range and orderings are
        self-consistent (pairwise exchange happens in parallel/comms)."""
        for c in self.node_comms:
            if c["local"] is None:
                return False
            if (np.asarray(c["local"]) < 1).any() or \
                    (np.asarray(c["local"]) > self.np_).any():
                return False
        return True

    def check_set_face_communicators(self) -> bool:
        """Face-comm mirror of the check above
        (PMMG_Check_Set_FaceCommunicators, libparmmg.h:2279-2346 flavor):
        every item set, local triangle ids in range."""
        for c in self.face_comms:
            if c["local"] is None:
                return False
            lo = np.asarray(c["local"])
            ntri = self.nt_ if self.tria is None \
                else max(self.nt_, len(self.tria))
            if (lo < 1).any() or (lo > ntri).any():
                return False
        return True

    def get_node_communicator_owners(self):
        """Owner rank of each node-comm item + its global id
        (PMMG_Get_NodeCommunicator_owners semantics: owner = max rank
        touching the entity, libparmmg.c:962-973).  Returns
        (owners_per_comm, globals_per_comm, nunique, ntot)."""
        owners, globs = [], []
        ntot = 0
        seen = set()
        for c in self.node_comms:
            n = 0 if c["local"] is None else len(c["local"])
            own = np.full(n, max(self.myrank, int(c["color_out"])), np.int64)
            owners.append(own)
            g = (np.zeros(n, np.int64) if c["global_"] is None
                 else np.asarray(c["global_"], np.int64))
            globs.append(g)
            ntot += n
            seen.update(int(x) for x in g)
        return owners, globs, len(seen), ntot

    def get_face_communicator_owners(self):
        """Face-comm mirror of the owners query.  Interface faces are
        shared by exactly 2 ranks; owner = max of the pair."""
        owners, globs = [], []
        ntot = 0
        seen = set()
        for c in self.face_comms:
            n = 0 if c["local"] is None else len(c["local"])
            own = np.full(n, max(self.myrank, int(c["color_out"])), np.int64)
            owners.append(own)
            g = (np.zeros(n, np.int64) if c["global_"] is None
                 else np.asarray(c["global_"], np.int64))
            globs.append(g)
            ntot += n
            seen.update(int(x) for x in g)
        return owners, globs, len(seen), ntot

    # ------------------------------------------------------------------
    # run
    # ------------------------------------------------------------------
    def _build_core_mesh(self):
        """Assemble the staged arrays into a core Mesh + metric."""
        import jax.numpy as jnp
        from ..core.mesh import make_mesh
        from ..ops.analysis import analyze_mesh

        if self.np_ == 0 or self.ne_ == 0:
            raise ValueError("mesh size not set")
        tets0 = self.tetra - 1                     # 1-based -> 0-based
        from ..utils.budget import plan_capacities
        capP, capT = plan_capacities(self.np_, self.ne_,
                                     self.info.mem_budget_mb)
        mesh = make_mesh(self.vert, tets0.astype(np.int32),
                         vref=self.vref, tref=self.tref,
                         capP=capP, capT=capT)
        # geometric analysis first (ridges/corners/normals from dihedrals)
        mesh = analyze_mesh(mesh, angedg=self.info.angedg()).mesh

        # overlay user-required / corner / ridge flags
        vtag = np.array(np.asarray(mesh.vtag), copy=True)
        vtag[: self.np_][self.vreq] |= C.MG_REQ
        vtag[: self.np_][self.vcrn] |= C.MG_CRN
        mesh = dataclasses.replace(mesh, vtag=jnp.asarray(vtag))

        # prism/quadrilateral vertices are frozen (Mmg keeps hybrid
        # elements untouched; their vertices must survive adaptation so
        # the pass-through connectivity stays valid)
        hybrid = np.concatenate([
            (self.prism.reshape(-1) if self.nprism_ else
             np.zeros(0, np.int64)),
            (self.quad.reshape(-1) if self.nquad_ else
             np.zeros(0, np.int64))])
        if len(hybrid):
            hyb = np.zeros(mesh.capP, bool)
            hyb[(hybrid - 1).astype(np.int64)] = True
            vtag = np.array(np.asarray(mesh.vtag), copy=True)
            vtag[hyb] |= C.MG_REQ
            # freeze the tet<->hybrid interface at full depth: any tet
            # face/edge whose vertices are all hybrid vertices lies on a
            # pass-through element; splitting such an edge would hang a
            # midpoint on the prism/quad face (non-conforming result).
            # Same mechanism as the required-tetra freeze below.
            from ..core.constants import IDIR, IARE
            tv = np.asarray(mesh.tet)
            hv = hyb[np.clip(tv, 0, mesh.capP - 1)] \
                & np.asarray(mesh.tmask)[:, None]
            ftag = np.array(np.asarray(mesh.ftag), copy=True)
            etag = np.array(np.asarray(mesh.etag), copy=True)
            for f in range(4):
                ftag[hv[:, IDIR[f]].all(axis=1), f] |= C.MG_REQ
            for e in range(6):
                etag[hv[:, IARE[e]].all(axis=1), e] |= C.MG_REQ
            mesh = dataclasses.replace(
                mesh, vtag=jnp.asarray(vtag), ftag=jnp.asarray(ftag),
                etag=jnp.asarray(etag))

        # required tetrahedra: freeze all their entities (faces, edges,
        # vertices get MG_REQ) so no wave touches them — the contract the
        # remesh kernels honor (same mechanism as the MG_PARBDY freeze)
        if self.tetra_req is not None and self.tetra_req.any():
            req = np.flatnonzero(self.tetra_req)
            ftag = np.array(np.asarray(mesh.ftag), copy=True)
            etag = np.array(np.asarray(mesh.etag), copy=True)
            vtag = np.array(np.asarray(mesh.vtag), copy=True)
            ftag[req] |= C.MG_REQ
            etag[req] |= C.MG_REQ
            tv = np.asarray(mesh.tet)[req]
            vtag[tv.reshape(-1)] |= C.MG_REQ
            mesh = dataclasses.replace(
                mesh, ftag=jnp.asarray(ftag), etag=jnp.asarray(etag),
                vtag=jnp.asarray(vtag))

        # user triangles: push refs onto matching boundary faces
        if self.nt_:
            mesh = self._apply_user_triangles(mesh)
        if self.na_:
            mesh = self._apply_user_edges(mesh)
            # stage the refs for edge-kind local parameters (the core
            # mesh keeps edge TAGS per tet slot, not refs — parsop edge
            # locals resolve against the user list, driver.py
            # apply_local_params typ 3)
            self.info._user_edges = (
                np.asarray(self.edge[: self.na_], np.int64) - 1,
                np.asarray(self.edgeref[: self.na_], np.int32))

        # metric
        cap = mesh.capP
        if self.met is None or self.met_type == 0:
            met = None
        elif self.met_type == 1:
            met = np.zeros(cap)
            met[: self.np_] = self.met
            met[self.np_:] = 1.0
        else:
            met = np.zeros((cap, 6))
            met[: self.np_] = self.met
            met[self.np_:] = np.array([1, 0, 0, 1, 0, 1.0])
        return mesh, (jnp.asarray(met) if met is not None else None)

    def _apply_user_triangles(self, mesh):
        """Match user boundary triangles to tet faces; transfer refs and
        required tags (what Mmg does from the Triangles field).

        With ``info.opnbdy`` (the reference's -opnbdy,
        libparmmg_tools.c usage + the OpnBdy_peninsula/island CI cases,
        cmake/testing/pmmg_tests.cmake:153-165): a user triangle that
        matches an INTERIOR face pair is ingested as an *open boundary*
        surface — BOTH face slots get MG_BDY | MG_OPNBDY (+ ref / REQ),
        so the hanging sheet behaves as a boundary for every wave
        (analysis treats it one-sided, ops.analysis.analyze_mesh).
        Without the flag interior triangles keep the previous behavior
        (refs transferred, no boundary promotion) — the reference
        ignores them unless -opnbdy is given.
        """
        import jax.numpy as jnp
        from ..core.mesh import tet_face_vertices

        fv = np.sort(np.asarray(tet_face_vertices(mesh.tet)), axis=2)
        capT = mesh.capT
        keys = fv.reshape(capT * 4, 3)
        tria = np.sort(self.tria - 1, axis=1)
        # dict-free matching: concatenate + lexsort; a key segment holds
        # 1 or 2 face-slot rows (hull / interior pair) + the tria row
        allk = np.concatenate([keys, tria])
        tag = np.concatenate([np.full(capT * 4, -1),
                              np.arange(len(tria))])
        o = np.lexsort(allk.T[::-1])
        ks, ts = allk[o], tag[o]
        n = len(ks)
        same_next = np.concatenate(
            [(ks[1:] == ks[:-1]).all(axis=1), [False]])
        head = np.concatenate([[True], ~same_next[:-1]])
        seg = np.cumsum(head) - 1
        nseg = seg[-1] + 1 if n else 0
        is_face = ts < 0
        is_tria = ~is_face
        # per segment: the tria id (if any) and the face rows
        tria_of = np.full(nseg, -1, np.int64)
        np.maximum.at(tria_of, seg[is_tria], ts[is_tria])
        nfaces = np.bincount(seg[is_face], minlength=nseg)
        ftag = np.array(np.asarray(mesh.ftag), copy=True).reshape(-1)
        fref = np.array(np.asarray(mesh.fref), copy=True).reshape(-1)
        face_rows = np.where(is_face)[0]
        fseg = seg[face_rows]
        hit = tria_of[fseg] >= 0
        tids = tria_of[fseg][hit]
        slots = o[face_rows[hit]]
        fref[slots] = self.triaref[tids]
        ftag[slots] |= np.where(self.tria_req[tids],
                                np.uint32(C.MG_REQ), np.uint32(0))
        if self.info.opnbdy:
            interior = nfaces[fseg][hit] == 2
            ftag[slots[interior]] |= np.uint32(C.MG_BDY | C.MG_OPNBDY)
        return dataclasses.replace(
            mesh, ftag=jnp.asarray(ftag.reshape(capT, 4)),
            fref=jnp.asarray(fref.reshape(capT, 4)))

    def _apply_user_edges(self, mesh):
        """Transfer user edge refs/ridge/required onto tet edge slots."""
        import jax.numpy as jnp
        from ..core.mesh import tet_edge_vertices

        ev = np.asarray(tet_edge_vertices(mesh.tet))
        capT = mesh.capT
        ev2 = np.sort(ev.reshape(capT * 6, 2), axis=1)
        ue = np.sort(self.edge - 1, axis=1)
        etag = np.array(np.asarray(mesh.etag), copy=True).reshape(-1)
        add = np.where(self.edge_ridge, np.uint32(C.MG_GEO), 0) | \
            np.where(self.edge_req, np.uint32(C.MG_REQ), 0) | \
            np.where(self.edgeref != 0, np.uint32(C.MG_REF), 0)
        key = ev2[:, 0].astype(np.int64) << 32 | ev2[:, 1]
        ukey = ue[:, 0].astype(np.int64) << 32 | ue[:, 1]
        o = np.argsort(ukey)
        pos = np.searchsorted(ukey[o], key)
        pos = np.clip(pos, 0, len(ukey) - 1)
        hit = ukey[o][pos] == key
        etag[hit] |= add[o][pos[hit]].astype(np.uint32)
        return dataclasses.replace(
            mesh, etag=jnp.asarray(etag.reshape(capT, 6)))

    def run(self) -> int:
        """The adaptation entry (PMMG_parmmglib_centralized /_distributed
        depending on staged comms).  Returns PMMG_SUCCESS/…"""
        from ..driver import parmmg_run
        from .params import InputError
        try:
            out, met, stats = parmmg_run(self)
        except InputError as e:
            from ..obs import trace as otrace
            otrace.log(0, f"  ## Error: {e}.",
                       verbose=self.info.imprim, err=True)
            return C.PMMG_STRONGFAILURE
        except MemoryError:
            return C.PMMG_STRONGFAILURE
        self._out, self._out_met, self._out_stats = out, met, stats
        # invalidate all output caches
        self._glonum = None
        self._out_vn = None
        self._out_ridge_nn = None
        self._out_vtag_cache = None
        self._out_host_cache = None
        self._out_edges_cache = None
        self._out_tria_cache = None
        self._out_ftag_cache = None
        # graded failure: the staged output above IS the saveable
        # conforming mesh (failed_handling, libparmmg1.c:974-1011)
        return stats.status

    # ------------------------------------------------------------------
    # output getters
    # ------------------------------------------------------------------
    def _out_host(self):
        from ..core.mesh import mesh_to_host
        if self._out is None:
            raise RuntimeError("run() first")
        # cached: the single-entity getters (get_vertex/tetrahedron/...)
        # are naturally called in a loop over all entities; recomputing
        # the O(N) compaction per call would make that O(N^2)
        if self._out_host_cache is None:
            self._out_host_cache = mesh_to_host(self._out)
        return self._out_host_cache

    def _out_ntria(self) -> int:
        m = self._out
        ftag = np.asarray(m.ftag)
        return int((((ftag & C.MG_BDY) != 0)
                    & np.asarray(m.tmask)[:, None]).sum())

    def get_vertices(self):
        vert, tet, vref, tref, vtag = self._out_host()
        return vert, vref

    def get_tetrahedra(self):
        vert, tet, vref, tref, vtag = self._out_host()
        return tet + 1, tref                       # back to 1-based

    def get_triangles(self):
        """Boundary faces of the adapted mesh as (tria [nt,3] 1-based,
        refs)."""
        tris, refs, _, _ = self._out_triangles()
        return tris, refs

    def get_metric(self):
        if self._out_met is None:
            return None
        m = np.asarray(self._out_met)
        vm = np.asarray(self._out.vmask)
        return m[vm]

    # -- single-entity getters (PMMG_Get_vertex/tetrahedron/triangle/edge,
    #    API_functions_pmmg.c; flags decoded from the MG_* tag bits) -------
    def get_vertex(self, pos: int):
        """(x, y, z, ref, isCorner, isRequired) of output vertex `pos`."""
        vert, _, vref, _, vtag = self._out_host()
        t = int(vtag[pos - 1])
        return (*map(float, vert[pos - 1]), int(vref[pos - 1]),
                bool(t & C.MG_CRN), bool(t & C.MG_REQ))

    def get_tetrahedron(self, pos: int):
        """(v0..v3 1-based, ref, isRequired).

        isRequired is derived from the freeze marker (all 4 faces
        MG_REQ), the mechanism ``set_required_tetrahedron`` uses; a tet
        whose 4 faces were all independently marked required via user
        triangles reads back as required too (the flat mesh carries no
        separate per-tet flag)."""
        _, tet, _, tref, _ = self._out_host()
        # cache the compacted ftag: the natural usage loops over all tets
        # and a fresh device pull per call would be O(N^2)
        if getattr(self, "_out_ftag_cache", None) is None:
            m = self._out
            self._out_ftag_cache = \
                np.asarray(m.ftag)[np.asarray(m.tmask)]
        req = bool((self._out_ftag_cache[pos - 1] & C.MG_REQ).all())
        return tuple(int(v) + 1 for v in tet[pos - 1]) + \
            (int(tref[pos - 1]), req)

    def get_triangle(self, pos: int):
        """(v0..v2 1-based, ref, isRequired) of output boundary tria."""
        tris, refs, req, _ = self._out_triangles()
        return tuple(int(v) for v in tris[pos - 1]) + \
            (int(refs[pos - 1]), bool(req[pos - 1]))

    def get_edges(self):
        """Feature edges (ridge/ref/required) of the adapted mesh:
        (edges [na,2] 1-based, refs, isRidge, isRequired).  The reference
        rebuilds the edge list from xtetra tags at output
        (MMG3D bdryBuild path); here it is one masked unique over the
        per-tet edge tag array.  Edge refs: staged user refs are carried
        only for edges whose endpoints are original staged vertices
        (midpoints inserted on a refined ref-edge lose the numeric ref —
        tracked gap, the MG_REF flag itself is preserved)."""
        if self._out_edges_cache is not None:
            return self._out_edges_cache
        from ..core.mesh import tet_edge_vertices
        m = self._out
        ev = np.asarray(tet_edge_vertices(m.tet)).reshape(-1, 2)
        etag = np.asarray(m.etag).reshape(-1)
        live = np.repeat(np.asarray(m.tmask), 6)
        feat = live & ((etag & (C.MG_GEO | C.MG_REQ | C.MG_REF)) != 0)
        e = np.sort(ev[feat], axis=1)
        tags = etag[feat]
        if len(e) == 0:                     # e.g. -nr on a smooth surface
            self._out_edges_cache = (
                np.zeros((0, 2), np.int64), np.zeros(0, np.int32),
                np.zeros(0, bool), np.zeros(0, bool))
            return self._out_edges_cache
        key = e[:, 0].astype(np.int64) << 32 | e[:, 1]
        o = np.argsort(key, kind="stable")
        key, e, tags = key[o], e[o], tags[o]
        head = np.concatenate([[True], key[1:] != key[:-1]])
        seg = np.cumsum(head) - 1
        # OR tags over duplicate tet-edge slots of the same edge
        utags = np.zeros(int(head.sum()), np.uint32)
        np.bitwise_or.at(utags, seg, tags.astype(np.uint32))
        e = e[head]
        vmask = np.asarray(m.vmask)
        new_id = np.cumsum(vmask) - 1
        # recover staged user edge refs where both endpoints are original
        # staged vertices (1-based output ids of staged vertex i = its
        # compacted position; staged vertices occupy the leading rows)
        refs = np.zeros(len(e), np.int32)
        if self.na_ and len(e):
            out_e = new_id[e]                       # 0-based output ids
            orig = (e < self.np_).all(axis=1)       # original-vertex rows
            ue = np.sort(self.edge - 1, axis=1)
            ukey = ue[:, 0].astype(np.int64) << 32 | ue[:, 1]
            # e rows are already (min,max)-sorted from construction
            ekey = e[:, 0].astype(np.int64) << 32 | e[:, 1]
            o = np.argsort(ukey)
            pos = np.clip(np.searchsorted(ukey[o], ekey), 0, len(ukey) - 1)
            hit = orig & (ukey[o][pos] == ekey)
            refs[hit] = self.edgeref[o][pos[hit]]
        self._out_edges_cache = (
            new_id[e] + 1, refs,
            (utags & C.MG_GEO) != 0, (utags & C.MG_REQ) != 0)
        return self._out_edges_cache

    def get_edge(self, pos: int):
        """(v0, v1 1-based, ref, isRidge, isRequired)."""
        e, r, rid, req = self.get_edges()
        return (int(e[pos - 1, 0]), int(e[pos - 1, 1]), int(r[pos - 1]),
                bool(rid[pos - 1]), bool(req[pos - 1]))

    def _input_vertex_remap(self):
        """Output 1-based id of each staged input vertex (vertices are
        frozen only if tagged; callers use this for pass-through hybrid
        elements whose vertices ARE frozen)."""
        if self._out is None:
            return None
        vm = np.asarray(self._out.vmask)
        new_id = np.cumsum(vm) - 1
        return new_id[: self.np_] + 1

    def get_prisms(self):
        """Prisms pass through adaptation untouched (their vertices are
        frozen at run(); PMMG_Get_prisms).  Connectivity is renumbered to
        the output vertex ids."""
        if self._out is not None and self.nprism_:
            rm = self._input_vertex_remap()
            return rm[self.prism - 1], self.prism_ref
        return self.prism, self.prism_ref

    def get_quadrilaterals(self):
        if self._out is not None and self.nquad_:
            rm = self._input_vertex_remap()
            return rm[self.quad - 1], self.quad_ref
        return self.quad, self.quad_ref

    def get_normals(self):
        """Unit outward normals at output boundary vertices [np,3]
        (PMMG_Get_normalAtVertex source data; zero off-surface)."""
        if getattr(self, "_out_vn", None) is None:
            from ..ops.analysis import analyze_mesh
            res = analyze_mesh(self._out)
            self._out_vn = np.asarray(res.vnormal)[np.asarray(
                self._out.vmask)]
        return self._out_vn

    def get_normal_at_vertex(self, pos: int):
        """(nx, ny, nz) at output vertex ``pos`` (1-based).

        At RIDGE points the averaged normal is geometrically meaningless
        (the reference keeps two per-side normals in the xPoint,
        analys_pmmg.c:199-1171, and exposes n1); here likewise the
        first-side normal is returned — use
        :meth:`get_ridge_normals_at_vertex` for both sides."""
        from ..core.constants import MG_GEO, MG_REF, MG_CRN, MG_NOM
        if getattr(self, "_out_vtag_cache", None) is None:
            self._out_vtag_cache = np.asarray(self._out.vtag)[
                np.asarray(self._out.vmask)]
        t = int(self._out_vtag_cache[pos - 1])
        if (t & (MG_GEO | MG_REF)) and not (t & (MG_CRN | MG_NOM)):
            n1, _ = self.get_ridge_normals_at_vertex(pos)
            return n1
        n = self.get_normals()[pos - 1]
        return float(n[0]), float(n[1]), float(n[2])

    def get_ridge_normals_at_vertex(self, pos: int):
        """Both per-side normals (n1, n2) at a ridge vertex (the xPoint
        n1/n2 of the reference); zeros at non-ridge points."""
        if getattr(self, "_out_ridge_nn", None) is None:
            from ..ops.analysis import ridge_vertex_normals
            n1, n2 = ridge_vertex_normals(self._out)
            vm = np.asarray(self._out.vmask)
            self._out_ridge_nn = (np.asarray(n1)[vm], np.asarray(n2)[vm])
        n1, n2 = self._out_ridge_nn
        return (tuple(float(x) for x in n1[pos - 1]),
                tuple(float(x) for x in n2[pos - 1]))

    def get_scalar_met(self, pos: int) -> float:
        return float(self.get_metric()[pos - 1])

    def get_scalar_mets(self) -> np.ndarray:
        return self.get_metric()

    def get_tensor_met(self, pos: int):
        return tuple(float(x) for x in self.get_metric()[pos - 1])

    def get_tensor_mets(self) -> np.ndarray:
        return self.get_metric()

    def _out_triangles(self):
        """(tris 1-based, refs, isRequired, tet_of_tria) of output
        boundary faces; ``tet_of_tria`` is the 0-based *compacted* id of
        the tet each boundary face belongs to (used e.g. to assign
        triangles to the shard that owns the adjacent tet)."""
        if self._out_tria_cache is not None:
            return self._out_tria_cache
        from ..core.mesh import tet_face_vertices
        m = self._out
        vm = np.asarray(m.vmask)
        new_id = np.cumsum(vm) - 1
        tm = np.asarray(m.tmask)
        tet_new = np.cumsum(tm) - 1
        fv = np.asarray(tet_face_vertices(m.tet))
        ftag = np.asarray(m.ftag)
        sel = ((ftag & C.MG_BDY) != 0) & tm[:, None]
        rows = np.nonzero(sel)[0]
        self._out_tria_cache = (
            new_id[fv[sel]] + 1, np.asarray(m.fref)[sel],
            (ftag[sel] & C.MG_REQ) != 0, tet_new[rows])
        return self._out_tria_cache

    def get_vertex_glonum(self, pos: int) -> int:
        if self._glonum is None:
            self._compute_glonum()
        return int(self._glonum[pos - 1])

    def get_vertices_glonum(self) -> np.ndarray:
        if self._glonum is None:
            self._compute_glonum()
        return self._glonum

    def _compute_glonum(self):
        """Output global numbering (single-process: identity; multi-shard
        handled by parallel.comms.global_node_numbering)."""
        vert, _, _, _, _ = self._out_host()
        self._glonum = np.arange(1, len(vert) + 1, dtype=np.int64)

    def get_triangle_glonum(self, pos: int) -> int:
        """PMMG_Get_triangleGloNum: global id of an output boundary tria
        (single-process: identity; the two-phase owned/parallel numbering
        of the reference collapses, libparmmg.c:464)."""
        return pos

    def get_triangles_glonum(self) -> np.ndarray:
        return np.arange(1, self._out_ntria() + 1, dtype=np.int64)

    def print_communicator(self, path: str) -> None:
        """PMMG_printCommunicator (libparmmg.h:2554): dump the staged
        node/face communicators to a text file for debugging."""
        with open(path, "w") as f:
            f.write(f"rank {self.myrank} / {self.nprocs}\n")
            f.write(f"node communicators: {self.n_node_comm}\n")
            for i, c in enumerate(self.node_comms):
                n = 0 if c["local"] is None else len(c["local"])
                f.write(f"  comm {i}: color_out {c['color_out']} "
                        f"nitem {n}\n")
                if n:
                    for lo, gl in zip(c["local"], c["global_"]):
                        f.write(f"    {int(lo)} {int(gl)}\n")
            f.write(f"face communicators: {self.n_face_comm}\n")
            for i, c in enumerate(self.face_comms):
                n = 0 if c["local"] is None else len(c["local"])
                f.write(f"  comm {i}: color_out {c['color_out']} "
                        f"nitem {n}\n")
                if n:
                    for lo, gl in zip(c["local"], c["global_"]):
                        f.write(f"    {int(lo)} {int(gl)}\n")

    @property
    def stats(self):
        return self._out_stats


def _count_api_seconds(cls):
    """The setters and getters lie outside ``run`` and a span a call
    would be too fine (``set_vertex`` in a loop): each public
    ``set_*`` / ``get_*`` adds its seconds to ``api.set_s`` /
    ``api.get_s`` (obs.metrics.REGISTRY).  Only the outermost call
    counts, so a getter built on another is not counted twice."""
    import functools
    import threading
    import time

    from ..obs.metrics import REGISTRY
    depth = threading.local()

    def timed(fn, series):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if getattr(depth, "n", 0):
                return fn(*args, **kwargs)
            depth.n = 1
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                depth.n = 0
                # looked up a call (REGISTRY.reset() drops the series)
                # lint: ok(R6) — series is one of the two literals below
                REGISTRY.counter(series).inc(time.perf_counter() - t0)
        return wrapper

    for name, fn in list(vars(cls).items()):
        if callable(fn) and name.startswith("set_"):
            setattr(cls, name, timed(fn, "api.set_s"))
        elif callable(fn) and name.startswith("get_"):
            setattr(cls, name, timed(fn, "api.get_s"))
    return cls


_count_api_seconds(ParMesh)
