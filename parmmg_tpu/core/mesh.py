"""Flat, masked, fixed-capacity mesh arrays — the TPU-native mesh structure.

Replaces the reference's pointer-rich ``MMG5_pMesh`` (linked xtetra/xpoint side
tables, realloc-on-demand, see /root/reference/src/libparmmgtypes.h:286-307 for
how groups wrap it) with a pytree of dense device arrays:

- static *capacity* (array shape) + dynamic *used prefix* + per-slot validity
  masks.  XLA needs static shapes; the Mmg pack/realloc dance
  (``MMG5_paktet``/``PMMG_fitMeshSize``, reference zaldy_pmmg.c:256-492)
  becomes mask-and-compact, with capacity growth done host-side between jitted
  phases (the analogue of the reference's memory budgeting).
- boundary data (Mmg's sparse ``xtetra``/``xpoint``) becomes dense per-face and
  per-edge tag arrays on every tet: regular layout beats sparse side tables on
  a vector machine.
- adjacency ``adja[ne,4]`` stores ``4*neighbor_tet + neighbor_face`` (same
  packing idea as Mmg) or -1 on a boundary face.

All fields are JAX arrays so a Mesh can cross jit boundaries as a pytree.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .constants import IDIR, IARE, MG_BDY, MG_CRN, MG_REQ


@partial(jax.tree_util.register_dataclass,
         data_fields=["vert", "vref", "vtag", "vmask", "vnrm",
                      "tet", "tref", "tmask", "adja",
                      "ftag", "fref", "etag",
                      "npoin", "nelem"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Mesh:
    """A tetrahedral mesh in fixed-capacity device arrays.

    Invalid slots form a suffix after :func:`compact`, but code must only rely
    on the masks. Vertex ids stored in ``tet`` are row indices into ``vert``.
    """
    # -- vertices -----------------------------------------------------------
    vert: jax.Array   # [capP, 3] float coordinates
    vref: jax.Array   # [capP]    int32 reference
    vtag: jax.Array   # [capP]    uint32 MG_* tag bits
    vmask: jax.Array  # [capP]    bool validity
    vnrm: jax.Array   # [capP, 3] carried unit surface normal (Mmg xPoint n1)
    #                   of a frozen seam vertex, whose fan this mesh holds
    #                   only in part; zero = derive it from the fan
    # -- tetrahedra ---------------------------------------------------------
    tet: jax.Array    # [capT, 4] int32 vertex ids
    tref: jax.Array   # [capT]    int32 reference (sub-domain id)
    tmask: jax.Array  # [capT]    bool validity
    adja: jax.Array   # [capT, 4] int32: 4*neigh+face, or -1 (boundary/none)
    # -- boundary / tag side data (dense replacement for xtetra) ------------
    ftag: jax.Array   # [capT, 4] uint32 per-face MG_* tags
    fref: jax.Array   # [capT, 4] int32 per-face surface reference
    etag: jax.Array   # [capT, 6] uint32 per-edge MG_* tags
    # -- dynamic counts (used-prefix hints; authoritative = masks) ----------
    npoin: jax.Array  # scalar int32
    nelem: jax.Array  # scalar int32

    # -- static helpers -----------------------------------------------------
    @property
    def capP(self) -> int:
        return self.vert.shape[0]

    @property
    def capT(self) -> int:
        return self.tet.shape[0]

    @property
    def dtype(self):
        return self.vert.dtype

    def np_counts(self):
        """(#valid points, #valid tets) as concrete ints (host sync)."""
        return int(jnp.sum(self.vmask)), int(jnp.sum(self.tmask))


# canonical field-name tuple for (de)serializing a Mesh as flat arrays
# (npz state handoffs: scripts/scale_big.py, resilience/checkpoint.py)
MESH_FIELDS = tuple(f.name for f in dataclasses.fields(Mesh))


def make_mesh(vert: np.ndarray, tet: np.ndarray,
              vref: np.ndarray | None = None,
              tref: np.ndarray | None = None,
              capP: int | None = None, capT: int | None = None,
              dtype=jnp.float32) -> Mesh:
    """Build a Mesh from host arrays, padding to the given capacities.

    Capacities default to a growth headroom of ~3x points / ~3x tets, the
    analogue of the reference memory-repartition budget
    (zaldy_pmmg.c:140-254) — adaptation inserts points, so headroom is the
    price of static shapes.  The 3x is for a mesh that GROWS IN PLACE (the
    input of the whole-mesh path): every wave sorts, gathers and scatters
    all ``capT`` rows, dead ones included, so a caller whose mesh does not
    grow passes its own capacities (``merge_shards`` does, shards get
    theirs from ``shard_capacity``).
    """
    vert = np.asarray(vert, dtype=np.float64)
    tet = np.asarray(tet, dtype=np.int32)
    n_p, n_t = vert.shape[0], tet.shape[0]
    if capP is None:
        capP = max(64, int(3 * n_p))
    if capT is None:
        capT = max(64, int(3 * n_t))
    if capP < n_p or capT < n_t:
        raise ValueError("capacity smaller than input mesh")
    if n_t and tet.max() >= n_p:
        raise ValueError("tet references nonexistent vertex")

    def pad(a, cap, fill=0, dt=None):
        out = np.full((cap,) + a.shape[1:], fill,
                      dtype=dt if dt is not None else a.dtype)
        out[: a.shape[0]] = a
        return out

    vref = np.zeros(n_p, np.int32) if vref is None else np.asarray(vref, np.int32)
    tref = np.zeros(n_t, np.int32) if tref is None else np.asarray(tref, np.int32)
    vmask = pad(np.ones(n_p, bool), capP, False)
    tmask = pad(np.ones(n_t, bool), capT, False)
    return Mesh(
        vert=jnp.asarray(pad(vert, capP), dtype=dtype),
        vref=jnp.asarray(pad(vref, capP)),
        vtag=jnp.zeros(capP, jnp.uint32),
        vmask=jnp.asarray(vmask),
        vnrm=jnp.zeros((capP, 3), dtype),
        tet=jnp.asarray(pad(tet, capT)),
        tref=jnp.asarray(pad(tref, capT)),
        tmask=jnp.asarray(tmask),
        adja=jnp.full((capT, 4), -1, jnp.int32),
        ftag=jnp.zeros((capT, 4), jnp.uint32),
        fref=jnp.zeros((capT, 4), jnp.int32),
        etag=jnp.zeros((capT, 6), jnp.uint32),
        npoin=jnp.asarray(n_p, jnp.int32),
        nelem=jnp.asarray(n_t, jnp.int32),
    )


def mesh_to_host(mesh: Mesh):
    """Extract compacted (vert, tet, vref, tref) numpy arrays.

    The inverse of :func:`make_mesh`; renumbers vertices densely.  This is the
    analogue of the final ``MMG5_paktet`` + API ``PMMG_Get_*`` readout
    (reference libparmmg1.c:156, API_functions_pmmg.c).
    """
    vmask = np.asarray(mesh.vmask)
    tmask = np.asarray(mesh.tmask)
    vert = np.asarray(mesh.vert)[vmask]
    vref = np.asarray(mesh.vref)[vmask]
    vtag = np.asarray(mesh.vtag)[vmask]
    new_id = np.cumsum(vmask) - 1          # old -> new vertex id
    tet = new_id[np.asarray(mesh.tet)[tmask]].astype(np.int32)
    tref = np.asarray(mesh.tref)[tmask]
    return vert, tet.reshape(-1, 4), vref, tref, vtag


# ---------------------------------------------------------------------------
# Derived element arrays (pure functions of the Mesh pytree)
# ---------------------------------------------------------------------------
_IDIR_J = jnp.asarray(IDIR)
_IARE_J = jnp.asarray(IARE)


def tet_face_vertices(tet: jax.Array) -> jax.Array:
    """[capT, 4, 3] vertex ids of each tet face (face f opposite vertex f)."""
    return tet[:, _IDIR_J]


def tet_edge_vertices(tet: jax.Array) -> jax.Array:
    """[capT, 6, 2] vertex ids of each tet edge."""
    return tet[:, _IARE_J]


def tet_volumes(mesh: Mesh) -> jax.Array:
    """Signed volume of every tet slot (garbage where tmask is False)."""
    p = mesh.vert[mesh.tet]                      # [capT,4,3]
    d1 = p[:, 1] - p[:, 0]
    d2 = p[:, 2] - p[:, 0]
    d3 = p[:, 3] - p[:, 0]
    det = jnp.einsum("ti,ti->t", d1, jnp.cross(d2, d3))
    return det / 6.0


def compact(mesh: Mesh) -> Mesh:
    """Host-side compaction: move valid slots to the front, renumber.

    The analogue of ``PMMG_packParMesh`` (reference libparmmg1.c:195): run
    between jitted phases when the free-slot suffix runs out.  Not jittable on
    purpose (gather with dynamic output size); capacities are preserved.
    """
    vmask = np.asarray(mesh.vmask)
    tmask = np.asarray(mesh.tmask)
    n_p, n_t = int(vmask.sum()), int(tmask.sum())
    vperm = np.argsort(~vmask, kind="stable")    # valid first, order kept
    tperm = np.argsort(~tmask, kind="stable")
    old2new = np.empty(mesh.capP, np.int32)
    old2new[vperm] = np.arange(mesh.capP, dtype=np.int32)

    tet = old2new[np.asarray(mesh.tet)[tperm]]
    # adjacency: renumber neighbor tet ids through tperm
    t_old2new = np.empty(mesh.capT, np.int32)
    t_old2new[tperm] = np.arange(mesh.capT, dtype=np.int32)
    adja = np.asarray(mesh.adja)[tperm]
    nb = adja >> 2
    valid = adja >= 0
    adja = np.where(valid, 4 * t_old2new[np.clip(nb, 0, mesh.capT - 1)]
                    + (adja & 3), -1).astype(np.int32)

    return Mesh(
        vert=jnp.asarray(np.asarray(mesh.vert)[vperm]),
        vref=jnp.asarray(np.asarray(mesh.vref)[vperm]),
        vtag=jnp.asarray(np.asarray(mesh.vtag)[vperm]),
        vmask=jnp.asarray(vmask[vperm]),
        vnrm=jnp.asarray(np.asarray(mesh.vnrm)[vperm]),
        tet=jnp.asarray(tet.astype(np.int32)),
        tref=jnp.asarray(np.asarray(mesh.tref)[tperm]),
        tmask=jnp.asarray(tmask[tperm]),
        adja=jnp.asarray(adja),
        ftag=jnp.asarray(np.asarray(mesh.ftag)[tperm]),
        fref=jnp.asarray(np.asarray(mesh.fref)[tperm]),
        etag=jnp.asarray(np.asarray(mesh.etag)[tperm]),
        npoin=jnp.asarray(n_p, jnp.int32),
        nelem=jnp.asarray(n_t, jnp.int32),
    )


def with_capacity(mesh: Mesh, capP: int, capT: int) -> Mesh:
    """Grow (never shrink below content) the capacities, host-side."""
    mesh = compact(mesh)
    n_p, n_t = mesh.np_counts()
    if capP < n_p or capT < n_t:
        raise ValueError("cannot shrink below live content")

    def grow(a, cap, fill=0):
        a = np.asarray(a)
        out = np.full((cap,) + a.shape[1:], fill, dtype=a.dtype)
        out[: a.shape[0]] = a[:min(a.shape[0], cap)]
        return jnp.asarray(out)

    return Mesh(
        vert=grow(mesh.vert, capP), vref=grow(mesh.vref, capP),
        vtag=grow(mesh.vtag, capP), vmask=grow(mesh.vmask, capP, False),
        vnrm=grow(mesh.vnrm, capP),
        tet=grow(mesh.tet, capT), tref=grow(mesh.tref, capT),
        tmask=grow(mesh.tmask, capT, False), adja=grow(mesh.adja, capT, -1),
        ftag=grow(mesh.ftag, capT), fref=grow(mesh.fref, capT),
        etag=grow(mesh.etag, capT),
        npoin=mesh.npoin, nelem=mesh.nelem,
    )
