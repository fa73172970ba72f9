"""The merged tail's table engine: edge table and adjacency off retained sorts.

The sort-based topology primitives (ops/edges.unique_edges,
ops/adjacency.build_adjacency) sort ALL 6*capT / 4*capT slot keys at
every derivation, also when the stages since the last one rewrote a few
hundred rows of a merged mesh: the regime the host tail (the merged
polish, then the fem rounds) lives in.  The reference never does this:
Mmg maintains its edge/tetra hash tables incrementally across operator
applications (MMG3D_hashTetra, hash_pmmg.c).  This module is the
sort-idiom analogue:

* the *dirty tet set* (rows created, killed or re-verticed) is
  accumulated as a [capT] bool mask — exact by construction, computed as
  an elementwise diff of (tet, tmask) across a stage (``mark_dirty``),
  the ONLY inputs the slot keys depend on;
* at the next table derivation the dirty tets' slots are re-keyed into a
  fixed-width band (the caller's, a function of the capacity alone:
  ``polish_bands``) and merged into the RETAINED sorted key table:
  survivors compact by rank (prefix sum), band entries binary-search
  their insertion position (lexicographic lower bound over the dense
  survivor table), and ONE packed scatter materializes the merged order
  — O(T log B) instead of the O(12T log 12T) full sort;
* no dirty tet at all hands the retained sort back as it is; more dirty
  tets than the widest band, or no retained sort yet, ``lax.cond``-fall
  back to the full sort, so exactness is by construction, never sampled.

Exactness argument (the bit-parity proof the tests pin):
``jnp.argsort``/``jnp.lexsort`` are STABLE, so the full sort's order is
exactly "sort by (key..., slot index)".  Slot keys are pure functions of
the owning tet's (tet row, tmask) — dead and padded slots key to
INT32_MAX — so a slot's key can only change when its tet is dirty.  The
merge partitions slots into survivors (clean, keys unchanged, relative
order retained) and the band (dirty, re-keyed from the current mesh),
and merges them under the SAME (key..., slot) lexicographic order; slot
indices are unique, so the merged permutation is the unique sorted
order, i.e. bit-identical to a fresh stable sort.  Tag payloads (etag)
are NOT retained — the shared epilogue re-gathers them from the current
mesh, so tag updates between two derivations (boundary_edge_tags) need
no dirty marks.

Who carries it: the merged tail, on the host, threads one ``TopoState``
through all its consumers of whole-mesh tables with no knob: the merged
polish from wave to wave (driver._merged_polish ->
ops/adapt.sliver_polish_impl), which hands the state it ends with to
driver._finish_run, whose fem rounds (ops/adapt.fem_pass_impl) derive
their edge table and adjacency off it and hand it from round to round.
A derivation merges at the narrowest of the caller's bands that holds
its dirty set and says whether its table came off the retained sort.
Whatever rewrites rows between two consumers keeps the state true or
drops it: the numpy repair is diffed like a stage (``mark_dirty``), a
regrow permutes the rows and changes the capacity, so the state starts
again (``topo_init``).

Who does not: the cycle blocks (parallel/groups, parallel/dist) sort
their tables in full.  The chip read both arms in the grouped block
(PERF.md section 6, PR 38): with the merge ``block_device_ms`` 303.5 ->
338.8 (iso) and 308.8 -> 348.9 (aniso), the late quiet blocks it was
built for 0.238 -> 0.282 s; PR 46 took that arm out.

The prefix-sum backbone of the merge is ``jnp.cumsum`` — integer adds —
and where the Pallas kernels are on lowers on a TPU to
ops/pallas_kernels.merge_prefix_pallas (8x128-tiled, SMEM carry),
bit-identical.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh, tet_edge_vertices, tet_face_vertices
from ..obs import trace as otrace

_INT32_MAX = 2147483647


def polish_bands(capT: int) -> tuple[int, ...]:
    """The bands the merged polish asks for (ops/adapt.sliver_polish_impl
    with a state), a function of the capacity alone: no setting.  Two
    rungs, because a merge costs what its band is wide, not what it
    holds: a sixteenth of the capacity for the tens to hundreds of rows
    a late wave dirties, a quarter for the thousands the first waves
    leave (iso-growth's second wave meets 5,563 of 47,895; a quarter's
    merge is still under half a full sort: PERF.md, PR 38)."""
    return tuple(sorted({min(capT, max(1024, capT // d))
                         for d in (16, 4)}))


class TopoState(NamedTuple):
    """Retained sorted-table + dirty-band state of one mesh (group slot).

    ``ekey``/``eslot`` are the packed edge sort (sorted keys + the sort
    permutation = original slot ids) retained from the last edge-table
    derivation; ``fk0``/``fkw``/``fslot`` the same for the 2-column face
    sort.  ``eok``/``fok`` gate reuse (False = no retained table — full
    rebuild).  ``edirty``/``fdirty`` accumulate the tets touched since
    the LAST derivation of each table (the edge and face tables are
    consumed at different points of a wave, so the masks reset
    independently)."""
    ekey: jax.Array     # [6*capT] int32 sorted packed edge keys
    eslot: jax.Array    # [6*capT] int32 edge sort permutation
    eok: jax.Array      # [] bool
    edirty: jax.Array   # [capT] bool
    fk0: jax.Array      # [4*capT] int32 sorted face key major column
    fkw: jax.Array      # [4*capT] int32 sorted face key packed minors
    fslot: jax.Array    # [4*capT] int32 face sort permutation
    fok: jax.Array      # [] bool
    fdirty: jax.Array   # [capT] bool


def topo_init(capT: int) -> TopoState:
    """All-zeros state (ok=False: first derivation is a full rebuild)."""
    z = jnp.zeros
    return TopoState(
        ekey=z((6 * capT,), jnp.int32), eslot=z((6 * capT,), jnp.int32),
        eok=z((), bool), edirty=z((capT,), bool),
        fk0=z((4 * capT,), jnp.int32), fkw=z((4 * capT,), jnp.int32),
        fslot=z((4 * capT,), jnp.int32), fok=z((), bool),
        fdirty=z((capT,), bool))


def mark_dirty(topo: TopoState, tet0: jax.Array, tmask0: jax.Array,
               mesh: Mesh) -> TopoState:
    """Accumulate the dirty tet set across one wave: a tet is dirty iff
    its vertex row or liveness changed — exactly the inputs the edge and
    face slot keys depend on, so the mask is exact (never sampled).
    One elementwise diff; over-marking would still be exact (a re-keyed
    clean slot merges to its old position), under-marking cannot
    happen."""
    d = jnp.any(mesh.tet != tet0, axis=1) | (mesh.tmask != tmask0)
    return topo._replace(edirty=topo.edirty | d, fdirty=topo.fdirty | d)


# ---------------------------------------------------------------------------
# the sorted-band merge
# ---------------------------------------------------------------------------

def _prefix_i32(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum — the merge's scan backbone (survivor
    rank compaction + insertion-shift histogram).  TPU lowers to the
    Pallas kernel; every other platform the jnp reference (integer adds:
    bit-identical, parity pinned in tests)."""
    from .pallas_kernels import (use_pallas, pallas_forced,
                                 merge_prefix_pallas)

    def ref(v):
        return jnp.cumsum(v, dtype=jnp.int32)

    if use_pallas():
        from ..utils.jaxcompat import platform_dependent
        off_tpu = (partial(merge_prefix_pallas, interpret=True)
                   if pallas_forced() else ref)
        return platform_dependent(
            x, tpu=partial(merge_prefix_pallas, interpret=False),
            default=off_tpu)
    return ref(x)


def _lower_bound(qkeys, qslot, keys, slot):
    """Lexicographic lower bound of each (qkeys..., qslot) query in the
    dense ascending (keys..., slot) table: the first index whose entry
    compares >= the query.  Static ``bit_length`` iteration count, run
    as a ``fori_loop`` (a program a step long: the polish holds ten
    merges) — O(log n) gathers per query, no data-dependent control
    flow."""
    n = slot.shape[0]

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        mc = jnp.clip(mid, 0, n - 1)
        less = jnp.zeros(qslot.shape, bool)
        eq = jnp.ones(qslot.shape, bool)
        for qk, k in zip(qkeys, keys):
            kv = k[mc]
            less = less | (eq & (kv < qk))
            eq = eq & (kv == qk)
        kv = slot[mc]
        less = less | (eq & (kv < qslot))
        return jnp.where(less, mid + 1, lo), jnp.where(less, hi, mid)

    lohi = (jnp.zeros(qslot.shape, jnp.int32),
            jnp.full(qslot.shape, n, jnp.int32))
    return jax.lax.fori_loop(0, max(1, int(n).bit_length()), step,
                             lohi)[0]


def band_order(bkeys, bslot):
    """Stable band sort permutation, ascending by (bkeys..., bslot) —
    the slot rides as an EXPLICIT trailing sort word because band
    record order differs from slot order."""
    return jnp.lexsort((bslot,) + tuple(bkeys)[::-1])


def merge_sorted_band(keys, slot, sd, bkeys, bslot):
    """Merge a re-keyed dirty band into a retained stable sort.

    ``keys`` (tuple of [n] int32 columns) + ``slot`` [n] are the
    retained sorted table (ascending by (keys..., slot) — what a stable
    sort produces); ``sd`` [n] marks the sorted positions owned by dirty
    tets (tombstones: their keys are stale).  ``bkeys``/``bslot`` [m]
    are the band's fresh records — every slot of every dirty tet, dead
    slots keyed INT32_MAX with their REAL slot id, pad entries keyed
    INT32_MAX with slot INT32_MAX.

    Survivors (~sd) compact by prefix-sum rank into a dense table padded
    with (+inf, +inf) sentinels; the band sorts locally (m << n) and
    each entry lower-bounds its insertion position; the merge-path
    identity (band j lands at pos_j + j, survivor i shifts by the
    inclusive histogram prefix of insertions at <= i) places every live
    record exactly once, and sentinel/pad rows provably land at index
    >= n, where ``mode="drop"`` discards them.  Returns the merged
    (keys tuple, slot) — bit-identical to a fresh stable sort of the
    current keys (module docstring proof)."""
    n = slot.shape[0]
    m = bslot.shape[0]
    nk = len(keys)
    keep = ~sd
    # survivor ranks: dense position = (# keepers at <= i) - 1
    r = _prefix_i32(keep.astype(jnp.int32)) - 1
    tgt = jnp.where(keep, r, n)
    pay = jnp.stack(list(keys) + [slot], axis=1)              # [n, nk+1]
    sur = jnp.full(pay.shape, _INT32_MAX, jnp.int32).at[tgt].set(
        pay, mode="drop", unique_indices=True)
    skeys = [sur[:, j] for j in range(nk)]
    sslot = sur[:, nk]
    # band sort: (keys..., slot) ascending — pads (all INT32_MAX) last
    border = band_order(bkeys, bslot)
    bks = [bk[border] for bk in bkeys]
    bs = bslot[border]
    pos = _lower_bound(bks, bs, skeys, sslot)                 # [m]
    # survivor shift = inclusive prefix of the insertion histogram
    # (pad entries are parked at bin n and excluded from the prefix)
    real = bs != _INT32_MAX
    hist = jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(real, pos, n)].add(1)
    shift = _prefix_i32(hist[:n])
    sur_final = jnp.arange(n, dtype=jnp.int32) + shift
    band_final = pos + jnp.arange(m, dtype=jnp.int32)
    idx = jnp.concatenate([sur_final, band_final])
    pay_all = jnp.concatenate([sur, jnp.stack(bks + [bs], axis=1)])
    merged = jnp.zeros_like(sur).at[idx].set(
        pay_all, mode="drop", unique_indices=True)
    return [merged[:, j] for j in range(nk)], merged[:, nk]


# ---------------------------------------------------------------------------
# band record extraction (profiled as ``band_extract``)
# ---------------------------------------------------------------------------

def edge_band_records(mesh: Mesh, dt: jax.Array):
    """Fresh packed edge keys + slot ids for the 6 edge slots of each
    band tet ``dt`` ([B] int32, capT-padded).  Dead tets key INT32_MAX
    with their REAL slot ids (tombstones); pads (dt == capT) get slot
    INT32_MAX and are dropped by the merge."""
    capT = mesh.capT
    dtc = jnp.clip(dt, 0, capT - 1)
    ev = tet_edge_vertices(mesh.tet[dtc])                    # [B, 6, 2]
    a = jnp.minimum(ev[..., 0], ev[..., 1])
    b = jnp.maximum(ev[..., 0], ev[..., 1])
    live = mesh.tmask[dtc] & (dt < capT)
    key = jnp.where(live[:, None], a * mesh.capP + b, _INT32_MAX)
    slot = jnp.where(
        (dt < capT)[:, None],
        dt[:, None] * 6 + jnp.arange(6, dtype=jnp.int32)[None, :],
        _INT32_MAX)
    return key.reshape(-1), slot.reshape(-1)


def face_band_records(mesh: Mesh, dt: jax.Array):
    """Fresh (major, packed-minor) face keys + slot ids for the 4 face
    slots of each band tet (same conventions as edge_band_records;
    matches ops/adjacency._face_keys' packed branch bit-for-bit)."""
    capT = mesh.capT
    dtc = jnp.clip(dt, 0, capT - 1)
    fv = jnp.sort(tet_face_vertices(mesh.tet[dtc]), axis=-1)  # [B, 4, 3]
    live = mesh.tmask[dtc] & (dt < capT)
    k0 = jnp.where(live[:, None], fv[..., 0], _INT32_MAX)
    kw = jnp.where(live[:, None], fv[..., 1] * mesh.capP + fv[..., 2],
                   _INT32_MAX)
    slot = jnp.where(
        (dt < capT)[:, None],
        dt[:, None] * 4 + jnp.arange(4, dtype=jnp.int32)[None, :],
        _INT32_MAX)
    return k0.reshape(-1), kw.reshape(-1), slot.reshape(-1)


# ---------------------------------------------------------------------------
# table derivations (band-merged or full, one lax.cond each)
# ---------------------------------------------------------------------------

def _narrowest(nd, rungs, merge_at):
    """The merge at the narrowest rung that holds ``nd`` dirty tets
    (``nd`` <= the widest: the caller's gate).  One rung is that merge
    itself, the program a single band always traced."""
    if len(rungs) == 1:
        return merge_at(rungs[0])
    which = sum((nd > b).astype(jnp.int32) for b in rungs[:-1])
    return lambda _: jax.lax.switch(
        which, [merge_at(b) for b in rungs], None)


# A jitted function a kind of table, because a polish wave derives an
# edge table in three places and an adjacency in two, and then traces,
# lowers and holds each kind once
@partial(jax.jit, static_argnames=("shell_slots", "band"))
def incr_unique_edges(mesh: Mesh, topo: TopoState, *,
                      shell_slots: int = 0, band: tuple[int, ...]):
    """EdgeTable via the retained sort: band-merge when the state is
    valid and the dirty set fits the band; otherwise the full packed
    sort (bit-identical to ops/edges.unique_edges either way — both
    feed the SAME shared epilogue).  Consumes ``edirty``.
    ``band``: the band's widths in tets, ascending, of which a
    derivation merges at the narrowest that holds its dirty set
    (``polish_bands``).
    Returns (EdgeTable, new TopoState, did the table come off the
    retained sort: merge or reuse)."""
    from .edges import (PACK_LIMIT, sort_carry, unique_edges,
                        unique_edges_from_sorted)
    with otrace.scope("tab.edges"):
        capT = mesh.capT
        n6 = capT * 6
        if mesh.capP > PACK_LIMIT:
            # the merge needs single-int32 packed keys; oversized id spaces
            # keep the exact legacy path (never reached at group shapes)
            et = unique_edges(mesh, shell_slots=shell_slots)
            topo = topo._replace(eok=jnp.zeros((), bool),
                                 edirty=jnp.zeros(capT, bool))
            return et, topo, jnp.zeros((), bool)
        nd = jnp.sum(topo.edirty, dtype=jnp.int32)
        use_band = topo.eok & (nd <= band[-1])
        etag6 = mesh.etag.reshape(n6)

        def _full(_):
            ev = tet_edge_vertices(mesh.tet).reshape(n6, 2)
            a = jnp.minimum(ev[:, 0], ev[:, 1])
            b = jnp.maximum(ev[:, 0], ev[:, 1])
            valid = jnp.repeat(mesh.tmask, 6)
            key = jnp.where(valid, a * mesh.capP + b, _INT32_MAX)
            # the full sort carries the tags; the state keeps none
            order, (ks,), (tags,) = sort_carry((key,), (etag6,))
            return ks, order, tags

        def _band(_):
            def _reuse(_):
                # zero dirty tets since the last derivation: the retained
                # sort IS the fresh sort (keys depend only on tet/tmask) —
                # the round that finds no candidate, the late quiet wave
                return topo.ekey, topo.eslot

            def _merge_at(B):
                def _merge(_):
                    sd = topo.edirty[topo.eslot // 6]
                    dt = jnp.nonzero(topo.edirty, size=B,
                                     fill_value=capT)[0].astype(jnp.int32)
                    bkey, bslot = edge_band_records(mesh, dt)
                    (ks,), order = merge_sorted_band(
                        (topo.ekey,), topo.eslot, sd, (bkey,), bslot)
                    return ks, order
                return _merge
            ks, order = jax.lax.cond(nd == 0, _reuse,
                                     _narrowest(nd, band, _merge_at), None)
            # a merged sort has no sort for the tags to ride in
            return ks, order, etag6[order]

        ks, order, tags = jax.lax.cond(use_band, _band, _full, None)
        et = unique_edges_from_sorted(mesh, order, ks,
                                      shell_slots=shell_slots, tags=tags)
        topo = topo._replace(ekey=ks, eslot=order,
                             eok=jnp.ones((), bool),
                             edirty=jnp.zeros(capT, bool))
        return et, topo, use_band


@partial(jax.jit, static_argnames=("band",))
def incr_build_adjacency(mesh: Mesh, topo: TopoState, *,
                         band: tuple[int, ...]):
    """Adjacency (and boundary tags) via the retained face sort — the
    incremental form of ops/adjacency.build_adjacency, re-deriving
    twins only where the band touched (merged face records feed the
    SAME pairing epilogue).  Consumes ``fdirty``.  ``band``: as
    :func:`incr_unique_edges`.  Returns (mesh with adja/ftag, new
    TopoState, off the retained sort?)."""
    from .edges import PACK_LIMIT, sort_carry
    from .adjacency import (_face_keys, adjacency_from_records,
                            build_adjacency, face_records_from_sorted,
                            pack_minor)
    with otrace.scope("tab.adjacency"):
        capT = mesh.capT
        if mesh.capP > PACK_LIMIT:
            mesh = build_adjacency(mesh)
            topo = topo._replace(fok=jnp.zeros((), bool),
                                 fdirty=jnp.zeros(capT, bool))
            return mesh, topo, jnp.zeros((), bool)
        nd = jnp.sum(topo.fdirty, dtype=jnp.int32)
        use_band = topo.fok & (nd <= band[-1])

        def _full(_):
            cols = _face_keys(mesh)
            order, (k0, kw), _ = sort_carry(
                (cols[:, 0], pack_minor(cols, mesh.capP)))
            return k0, kw, order

        def _band(_):
            def _reuse(_):
                return topo.fk0, topo.fkw, topo.fslot

            def _merge_at(B):
                def _merge(_):
                    sd = topo.fdirty[topo.fslot // 4]
                    dt = jnp.nonzero(topo.fdirty, size=B,
                                     fill_value=capT)[0].astype(jnp.int32)
                    bk0, bkw, bslot = face_band_records(mesh, dt)
                    (k0, kw), order = merge_sorted_band(
                        (topo.fk0, topo.fkw), topo.fslot, sd, (bk0, bkw),
                        bslot)
                    return k0, kw, order
                return _merge
            return jax.lax.cond(nd == 0, _reuse,
                                _narrowest(nd, band, _merge_at), None)

        k0, kw, order = jax.lax.cond(use_band, _band, _full, None)
        t, f, tp, fp, matched, _ = face_records_from_sorted(order, k0, kw)
        mesh = adjacency_from_records(mesh, t, f, tp, fp, matched)
        topo = topo._replace(fk0=k0, fkw=kw, fslot=order,
                             fok=jnp.ones((), bool),
                             fdirty=jnp.zeros(capT, bool))
        return mesh, topo, use_band
