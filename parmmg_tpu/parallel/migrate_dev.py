"""Device-resident band migration — the O(band + interface) host path.

The round-2/3 incremental migration (parallel/migrate.py) made the
DEVICE traffic O(band), but the host still pulled every shard's full
arrays each outer iteration (``pull_views``), re-scanned every live
tet's faces (``recompute_interface``) and re-derived tag membership at
full width (``_retag_interfaces``) — the host-side scaling ceiling the
reference never has: ParMmg's loop touches only moving groups and
OLDPARBDY entities (/root/reference/src/distributegrps_pmmg.c:1631-1841,
analys_pmmg.c:1571).

This module moves the whole between-iteration pipeline onto the device:

  - ``device_migrate``: donor floor (deepest-flood-layer-first, the
    moveinterfaces_pmmg.c:1343 front-order semantics), band compaction,
    cross-shard package transfer (XLA inserts the all-to-all over the
    sharded axis), arrival resolution by global id including slot
    resurrection, vertex-slot allocation, liveness, and the session
    numbering extension — ONE jitted program, all static shapes.
  - ``exposed_face_probe``: per-shard exposed-face tables (global-id
    triples), compacted to an interface-sized budget on device.

The host sees only compacted, band/interface-sized tables: arrival
(row, gid) pairs, fresh-id assignments, exposed-face keys, and tag
values at (old ∪ new) interface slots.  Budget overflows set ``ok=False``
and the caller falls back to the full-view path (parallel/migrate.py),
which remains the correctness oracle (tests/test_band_path.py asserts
end-state parity between the two paths).

Global ids ride int32 on device: the session counter is monotonic and
stays far below 2^31 for any mesh this single-controller path hosts
(10M tets x a few ids/tet/iteration); the host mirror stays int64.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .multihost import pull_host as _pull
from ..core.mesh import Mesh
from ..obs import trace as otrace
from ..core.constants import IDIR
from ..utils.compilecache import bucket, governed

_I32MAX = 2147483647


# ---------------------------------------------------------------------------
# the migration program
# ---------------------------------------------------------------------------
# NO donation: on a budget overflow (ok=False) the caller falls back to
# the full-view path with the ORIGINAL arrays — donating them here would
# hand back deleted buffers exactly on that path
@governed("migrate_dev.device_migrate", budget=4)
@partial(jax.jit, static_argnames=("KB", "KV"))
def device_migrate(stacked: Mesh, met_s, glo_d, labels, depth,
                   KB: int, KV: int):
    """Apply the displaced partition on device.

    ``glo_d``: [S, capP] int32 global vertex ids (-1 dead).
    ``labels``/``depth``: flood output [S, capT].
    ``KB``: max moved tets per shard (and max arrivals per shard);
    ``KV``: max new vertex rows per shard.

    Returns (stacked', met', glo_d', info) with info = dict of
      ok          scalar bool — every budget respected; when False the
                  outputs are UNDEFINED and the caller must fall back
      nmoved      scalar int32 total moved tets
      arr_rows/arr_gids [S, KV] newly-allocated vertex rows (-1 pad)
      dep_slots   [S, KB] departed tet slots (capT pad)
      arr_slots   [S, KB] arrival tet slots (capT pad)
    """
    S, capT = stacked.tet.shape[:2]
    capP = stacked.vert.shape[1]
    me = jnp.arange(S, dtype=jnp.int32)[:, None]
    live = stacked.tmask
    nlive = jnp.sum(live, axis=1)

    # ---- donor floor: revert deepest flood layers first -----------------
    floor = jnp.minimum(6, nlive // 2 + 1)
    moved0 = live & (labels != me)
    nmove0 = jnp.sum(moved0, axis=1)
    excess = jnp.maximum(0, nmove0 - (nlive - floor))
    ordd = jnp.argsort(jnp.where(moved0, -depth, _I32MAX), axis=1,
                       stable=True)
    rank = jnp.zeros((S, capT), jnp.int32).at[
        jnp.arange(S)[:, None], ordd].set(
        jnp.broadcast_to(jnp.arange(capT, dtype=jnp.int32), (S, capT)))
    revert = moved0 & (rank < excess[:, None])
    labels = jnp.where(revert, me, labels)
    moved = moved0 & ~revert
    nmove = jnp.sum(moved, axis=1)
    nmoved = jnp.sum(nmove)
    ok = jnp.all(nmove <= KB)

    # ---- band compaction + cross-shard pool -----------------------------
    midx = jax.vmap(lambda m: jnp.nonzero(m, size=KB,
                                          fill_value=capT)[0])(moved)
    mvalid = midx < capT
    mslot = jnp.clip(midx, 0, capT - 1)
    src2 = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None],
                            (S, KB))
    mdst = jnp.where(mvalid,
                     labels[src2, mslot].astype(jnp.int32), S)
    P = S * KB
    p_src = src2.reshape(P)
    p_slot = mslot.reshape(P)
    p_dst = mdst.reshape(P)
    # payload gathers (cross-shard reads; the sharded axis makes this the
    # band all-to-all)
    p_tet = stacked.tet[p_src, p_slot]                     # [P,4] local
    p_gt = glo_d[p_src[:, None], jnp.clip(p_tet, 0, capP - 1)]
    p_tref = stacked.tref[p_src, p_slot]
    p_ftag = stacked.ftag[p_src, p_slot]
    p_fref = stacked.fref[p_src, p_slot]
    p_etag = stacked.etag[p_src, p_slot]
    p_vert = stacked.vert[p_src[:, None], jnp.clip(p_tet, 0, capP - 1)]
    p_vtag = stacked.vtag[p_src[:, None], jnp.clip(p_tet, 0, capP - 1)]
    p_vref = stacked.vref[p_src[:, None], jnp.clip(p_tet, 0, capP - 1)]
    p_met = met_s[p_src[:, None], jnp.clip(p_tet, 0, capP - 1)]

    # sort the pool by destination -> contiguous per-recipient segments
    ordp = jnp.argsort(p_dst, stable=True)
    sdst = p_dst[ordp]
    seg_start = jnp.searchsorted(sdst, jnp.arange(S, dtype=sdst.dtype))
    seg_cnt = jnp.searchsorted(
        sdst, jnp.arange(S, dtype=sdst.dtype), side="right") - seg_start
    ok = ok & jnp.all(seg_cnt <= KB)

    def take_seg(arr):
        """[P, ...] sorted-pool array -> [S, KB, ...] per recipient.

        The sorted pool is padded by KB rows so a segment starting near
        the end never clamps (a clamped dynamic_slice would shift the
        segment and misalign the validity mask)."""
        s_arr = arr[ordp]
        pad = jnp.zeros((KB,) + arr.shape[1:], arr.dtype)
        s_arr = jnp.concatenate([s_arr, pad], axis=0)

        def one(start):
            return jax.lax.dynamic_slice_in_dim(s_arr, start, KB, axis=0)
        return jax.vmap(one)(seg_start)

    apos = jnp.arange(KB)[None, :]
    avalid = apos < seg_cnt[:, None]                       # [S,KB]
    a_gt = jnp.where(avalid[..., None], take_seg(p_gt), -1)
    a_tref = take_seg(p_tref)
    a_ftag = take_seg(p_ftag)
    a_fref = take_seg(p_fref)
    a_etag = take_seg(p_etag)
    a_vert = take_seg(p_vert)                              # [S,KB,4,3]
    a_vtag = take_seg(p_vtag)
    a_vref = take_seg(p_vref)
    a_met = take_seg(p_met)

    # ---- departures ------------------------------------------------------
    tmask1 = live & ~moved

    # ---- arrival vertex resolution by global id -------------------------
    # recipient's current gid -> row table (dead rows sort last)
    gkey = jnp.where(glo_d >= 0, glo_d, _I32MAX)
    gord = jnp.argsort(gkey, axis=1)                       # [S,capP]
    gsorted = jnp.take_along_axis(gkey, gord, axis=1)
    A4 = KB * 4
    agid = a_gt.reshape(S, A4)
    a4valid = agid >= 0
    pos = jax.vmap(jnp.searchsorted)(gsorted, jnp.where(a4valid, agid, 0))
    posc = jnp.clip(pos, 0, capP - 1)
    found = a4valid & (jnp.take_along_axis(gsorted, posc, 1) == agid)
    found_row = jnp.take_along_axis(gord, posc, 1)         # [S,A4]

    # unique missing gids per shard: sort, head-detect, allocate
    mkey = jnp.where(a4valid & ~found, agid, _I32MAX)
    mord = jnp.argsort(mkey, axis=1)
    msort = jnp.take_along_axis(mkey, mord, axis=1)
    mhead = jnp.concatenate(
        [jnp.ones((S, 1), bool), msort[:, 1:] != msort[:, :-1]], axis=1)
    mhead = mhead & (msort != _I32MAX)
    n_new = jnp.sum(mhead, axis=1)                         # [S]
    # free vertex rows (ascending)
    fidx = jax.vmap(lambda g: jnp.nonzero(g < 0, size=KV,
                                          fill_value=capP)[0])(glo_d)
    nfree = jnp.sum(glo_d < 0, axis=1)
    ok = ok & jnp.all(n_new <= KV) & jnp.all(n_new <= nfree)
    alloc_ord = jnp.cumsum(mhead, axis=1) - 1              # [S,A4]
    new_row_sorted = jnp.where(
        mhead, jnp.take_along_axis(
            fidx, jnp.clip(alloc_ord, 0, KV - 1), 1), capP)
    # broadcast the head's row to its duplicates (same gid, same segment)
    seg_id = jnp.cumsum(mhead, axis=1) - 1
    head_row_of_seg = jnp.full((S, A4), -1, jnp.int32).at[
        jnp.arange(S)[:, None],
        jnp.where(mhead, seg_id, A4)].max(
        new_row_sorted.astype(jnp.int32), mode="drop")
    row_sorted = head_row_of_seg[jnp.arange(S)[:, None],
                                 jnp.clip(seg_id, 0, A4 - 1)]
    # unsort back to arrival-corner order
    row_missing = jnp.zeros((S, A4), jnp.int32).at[
        jnp.arange(S)[:, None], mord].set(row_sorted)
    a_row = jnp.where(found, found_row, row_missing)       # [S,A4]
    a_row = jnp.where(a4valid, a_row, capP)

    # ---- scatter new vertex rows ----------------------------------------
    # payload source: the sorted head corners (first occurrence wins)
    pay_corner = mord                                       # [S,A4] corner
    vsrc = jnp.clip(pay_corner, 0, A4 - 1)
    tgt_new = jnp.where(mhead, new_row_sorted, capP)        # [S,A4]
    sidx = jnp.arange(S)[:, None]
    av_flat = a_vert.reshape(S, A4, 3)
    at_flat = a_vtag.reshape(S, A4)
    ar_flat = a_vref.reshape(S, A4)
    am_flat = a_met.reshape(S, A4, *a_met.shape[3:])
    vert2 = stacked.vert.at[sidx, tgt_new].set(
        jnp.take_along_axis(av_flat, vsrc[..., None], 1), mode="drop")
    vtag2 = stacked.vtag.at[sidx, tgt_new].set(
        jnp.take_along_axis(at_flat, vsrc, 1), mode="drop")
    vref2 = stacked.vref.at[sidx, tgt_new].set(
        jnp.take_along_axis(ar_flat, vsrc, 1), mode="drop")
    if am_flat.ndim == 2:
        met2 = met_s.at[sidx, tgt_new].set(
            jnp.take_along_axis(am_flat, vsrc, 1), mode="drop")
    else:
        met2 = met_s.at[sidx, tgt_new].set(
            jnp.take_along_axis(am_flat, vsrc[..., None], 1), mode="drop")
    glo2 = glo_d.at[sidx, tgt_new].set(
        jnp.where(mhead, msort, 0).astype(jnp.int32), mode="drop")

    # ---- place arrival tets in free slots -------------------------------
    tfree = jax.vmap(lambda m: jnp.nonzero(~m, size=KB,
                                           fill_value=capT)[0])(tmask1)
    nfree_t = jnp.sum(~tmask1, axis=1)
    ok = ok & jnp.all(seg_cnt <= nfree_t)
    arr_slot = jnp.where(avalid, tfree[:, :KB], capT)      # [S,KB]
    lt = a_row.reshape(S, KB, 4).astype(jnp.int32)
    lt = jnp.clip(lt, 0, capP - 1)
    tet2 = stacked.tet.at[sidx, arr_slot].set(lt, mode="drop")
    tref2 = stacked.tref.at[sidx, arr_slot].set(a_tref, mode="drop")
    ftag2 = stacked.ftag.at[sidx, arr_slot].set(a_ftag, mode="drop")
    fref2 = stacked.fref.at[sidx, arr_slot].set(a_fref, mode="drop")
    etag2 = stacked.etag.at[sidx, arr_slot].set(a_etag, mode="drop")
    tmask2 = tmask1.at[sidx, arr_slot].set(True, mode="drop")

    # ---- liveness + watermarks ------------------------------------------
    tid = jnp.where(tmask2[..., None], tet2, capP)
    # (row index [S, 1] against [S, 4 capT]: one more axis on it would
    # broadcast shard against shard and mark every shard's rows live in
    # every other)
    ref = jnp.zeros((S, capP + 1), bool).at[
        sidx, tid.reshape(S, -1)].max(
        True, mode="drop")[:, :capP]
    vmask2 = ref
    glo2 = jnp.where(ref, glo2, -1)
    rowsP = jnp.broadcast_to(jnp.arange(capP, dtype=jnp.int32),
                             (S, capP))
    npoin2 = jnp.max(jnp.where(ref, rowsP + 1, 0), axis=1)
    rowsT = jnp.broadcast_to(jnp.arange(capT, dtype=jnp.int32),
                             (S, capT))
    nelem2 = jnp.max(jnp.where(tmask2, rowsT + 1, 0), axis=1)

    out = dataclasses.replace(
        stacked, vert=vert2, vtag=vtag2, vref=vref2, vmask=vmask2,
        tet=tet2, tref=tref2, tmask=tmask2, ftag=ftag2, fref=fref2,
        etag=etag2, npoin=npoin2.astype(jnp.int32),
        nelem=nelem2.astype(jnp.int32))
    # newly-allocated vertex rows, compacted to [S, KV] for the host glo
    # mirror sync
    alloc_tgt = jnp.where(mhead, jnp.clip(alloc_ord, 0, KV - 1), KV)
    arr_rows = jnp.full((S, KV), -1, jnp.int32).at[sidx, alloc_tgt].set(
        new_row_sorted.astype(jnp.int32), mode="drop")
    arr_gids = jnp.full((S, KV), -1, jnp.int32).at[sidx, alloc_tgt].set(
        msort.astype(jnp.int32), mode="drop")
    # newly-DEAD vertex rows (id-carrying before, unreferenced after the
    # departures), compacted: the band-sized liveness DELTA that lets
    # the host glo mirror sync without an O(mesh) vmask allgather
    # (migrate.kill_glo_rows; dying rows are vertices of departed tets,
    # so the KV budget that bounds arrivals bounds them too — overflow
    # joins the ok fallback like every other budget)
    newly_dead = (glo_d >= 0) & ~ref
    n_dead = jnp.sum(newly_dead, axis=1)
    ok = ok & jnp.all(n_dead <= KV)
    dead_rows = jax.vmap(lambda m: jnp.nonzero(m, size=KV,
                                               fill_value=capP)[0])(
        newly_dead).astype(jnp.int32)
    info = dict(ok=ok, nmoved=nmoved, arr_rows=arr_rows,
                arr_gids=arr_gids, dep_slots=midx,
                arr_slots=arr_slot, labels=labels,
                dead_rows=dead_rows, dead_cnt=n_dead.astype(jnp.int32),
                # per-condition diagnostics (which budget blew)
                ok_parts=jnp.stack([
                    jnp.all(nmove <= KB), jnp.all(seg_cnt <= KB),
                    jnp.all(n_new <= KV), jnp.all(n_new <= nfree),
                    jnp.all(seg_cnt <= nfree_t),
                    jnp.all(n_dead <= KV)]))
    return out, met2, glo2, info


# ---------------------------------------------------------------------------
# exposed-face probe
# ---------------------------------------------------------------------------
@governed("migrate_dev.exposed_face_probe", budget=4)
@partial(jax.jit, static_argnames=("KF",))
def exposed_face_probe(stacked: Mesh, glo_d, KF: int):
    """Per-shard exposed faces as global-id triples, device-compacted.

    Returns (keys [S, KF, 3] int32 sorted-gid triples (-1 pad),
             slots [S, KF] int32 4*tet+face (capT*4 pad),
             cnt [S], ok scalar bool).
    """
    S, capT = stacked.tet.shape[:2]
    capP = stacked.vert.shape[1]
    idir = jnp.asarray(IDIR)

    def one(tet, tmask, glo_s):
        gtet = glo_s[jnp.clip(tet, 0, capP - 1)]           # [capT,4]
        tri = jnp.sort(gtet[:, idir], axis=2).reshape(capT * 4, 3)
        valid = jnp.repeat(tmask, 4)
        c0 = jnp.where(valid, tri[:, 0], _I32MAX)
        c1 = jnp.where(valid, tri[:, 1], _I32MAX)
        c2 = jnp.where(valid, tri[:, 2], _I32MAX)
        order = jnp.lexsort((c2, c1, c0))
        k0, k1, k2 = c0[order], c1[order], c2[order]
        eq_next = (k0[1:] == k0[:-1]) & (k1[1:] == k1[:-1]) & \
            (k2[1:] == k2[:-1]) & (k0[:-1] != _I32MAX)
        same_next = jnp.concatenate([eq_next, jnp.array([False])])
        same_prev = jnp.concatenate([jnp.array([False]), eq_next])
        exposed_s = ~(same_next | same_prev) & (k0 != _I32MAX)
        slot4 = order.astype(jnp.int32)      # flat index IS 4*tet+face
        cnt = jnp.sum(exposed_s, dtype=jnp.int32)
        sel = jnp.nonzero(exposed_s, size=KF, fill_value=capT * 4)[0]
        selc = jnp.clip(sel, 0, capT * 4 - 1)
        keys = jnp.where((sel < capT * 4)[:, None],
                         jnp.stack([k0, k1, k2], 1)[selc], -1)
        slots = jnp.where(sel < capT * 4, slot4[selc], capT * 4)
        return keys, slots, cnt

    keys, slots, cnt = jax.vmap(one)(stacked.tet, stacked.tmask, glo_d)
    return keys, slots, cnt, jnp.all(cnt <= KF)


# ---------------------------------------------------------------------------
# freeze / unfreeze retag, fully on device
# ---------------------------------------------------------------------------
def _freeze_bits_j(tags, is_edge_or_vert: bool, true_bdy=None):
    """jnp mirror of migrate._freeze_bits (tag_pmmg.c:39-124 contract)."""
    from ..core.constants import (PARBDY_TAGS, MG_REQ, MG_NOSURF, MG_BDY,
                                  MG_PARBDYBDY)
    user_req = (tags & MG_REQ) != 0
    out = tags | PARBDY_TAGS
    if is_edge_or_vert:
        tb = (tags & MG_BDY) != 0 if true_bdy is None else true_bdy
        out = jnp.where(tb, out | MG_PARBDYBDY, out)
    out = jnp.where(user_req, out & ~jnp.uint32(MG_NOSURF), out)
    return out


def _unfreeze_bits_j(tags, is_edge_or_vert: bool):
    """jnp mirror of migrate._unfreeze_bits (no MG_OLDPARBDY — see the
    rationale in migrate._unfreeze_bits)."""
    from ..core.constants import (PARBDY_TAGS, MG_REQ, MG_NOSURF, MG_BDY,
                                  MG_PARBDY, MG_PARBDYBDY)
    was = (tags & MG_PARBDY) != 0
    user_req = was & ((tags & MG_NOSURF) == 0) & ((tags & MG_REQ) != 0)
    true_bdy = was & ((tags & MG_PARBDYBDY) != 0)
    out = jnp.where(was,
                    tags & ~jnp.uint32(PARBDY_TAGS | MG_PARBDYBDY), tags)
    if is_edge_or_vert:
        out = jnp.where(true_bdy, out | MG_BDY, out)
    out = jnp.where(user_req, out | MG_REQ, out)
    return out


@governed("migrate_dev.retag_device", budget=2)
@partial(jax.jit, donate_argnums=(0,))
def retag_device(stacked: Mesh, glo_d, ifc_slots, ifc_vrows):
    """Reconcile freeze tags with the NEW interface, on device.

    ``ifc_slots`` [S, KF2] int32 4*tet+face slots of the new interface
    (pad capT*4); ``ifc_vrows`` [S, KN] shared-vertex rows (pad capP).
    Faces/vertices: membership by slot/row.  Edges: every local slot of
    a geometric edge of any interface face must (un)freeze — membership
    resolved with a per-shard 2-column sort-join on global edge keys
    (the _retag_interfaces in_new computation, device-resident).
    """
    from ..core.constants import (IARE, FACE_EDGES, MG_PARBDY)
    S, capT = stacked.tet.shape[:2]
    capP = stacked.vert.shape[1]
    sidx = jnp.arange(S)[:, None]
    KF2 = ifc_slots.shape[1]
    iare = jnp.asarray(IARE)
    fedges = jnp.asarray(FACE_EDGES)                       # [4,3]

    # ---- faces ----
    slot_ifc = jnp.zeros((S, capT * 4), bool).at[
        sidx, jnp.where(ifc_slots < capT * 4, ifc_slots, capT * 4)].set(
        True, mode="drop", unique_indices=True).reshape(S, capT, 4)
    tm = stacked.tmask
    cur_f = ((stacked.ftag & MG_PARBDY) != 0) & tm[..., None]
    ftag = jnp.where(slot_ifc & ~cur_f,
                     _freeze_bits_j(stacked.ftag, False), stacked.ftag)
    ftag = jnp.where(cur_f & ~slot_ifc,
                     _unfreeze_bits_j(ftag, False), ftag)

    # ---- edges ----
    def one_shard(tet, tmask, glo_s, slot_ifc_s, etag_s):
        gtet = glo_s[jnp.clip(tet, 0, capP - 1)]           # [capT,4]
        ev = jnp.sort(gtet[:, iare], axis=2)               # [capT,6,2]
        ka = ev[..., 0].reshape(-1)
        kb = ev[..., 1].reshape(-1)
        n6 = capT * 6
        valid = jnp.repeat(tmask, 6)
        # interface-edge markers: the 3 edges of every interface face
        mark = jnp.zeros((capT, 6), bool)
        for f in range(4):
            for j in range(3):
                # lint: ok(R2) — FACE_EDGES is a static host table;
                # constant fold at trace time, no device sync
                e = int(FACE_EDGES[f, j])
                mark = mark.at[:, e].set(
                    mark[:, e] | slot_ifc_s[:, f])
        mark = mark.reshape(-1) & valid
        # 2-col sort join: does my (ka,kb) match ANY marked slot?
        ordj = jnp.lexsort((jnp.where(valid, kb, _I32MAX),
                            jnp.where(valid, ka, _I32MAX)))
        ka_s = jnp.where(valid, ka, _I32MAX)[ordj]
        kb_s = jnp.where(valid, kb, _I32MAX)[ordj]
        first = jnp.concatenate(
            [jnp.array([True]),
             (ka_s[1:] != ka_s[:-1]) | (kb_s[1:] != kb_s[:-1])])
        seg = jax.lax.associative_scan(
            jnp.maximum, jnp.where(first, jnp.arange(n6), 0))
        mk_s = mark[ordj].astype(jnp.int32)
        # segment OR: total at every member via max-scan + head gather
        def seg_or(pa, pb):
            fa, va = pa
            fb, vb = pb
            return fa | fb, jnp.where(fb, vb, va | vb)
        _, or_run = jax.lax.associative_scan(seg_or, (first, mk_s))
        is_last = jnp.concatenate([first[1:], jnp.array([True])])
        tot = jnp.zeros(n6, jnp.int32).at[
            jnp.where(is_last, seg, n6)].set(
            or_run, mode="drop", unique_indices=True)
        in_new_s = tot[seg] > 0
        in_new = jnp.zeros(n6, bool).at[ordj].set(
            in_new_s, unique_indices=True).reshape(capT, 6)
        in_new = in_new & tmask[:, None]
        cur = ((etag_s & MG_PARBDY) != 0) & tmask[:, None]
        out = jnp.where(in_new & ~cur,
                        _freeze_bits_j(etag_s, True), etag_s)
        out = jnp.where(cur & ~in_new, _unfreeze_bits_j(out, True), out)
        return out

    etag = jax.vmap(one_shard)(stacked.tet, stacked.tmask, glo_d,
                               slot_ifc, stacked.etag)

    # ---- vertices ----
    new_v = jnp.zeros((S, capP), bool).at[
        sidx, jnp.where(ifc_vrows < capP, ifc_vrows, capP)].set(
        True, mode="drop", unique_indices=True)
    cur_v = ((stacked.vtag & MG_PARBDY) != 0) & stacked.vmask
    vtag = jnp.where(new_v & ~cur_v,
                     _freeze_bits_j(stacked.vtag, True), stacked.vtag)
    vtag = jnp.where(cur_v & ~new_v, _unfreeze_bits_j(vtag, True), vtag)

    return dataclasses.replace(stacked, ftag=ftag, etag=etag, vtag=vtag)


# ---------------------------------------------------------------------------
# band-scoped weld region probe
# ---------------------------------------------------------------------------
@governed("migrate_dev.band_region_probe", budget=4)
@partial(jax.jit, static_argnames=("KW", "KWp"))
def band_region_probe(stacked: Mesh, glo_d, seed_tets, KW: int, KWp: int):
    """Tets/vertices within one ring of the seed tet rows, compacted.

    ``seed_tets`` [S, KB] local tet slots (pad >= capT) — the migration
    arrival tets (their vertices span the whole band, including the old
    now-interior interface where the duplicate pairs live).  Returns
    (trow [S,KW], vrow [S,KWp], tcnt, vcnt, v_open [S,KWp] bool —
    vertex has an incident tet OUTSIDE the region (must not be welded
    away), ok)."""
    S, capT = stacked.tet.shape[:2]
    capP = stacked.vert.shape[1]
    sidx = jnp.arange(S)[:, None]
    seedc = jnp.clip(seed_tets, 0, capT - 1)
    seed_ok = (seed_tets < capT)[..., None]                # [S,KB,1]
    seed_vids = jnp.where(seed_ok, stacked.tet[sidx, seedc], capP)
    vmark = jnp.zeros((S, capP + 1), bool).at[
        sidx, seed_vids.reshape(S, -1)].max(
        True, mode="drop")[:, :capP]
    tc = jnp.clip(stacked.tet, 0, capP - 1)

    def ring(vm):
        touch = jnp.any(vm[sidx[..., None], tc], axis=2) & stacked.tmask
        vm2 = jnp.zeros((S, capP + 1), bool).at[
            sidx[..., None],
            jnp.where(touch[..., None], stacked.tet, capP)].max(
            True, mode="drop")[:, :capP]
        return touch, vm | vm2

    _, vm1 = ring(vmark)
    touch2, vm2 = ring(vm1)
    tcnt = jnp.sum(touch2, axis=1)
    vcnt = jnp.sum(vm2 & stacked.vmask, axis=1)
    ok = jnp.all(tcnt <= KW) & jnp.all(vcnt <= KWp)
    trow = jax.vmap(lambda m: jnp.nonzero(m, size=KW,
                                          fill_value=capT)[0])(touch2)
    vrow = jax.vmap(lambda m: jnp.nonzero(m, size=KWp,
                                          fill_value=capP)[0])(
        vm2 & stacked.vmask)
    # vertices with an incident tet outside the region stay frozen for
    # the weld (rewriting them would dangle the outside tets)
    outside = stacked.tmask & ~touch2
    vopen = jnp.zeros((S, capP + 1), bool).at[
        sidx[..., None],
        jnp.where(outside[..., None], stacked.tet, capP)].max(
        True, mode="drop")[:, :capP]
    v_open = vopen[sidx, jnp.clip(vrow, 0, capP - 1)]
    return trow, vrow, tcnt, vcnt, v_open, ok


@governed("migrate_dev.extend_ids_device", budget=2)
@partial(jax.jit, static_argnames=("KN",))
def extend_ids_device(glo_d, vmask, top, KN: int):
    """Assign fresh global ids to adapt-created vertices on device.

    Fresh = live rows with glo<0; ids are a disjoint block per shard
    starting at ``top`` (same assignment the host extend_global_ids
    makes: ascending row order within a shard, shards in order).
    Returns (glo', new_top, fresh_rows [S,KN], fresh_gids [S,KN], ok)."""
    S, capP = glo_d.shape
    fresh = vmask & (glo_d < 0)
    nf = jnp.sum(fresh, axis=1)
    ok = jnp.all(nf <= KN)
    base = top + jnp.concatenate(
        [jnp.zeros(1, nf.dtype), jnp.cumsum(nf)[:-1]])
    rows = jax.vmap(lambda m: jnp.nonzero(m, size=KN,
                                          fill_value=capP)[0])(fresh)
    sidx = jnp.arange(S)[:, None]
    offs = jnp.broadcast_to(jnp.arange(KN), (S, KN))
    gids = (base[:, None] + offs).astype(jnp.int32)
    valid = rows < capP
    glo2 = glo_d.at[sidx, jnp.where(valid, rows, capP)].set(
        jnp.where(valid, gids, 0), mode="drop")
    # dead rows lose their id (mirrors extend_global_ids)
    glo2 = jnp.where(vmask, glo2, -1)
    return (glo2, top + jnp.sum(nf),
            jnp.where(valid, rows, -1).astype(jnp.int32),
            jnp.where(valid, gids, -1), ok)


@governed("migrate_dev.dead_rows", budget=4)
@partial(jax.jit, static_argnames=("KD",))
def dead_glo_rows(glo_d, vmask, KD: int):
    """Compacted newly-dead vertex rows: live-id rows of the numbering
    whose liveness mask has dropped (adapt-cycle collapses since the
    last mirror sync).  The band-sized DELTA replacing the hot-loop
    O(mesh) vmask allgather of the pre-pod multi-host path — the host
    mirror kills exactly these rows (migrate.kill_glo_rows).

    Returns (rows [S, KD] int32 (pad capP), cnt [S], ok); ok False =
    budget overflow, caller takes the metered pull_host escape hatch."""
    S, capP = glo_d.shape
    dead = (glo_d >= 0) & ~vmask
    cnt = jnp.sum(dead, axis=1, dtype=jnp.int32)
    rows = jax.vmap(lambda m: jnp.nonzero(m, size=KD,
                                          fill_value=capP)[0])(
        dead).astype(jnp.int32)
    return rows, cnt, jnp.all(cnt <= KD)


def session_ids_fit(top: int, n_shards: int, KN: int) -> bool:
    """Whether this iteration's fresh-id block provably fits the int32
    device numbering (the module-docstring contract): extend_ids_device
    hands out at most ``n_shards * KN`` ids starting at ``top``, and the
    monotone session counter must never wrap int32 — on a miss the
    caller takes the host ``extend_global_ids_from_vmask`` path, whose
    mirror carries int64 (ADVICE r3: guard, don't assume)."""
    return int(top) + int(n_shards) * int(KN) < 2 ** 31


def has_multiway_face_run(eq: np.ndarray) -> bool:
    """True when the sorted exposed-face keys contain a run of length
    > 2 — a global-id triple exposed by 3+ shards (non-manifold parallel
    face).  ``eq`` is the consecutive-equality mask of the lexsorted
    keys; two adjacent True entries mean three equal keys.  The
    consecutive-pair linking in band_migrate_iteration would double-link
    the middle slot, so the caller must fall back to the full-view path
    for that iteration (ADVICE r3)."""
    return eq.size > 1 and bool(np.any(eq[1:] & eq[:-1]))


# ---------------------------------------------------------------------------
# host orchestration: one O(band + interface) migration iteration
# ---------------------------------------------------------------------------
def band_migrate_iteration(stacked: Mesh, met_s, glo_d,
                           glo: list[np.ndarray],
                           labels_d, depth_d, shared_prev: np.ndarray,
                           n_shards: int, verbose: int = 0):
    """Run device_migrate + interface rebuild with band-sized host work.

    ``glo_d``: [S, capP] int32 device numbering (kept in lockstep with
    the host ``glo`` mirror); ``shared_prev``: gids shared across shards
    before this migration (candidates for the incremental shared-vertex
    update: a gid can only BECOME shared through a band arrival).

    Returns (stacked, met_s, glo_d, comms, shared_now, nmoved) or None
    when any device budget overflowed — the caller falls back to the
    full-view path (parallel/migrate.py), the correctness oracle.
    """
    from .comms import pad_comm_tables
    S = n_shards
    capT = stacked.tet.shape[1]
    capP = stacked.vert.shape[1]
    # a 2-layer advancing front can move a large fraction of a donor and
    # concentrate on one recipient: the band budget scales with capacity
    # (so a grow-retry genuinely raises it), not with a fixed floor
    KB = max(256, capT // 2)
    KV = max(256, capP // 2)
    KF = max(512, capT // 2)

    stacked2, met2, glo_d2, info = device_migrate(
        stacked, met_s, glo_d, labels_d, depth_d, KB=KB, KV=KV)
    ok = bool(info["ok"])
    nmoved = int(info["nmoved"])
    if not ok:
        if verbose >= 1:
            names = ("nmove<=KB", "arrivals<=KB", "new_v<=KV",
                     "new_v<=free_v", "arrivals<=free_t", "dead<=KV")
            # lint: ok(R7) — fallback diagnostic off the steady path
            # (the iteration is being abandoned to the full-view
            # oracle); tiny [6] bool vector
            parts = _pull(info["ok_parts"])
            bad = [n for n, p in zip(names, parts) if not p]
            otrace.log(1, f"  band migrate overflow: {bad}",
                       verbose=verbose)
        return None         # fallback: caller re-runs the full path
    if nmoved == 0:
        return stacked2, met2, glo_d2, None, shared_prev, 0, None

    # ---- exposed-face probe (budget-checked BEFORE any mirror mutation:
    # the okf fallback must hand the caller an untouched numbering) -----
    keys, slots, cnt, okf = exposed_face_probe(stacked2, glo_d2, KF=KF)
    if not bool(okf):
        return None

    # ---- cross-shard face match (band exchange, pod.gather_band) --------
    from .pod import gather_band
    keys, slots, cnt = gather_band(keys, slots, cnt, what="faces")
    ks, sl, sh = [], [], []
    for s in range(S):
        n = int(cnt[s])
        ks.append(keys[s][:n])
        sl.append(slots[s][:n])
        sh.append(np.full(n, s, np.int32))
    K = np.concatenate(ks) if ks else np.zeros((0, 3), np.int32)
    SL = np.concatenate(sl)
    SH = np.concatenate(sh)
    order = np.lexsort((K[:, 2], K[:, 1], K[:, 0]))
    Ks, SLs, SHs = K[order], SL[order], SH[order]
    eq = (Ks[1:] == Ks[:-1]).all(1)
    if has_multiway_face_run(eq):
        # a global-id triple exposed by 3+ shards (non-manifold parallel
        # face): the consecutive-pair linking below would double-link the
        # middle slot — fall back to the full-view path this iteration
        # (the host oracle shares the 2-shard assumption but rebuilds
        # interfaces from whole views, which stays consistent)
        return None

    # ---- host glo mirror sync (arrivals + newly-dead delta) -------------
    # (after the pairing guard: a None return above must leave the host
    # glo mirror untouched for the full-view fallback.)  One band
    # exchange replaces the old O(mesh) vmask allgather: arrivals write
    # their device-assigned rows, the compacted dead delta drops its
    # ids — the mirror invariant (glo >= 0 iff live id-carrying row)
    # makes the delta exact (migrate.kill_glo_rows)
    from .migrate import apply_fresh_ids, kill_glo_rows
    arr_rows, arr_gids, dead_rows, dead_cnt, arr_slots = gather_band(
        info["arr_rows"], info["arr_gids"], info["dead_rows"],
        info["dead_cnt"], info["arr_slots"], what="migrate_glo")
    apply_fresh_ids(glo, arr_rows, arr_gids)
    kill_glo_rows(glo, dead_rows, dead_cnt)

    pair = np.concatenate([eq, [False]])
    iA = np.where(pair)[0]
    iB = iA + 1
    face_lists = [[[] for _ in range(S)] for _ in range(S)]
    ifc_face_slots = [[] for _ in range(S)]
    a_arr, b_arr = SHs[iA], SHs[iB]
    sa_arr, sb_arr = SLs[iA], SLs[iB]
    for a, b, sa, sb in zip(a_arr, b_arr, sa_arr, sb_arr):
        a, b = int(a), int(b)
        face_lists[a][b].append(int(sa))
        face_lists[b][a].append(int(sb))
        ifc_face_slots[a].append(int(sa))
        ifc_face_slots[b].append(int(sb))

    # ---- incremental shared-vertex update -------------------------------
    # candidates: previously shared ∪ band-arrival gids ∪ interface-face
    # endpoint gids (the only routes by which a gid can become shared)
    endp = Ks[iA].reshape(-1).astype(np.int64)
    cands = np.unique(np.concatenate(
        [shared_prev.astype(np.int64),
         arr_gids[arr_gids >= 0].astype(np.int64), endp]))
    rows_per = []
    live_per = []
    for s in range(S):
        o = np.argsort(glo[s], kind="stable")
        gs = glo[s][o]
        lo = np.searchsorted(gs, cands)
        loc = np.clip(lo, 0, len(gs) - 1)
        hit = (gs[loc] == cands) & (cands >= 0)
        row = np.where(hit, o[loc], -1)
        # liveness IS the id hit: the mirror invariant (synced above)
        # guarantees glo >= 0 only at live rows — no mask consult
        live = hit & (row >= 0)
        rows_per.append(np.where(live, row, -1))
        live_per.append(live)
    nliv = np.sum(live_per, axis=0)
    shared = nliv >= 2
    shared_now = cands[shared]
    owner_of = np.full(len(cands), -1, np.int32)
    for s in range(S):
        owner_of[live_per[s]] = s          # ascending: max rank wins
    node_lists = [[[] for _ in range(S)] for _ in range(S)]
    ifc_vert_rows = [[] for _ in range(S)]
    owner = [np.full(capP, s, np.int32) for s in range(S)]
    sh_idx = np.where(shared)[0]           # ascending gid order (A.4)
    for s in range(S):
        rows_s = rows_per[s][sh_idx]
        here = rows_s >= 0
        ifc_vert_rows[s] = [int(r) for r in rows_s[here]]
        owner[s][rows_s[here]] = owner_of[sh_idx][here]
    for ci in sh_idx:
        holders = [s for s in range(S) if live_per[s][ci]]
        for i in range(len(holders)):
            for j in range(i + 1, len(holders)):
                a, b = holders[i], holders[j]
                node_lists[a][b].append(int(rows_per[a][ci]))
                node_lists[b][a].append(int(rows_per[b][ci]))

    comms = pad_comm_tables(node_lists, face_lists, owner, S)

    # ---- retag on device ------------------------------------------------
    # bucket the static shapes (compile governor) so the jitted retag
    # program is reused across iterations instead of recompiling for
    # every distinct interface size
    KF2 = bucket(max(len(x) for x in ifc_face_slots), floor=256)
    KN = bucket(max(len(x) for x in ifc_vert_rows), floor=256)
    slots_d = np.full((S, KF2), capT * 4, np.int32)
    vrows_d = np.full((S, KN), capP, np.int32)
    for s in range(S):
        slots_d[s, :len(ifc_face_slots[s])] = ifc_face_slots[s]
        vrows_d[s, :len(ifc_vert_rows[s])] = ifc_vert_rows[s]
    stacked2 = retag_device(stacked2, glo_d2, jnp.asarray(slots_d),
                            jnp.asarray(vrows_d))
    otrace.log(2, f"  band migration: moved {nmoved} tets, "
                  f"{len(iA)} interface faces, "
                  f"{int(shared.sum())} shared vertices "
                  "(device path)", verbose=verbose)
    return (stacked2, met2, glo_d2, comms, shared_now, nmoved,
            arr_slots)


def band_weld(stacked: Mesh, met_s, glo_d, glo: list[np.ndarray],
              arr_slots: np.ndarray, n_shards: int, verbose: int = 0):
    """Region-scoped near-duplicate weld after a band migration.

    Pulls only the 1-ring neighborhood of the arrival vertices per
    recipient shard and runs the sequential weld there (the
    distribute._weld_close_pairs semantics); vertices with incident
    tets outside the region are poisoned so the weld cannot dangle
    outside references.  Returns (stacked, nweld)."""
    from .distribute import _weld_close_pairs
    S = n_shards
    capT = stacked.tet.shape[1]
    capP = stacked.vert.shape[1]
    KW = max(512, capT // 2)
    KWp = max(512, capP // 2)
    seed = jnp.asarray(arr_slots)
    while True:
        trow, vrow, tcnt, vcnt, v_open, ok = band_region_probe(
            stacked, glo_d, seed, KW=KW, KWp=KWp)
        if bool(ok):
            break
        if KW >= capT and KWp >= capP:
            # cannot happen (the region is at most the live mesh, and
            # the full-width probe holds it) — kept as the caller's
            # documented full-weld fallback signal
            return stacked, glo_d, -1
        # the probe budget is a COMPACTION table, not a capacity: a big
        # arrival neighborhood just needs a wider table.  Double toward
        # the full width (one extra governed variant at most) instead
        # of abandoning the band path — the full-view weld fallback is
        # single-controller and would kill a multi-process run.
        KW = min(capT, KW * 2)
        KWp = min(capP, KWp * 2)
    from .pod import gather_band
    trow, vrow, tcnt, vcnt, v_open = gather_band(
        trow, vrow, tcnt, vcnt, v_open, what="weld_probe")
    # one consolidated region gather (device compaction) + ONE band
    # exchange of the resulting tables
    sidx = jnp.arange(S)[:, None]
    tr_c = jnp.clip(jnp.asarray(trow), 0, capT - 1)
    vr_c = jnp.clip(jnp.asarray(vrow), 0, capP - 1)
    tet_r, tref_r, ftag_r, etag_r, vert_r, vtag_r, met_r = gather_band(
        stacked.tet[sidx, tr_c], stacked.tref[sidx, tr_c],
        stacked.ftag[sidx, tr_c], stacked.etag[sidx, tr_c],
        stacked.vert[sidx, vr_c], stacked.vtag[sidx, vr_c],
        met_s[sidx, vr_c], what="weld_region")
    tet_d = stacked.tet
    tmask_d = stacked.tmask
    vmask_d = stacked.vmask
    glo_d_out = glo_d
    ntot = 0
    for s in range(S):
        nt, nv = int(tcnt[s]), int(vcnt[s])
        if nt == 0 or nv == 0:
            continue
        vr_s = vrow[s][:nv]
        l2r = np.full(capP, -1, np.int64)
        l2r[vr_s] = np.arange(nv)
        tloc = l2r[tet_r[s][:nt]]
        if (tloc < 0).any():        # ring closure failed — skip shard
            continue
        vtag_s = vtag_r[s][:nv].copy()
        vtag_s[v_open[s][:nv]] |= np.uint32(0x80000000)   # poison
        tet2, vkeep, tkeep = _weld_close_pairs(
            vert_r[s][:nv], tloc.astype(np.int32), vtag_s,
            met_r[s][:nv], tref_r[s][:nt], ftag_r[s][:nt],
            etag_r[s][:nt])
        if vkeep.all() and tkeep.all() and np.array_equal(tet2, tloc):
            continue
        ntot += int((~vkeep).sum())
        chg = np.where(np.any(tet2 != tloc, axis=1) | ~tkeep)[0]
        rows_g = trow[s][chg]
        tet_g = vr_s[np.clip(tet2[chg], 0, nv - 1)].astype(np.int32)
        tet_d = tet_d.at[s, jnp.asarray(rows_g)].set(jnp.asarray(tet_g))
        dead_rows = trow[s][np.where(~tkeep)[0]]
        if len(dead_rows):
            tmask_d = tmask_d.at[s, jnp.asarray(dead_rows)].set(False)
        dead_v = vr_s[np.where(~vkeep)[0]]
        if len(dead_v):
            vmask_d = vmask_d.at[s, jnp.asarray(dead_v)].set(False)
            glo[s][dead_v] = -1
            # the DEVICE numbering must drop the welded gids too: the
            # next adapt cycles run before extend_ids_device and can
            # reuse these slots — a stale gid there would resurrect
            # under the old identity and corrupt shared-vertex matching
            glo_d_out = glo_d_out.at[s, jnp.asarray(dead_v)].set(-1)
    if ntot == 0:
        return stacked, glo_d_out, 0
    otrace.log(2, f"  band weld: {ntot} near-duplicate pairs "
                  "contracted", verbose=verbose)
    out = dataclasses.replace(stacked, tet=tet_d, tmask=tmask_d,
                              vmask=vmask_d)
    return out, glo_d_out, ntot


# ---------------------------------------------------------------------------
# flood-label contiguity / reachability repair
# ---------------------------------------------------------------------------
# The advancing-front flood (migrate.flood_labels) propagates colors via
# vertex priorities, so each color region is vertex-connected to its
# seeds BY CONSTRUCTION — but priority ties between competing colors can
# cut a region off its front (an unreachable moving blob), and two
# fronts meeting can enclose an unflooded pocket of retained tets.  The
# reference repairs exactly these on the displaced partition:
# sub-blob merge (/root/reference/src/moveinterfaces_pmmg.c:475-626) and
# destination reachability (:627-720).  Here both checks run on a
# band-sized compacted probe (moving tets + their retained 1-ring), so
# the host never touches O(mesh) state.

def _flood_probe_one(tet, tmask, adja, label, depth, me, KB: int,
                     capP: int):
    capT = tet.shape[0]
    moving = tmask & (label != me)
    nbrc = jnp.clip(adja >> 2, 0, capT - 1)
    has = (adja >= 0) & tmask[:, None]
    nbr_mov = jnp.where(has, moving[nbrc], False)          # [T,4]
    ring = tmask & ~moving & jnp.any(nbr_mov, axis=1)
    band = moving | ring
    cnt = jnp.sum(band, dtype=jnp.int32)
    rows = jnp.nonzero(band, size=KB, fill_value=capT)[0].astype(jnp.int32)
    rv = rows < capT
    rc = jnp.clip(rows, 0, capT - 1)
    # vertices held by a retained tet OUTSIDE the band: a ring component
    # with no such vertex is an enclosed island
    out_ret = tmask & ~band
    vout = jnp.zeros(capP + 1, bool).at[
        jnp.where(out_ret[:, None], tet, capP).reshape(-1)].set(
        True, mode="drop")[:capP]
    row_tet = jnp.where(rv[:, None], tet[rc], 0)
    out_touch = jnp.any(vout[jnp.clip(row_tet, 0, capP - 1)],
                        axis=1) & rv
    return (cnt, rows,
            jnp.where(rv, label[rc], -1),
            jnp.where(rv, depth[rc], 0),
            jnp.where(rv[:, None], row_tet, -1),
            out_touch)


@governed("migrate_dev.flood_band_counts", budget=4)
@partial(jax.jit, static_argnames=("n_shards",))
def flood_band_counts(stacked: Mesh, labels, n_shards: int):
    """[S] int32: band size (moving + retained 1-ring) per shard.
    Ledger-registered: runs every rebalance iteration (G=1 AND the
    grouped layout share the logical-leading-axis program family)."""
    me = jnp.arange(n_shards, dtype=jnp.int32)

    def one(tet, tm, adja, lab, m):
        capT = tet.shape[0]
        moving = tm & (lab != m)
        nbrc = jnp.clip(adja >> 2, 0, capT - 1)
        has = (adja >= 0) & tm[:, None]
        ring = tm & ~moving & jnp.any(
            jnp.where(has, moving[nbrc], False), axis=1)
        return jnp.sum(moving | ring, dtype=jnp.int32)

    return jax.vmap(one)(stacked.tet, stacked.tmask, stacked.adja,
                         labels, me)


@governed("migrate_dev.flood_probe", budget=4)
@partial(jax.jit, static_argnames=("n_shards", "KB"))
def flood_probe(stacked: Mesh, labels, depth, n_shards: int, KB: int):
    me = jnp.arange(n_shards, dtype=jnp.int32)
    capP = stacked.vert.shape[-2]
    return jax.vmap(
        lambda t, tm, a, l, d, m: _flood_probe_one(
            t, tm, a, l, d, m, KB, capP)
    )(stacked.tet, stacked.tmask, stacked.adja, labels, depth, me)


@governed("migrate_dev.apply_label_fixes", budget=4)
@jax.jit
def _apply_label_fixes(labels, rows, newlab):
    def one(lab, r, nl):
        capT = lab.shape[0]
        tgt = jnp.where((r >= 0) & (r < capT) & (nl >= 0), r, capT)
        return lab.at[tgt].set(jnp.where(nl >= 0, nl, 0), mode="drop")
    return jax.vmap(one)(labels, rows, newlab)


def _vertex_components(rtet: np.ndarray, sel: np.ndarray) -> np.ndarray:
    """Connected components (by shared vertex) among the selected rows.

    Returns [n] int component id (-1 on unselected rows).  Vectorized
    min-label propagation over the (row, vertex) incidence — O(band *
    diameter) numpy passes, no per-row Python (the band can reach tens
    of thousands of rows on a big displaced partition)."""
    n = rtet.shape[0]
    rows = np.repeat(np.arange(n), rtet.shape[1])
    verts = rtet.reshape(-1)
    keep = (verts >= 0) & sel[rows]
    rows, verts = rows[keep], verts[keep]
    if not len(rows):
        return np.full(n, -1, np.int64)
    uv, vid = np.unique(verts, return_inverse=True)
    comp = np.where(sel, np.arange(n), n).astype(np.int64)
    for _ in range(64):                    # >> any real blob diameter
        vmin = np.full(len(uv), n, np.int64)
        np.minimum.at(vmin, vid, comp[rows])
        new_c = comp.copy()
        np.minimum.at(new_c, rows, vmin[vid])
        if (new_c == comp).all():
            break
        comp = new_c
    comp[~sel] = -1
    return comp


def repair_flood_labels(stacked: Mesh, labels_d, depth_d, n_shards: int,
                        verbose: int = 0):
    """Contiguity + reachability repair on the flood-displaced labels.

    - an unreachable moving blob (a same-color vertex-connected
      component with no depth-1 member, i.e. cut off its seed front by
      color competition) reverts to its owner;
    - an enclosed retained pocket (a ring component touching no retained
      tet outside the band) joins the surrounding moving color (majority
      among vertex-adjacent moving rows).

    Returns (labels_d, nfixed).  Reference semantics:
    moveinterfaces_pmmg.c:475-626 (fix_contiguity merge into a neighbor
    color) and :627-720 (check_reachability revert)."""
    from .pod import gather_band
    cnts = gather_band(flood_band_counts(stacked, labels_d, n_shards),
                       what="flood_counts")
    if int(cnts.max()) == 0:
        return labels_d, 0
    capT = stacked.tet.shape[1]
    KB = bucket(int(cnts.max()), floor=1024, cap=capT)
    # band exchange, not a per-leaf allgather: the probe outputs are
    # 'shard'-sharded compacted tables and every process computes the
    # identical host repair from the replicated copies
    cnt, rows, lab, dep, rtet, out_touch = gather_band(
        *flood_probe(stacked, labels_d, depth_d, n_shards, KB),
        what="flood_probe")
    new_lab = np.full((n_shards, KB), -1, np.int32)
    nfixed = 0
    for s in range(n_shards):
        n = int(cnt[s])
        if n == 0:
            continue
        lab_s = np.array(lab[s][:n])
        dep_s = dep[s][:n]
        rtet_s = rtet[s][:n]
        touch_s = out_touch[s][:n]
        fixed_s = np.zeros(n, bool)
        # --- moving blobs: same-color components need a depth-1 seed ---
        for c in np.unique(lab_s):
            c = int(c)
            if c == s or c < 0:
                continue
            selc = lab_s == c
            comp = _vertex_components(rtet_s, selc)
            for cid in np.unique(comp[selc]):
                mem = comp == cid
                if not (dep_s[mem] == 1).any():
                    lab_s[mem] = s              # revert: unreachable
                    fixed_s |= mem
        # --- retained pockets: ring components with no outside anchor --
        selr = lab_s == s
        comp = _vertex_components(rtet_s, selr)
        # vectorized vertex -> (component, moving-label) incidence for
        # the anchored test + majority relabel (no per-row Python)
        rows_i = np.repeat(np.arange(n), rtet_s.shape[1])
        verts_i = rtet_s.reshape(-1)
        vok = verts_i >= 0
        rows_i, verts_i = rows_i[vok], verts_i[vok]
        mov_i = (lab_s[rows_i] != s) & (lab_s[rows_i] >= 0)
        for cid in np.unique(comp[selr]):
            mem = comp == cid
            if touch_s[mem].any():
                continue                        # anchored to the interior
            vset = np.unique(verts_i[mem[rows_i]])
            nbr = mov_i & np.isin(verts_i, vset)
            if not nbr.any():
                continue
            vals, freq = np.unique(lab_s[rows_i[nbr]],
                                   return_counts=True)
            lab_s[mem] = int(vals[np.argmax(freq)])
            fixed_s |= mem
        if fixed_s.any():
            new_lab[s, :n][fixed_s] = lab_s[fixed_s]
            nfixed += int(fixed_s.sum())
    if nfixed == 0:
        return labels_d, 0
    otrace.log(2, f"  flood repair: relabeled {nfixed} band tets "
                  "(contiguity/reachability)", verbose=verbose)
    labels_d = _apply_label_fixes(labels_d, jnp.asarray(rows),
                                  jnp.asarray(new_lab))
    return labels_d, nfixed


# ---------------------------------------------------------------------------
# graph-balancing labels from device-compacted tables (zero full pulls)
# ---------------------------------------------------------------------------
# The reference's graph mode gathers ONLY the group graph to rank 0 and
# runs METIS on it (/root/reference/src/metis_pmmg.c:845-1550).  Round 3
# matched the algorithm (morton clusters as redistribution groups +
# weighted KL/FM on the cluster graph) but still pulled full shard views
# to build it.  Here the cluster assignment, cluster weights, the
# intra-shard cluster adjacency (via the maintained adja — no face
# sort), and the interface-slot cluster ids are computed ON DEVICE and
# only O(S*G^2 + interface) tables reach the host.

@governed("migrate_dev.graph_probe", budget=4)
@partial(jax.jit, static_argnames=("n_shards", "G"))
def graph_probe(stacked: Mesh, face_idx, n_shards: int, G: int):
    """Per shard: morton cluster id per live tet [S, capT], live count
    [S], cluster weights [S, G], intra-shard cluster-pair face counts
    [S, G*G], and the cluster id at each comm face slot [S, K, I]."""
    capP = stacked.vert.shape[1]

    def one(tet, tm, adja, vert, fidx):
        from ..ops.edges import morton_codes
        capT = tet.shape[0]
        cent = jnp.mean(vert[jnp.clip(tet, 0, capP - 1)], axis=1)
        code = morton_codes(cent, tm, bits=10)
        key = jnp.where(tm, code, _I32MAX)
        order = jnp.argsort(key)
        rank = jnp.zeros(capT, jnp.int32).at[order].set(
            jnp.arange(capT, dtype=jnp.int32))
        nlive = jnp.sum(tm, dtype=jnp.int32)
        # equal-count chunks along the curve = the redistribution groups
        clus = jnp.clip((rank * G) // jnp.maximum(nlive, 1), 0, G - 1)
        clus = jnp.where(tm, clus, 0).astype(jnp.int32)
        cw = jnp.zeros(G, jnp.int32).at[
            jnp.where(tm, clus, G)].add(1, mode="drop")
        # intra-shard cluster adjacency from adja (cross-shard faces are
        # adja=-1 at the frozen interface and counted via the comms)
        nbrt = jnp.clip(adja >> 2, 0, capT - 1)
        tid = jnp.arange(capT, dtype=jnp.int32)[:, None]
        own = (adja >= 0) & tm[:, None] & (tid < (adja >> 2)) & \
            tm[nbrt]
        ci = jnp.broadcast_to(clus[:, None], (capT, 4))
        cj = clus[nbrt]
        cross = own & (ci != cj)
        pk = jnp.where(cross, jnp.minimum(ci, cj) * G +
                       jnp.maximum(ci, cj), G * G)
        pcnt = jnp.zeros(G * G, jnp.int32).at[pk.reshape(-1)].add(
            1, mode="drop")
        # cluster at each interface face slot (order matches both sides)
        ft = jnp.clip(fidx // 4, 0, capT - 1)
        cif = jnp.where(fidx >= 0, clus[ft], -1)
        return clus, nlive, cw, pcnt, cif

    return jax.vmap(one)(stacked.tet, stacked.tmask, stacked.adja,
                         stacked.vert, face_idx)


@partial(jax.jit, static_argnames=("n_shards",))
def _labels_from_parts(clus, tmask, new_part, n_shards: int):
    me = jnp.arange(n_shards, dtype=jnp.int32)
    G = new_part.shape[0] // n_shards

    def one(c, tm, m):
        lab = new_part[m * G + c]
        return jnp.where(tm, lab, m).astype(jnp.int32)

    return jax.vmap(one)(clus, tmask, me)


def graph_repartition_labels_band(stacked: Mesh, comms, n_shards: int,
                                  clusters_per_shard: int = 8,
                                  verbose: int = 0):
    """Device-resident graph-balancing labels: [S, capT] target shard
    per tet (device array), from O(S*G^2 + interface) host tables only.

    Same algorithm as migrate.graph_repartition_labels (morton clusters
    + weighted KL/FM on the cluster graph, the metis_pmmg.c:845-1550
    gather-only-the-graph role) without the full views pull."""
    from .partition import refine_partition
    S, G = n_shards, clusters_per_shard
    # bucket the comm-table pad shape (compile governor): the tables are
    # rebuilt every rebalance iteration and an exact-shape jit would
    # recompile graph_probe each time (the same recompile class the
    # retag KF2/KN bucketing fixes).  Same ladders as pad_comm_tables
    # (geo/64 items, pow2/2 capped neighbors) so tables it built pass
    # through untouched — bucket() is idempotent on its own ladder —
    # and graph_probe shares the other consumers' compiled-shape
    # family; only older callers' exact tables get re-padded here.
    fi = comms.face_idx
    If = bucket(fi.shape[2], floor=64, scheme="geo")
    Kn = bucket(fi.shape[1], floor=2,
                cap=max(fi.shape[1], n_shards - 1))
    if (Kn, If) != fi.shape[1:]:
        fi2 = np.full((fi.shape[0], Kn, If), -1, fi.dtype)
        fi2[:, :fi.shape[1], :fi.shape[2]] = fi
        fi = fi2
    # band exchange (pod.gather_band): every process receives the same
    # O(S*G^2 + interface) tables through one compiled collective.
    # clus/nlive stay DEVICE-resident: the host graph build never reads
    # them (clus feeds _labels_from_parts on device) — the pre-pod path
    # allgathered the O(mesh) cluster map just to re-upload it
    from .pod import gather_band
    clus, nlive, cw, pcnt, cif = graph_probe(stacked, jnp.asarray(fi),
                                             S, G)
    cw, pcnt, cif = gather_band(cw, pcnt, cif, what="graph")
    nclu = S * G
    pi, pj, w = [], [], []
    for s in range(S):
        mat = pcnt[s]
        nz = np.where(mat > 0)[0]
        if len(nz):
            pi.append(s * G + nz // G)
            pj.append(s * G + nz % G)
            w.append(mat[nz].astype(float))
    # interface edges: the comm tables are ordered identically on both
    # sides of a pair, so zipping the two shards' slot-cluster rows
    # gives the cross-shard cluster pairs directly
    nbr = comms.nbr
    fcnt = comms.face_cnt
    for s in range(S):
        for k in range(nbr.shape[1]):
            b = int(nbr[s, k])
            if b <= s:
                continue
            n_items = int(fcnt[s, k])
            if n_items == 0:
                continue
            kb = int(np.where(nbr[b] == s)[0][0])
            ca = cif[s, k, :n_items]
            cb = cif[b, kb, :n_items]
            okm = (ca >= 0) & (cb >= 0)
            key = (s * G + ca[okm]).astype(np.int64) * nclu + \
                (b * G + cb[okm])
            uk, cnts = np.unique(key, return_counts=True)
            pi.append((uk // nclu).astype(np.int64))
            pj.append((uk % nclu).astype(np.int64))
            w.append(cnts.astype(float))
    if not pi:
        return None
    pi = np.concatenate(pi)
    pj = np.concatenate(pj)
    w = np.concatenate(w)
    init = np.repeat(np.arange(S, dtype=np.int32), G)
    new_part = refine_partition(init, S, (pi, pj), w,
                                elem_w=cw.reshape(-1).astype(float),
                                npasses=5)
    nmv = int((new_part != init).sum())
    otrace.log(2, f"  graph band labels: {nmv}/{nclu} clusters "
                  "reassigned", verbose=verbose)
    return _labels_from_parts(clus, stacked.tmask,
                              jnp.asarray(new_part), S)
