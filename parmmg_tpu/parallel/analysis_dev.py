"""Device-resident cross-shard surface analysis (PMMG_update_analys).

The host-numpy ``analysis_par.analyze_shards`` pulls every shard's full
arrays each outer iteration and re-derives the classification in Python
loops — the reference instead refreshes analysis per migration with
rank-local work + neighbor exchanges (analys_pmmg.c:1571,2001,1679).
This module is the jitted SPMD equivalent, so the between-iteration
refresh stays on device:

- every shard extracts its boundary-face edge records at static width
  [12*capT] (three edges per boundary face), keyed by the persistent
  GLOBAL vertex numbering;
- records whose two endpoints are NOT both interface (MG_PARBDY)
  vertices can only ever meet records of the same shard — they are
  grouped and classified locally (sort/segment: dihedral ridge test on
  2-record edges, ref-mismatch, non-manifold on counts != 2 — the
  PMMG_setdhd / MG_NOM rules);
- potentially-shared records (both endpoints interface) are compacted
  into a fixed [KS] buffer and ``all_gather``-ed over the shard axis
  (the ICI analogue of the reference's edge-comm normal exchange,
  analys_pmmg.c:2001): every shard runs the identical global grouping
  and reads back the verdicts for its own records;
- vertex singularity classification (corner = 1 or >2 incident special
  edges, ridge-point = 2; PMMG_singul:1679) needs GLOBAL incident
  counts: each special edge contributes +1 at its endpoints exactly
  once (the globally-first record's shard owns the contribution), and
  interface vertices sum their partial counts over the node comm tables
  (the int-comm count reduction of the reference);
- edge tags are rewritten in place: stale classification bits are
  cleared on plain-boundary slots elementwise, record slots receive
  their verdicts directly, and a keyed OR-join propagates the special
  bits to every other local slot of the same edge (interior tets
  sharing a ridge edge keep MG_GEO — tag routing reads per-slot tags).

The [KS] shared-record budget is static; if a shard exceeds it the
program reports overflow and the caller falls back to the host path for
that iteration (never silently truncates).

**Groups x shards (G > 1)**: :func:`dist_analysis_grouped` runs the
same pipeline when each device hosts G logical shards (the reference's
rank-level x group-level decomposition, grpsplit_pmmg.c:1551-1614).
The [R]-width sort/segment phases run per group under ``lax.map`` —
the same HBM discipline as the adapt block: peak working set is ONE
group's record table, not G of them — while the cross-shard phases ride
two collectives on interface-sized data: one ``all_gather`` of the
[G, KS] shared-record packs (logical shard l = device*G + slot) and one
grouped node-comm halo exchange (:func:`comms.halo_exchange_grouped`,
or its per-device-pair packed variant when the neighbor table is
sparse).  The per-group record extraction runs ONCE (fused, PR 12):
the pack phase also computes the local verdicts and carries the
per-record bits ([G, 12*capT] uint32 + head bool — 5 bytes/record)
across the map, and the tail re-derives only the cheap endpoint/slot
gathers instead of re-running the normals + global-id extraction (the
``extract2x_s`` decision input that priced this, retired with it).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mesh import Mesh
from ..core.constants import (
    IDIR, MG_BDY, MG_CRN, MG_GEO, MG_NOM, MG_PARBDY, MG_REF)
from ..ops.edges import segmented_or

CLS = np.uint32(MG_GEO | MG_CRN | MG_REF | MG_NOM)
_EDGE_PAIRS = ((0, 1), (1, 2), (0, 2))
_I32MAX = jnp.iinfo(jnp.int32).max


def _sort2(a, b, valid):
    """Two-column ascending sort of (a, b) id pairs, invalid last.
    Global ids do not fit the packed single-key trick; always lexsort.
    Returns (order, ka, kb, first)."""
    aa = jnp.where(valid, a, _I32MAX)
    bb = jnp.where(valid, b, _I32MAX)
    order = jnp.lexsort((bb, aa))
    ka, kb = aa[order], bb[order]
    first = jnp.concatenate([jnp.array([True]),
                             (ka[1:] != ka[:-1]) | (kb[1:] != kb[:-1])])
    return order, ka, kb, first


def _seg_fields(first, valid_sorted):
    """(seg_id, cnt_of_my_segment, is_head) helpers for a sorted run."""
    n = first.shape[0]
    seg = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, jnp.arange(n), 0))
    ones = valid_sorted.astype(jnp.int32)
    # inclusive per-segment count at the LAST member, broadcast back
    def seg_add(pa, pb):
        fa, va = pa
        fb, vb = pb
        return fa | fb, jnp.where(fb, vb, va + vb)
    _, run = jax.lax.associative_scan(seg_add, (first, ones))
    is_last = jnp.concatenate([first[1:], jnp.array([True])])
    total_at_head = jnp.zeros(n, jnp.int32).at[
        jnp.where(is_last, seg, n)].set(run, mode="drop",
                                        unique_indices=True)
    return seg, total_at_head[seg], is_last


def _classify_sorted(first, valid_s, nu_s, fref_s, angedg):
    """Per-ROW verdict bits for a sorted record run: the segment verdict
    (ridge/ref/non-manifold) broadcast to every member row."""
    n = first.shape[0]
    seg, cnt, _ = _seg_fields(first, valid_s)
    nxt_same = jnp.concatenate([~first[1:], jnp.array([False])])
    dot = jnp.sum(nu_s * jnp.concatenate(
        [nu_s[1:], nu_s[:1]], axis=0), axis=-1)
    ref_mis = fref_s != jnp.concatenate([fref_s[1:], fref_s[:1]])
    # verdicts are decided at the segment HEAD of 2-record segments
    ridge_h = first & (cnt == 2) & nxt_same & (dot < angedg)
    ref_h = first & (cnt == 2) & nxt_same & ref_mis
    nom_h = first & valid_s & (cnt != 2)
    bits_h = (jnp.where(ridge_h, jnp.uint32(MG_GEO), 0)
              | jnp.where(ref_h, jnp.uint32(MG_REF), 0)
              | jnp.where(nom_h, jnp.uint32(MG_NOM), 0))
    bits_head = jnp.zeros(n, jnp.uint32).at[
        jnp.where(first, seg, n)].set(bits_h, mode="drop",
                                      unique_indices=True)
    bits_row = jnp.where(valid_s, bits_head[seg], 0)
    return bits_row, first & valid_s      # (row verdicts, head-row mask)


class _Records(NamedTuple):
    """Boundary-face edge records of ONE shard at static width
    R = 12*capT (3 edges x 4 faces per tet)."""
    la: jax.Array          # [R] local endpoint a
    lb: jax.Array          # [R] local endpoint b
    valid: jax.Array       # [R] record is a live plain-boundary face edge
    nu: jax.Array          # [R, 3] unit face normal
    frf: jax.Array         # [R] face ref
    trow: jax.Array        # [R] tet row
    le: jax.Array          # [R] local edge slot 0..5
    g_lo: jax.Array        # [R] global endpoint min
    g_hi: jax.Array        # [R] global endpoint max
    loc_rec: jax.Array     # [R] purely-local record
    sh_rec: jax.Array      # [R] potentially-shared record


def _extract_records(mesh: Mesh, glo=None) -> _Records:
    """Extract the [R] record table (the rank-local half of the
    reference's analys exchange).

    ``glo=None`` extracts the LIGHT table: endpoint/slot fields only
    (la/lb/valid/trow/le — cheap index gathers), with the
    normal/ref/global-id/interface fields zeroed.  The fused grouped
    analysis (:func:`shard_analysis_body_grouped`) runs the FULL
    extraction exactly once per group (pack phase) and carries the
    verdict bits across the map; its tail re-derives only this light
    table — the cross products, normalization, global-id and
    interface-classification gathers of the second extraction are the
    work the fusion removed (the retired ``extract2x_s`` cost)."""
    capT, capP = mesh.capT, mesh.capP
    idir = jnp.asarray(IDIR)
    full = glo is not None
    glo_i = glo.astype(jnp.int32) if full else None
    la_l, lb_l, valid_l, nrm_l, fref_l, trow_l, le_l = \
        [], [], [], [], [], [], []
    for f in range(4):
        tri = mesh.tet[:, idir[f]]                        # [T,3]
        if full:
            p = mesh.vert[tri]
            nrm = jnp.cross(p[:, 1] - p[:, 0], p[:, 2] - p[:, 0])
        is_b = mesh.tmask & ((mesh.ftag[:, f] & MG_BDY) != 0) & \
            ((mesh.ftag[:, f] & MG_PARBDY) == 0)
        for (a, b) in _EDGE_PAIRS:
            la_l.append(tri[:, a])
            lb_l.append(tri[:, b])
            valid_l.append(is_b)
            if full:
                nrm_l.append(nrm)
                fref_l.append(mesh.fref[:, f])
            trow_l.append(jnp.arange(capT, dtype=jnp.int32))
            from ..ops.swap import _EDGE_OF
            # lint: ok(R2) — _EDGE_OF is a static host table; the int()
            # folds a Python constant at trace time, no device sync
            eid = int(_EDGE_OF[IDIR[f][a], IDIR[f][b]])
            le_l.append(jnp.full(capT, eid, jnp.int32))
    la = jnp.concatenate(la_l)
    lb = jnp.concatenate(lb_l)
    valid = jnp.concatenate(valid_l)
    trow = jnp.concatenate(trow_l)
    le = jnp.concatenate(le_l)
    R = la.shape[0]
    if not full:
        zi = jnp.zeros(R, jnp.int32)
        return _Records(la, lb, valid, jnp.zeros((R, 3), mesh.vert.dtype),
                        zi, trow, le, zi, zi,
                        jnp.zeros(R, bool), jnp.zeros(R, bool))
    nrm = jnp.concatenate(nrm_l)
    nu = nrm / jnp.maximum(
        jnp.linalg.norm(nrm, axis=-1, keepdims=True), 1e-30)
    frf = jnp.concatenate(fref_l)
    ga = glo_i[jnp.clip(la, 0, capP - 1)]
    gb = glo_i[jnp.clip(lb, 0, capP - 1)]
    g_lo = jnp.minimum(ga, gb)
    g_hi = jnp.maximum(ga, gb)

    both_ifc = ((mesh.vtag[jnp.clip(la, 0, capP - 1)] & MG_PARBDY) != 0) \
        & ((mesh.vtag[jnp.clip(lb, 0, capP - 1)] & MG_PARBDY) != 0)
    return _Records(la, lb, valid, nu, frf, trow, le, g_lo, g_hi,
                    valid & ~both_ifc, valid & both_ifc)


def _local_bits(rec: _Records, angedg: float):
    """Local grouping + verdicts for the purely-local records.
    Returns (bits_rec [R] uint32, head_rec [R] bool)."""
    R = rec.la.shape[0]
    order, _, _, first = _sort2(rec.g_lo, rec.g_hi, rec.loc_rec)
    bits_srt, head_srt = _classify_sorted(
        first, rec.loc_rec[order], rec.nu[order], rec.frf[order], angedg)
    bits_rec = jnp.zeros(R, jnp.uint32).at[order].set(
        bits_srt, unique_indices=True)
    head_rec = jnp.zeros(R, bool).at[order].set(
        head_srt, unique_indices=True)
    return bits_rec, head_rec


def _shared_pack(rec: _Records, KS: int):
    """Compact the potentially-shared records into the fixed [KS]
    exchange buffer.  Returns (pack dict, overflow bool)."""
    R = rec.la.shape[0]
    n_sh = jnp.sum(rec.sh_rec.astype(jnp.int32))
    ovf = n_sh > KS
    widx = jnp.nonzero(rec.sh_rec, size=KS, fill_value=R)[0]
    wv = widx < R
    wc = jnp.clip(widx, 0, R - 1)
    pack = {
        "glo": jnp.where(wv, rec.g_lo[wc], _I32MAX),
        "ghi": jnp.where(wv, rec.g_hi[wc], _I32MAX),
        "nu": jnp.where(wv[:, None], rec.nu[wc], 0.0),
        "fref": jnp.where(wv, rec.frf[wc], 0),
        "row": jnp.where(wv, wc, R).astype(jnp.int32),
        "valid": wv,
    }
    return pack, ovf


def _merge_pack_verdicts(bits_rec, head_rec, pack, sh_bits, sh_head):
    """Scatter the [KS] global-exchange verdicts back onto the record
    rows (pack['row'] already points at R for pad slots)."""
    bits_rec = bits_rec.at[pack["row"]].max(sh_bits, mode="drop")
    head_rec = head_rec.at[pack["row"]].max(sh_head & pack["valid"],
                                            mode="drop")
    return bits_rec, head_rec


def _vertex_payload(mesh: Mesh, rec: _Records, bits_rec, head_rec):
    """Per-vertex partials of the int-comm reduction: [capP, 4] float32
    columns (nsing, has_ref, has_nom, on_bdy)."""
    capP = mesh.capP
    is_spec_rec = bits_rec != 0
    contrib = head_rec & is_spec_rec
    la, lb, valid = rec.la, rec.lb, rec.valid
    idx2 = jnp.concatenate([jnp.where(contrib, la, capP),
                            jnp.where(contrib, lb, capP)])
    nsing = jnp.zeros(capP + 1, jnp.int32).at[idx2].add(1, mode="drop")
    nsing = nsing[:capP]
    # partial per-vertex bit union (BDY from any record; REF/NOM presence)
    idx_all = jnp.concatenate([jnp.where(valid, la, capP),
                               jnp.where(valid, lb, capP)])
    vbits = jnp.zeros(capP + 1, jnp.uint32)
    vbits = vbits.at[idx_all].max(jnp.uint32(MG_BDY), mode="drop")
    has_ref = jnp.zeros(capP + 1, bool).at[jnp.concatenate([
        jnp.where(contrib & ((bits_rec & MG_REF) != 0), la, capP),
        jnp.where(contrib & ((bits_rec & MG_REF) != 0), lb, capP)])].max(
        True, mode="drop")[:capP]
    has_nom = jnp.zeros(capP + 1, bool).at[jnp.concatenate([
        jnp.where(contrib & ((bits_rec & MG_NOM) != 0), la, capP),
        jnp.where(contrib & ((bits_rec & MG_NOM) != 0), lb, capP)])].max(
        True, mode="drop")[:capP]
    on_bdy_local = (vbits[:capP] & MG_BDY) != 0
    return jnp.stack([
        nsing.astype(jnp.float32),
        has_ref.astype(jnp.float32),
        has_nom.astype(jnp.float32),
        on_bdy_local.astype(jnp.float32)], axis=1)       # [capP, 4]


def _vtag_from_payload(vtag, vmask, payload, acc):
    """Final vertex classification from the local payload + the summed
    neighbor contributions (shape-polymorphic over leading axes)."""
    nsing_t = payload[..., 0].astype(jnp.int32) + \
        acc[..., 0].astype(jnp.int32)
    ref_t = (payload[..., 1] > 0) | (acc[..., 1] > 0)
    nom_t = (payload[..., 2] > 0) | (acc[..., 2] > 0)
    bdy_t = (payload[..., 3] > 0) | (acc[..., 3] > 0)
    gtag = jnp.where(bdy_t, jnp.uint32(MG_BDY), 0)
    gtag = gtag | jnp.where(nsing_t == 2, jnp.uint32(MG_GEO), 0)
    gtag = gtag | jnp.where((nsing_t == 1) | (nsing_t > 2),
                            jnp.uint32(MG_CRN), 0)
    gtag = gtag | jnp.where(ref_t, jnp.uint32(MG_REF), 0)
    gtag = gtag | jnp.where(nom_t, jnp.uint32(MG_NOM), 0)
    vtag_new = (vtag & ~jnp.uint32(CLS)) | (gtag & CLS) | \
        (gtag & MG_BDY)
    return jnp.where(vmask, vtag_new, vtag)


def _etag_rewrite(mesh: Mesh, rec: _Records, bits_rec):
    """Edge-tag rewrite: clear stale classification on plain-boundary
    slots, write record verdicts, then OR-join the special bits onto
    every local slot of the same (local vertex pair) edge."""
    capT, capP = mesh.capT, mesh.capP
    R = rec.la.shape[0]
    la, lb, valid = rec.la, rec.lb, rec.valid
    is_spec_rec = bits_rec != 0
    plain = ((mesh.etag & MG_BDY) != 0) & ((mesh.etag & MG_PARBDY) == 0)
    etag_flat = (mesh.etag & ~jnp.where(plain, CLS, jnp.uint32(0))
                 ).reshape(-1)
    # record-slot verdicts: scatter-OR realized as gather|OR|set —
    # colliding writes (two boundary faces of one tet sharing the edge)
    # carry IDENTICAL verdict bits (same global segment), so duplicate
    # set()s are deterministic; a scatter-MAX would drop bits instead
    # of uniting them
    slot_flat = jnp.where(valid, rec.trow * 6 + rec.le, capT * 6)
    slot_c = jnp.clip(slot_flat, 0, capT * 6 - 1)
    merged = etag_flat[slot_c] | jnp.where(valid, bits_rec, 0)
    etag_new = etag_flat.at[slot_flat].set(merged, mode="drop")
    # keyed OR-join: donors = special records (local pair), receivers =
    # all live tet-edge slots
    from ..core.mesh import tet_edge_vertices
    from ..ops.edges import sort_pairs
    ev = tet_edge_vertices(mesh.tet).reshape(capT * 6, 2)
    ka = jnp.minimum(ev[:, 0], ev[:, 1])
    kb = jnp.maximum(ev[:, 0], ev[:, 1])
    alive6 = jnp.repeat(mesh.tmask, 6)
    don_a = jnp.minimum(la, lb)
    don_b = jnp.maximum(la, lb)
    don_v = valid & is_spec_rec
    n_all = capT * 6 + R
    aa = jnp.concatenate([ka, don_a])
    bb = jnp.concatenate([kb, don_b])
    vvv = jnp.concatenate([alive6, don_v])
    order_j, _, _, first_j, _ = sort_pairs(aa, bb, vvv, capP)
    seg_j = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first_j, jnp.arange(n_all), 0))
    dbits = jnp.where((order_j >= capT * 6) & vvv[order_j],
                      bits_rec[jnp.clip(order_j - capT * 6, 0, R - 1)],
                      0)
    or_run = segmented_or(first_j, dbits)
    is_last_j = jnp.concatenate([first_j[1:], jnp.array([True])])
    tot = jnp.zeros(n_all, jnp.uint32).at[
        jnp.where(is_last_j, seg_j, n_all)].set(
        or_run, mode="drop", unique_indices=True)
    add_srt = tot[seg_j]
    recv_rows = (order_j < capT * 6) & vvv[order_j]
    tgt_j = jnp.where(recv_rows, order_j, capT * 6)
    merged_j = etag_new[jnp.clip(tgt_j, 0, capT * 6 - 1)] | add_srt
    # receiver rows are unique (each tet-edge slot appears once)
    etag_new = etag_new.at[tgt_j].set(merged_j, mode="drop",
                                      unique_indices=True)
    return etag_new.reshape(capT, 6)


def shard_analysis_body(mesh: Mesh, glo, node_idx, nbr, angedg: float,
                        KS: int, axis_name: str = "shard"):
    """Per-shard analysis body (call inside shard_map), G = 1 layout.

    Returns (vtag_new [capP], etag_new [capT,6], overflow scalar bool).
    """
    capP = mesh.capP
    R = 12 * mesh.capT

    # ---- extract + local grouping + verdicts ----------------------------
    rec = _extract_records(mesh, glo)
    bits_rec, head_rec = _local_bits(rec, angedg)

    # ---- shared records: compact, all_gather, global grouping -----------
    pack, ovf = _shared_pack(rec, KS)
    me = jax.lax.axis_index(axis_name)
    gath = {k: jax.lax.all_gather(v, axis_name) for k, v in pack.items()}
    S = gath["glo"].shape[0]
    shard_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), KS)
    gl = gath["glo"].reshape(S * KS)
    gh = gath["ghi"].reshape(S * KS)
    gn = gath["nu"].reshape(S * KS, 3)
    gf = gath["fref"].reshape(S * KS)
    grow = gath["row"].reshape(S * KS)
    gv = gath["valid"].reshape(S * KS)
    order_g, _, _, first_g = _sort2(gl, gh, gv)
    bits_g, head_g = _classify_sorted(
        first_g, gv[order_g], gn[order_g], gf[order_g], angedg)
    # back to MY record rows: rows of the gathered run with shard == me
    mine_g = (shard_of[order_g] == me) & gv[order_g]
    tgt = jnp.where(mine_g, grow[order_g], R)
    bits_rec = bits_rec.at[tgt].max(bits_g, mode="drop")
    head_rec = head_rec.at[tgt].max(head_g & mine_g, mode="drop")
    ovf = jax.lax.pmax(ovf.astype(jnp.int32), axis_name) > 0

    # ---- vertex classification ------------------------------------------
    # +1 per endpoint per special edge, contributed by the globally-first
    # record's shard, then summed across shards at interface vertices
    # (the int-comm reduction)
    from .comms import halo_exchange
    payload = _vertex_payload(mesh, rec, bits_rec, head_rec)
    recv = halo_exchange(payload, node_idx, nbr, axis_name)  # [K,I,4]
    K, I = node_idx.shape
    flat = jnp.where(node_idx >= 0, node_idx, capP).reshape(-1)
    acc = jnp.zeros((capP + 1, 4), jnp.float32).at[flat].add(
        recv.reshape(K * I, 4), mode="drop")[:capP]
    vtag_new = _vtag_from_payload(mesh.vtag, mesh.vmask, payload, acc)

    # ---- edge tags -------------------------------------------------------
    etag_new = _etag_rewrite(mesh, rec, bits_rec)
    return vtag_new, etag_new, ovf


def shard_analysis_body_grouped(mesh_s: Mesh, glo_s, node_idx_s, nbr_s,
                                angedg: float, KS: int, G: int,
                                packed_M: int | None = None,
                                axis_name: str = "shard"):
    """Grouped analysis body (call inside shard_map): the device hosts
    ``G`` logical shards on the leading axis (logical shard l = device
    ``l // G``, slot ``l % G`` — the dist.py grouped layout).

    [R]-width phases run one group at a time under ``lax.map``; the
    cross-shard exchange gathers the [G, KS] shared-record packs in one
    collective and routes the vertex int-comm reduction through the
    grouped halo exchange (dense, or per-device-pair packed when
    ``packed_M`` is set).

    **Fused single extraction** (PR 12, ROADMAP 4a): the [12*capT]
    record extraction runs ONCE per group per refresh.  Phase 1 does
    the full extraction AND the local sort/classification, carrying the
    per-record verdict bits ([G, R] uint32 + the [G, R] head-row bool —
    5 bytes/record, vs the ~50-byte full record row the old design
    refused to persist) across the map; the tail re-derives only the
    cheap endpoint/slot gathers (light ``_extract_records``).  The
    predecessor extracted twice to keep the cross-map intermediate at
    [G, KS]; the ``extract2x_s`` probe priced that redundant second
    extraction at ~G x one extraction per refresh, which bought this
    trade.

    Returns (vtag_new [G, capP], etag_new [G, capT, 6], overflow bool).
    """
    from .comms import halo_exchange_grouped, halo_exchange_grouped_packed
    capP = mesh_s.vert.shape[1]

    # ---- phase 1 (per group, lax.map): ONE full extraction — local
    # verdicts + shared-record packs + the [G, R] verdict carry ----------
    def pack_one(args):
        mesh_g, glo_g = args
        rec = _extract_records(mesh_g, glo_g)
        bits_rec, head_rec = _local_bits(rec, angedg)
        pack, ovf = _shared_pack(rec, KS)
        return pack, ovf, bits_rec, head_rec

    packs, ovf_g, bits_all, head_all = \
        jax.lax.map(pack_one, (mesh_s, glo_s))              # [G, ...]
    ovf = jnp.any(ovf_g)

    # ---- phase 2: one all_gather + the global grouping ------------------
    # (the "row" field stays local: grouped verdicts return through the
    # pack-slot index, so the record-row mapping never rides the wire)
    me = jax.lax.axis_index(axis_name)
    gath = {k: jax.lax.all_gather(v, axis_name)
            for k, v in packs.items() if k != "row"}
    S = gath["glo"].shape[0]                   # devices on the axis
    L = S * G                                  # logical shards
    logical_of = jnp.repeat(jnp.arange(L, dtype=jnp.int32), KS)
    gl = gath["glo"].reshape(L * KS)
    gh = gath["ghi"].reshape(L * KS)
    gn = gath["nu"].reshape(L * KS, 3)
    gf = gath["fref"].reshape(L * KS)
    gv = gath["valid"].reshape(L * KS)
    order_g, _, _, first_g = _sort2(gl, gh, gv)
    bits_g, head_g = _classify_sorted(
        first_g, gv[order_g], gn[order_g], gf[order_g], angedg)
    # verdicts for MY logical shards, back in [G, KS] pack-slot layout
    lo = logical_of[order_g]
    mine_g = (lo // G == me) & gv[order_g]
    # pack slot j = flat % KS, group g = (flat // KS) % G
    src_flat = order_g                          # original gathered index
    g_tgt = jnp.where(mine_g, (src_flat // KS) % G, G)
    j_tgt = jnp.where(mine_g, src_flat % KS, 0)
    sh_bits = jnp.zeros((G, KS), jnp.uint32).at[g_tgt, j_tgt].max(
        bits_g, mode="drop")
    sh_head = jnp.zeros((G, KS), bool).at[g_tgt, j_tgt].max(
        head_g & mine_g, mode="drop")
    ovf = jax.lax.pmax(ovf.astype(jnp.int32), axis_name) > 0

    # ---- phase 3 (per group, lax.map): verdict merge + local tail -------
    # (light re-extraction only: the verdict bits and the pack-slot
    # mapping were carried from phase 1 — no second full extraction)
    def tail_one(args):
        (mesh_g, bits_rec, head_rec, row_g, pv_g,
         sh_bits_g, sh_head_g) = args
        rec = _extract_records(mesh_g)                     # light
        bits_rec, head_rec = _merge_pack_verdicts(
            bits_rec, head_rec, {"row": row_g, "valid": pv_g},
            sh_bits_g, sh_head_g)
        payload = _vertex_payload(mesh_g, rec, bits_rec, head_rec)
        etag_new = _etag_rewrite(mesh_g, rec, bits_rec)
        return etag_new, payload

    etag_new, payload = jax.lax.map(
        tail_one, (mesh_s, bits_all, head_all, packs["row"],
                   packs["valid"], sh_bits, sh_head))

    # ---- phase 4: grouped int-comm reduction + vertex classification ---
    if packed_M is not None:
        recv = halo_exchange_grouped_packed(
            payload, node_idx_s, nbr_s, G, packed_M, axis_name)
    else:
        recv = halo_exchange_grouped(payload, node_idx_s, nbr_s, G,
                                     axis_name)               # [G,K,I,4]
    K, I = node_idx_s.shape[1:]
    flat = jnp.where(node_idx_s >= 0, node_idx_s, capP)       # [G,K,I]

    def acc_one(fl, rc):
        return jnp.zeros((capP + 1, 4), jnp.float32).at[
            fl.reshape(-1)].add(rc.reshape(-1, 4), mode="drop")[:capP]

    acc = jax.vmap(acc_one)(flat, recv)                       # [G,capP,4]
    vtag_new = _vtag_from_payload(mesh_s.vtag, mesh_s.vmask, payload, acc)
    return vtag_new, etag_new, ovf


def dist_analysis(dmesh, angedg: float, KS: int):
    """Build the jitted SPMD analysis-refresh program for a device mesh.

    Returns fn(stacked_mesh, glo_s [S,capP] int32, node_idx_s, nbr_s) ->
      (vtag [S,capP], etag [S,capT,6], overflow scalar).
    """
    from jax.sharding import PartitionSpec as P
    from ..utils.jaxcompat import shard_map
    from .dist import _unstack

    spec = P("shard")

    def local(mesh_s, glo_s, node_idx_s, nbr_s):
        mesh = _unstack(mesh_s)
        vt, et, ovf = shard_analysis_body(
            mesh, glo_s[0], node_idx_s[0], nbr_s[0], angedg, KS)
        return vt[None], et[None], ovf.astype(jnp.int32)

    # lint: ok(R1) — builder: the sole caller (dist.refresh_shard_
    # analysis_device) caches by (angedg,KS,S,G,Mp) and wraps the
    # product in governed("dist.analysis", budget=2)
    fn = shard_map(local, mesh=dmesh,
                   in_specs=(spec, spec, spec, spec),
                   out_specs=(spec, spec, P()), check_vma=False)
    # lint: ok(R1) — same builder contract as above
    return jax.jit(fn)


def dist_analysis_grouped(dmesh, angedg: float, KS: int, G: int,
                          packed_M: int | None = None):
    """Grouped (G logical shards per device) SPMD analysis-refresh
    program: same contract as :func:`dist_analysis` with the stacked
    leading axis carrying S*G logical shards.

    Returns fn(stacked_mesh, glo_s [S*G,capP] int32, node_idx_s, nbr_s)
      -> (vtag [S*G,capP], etag [S*G,capT,6], overflow scalar).
    """
    from jax.sharding import PartitionSpec as P
    from ..utils.jaxcompat import shard_map

    spec = P("shard")

    def local(mesh_s, glo_s, node_idx_s, nbr_s):
        vt, et, ovf = shard_analysis_body_grouped(
            mesh_s, glo_s, node_idx_s, nbr_s, angedg, KS, G,
            packed_M=packed_M)
        return vt, et, ovf.astype(jnp.int32)

    # lint: ok(R1) — builder: the sole caller (dist.refresh_shard_
    # analysis_device) caches by (angedg,KS,S,G,Mp) and wraps the
    # product in governed("dist.analysis_grouped", budget=2)
    fn = shard_map(local, mesh=dmesh,
                   in_specs=(spec, spec, spec, spec),
                   out_specs=(spec, spec, P()), check_vma=False)
    # lint: ok(R1) — same builder contract as above
    return jax.jit(fn)
