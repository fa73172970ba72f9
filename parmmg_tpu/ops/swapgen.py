"""Generalized edge swaps: shell degree 4-6 ring re-triangulation.

Reference behavior: Mmg's swap pass (``MMG5_swpmsh``/``MMG3D_swpgen``,
invoked from the remesher the reference calls per group at
/root/reference/src/libparmmg1.c:737-739) removes an interior edge whose
shell has n tets by re-triangulating the ring polygon p0..p_{n-1} into
n-2 triangles; each triangle T yields the two tets (T, a), (T, b).  Mmg
enumerates triangulation configurations from precomputed tables and
applies the one whose worst new quality beats the old shell by the swap
gain.  n=3 is the classic 3-2 swap (ops/swap.py); THIS kernel handles
n = 4..6 — the degree classes whose absence capped the final min
quality (the worst surviving tets are exactly the ones only a
higher-degree re-triangulation can fix).

TPU design: one batched wave.  Candidates (interior untagged edges with
a 4-6 tet shell) are top-K compacted by worst shell quality; the ring
is chained from the shell tets with a fixed-trip unrolled walk; all n
FAN triangulations are evaluated in one stacked quality call (for n=4,5
fans enumerate ALL triangulations — Catalan(2)=2, Catalan(3)=5; for n=6
a 6-of-14 subset); the best valid fan is applied under the same
exclusive shell-claim machinery as the other swap kernels.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mesh import Mesh
from ..core.constants import EPSD, QUAL_FLOOR, EDGE_FACES
from .edges import unique_edges, claim_shells, wave_budget
from .quality import quality_from_points
from .swap import SWAP_GAIN, _EDGE_OF

RING_MAX = 6            # max shell degree handled
NTRI = RING_MAX - 2     # fan triangles (padded)
NT_NEW = 2 * NTRI       # new tets per fan (padded)


class SwapGenResult(NamedTuple):
    mesh: Mesh
    nswap: jax.Array
    # with a worklist only (ops/worklist):
    keep: jax.Array = None      # [capT] bool, rows that stay on the list
    ncand: jax.Array = None     # candidate rows the top-K selected
    nlist: jax.Array = None     # of them on the list: what was judged


def swapgen_wave(mesh: Mesh, met: jax.Array,
                 budget_div: int = 8, budget: int | None = None,
                 lmax: float | None = None,
                 worklist=None, et=None) -> SwapGenResult:
    """``worklist``: an ``ops/worklist.Dirty``, what changed since this
    kernel last judged the mesh.  Only the candidates it lists are
    evaluated, in chunks as wide as the list; the result is the full
    evaluation's to the bit while the caller keeps the list by that
    module's rules.  None evaluates every candidate.

    ``et``: the mesh's edge table with ``RING_MAX`` shell slots where
    the caller has it (the merged polish, off the sort it carries:
    ops/topo_incr); None builds it here."""
    from ..core.constants import LLONG
    from . import worklist as wl
    if lmax is None:
        lmax = LLONG
    capT, capP = mesh.capT, mesh.capP
    if et is None:
        et = unique_edges(mesh, shell_slots=RING_MAX)
    m6 = None if met.ndim == 1 else met
    Efull = et.ev.shape[0]

    # ---- full-width candidacy + worst-shell priority --------------------
    q_tet = quality_from_points(
        mesh.vert[mesh.tet], None if m6 is None else m6[mesh.tet])
    sh_f = et.shell3                                     # [E, 6]
    shc_f = jnp.clip(sh_f, 0, capT - 1)
    slot_valid_f = sh_f >= 0
    qs = jnp.where(slot_valid_f, q_tet[shc_f], jnp.inf)
    q_shell_f = jnp.min(qs, axis=1)
    tref0 = mesh.tref[shc_f[:, 0]]
    same_ref = jnp.all(
        ~slot_valid_f | (mesh.tref[shc_f] == tref0[:, None]), axis=1)
    pre = et.emask & (et.etag == 0) & (et.nshell >= 4) & \
        (et.nshell <= RING_MAX) & same_ref
    # NOTE the remaining static gates (vanishing-face tags, ring
    # closure) are applied post-compaction: they need per-slot corner
    # positions, too heavy at [E,6] width.  Statically-doomed candidates
    # can therefore pin budget slots; this kernel runs in the
    # wide-budget polish phase where K covers the population.
    K = min(Efull, wave_budget(capT, budget_div, budget))
    _, selx = jax.lax.top_k(jnp.where(pre, -q_shell_f, -jnp.inf), K)

    def shell_of(sel):
        """A selection's compacted shell: slots [k, 6] clipped, which of
        them count, the edge's ends."""
        n = et.nshell[sel]
        sh = sh_f[sel]
        slot_valid = (sh >= 0) & \
            (jnp.arange(RING_MAX)[None, :] < n[:, None])
        a = jnp.clip(et.ev[sel, 0], 0, capP - 1)
        b = jnp.clip(et.ev[sel, 1], 0, capP - 1)
        return n, jnp.clip(sh, 0, capT - 1), slot_valid, a, b

    def stage(sel):
        """The candidate stage, row by row: ring chain, the six fans,
        the best fan's rows and tags."""
        return _ring_stage(mesh, met, lmax, pre[sel], q_shell_f[sel],
                           *shell_of(sel))

    pos = None
    if worklist is None:
        rows = stage(selx)
    else:
        # the compaction line: ahead of it the wave costs its capacity,
        # after it what the list holds
        _, shc0, valid0, a0, b0 = shell_of(selx)
        listed = pre[selx] & wl.on_list(worklist, shc0, valid0, a0, b0)
        pos, nlist = wl.listed_first(listed)
        selx = selx[pos]
        rows = wl.staged(stage, selx, nlist)
        rows = rows._replace(cand=rows.cand & (jnp.arange(K) < nlist))
    cand, n, shc, slot_valid = rows.cand, rows.n, rows.shc, rows.slot_valid
    q_old, q_new = rows.q_old, rows.q_new

    # ---- claims ----------------------------------------------------------
    sh_eff = tuple(
        jnp.where(slot_valid[:, k], shc[:, k], shc[:, 0])
        for k in range(RING_MAX))
    win = claim_shells(q_new - q_old, cand, sh_eff, capT, pos=pos)

    # ---- allocation of the extra (n-4) slots -----------------------------
    # slot-reusing pool (edges.free_rows): each winner takes up to
    # RING_MAX-4 consecutive POOL entries, not consecutive slots
    from .edges import free_rows
    LF = 2 * K
    frow_t, nfree_t = free_rows(mesh.tmask, LF)
    extra = jnp.where(win, n - 4, 0)
    off = jnp.cumsum(extra) - extra
    fits = (off + extra) <= jnp.minimum(nfree_t, LF)
    win = win & fits
    extra = jnp.where(win, n - 4, 0)
    off = jnp.cumsum(extra) - extra

    # ---- write: m < n reuses shell slots, m >= n allocates ---------------
    nsw = jnp.sum(win.astype(jnp.int32))

    def _apply(_):
        tet_o = mesh.tet
        ftag_o = mesh.ftag
        fref_o = mesh.fref
        etag_o = mesh.etag
        tmask_o = mesh.tmask
        tref_o = mesh.tref
        idx_all = []
        for m in range(NT_NEW):
            valid_m = win & (m < 2 * (n - 2))
            tgt = jnp.where(
                m < n, shc[:, min(m, RING_MAX - 1)],
                frow_t[jnp.clip(off + jnp.maximum(m - n, 0), 0, LF - 1)])
            idx_all.append(jnp.where(valid_m, tgt, capT))
        idx_cat = jnp.concatenate(idx_all)
        tet_o = tet_o.at[idx_cat].set(
            rows.tets.transpose(1, 0, 2).reshape(NT_NEW * K, 4),
            mode="drop")
        ftag_o = ftag_o.at[idx_cat].set(
            rows.ftag.transpose(1, 0, 2).reshape(NT_NEW * K, 4),
            mode="drop")
        fref_o = fref_o.at[idx_cat].set(
            rows.fref.transpose(1, 0, 2).reshape(NT_NEW * K, 4),
            mode="drop")
        etag_o = etag_o.at[idx_cat].set(
            rows.etag.transpose(1, 0, 2).reshape(NT_NEW * K, 6),
            mode="drop")
        tmask_o = tmask_o.at[idx_cat].set(True, mode="drop")
        tref_o = tref_o.at[idx_cat].set(
            jnp.tile(tref0[selx], NT_NEW), mode="drop")
        return tet_o, ftag_o, fref_o, etag_o, tmask_o, tref_o

    def _skip(_):
        return (mesh.tet, mesh.ftag, mesh.fref, mesh.etag, mesh.tmask,
                mesh.tref)

    tet_o, ftag_o, fref_o, etag_o, tmask_o, tref_o = jax.lax.cond(
        nsw > 0, _apply, _skip, None)
    used_hi = jnp.where(extra > 0,
                        frow_t[jnp.clip(off + extra - 1, 0, LF - 1)] + 1, 0)
    nelem = jnp.maximum(mesh.nelem, jnp.max(used_hi))
    out = dataclasses.replace(
        mesh, tet=tet_o, tmask=tmask_o, tref=tref_o, ftag=ftag_o,
        fref=fref_o, etag=etag_o, nelem=nelem.astype(jnp.int32))
    if worklist is None:
        return SwapGenResult(out, nsw)
    # every gate of the stage reads the shell alone, so what stays on the
    # list is what passed them all and lost a claim or found no free row
    npre = jnp.sum(pre, dtype=jnp.int32)
    return SwapGenResult(
        out, nsw, wl.keep_rows(cand & ~win, shc[:, 0], npre, capT),
        jnp.minimum(npre, K), nlist)


class _RingRows(NamedTuple):
    """Per candidate row, what the claims and the apply read."""
    cand: jax.Array         # [k] passed every gate (all read the shell)
    n: jax.Array            # [k] shell degree
    shc: jax.Array          # [k, 6] shell rows, clipped
    slot_valid: jax.Array   # [k, 6]
    q_old: jax.Array
    q_new: jax.Array
    tets: jax.Array         # [k, NT_NEW, 4] the best fan's tets
    ftag: jax.Array
    fref: jax.Array
    etag: jax.Array         # [k, NT_NEW, 6]


def _ring_stage(mesh: Mesh, met, lmax, cand, q_old, n, shc,
                slot_valid, a, b) -> _RingRows:
    """swapgen_wave's candidate stage on compacted rows, each row
    by itself: every value read is one of the row's shell (its tets'
    vertex ids, tags and references, their vertices' coordinates and
    metric), which is what lets a worklist skip a row whose shell did not
    change."""
    capP = mesh.capP
    m6 = None if met.ndim == 1 else met
    nrow = cand.shape[0]
    ar = jnp.arange(nrow)
    eof = jnp.asarray(_EDGE_OF)
    efaces = jnp.asarray(EDGE_FACES)
    tvs = mesh.tet[shc]                                  # [K,6,4]
    is_a = tvs == a[:, None, None]
    is_b = tvs == b[:, None, None]
    is_ab = is_a | is_b
    # every (valid) shell tet must contain both endpoints
    cand = cand & jnp.all(
        ~slot_valid | (jnp.sum(is_ab.astype(jnp.int32), 2) == 2), axis=1)
    pos_a = jnp.argmax(is_a, axis=2).astype(jnp.int32)   # [K,6]
    pos_b = jnp.argmax(is_b, axis=2).astype(jnp.int32)
    # the two ring corners of each shell tet (stable argsort: non-ab first)
    ordr = jnp.argsort(is_ab.astype(jnp.int32), axis=2, stable=True)
    x = jnp.take_along_axis(tvs, ordr[:, :, 0:1], 2)[:, :, 0]   # [K,6]
    y = jnp.take_along_axis(tvs, ordr[:, :, 1:2], 2)[:, :, 0]

    # ---- vanishing-face gate: the n faces containing (a,b) die ----------
    lae = eof[pos_a, pos_b]                              # [K,6]
    ftags_sh = mesh.ftag[shc]                            # [K,6,4]
    fc = jnp.take_along_axis(ftags_sh, efaces[lae][..., 0:1], 2)[..., 0]
    fc2 = jnp.take_along_axis(ftags_sh, efaces[lae][..., 1:2], 2)[..., 0]
    cand = cand & jnp.all(~slot_valid | ((fc == 0) & (fc2 == 0)), axis=1)

    # ---- ring chain ------------------------------------------------------
    # walk the shell: pair slot 0 covers (ring0, ring1); each step finds
    # the unused shell tet containing the chain head; the final unused
    # tet must close the cycle.  A ring vertex belongs to exactly 2
    # shell tets in a valid ring, so the chain is deterministic.
    ring = jnp.zeros((nrow, RING_MAX), jnp.int32)
    tet_of_pair = jnp.zeros((nrow, RING_MAX), jnp.int32)    # shell SLOT idx
    ring = ring.at[:, 0].set(x[:, 0])
    ring = ring.at[:, 1].set(y[:, 0])
    used = jnp.zeros((nrow, RING_MAX), bool).at[:, 0].set(True)
    used = used | ~slot_valid                            # pad slots "used"
    cur = y[:, 0]
    for step in range(2, RING_MAX):
        active = step < n
        has = (~used) & ((x == cur[:, None]) | (y == cur[:, None]))
        j = jnp.argmax(has, axis=1)
        found = jnp.any(has, axis=1)
        xj = x[ar, j]
        yj = y[ar, j]
        other = jnp.where(xj == cur, yj, xj)
        ring = ring.at[:, step].set(jnp.where(active, other, ring[:, 0]))
        tet_of_pair = tet_of_pair.at[:, step - 1].set(
            jnp.where(active, j, tet_of_pair[:, step - 1]))
        used = used.at[ar, j].set(used[ar, j] | (active & found))
        cand = cand & (~active | found)
        cur = jnp.where(active, other, cur)
    # closing pair (ring[n-1], ring[0]) must be the one unused slot
    r0 = ring[:, 0]
    has_close = (~used) & \
        (((x == cur[:, None]) & (y == r0[:, None])) |
         ((y == cur[:, None]) & (x == r0[:, None])))
    jc = jnp.argmax(has_close, axis=1)
    cand = cand & jnp.any(has_close, axis=1)
    nm1 = jnp.clip(n - 1, 0, RING_MAX - 1)
    tet_of_pair = tet_of_pair.at[ar, nm1].set(jc)

    # ---- per-ring-position tag sources ----------------------------------
    # pair r covers ring edge (ring[r], ring[(r+1)%n]) inside old shell
    # tet t = sh[tet_of_pair[r]].
    rp1 = jnp.where(jnp.arange(RING_MAX)[None, :] + 1 < n[:, None],
                    jnp.arange(RING_MAX)[None, :] + 1, 0)
    ring_next = jnp.take_along_axis(ring, rp1, 1)        # [K,6]
    tp = jnp.take_along_axis(shc, tet_of_pair, 1)        # [K,6] tet ids
    tvp = mesh.tet[tp]                                   # [K,6,4]
    pa_p = jnp.argmax(tvp == a[:, None, None], 2).astype(jnp.int32)
    pb_p = jnp.argmax(tvp == b[:, None, None], 2).astype(jnp.int32)
    pr_p = jnp.argmax(tvp == ring[:, :, None], 2).astype(jnp.int32)
    pn_p = jnp.argmax(tvp == ring_next[:, :, None], 2).astype(jnp.int32)
    etag_p = mesh.etag[tp]                               # [K,6,6]
    ftag_p = mesh.ftag[tp]
    fref_p = mesh.fref[tp]

    def _take(rows, idx):
        return jnp.take_along_axis(rows, idx[..., None], 2)[..., 0]

    ring_etag = _take(etag_p, eof[pr_p, pn_p])           # ring edge (r,r+1)
    spoke_a = _take(etag_p, eof[pr_p, pa_p])             # edge (ring_r, a)
    spoke_b = _take(etag_p, eof[pr_p, pb_p])
    face_a = _take(ftag_p, pb_p)         # face (ring_r, ring_{r+1}, a)
    face_b = _take(ftag_p, pa_p)
    fref_a = _take(fref_p, pb_p)
    fref_b = _take(fref_p, pa_p)

    # ---- fan enumeration -------------------------------------------------
    # fan center c: triangles (c, c+k+1, c+k+2) mod n, k = 0..n-3.
    # tets: (pi, pj, pk, a) and (pj, pi, pk, b).
    pav = mesh.vert[a]
    pbv = mesh.vert[b]
    ringp = mesh.vert[jnp.clip(ring, 0, capP - 1)]       # [K,6,3]

    def ring_at(idx):
        """Gather ring vertex ids/[K] positions at (idx % n)."""
        m = jnp.where(idx < n, idx, idx - n)
        m = jnp.where(m < n, m, 0)
        return m

    fan_q = []
    fan_ok = []
    fan_tets = []        # per fan: [K, NT_NEW, 4] vertex ids
    fan_flip = []
    from .quality import edge_length_iso, edge_length_ani

    def _elen(gu, gv):
        pu, pv = mesh.vert[gu], mesh.vert[gv]
        if m6 is None:
            return edge_length_iso(pu, pv, met[gu], met[gv])
        return edge_length_ani(pu, pv, m6[gu], m6[gv])

    for c in range(RING_MAX):
        active_fan = (c < n) & cand
        vols_a = []
        vols_b = []
        tris = []
        diag_long = jnp.zeros((nrow,), bool)
        for k in range(NTRI):
            i_i = ring_at(jnp.full((nrow,), c, jnp.int32))
            i_j = ring_at(c + k + 1 + jnp.zeros((nrow,), jnp.int32))
            i_k = ring_at(c + k + 2 + jnp.zeros((nrow,), jnp.int32))
            pi = ringp[ar, i_i]
            pj = ringp[ar, i_j]
            pk = ringp[ar, i_k]
            nrm = jnp.cross(pj - pi, pk - pi)
            vols_a.append(jnp.sum(nrm * (pav - pi), -1))
            vols_b.append(-jnp.sum(nrm * (pbv - pi), -1))
            tris.append((i_i, i_j, i_k))
            # new DIAGONAL edges must not exceed the split threshold —
            # nothing re-splits after the polish phase this kernel runs
            # in, so an overlong diagonal would survive to the output
            kv = k < (n - 2)
            if k > 0:               # (pi,pj) is a diagonal unless k==0
                diag_long = diag_long | (
                    kv & (_elen(ring[ar, i_i], ring[ar, i_j]) > lmax))
            diag_long = diag_long | (
                kv & (k < n - 3) &  # (pi,pk) diagonal unless k==n-3
                (_elen(ring[ar, i_i], ring[ar, i_k]) > lmax))
        va_s = jnp.stack(vols_a, 1)                      # [K, NTRI]
        vb_s = jnp.stack(vols_b, 1)
        kvalid = jnp.arange(NTRI)[None, :] < (n - 2)[:, None]
        tot_a = jnp.sum(jnp.where(kvalid, va_s, 0.0), axis=1)
        sgn = jnp.where(tot_a >= 0, 1.0, -1.0)           # ring orientation
        ok = jnp.all(~kvalid | ((va_s * sgn[:, None] > EPSD) &
                                (vb_s * sgn[:, None] > EPSD)), axis=1) \
            & ~diag_long
        # tets with orientation fix: flip (pi, pj) when sgn < 0
        flip = sgn < 0
        tet_rows = []
        for k, (i_i, i_j, i_k) in enumerate(tris):
            gi = ring[ar, i_i]
            gj = ring[ar, i_j]
            gk = ring[ar, i_k]
            w0a = jnp.where(flip, gj, gi)
            w1a = jnp.where(flip, gi, gj)
            tet_rows.append(jnp.stack([w0a, w1a, gk, a], 1))
            # b-apex tet: base orientation (pj, pi, pk, b), flip undoes
            w0b = jnp.where(flip, gi, gj)
            w1b = jnp.where(flip, gj, gi)
            tet_rows.append(jnp.stack([w0b, w1b, gk, b], 1))
        rows = jnp.stack(tet_rows, 1)                    # [K, NT_NEW, 4]
        qf = quality_from_points(
            mesh.vert[rows.reshape(nrow * NT_NEW, 4)],
            None if m6 is None else m6[rows.reshape(nrow * NT_NEW, 4)])
        qf = qf.reshape(nrow, NT_NEW)
        mvalid = jnp.repeat(kvalid, 2, axis=1)           # [K, NT_NEW]
        fan_q.append(jnp.min(jnp.where(mvalid, qf, jnp.inf), axis=1))
        fan_ok.append(active_fan & ok)
        fan_tets.append(rows)
        fan_flip.append(flip)

    fq = jnp.stack(fan_q, 1)                             # [K, 6]
    fok = jnp.stack(fan_ok, 1)
    fq_m = jnp.where(fok, fq, -jnp.inf)
    best_c = jnp.argmax(fq_m, axis=1)                    # [K]
    q_new = fq_m[ar, best_c]
    cand = cand & jnp.any(fok, axis=1) & \
        (q_new > jnp.maximum(SWAP_GAIN * q_old, QUAL_FLOOR))

    # ---- gather the winning fan's rows + route tags ----------------------
    tets_best = jnp.stack(fan_tets, 1)[ar, best_c]       # [K, NT_NEW, 4]
    flip_best = jnp.stack(fan_flip, 1)[ar, best_c]       # [K]

    def route(c_arr, k, apex_is_a):
        """Face/edge tags of new tet (tri k of fan c, given apex).

        Base corner order (pi, pj, pk, apex); a corner-(0,1) swap
        permutes face cols (0,1) and edge cols (0,3,4,1,2,5) — the
        ops/swap.py routing convention.  The a-tet is built flipped when
        flip_best; the b-tet starts from (pj, pi, pk, b), so its
        effective routing flip is the NEGATION of flip_best.
        """
        eff_flip = flip_best if apex_is_a else ~flip_best
        i_j = ring_at(c_arr + k + 1)
        i_k = ring_at(c_arr + k + 2)
        pair_j = i_j                 # ring pair (c+k+1, c+k+2): always
        f_src = face_a if apex_is_a else face_b
        fr_src = fref_a if apex_is_a else fref_b
        sp_src = spoke_a if apex_is_a else spoke_b
        zero_u = jnp.zeros(nrow, jnp.uint32)
        zero_i = jnp.zeros(nrow, jnp.int32)
        is_first = k == 0                                # (pi,pj) ring pair
        nlast = (k == (n - 3))                           # (pi,pk) ring pair
        pair_c = ring_at(c_arr)                          # pair index c
        pair_last = ring_at(c_arr + k + 2)               # pair (c+k+2)=c-1
        # face cols: 0 opp pi = (pj,pk,ap) <- pair_j; 1 opp pj =
        # (pi,pk,ap) <- pair (c-1) iff k==n-3; 2 opp pk = (pi,pj,ap) <-
        # pair c iff k==0; 3 opp apex = triangle, interior
        f0 = f_src[ar, pair_j]
        f1 = jnp.where(nlast, f_src[ar, pair_last], zero_u)
        f2 = (f_src[ar, pair_c] if is_first
              else zero_u)
        fr0 = fr_src[ar, pair_j]
        fr1 = jnp.where(nlast, fr_src[ar, pair_last], zero_i)
        fr2 = (fr_src[ar, pair_c] if is_first else zero_i)
        ftag_n = jnp.stack([
            jnp.where(eff_flip, f1, f0),
            jnp.where(eff_flip, f0, f1),
            f2, zero_u], 1)
        fref_n = jnp.stack([
            jnp.where(eff_flip, fr1, fr0),
            jnp.where(eff_flip, fr0, fr1),
            fr2, zero_i], 1)
        # edges (pi-pj, pi-pk, pi-ap, pj-pk, pj-ap, pk-ap)
        e0 = (ring_etag[ar, pair_c] if is_first else zero_u)
        e1 = jnp.where(nlast, ring_etag[ar, pair_last], zero_u)
        e2 = sp_src[ar, ring_at(c_arr)]
        e3 = ring_etag[ar, pair_j]
        e4 = sp_src[ar, i_j]
        e5 = sp_src[ar, i_k]
        cols = [e0, e1, e2, e3, e4, e5]
        flipped = [cols[0], cols[3], cols[4], cols[1], cols[2], cols[5]]
        etag_n = jnp.stack(
            [jnp.where(eff_flip, fv, nv)
             for nv, fv in zip(cols, flipped)], 1)
        return ftag_n, fref_n, etag_n

    c_arr = best_c.astype(jnp.int32)
    ftag_rows, fref_rows, etag_rows = [], [], []
    for k in range(NTRI):
        for apex_is_a in (True, False):
            fa, fr, ea = route(c_arr, k, apex_is_a)
            ftag_rows.append(fa)
            fref_rows.append(fr)
            etag_rows.append(ea)
    # m-slot order must match tet_rows construction: (k, a), (k, b)
    ftag_new = jnp.stack(ftag_rows, 1)                   # [K, NT_NEW, 4]
    fref_new = jnp.stack(fref_rows, 1)
    etag_new = jnp.stack(etag_rows, 1)                   # [K, NT_NEW, 6]

    return _RingRows(cand, n, shc, slot_valid, q_old, q_new, tets_best,
                     ftag_new, fref_new, etag_new)


# eager entry point: ONE module-level jitted object + compile-ledger
# registration (the ROADMAP governor follow-on for the swapgen/repair
# tails).  The production hot path calls swapgen_wave inline from the
# already-jitted sliver_polish_impl and is unaffected; this is the
# governed front door for callers OUTSIDE an enclosing jit (tests,
# diagnostics, future eager tails) so they neither retrace the wave
# op-by-op nor mint a fresh jax.jit object per call
def _make_swapgen_jit():
    from functools import partial as _partial
    from ..utils.compilecache import governed
    return governed("ops.swapgen_wave", budget=4)(
        _partial(jax.jit, static_argnames=("budget_div", "budget", "lmax"))(
            swapgen_wave))


swapgen_wave_j = _make_swapgen_jit()
