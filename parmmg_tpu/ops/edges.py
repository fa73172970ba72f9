"""Unique mesh edges and edge->tet incidence, sort-based (jittable).

Replaces Mmg's edge hash tables (``MMG5_hashEdge`` family; the reference's
parallel variants live in hash_pmmg.c:38-234) with the sort/segment idiom:
all 6*capT tet edges are materialized, lexsorted by (min vid, max vid), and
the first occurrence of each key becomes the representative unique edge.
Every (tet, local-edge) slot learns its unique-edge id — that gather table is
what the split/collapse/swap kernels use to look up per-edge decisions.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh, tet_edge_vertices
from ..core.constants import IARE
from ..obs import trace as otrace
from ..utils import placement

_INT32_MAX = 2147483647


PACK_LIMIT = 46340     # floor(sqrt(2^31)): a*capP+b stays in int32


def wave_budget(capT: int, div: int = 8, rows: int | None = None) -> int:
    """Per-wave top-K compaction budget shared by every wave kernel: the
    K = max(2048, capT//div) highest-priority candidates go through the
    heavy geometry/routing/scatter machinery (cost is linear in index
    count — PERF.md section 5); the rest are deferred to the next
    wave.  The polish passes div=2 for full coverage.

    ``capT // div`` ties the budget to the PADDING: right for a mesh
    whose capacity is sized for the content it will grow to (the cycle
    blocks), wrong for one whose capacity is only a convenience.  A
    caller that knows its content gives the budget in ``rows`` instead
    (the merged polish: driver.polish_budget), and then the capacity can
    change without the candidate set changing with it."""
    return max(2048, capT // div) if rows is None else rows


def free_rows(mask: jax.Array, K: int):
    """First ``K`` dead rows (``mask`` False) — the slot-reusing
    allocation pool shared by the allocating wave kernels (split,
    swap23, swapgen).

    Allocating from the watermark cursor alone (the rounds-1..3 scheme)
    never reclaims interior rows freed by collapses; once the watermark
    reaches capacity every split is capacity-dropped FOREVER even when
    most of the array is dead — observed as a permanently-overflowing
    bench at ~92% live fill (the reference instead reuses freed slots
    through its linked free lists, MMG3D_newElt/MMG3D_delElt).  One
    [cap]-width compaction per allocating wave buys exact slot reuse;
    watermarks remain monotone upper bounds (used-prefix hints only —
    mesh.py documents masks as authoritative).

    Returns (rows [K] int32, cap-padded; nfree scalar int32)."""
    cap = mask.shape[0]
    rows = jnp.nonzero(~mask, size=K, fill_value=cap)[0].astype(jnp.int32)
    return rows, jnp.sum(~mask, dtype=jnp.int32)


def sort_carry(keys, payloads=()):
    """ONE stable ``lax.sort`` over the key columns, most significant
    first, that hands back what it sorted: ``(order, sorted keys, sorted
    payloads)``, tuples as given.  ``order`` is what ``jnp.argsort`` /
    ``jnp.lexsort`` return (they ARE this sort, with the sorted keys
    thrown away), so every result equals the argsort-and-fetch
    formulation to the bit on any backend.

    The sorted keys are operands of the sort already and cost nothing;
    a payload column rides as one more operand.  On the chip that is
    0.07 ms at 6 x capT where the fetch through the permutation (a
    gather out of a table as long as its index) is 1.7-4.4 ms.  XLA:CPU
    pays the other way (an operand costs its sort a quarter, 18 ms at
    that width, the fetch under 1 ms) but meets 2 to 14 such sorts a
    job, most at band width: one path (PERF.md section 5, "a
    permutation's two ways")."""
    nk = len(keys)
    iota = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    out = jax.lax.sort((*keys, iota, *payloads), num_keys=nk,
                       is_stable=True)
    return out[nk], tuple(out[:nk]), tuple(out[nk + 1:])


def unsort(order, cols):
    """The way back: ``cols`` (int32, in sorted order) in slot order,
    ``out[order[i]] = col[i]`` for a permutation ``order`` of all slots.
    Placed on a TPU it is a sort keyed on ``order`` that carries the
    columns (0.42 ms for two at 6 x capT, where the scatter is 3.6: the
    compiler sorts the indices anyway and then fetches the rows through
    them); elsewhere ONE packed scatter (XLA:CPU: 1.4 ms against a sort
    of 86; PERF.md section 5, "a permutation's two ways")."""
    if placement.placed_on_tpu():
        return tuple(jax.lax.sort((order, *cols), num_keys=1)[1:])
    pay = jnp.stack(cols, axis=1)
    back = jnp.zeros_like(pay).at[order].set(pay, unique_indices=True)
    return tuple(back[:, j] for j in range(len(cols)))


def sort_pairs(a: jax.Array, b: jax.Array, valid: jax.Array, capP: int,
               payloads=()):
    """Sort (a, b) id pairs ascending, invalid slots last.

    Returns (order, ka, kb, first, pays): the sort permutation, the
    sorted key columns (INT32_MAX on invalid slots), the unique-segment
    heads, and ``payloads`` in sorted order (:func:`sort_carry`: nothing
    is fetched back through ``order`` on the chip).
    When ids fit (capP <= PACK_LIMIT — always true for ParMmg-sized
    shards, the reference targets ~30k-element groups) both keys pack
    into ONE int32 key column.  The sort itself is cheap on the chip (a
    block's 58 sorts are 2 % of it); what a table costs is the gathers
    and scatters round it (PERF.md section 5).
    """
    if capP <= PACK_LIMIT:
        key = jnp.where(valid, a * capP + b, _INT32_MAX)
        order, (ks,), pays = sort_carry((key,), payloads)
        first = segment_first((ks,))
        inv = ks == _INT32_MAX
        ka = jnp.where(inv, _INT32_MAX, ks // capP)
        kb = jnp.where(inv, _INT32_MAX, ks % capP)
        return order, ka, kb, first, pays
    aa = jnp.where(valid, a, _INT32_MAX)
    bb = jnp.where(valid, b, _INT32_MAX)
    order, (ka, kb), pays = sort_carry((aa, bb), payloads)
    first = segment_first((ka, kb))
    return order, ka, kb, first, pays


def segment_first(words) -> jax.Array:
    """Segment-start flags over sorted columns: first[i] is True iff
    i == 0 or any words[j][i] != words[j][i-1]."""
    neq = words[0][1:] != words[0][:-1]
    for w in words[1:]:
        neq = neq | (w[1:] != w[:-1])
    return jnp.concatenate([jnp.array([True]), neq])


def segmented_or(first: jax.Array, values: jax.Array) -> jax.Array:
    """Inclusive segmented bitwise-OR scan over sorted segments.

    ``first`` marks segment heads; returns the running OR within each
    segment (the LAST element of a segment holds the full segment OR).
    Shared by unique_edges and the collapse edge/face tag-transfer joins.
    """
    def seg_or(pair_a, pair_b):
        fa, va = pair_a
        fb, vb = pair_b
        return fa | fb, jnp.where(fb, vb, va | vb)
    _, out = jax.lax.associative_scan(seg_or, (first, values))
    return out


def segmented_max(first: jax.Array, values: jax.Array) -> jax.Array:
    """Inclusive segmented max scan (same contract as segmented_or)."""
    def seg_max(pair_a, pair_b):
        fa, va = pair_a
        fb, vb = pair_b
        return fa | fb, jnp.where(fb, vb, jnp.maximum(va, vb))
    _, out = jax.lax.associative_scan(seg_max, (first, values))
    return out


class EdgeTable(NamedTuple):
    """Unique edges of the mesh.  capE = 6*capT slots, masked.

    ``edge_id[t, e]`` maps each tet-edge slot to its unique edge id
    (garbage on invalid tets).  ``ev`` are the (min, max) vertex ids of the
    unique edge; ``emask`` marks live unique-edge slots; ``etag`` is the OR
    of the per-tet edge tags over all incident tets (tags must agree, the
    OR makes the table robust to partially-propagated tags); ``nshell`` is
    the number of incident tets (the shell size).
    """
    ev: jax.Array       # [capE, 2] int32
    emask: jax.Array    # [capE] bool
    etag: jax.Array     # [capE] uint32
    nshell: jax.Array   # [capE] int32
    edge_id: jax.Array  # [capT, 6] int32
    shell3: jax.Array   # [capE, S] int32 first S shell tet ids (-1 unused;
    #                     S = 3 by default, wider for the generalized swaps
    #                     — see unique_edges(shell_slots=...))
    shell_rank: jax.Array  # [capT, 6] int32 rank of this tet in the edge's
    #                     shell (ascending tet id) — free by-product of the
    #                     sort; lets split_wave skip its own ranking sort
    skey: jax.Array = None  # [capE] ascending packed keys a*capP+b of the
    #                     internal sort (duplicates included, INT32_MAX on
    #                     invalid slots); empty [0] when capP > PACK_LIMIT.
    #                     Lets swap22's duplicate-diagonal existence probe
    #                     binary-search without re-sorting the table


def unique_edges(mesh: Mesh, shell_slots: int = 3) -> EdgeTable:
    """``shell_slots=0`` skips the shell-tet-id scatter entirely (returns
    ``shell3`` with zero columns) — split/collapse never read it, only the
    swap kernels do, and every scatter at [6*capT] width is a measured
    multi-ms item on this device (scripts/tpu_microbench.py; a block by
    phase: PERF.md section 5)."""
    with otrace.scope("tab.edges"):
        capT = mesh.capT
        n6 = capT * 6
        ev = tet_edge_vertices(mesh.tet).reshape(n6, 2)
        a = jnp.minimum(ev[:, 0], ev[:, 1])
        b = jnp.maximum(ev[:, 0], ev[:, 1])
        valid = jnp.repeat(mesh.tmask, 6)
        order, ka, kb, first, (tags,) = sort_pairs(
            a, b, valid, mesh.capP, (mesh.etag.reshape(n6),))
        return _edges_epilogue(mesh, order, ka, kb, first, shell_slots,
                               tags)


def unique_edges_from_sorted(mesh: Mesh, order: jax.Array, ks: jax.Array,
                             shell_slots: int = 0, tags=None) -> EdgeTable:
    """EdgeTable from a precomputed PACKED edge sort: ``order`` is the
    stable sort permutation over the 6*capT slot keys and ``ks`` the
    ascending packed keys (a*capP+b, INT32_MAX on invalid slots) —
    exactly what ``sort_pairs``' packed branch produces.  This is the
    epilogue of :func:`unique_edges` factored out so the incremental
    path (ops/topo_incr) can feed a band-merged sort through the SAME
    code.  ``tags``: the CURRENT mesh's edge tags in sorted order where
    the caller has them (a full sort carried them); the retained state
    carries none by design, so None fetches them through ``order`` (a
    merged sort has no sort to ride in).  Requires ``capP <=
    PACK_LIMIT``."""
    first = segment_first((ks,))
    inv = ks == _INT32_MAX
    ka = jnp.where(inv, _INT32_MAX, ks // mesh.capP)
    kb = jnp.where(inv, _INT32_MAX, ks % mesh.capP)
    return _edges_epilogue(mesh, order, ka, kb, first, shell_slots, tags)


def _edges_epilogue(mesh: Mesh, order, ka, kb, first,
                    shell_slots: int, tags=None) -> EdgeTable:
    """Shared unique_edges epilogue: the segment scans, the way back to
    slot order and the shell scatter, from the sorted key columns.
    ``tags``: the slots' edge tags in sorted order where the sort carried
    them (``unique_edges``); None fetches them through ``order``."""
    capT = mesh.capT
    n6 = capT * 6
    valid_s = ka != _INT32_MAX          # sorted-order validity, no gather
    # unique-edge id of each sorted slot = index of its segment head.
    # ONE tuple-carry scan produces the segment head AND the running
    # etag-OR together (two separate scans were a measured cost).
    pos = jnp.arange(n6)
    if tags is None:
        tags = mesh.etag.reshape(n6)[order]
    tags = jnp.where(valid_s, tags, 0)

    def seg_comb2(pa, pb):
        fa, ha, va = pa
        fb, hb, vb = pb
        return (fa | fb, jnp.where(fb, hb, jnp.maximum(ha, hb)),
                jnp.where(fb, vb, va | vb))

    _, seg_head, or_scan = jax.lax.associative_scan(
        seg_comb2, (first, jnp.where(first, pos, 0), tags))
    eid_sorted = seg_head
    rank = pos - seg_head
    is_last = jnp.concatenate([first[1:], jnp.array([True])])

    emask = first & valid_s
    ev_u = jnp.stack([ka, kb], axis=1)
    # per-unique-edge values (full OR of tags; shell count = last rank+1)
    # stand at the segment's LAST slot: a reverse segmented scan hands
    # them to its head (a drop scatter to the head was 3.6 ms a table on
    # the chip, the scan 0.39; on XLA:CPU they cost the same)
    def seg_last(pa, pb):
        # reverse scan: pa is the element further RIGHT
        la, va, na = pa
        lb, vb, nb = pb
        return la | lb, jnp.where(lb, vb, va), jnp.where(lb, nb, na)

    _, tot_or, tot_n = jax.lax.associative_scan(
        seg_last, (is_last, or_scan, (rank + 1).astype(jnp.int32)),
        reverse=True)
    etag = jnp.where(first, tot_or, 0).astype(jnp.uint32)
    nshell = jnp.where(first, tot_n, 0)
    # per (tet, local edge) slot: unique edge id + rank within the shell
    # (stable lexsort keeps equal keys in slot order = ascending tet id),
    # back in slot order through the permutation
    edge_id, shell_rank = (c.reshape(capT, 6) for c in unsort(
        order, (eid_sorted.astype(jnp.int32), rank.astype(jnp.int32))))
    # first-S shell tet ids per edge (3 for the 3-2 swap; 6-7 for the
    # generalized ring swaps): rank within segment
    if shell_slots > 0:
        tet_of_slot = (order // 6).astype(jnp.int32)
        shell3 = jnp.full((n6, shell_slots), -1, jnp.int32)
        tgt_e = jnp.where(valid_s & (rank < shell_slots), eid_sorted, n6)
        shell3 = shell3.at[tgt_e, jnp.clip(rank, 0, shell_slots - 1)].set(
            tet_of_slot, mode="drop", unique_indices=True)
    else:
        shell3 = jnp.zeros((n6, 0), jnp.int32)
    if shell_slots > 0 and mesh.capP <= PACK_LIMIT:
        # only the swap kernels consume skey; the slim split/collapse
        # tables (shell_slots=0) skip materializing it
        skey = jnp.where(valid_s, ka * mesh.capP + kb, _INT32_MAX)
    else:
        skey = jnp.zeros((0,), jnp.int32)
    return EdgeTable(ev=ev_u, emask=emask, etag=etag, nshell=nshell,
                     edge_id=edge_id, shell3=shell3, shell_rank=shell_rank,
                     skey=skey)


def edge_lengths(mesh: Mesh, et: EdgeTable, met: jax.Array) -> jax.Array:
    """[capE] metric length of each unique edge (garbage on dead slots).

    TPU lowering uses the fused Pallas kernels; every other platform the
    jnp formula — selected per lowering platform (NOT per process
    default backend, which may be a TPU plugin while this computation
    lowers for CPU devices)."""
    from functools import partial
    from .quality import edge_length_iso, edge_length_ani
    from .pallas_kernels import (use_pallas, pallas_forced,
                                 edge_length_iso_pallas,
                                 edge_length_ani_pallas)
    i0 = jnp.clip(et.ev[:, 0], 0, mesh.capP - 1)
    i1 = jnp.clip(et.ev[:, 1], 0, mesh.capP - 1)
    if met.ndim == 1:
        # pack (x, y, z, h) so each endpoint costs ONE row gather
        # (gather cost is linear in index count on this device)
        vm = jnp.concatenate([mesh.vert, met[:, None]], axis=1)
        r0, r1 = vm[i0], vm[i1]
        p0, p1 = r0[:, :3], r1[:, :3]
        m0, m1 = r0[:, 3], r1[:, 3]
    else:
        p0, p1 = mesh.vert[i0], mesh.vert[i1]
        m0, m1 = met[i0], met[i1]
    pal = (edge_length_iso_pallas if met.ndim == 1
           else edge_length_ani_pallas)
    ref = edge_length_iso if met.ndim == 1 else edge_length_ani
    if use_pallas():
        # the off-TPU branch is chosen at LOWERING time (the process
        # default may be a TPU plugin while this computation lowers for
        # CPU devices): jnp formula normally, interpreted Pallas kernel
        # when PARMMG_TPU_PALLAS=1 forces kernel numerics everywhere
        from ..utils.jaxcompat import platform_dependent
        off_tpu = partial(pal, interpret=True) if pallas_forced() else ref
        return platform_dependent(
            p0, p1, m0, m1,
            tpu=partial(pal, interpret=False), default=off_tpu)
    return ref(p0, p1, m0, m1)


def topk_prep(cand: jax.Array, val: jax.Array):
    """Top-k budget prep for a wave's candidate cut.

    Returns ``(where(cand, -val, -inf), sum(cand))`` — the score vector
    handed to ``lax.top_k`` and the int32 candidate count behind every
    ``defer`` flag.  These are exactly the two expressions each wave
    wrote inline, so wiring this in is bit-neutral; the TPU lowering
    fuses them into one VMEM pass + cross-block reduction
    (pallas_kernels.score_count_pallas, gated by PARMMG_PALLAS_SCORE),
    every other platform keeps the jnp reference.
    """
    from functools import partial
    from .pallas_kernels import (use_pallas, pallas_forced,
                                 pallas_score_enabled, score_count_pallas)

    def ref(c, v):
        return jnp.where(c, -v, -jnp.inf), jnp.sum(c.astype(jnp.int32))

    if use_pallas() and pallas_score_enabled():
        from ..utils.jaxcompat import platform_dependent
        off_tpu = (partial(score_count_pallas, interpret=True)
                   if pallas_forced() else ref)
        return platform_dependent(
            cand, val,
            tpu=partial(score_count_pallas, interpret=False),
            default=off_tpu)
    return ref(cand, val)


def topk_prep3(cand: jax.Array, v0: jax.Array, v1: jax.Array,
               v2: jax.Array):
    """``topk_prep`` fused with the 3-way shell-quality minimum of
    swap_edges_wave: ``val = min(v0, min(v1, v2))`` in that exact
    association order (f32 minimum is exact, so the fused kernel is
    bit-identical to the reference chain)."""
    from functools import partial
    from .pallas_kernels import (use_pallas, pallas_forced,
                                 pallas_score_enabled, score3_count_pallas)

    def ref(c, a, b, d):
        v = jnp.minimum(a, jnp.minimum(b, d))
        return jnp.where(c, -v, -jnp.inf), jnp.sum(c.astype(jnp.int32))

    if use_pallas() and pallas_score_enabled():
        from ..utils.jaxcompat import platform_dependent
        off_tpu = (partial(score3_count_pallas, interpret=True)
                   if pallas_forced() else ref)
        return platform_dependent(
            cand, v0, v1, v2,
            tpu=partial(score3_count_pallas, interpret=False),
            default=off_tpu)
    return ref(cand, v0, v1, v2)


def claim_shells(score, cand, shells, capT, pos=None):
    """Exclusive multi-slot claims: winner must be the two-channel
    (score, tie-hash) max at EVERY shell slot it touches.  Winners are
    pairwise shell-disjoint: two winners sharing a slot would both be
    that slot's pooled (s,t)-max — impossible, t is unique.  Shared by
    the swap kernels (each candidate claims its 2-3 cavity tets).

    ``pos``: the slot index each candidate's tie hash is taken of, for
    a caller that has permuted its candidates (ops/worklist) and must
    break ties as the unpermuted array would.

    All shells are claimed in ONE concatenated scatter per channel and
    checked with one stacked gather — per-op overhead dominates
    scatter/gather cost on this device (scripts/tpu_microbench.py)."""
    ps, pt = claim_channels(score, cand, pos=pos)
    k = len(shells)
    shs = jnp.stack(shells)                               # [k, E]
    idx = jnp.where(cand[None, :], shs, capT).reshape(-1)
    cl_s = jnp.full(capT + 1, NEG_INF).at[idx].max(
        jnp.tile(ps, k), mode="drop")
    eq = cand & jnp.all(ps[None, :] == cl_s[shs], axis=0)
    idx2 = jnp.where(eq[None, :], shs, capT).reshape(-1)
    cl_t = jnp.full(capT + 1, PRI_MIN).at[idx2].max(
        jnp.tile(pt, k), mode="drop")
    win = eq & jnp.all(pt[None, :] == cl_t[shs], axis=0)
    return win


def unique_priority(score: jax.Array, mask: jax.Array) -> jax.Array:
    """Turn a float score into a unique int32 priority (higher = better).

    Ties are broken by argsort rank; masked slots get priority 0.  Used by
    the independent-set claim resolution in the remesh kernels (the
    parallel analogue of Mmg's sequential everything-in-order
    application).  NOTE a sortless quantized variant (score top-bits +
    slot-index tie-break) was tried and reverted: index-ordered tie-breaks
    spatially bias the winner sets and measurably degrade final min
    quality.

    Retained for reference/tests; the production waves use the sort-free
    two-channel scheme below (full-precision f32 score + bijective-hash
    tie-break), which has the same total order without the O(n log^2 n)
    TPU sort and without the spatial bias of index tie-breaks.
    """
    n = score.shape[0]
    neg = jnp.where(mask, -score, jnp.inf)
    order = priority_order(neg)       # best (highest score) first
    rank = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    pri = n - rank                    # in [1, n], unique
    return jnp.where(mask, pri, 0).astype(jnp.int32)


def priority_order(neg: jax.Array) -> jax.Array:
    """Stable ascending argsort of the negated-score vector — the
    priority rank's sort leg (ties break by lane index)."""
    return jnp.argsort(neg)


# ---------------------------------------------------------------------------
# Sort-free claim priorities.
#
# The waves need a deterministic TOTAL order over candidate entities to
# resolve claim conflicts.  A rank (sort) gives one, but TPU sorts are
# O(n log^2 n) bitonic passes.  Instead compare candidates by the pair
#   (score: float32, tie: int32)
# lexicographically: the score keeps its FULL f32 precision (no
# quantization), and the tie channel is a *bijective* integer mix of the
# slot index — unique by construction, pseudo-random in order, so equal
# scores (ubiquitous in structured meshes) break without spatial bias.
# Claim resolution then needs only elementwise max / scatter-max passes:
# first on the score channel, then on the tie channel restricted to
# score-maximal slots.
# ---------------------------------------------------------------------------
PRI_MIN = jnp.int32(-2147483648)     # tie-channel sentinel (< every hash)
NEG_INF = jnp.float32(-jnp.inf)      # score-channel sentinel


def tie_hash(n: int, salt: int = 0, pos=None) -> jax.Array:
    """Unique pseudo-random int32 per slot: a bijective avalanche mix of
    the index (odd multiplications and xor-shifts are invertible mod
    2^32), so distinct slots NEVER collide — the total order is exact.
    ``pos`` [n]: hash these (distinct) indices instead of 0..n-1."""
    idx = jnp.arange(n, dtype=jnp.uint32) if pos is None \
        else pos.astype(jnp.uint32)
    x = idx + jnp.uint32(salt) * jnp.uint32(
        2246822519)
    x = x * jnp.uint32(2654435761)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    x = x ^ (x >> 16)
    return x.astype(jnp.int32)


def claim_channels(score: jax.Array, mask: jax.Array, salt: int = 0,
                   pos=None):
    """(s, t) channels for the two-channel claim scheme: masked slots get
    (-inf, PRI_MIN) and lose every comparison."""
    s = jnp.where(mask, score.astype(jnp.float32), NEG_INF)
    t = jnp.where(mask, tie_hash(score.shape[0], salt, pos), PRI_MIN)
    return s, t


def scatter_argmax2(site: jax.Array, s: jax.Array, t: jax.Array,
                    mask: jax.Array, nsites: int):
    """Is each slot the unique (s,t)-max among slots scattered to its site?

    Returns (is_max [slots] bool, c_s [nsites+1], c_t [nsites+1]):
    ``is_max`` is True iff ``mask`` and no other slot with the same
    ``site`` has a lexicographically larger (s, t); c_s/c_t are the
    per-site channel maxima (sentinels where no slot landed).  Two
    scatter-max passes; exact because t is unique.
    """
    sited = jnp.clip(site, 0, nsites - 1)
    safe = jnp.where(mask, site, nsites)
    c_s = jnp.full(nsites + 1, NEG_INF).at[safe].max(
        jnp.where(mask, s, NEG_INF), mode="drop")
    at_max = mask & (s == c_s[sited])
    safe2 = jnp.where(at_max, site, nsites)
    c_t = jnp.full(nsites + 1, PRI_MIN).at[safe2].max(
        jnp.where(at_max, t, PRI_MIN), mode="drop")
    return at_max & (t == c_t[sited]), c_s, c_t


def morton_codes(pts: jax.Array, valid: jax.Array, bits: int = 10):
    """[n] int32 morton (Z-order) codes of 3D points, normalized over
    the bounding box of the ``valid`` rows; ``3*bits <= 30`` so the code
    stays in int32.  Used by the device cluster assignment of the
    graph-balancing probe (parallel/migrate_dev.graph_probe)."""
    lo = jnp.min(jnp.where(valid[:, None], pts, jnp.inf), axis=0)
    hi = jnp.max(jnp.where(valid[:, None], pts, -jnp.inf), axis=0)
    u = jnp.clip((pts - lo) / jnp.maximum(hi - lo, 1e-30),
                 0.0, 1.0 - 1e-7)
    q = (u * float(1 << bits)).astype(jnp.uint32)

    def spread(x):          # interleave up to 10 bits -> every 3rd bit
        x = (x | (x << 16)) & jnp.uint32(0x030000FF)
        x = (x | (x << 8)) & jnp.uint32(0x0300F00F)
        x = (x | (x << 4)) & jnp.uint32(0x030C30C3)
        x = (x | (x << 2)) & jnp.uint32(0x09249249)
        return x

    code = spread(q[:, 0]) | (spread(q[:, 1]) << 1) | \
        (spread(q[:, 2]) << 2)
    return code.astype(jnp.int32)
