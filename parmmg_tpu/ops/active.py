"""Active-scoped narrow adaptation — the TPU analogue of Mmg's worklist.

The reference's sequential kernel (``MMG5_mmg3d1_delone``, called per group
at /root/reference/src/libparmmg1.c:737) is *worklist-driven*: each pass
walks a cascade of entities affected by earlier operations, so a nearly
converged mesh costs almost nothing.  Our batched waves historically paid
full [capT]-width table builds and gather/scatter passes per cycle even
when only a handful of candidates remained — the measured throughput
ceiling of rounds 1-3.

This module restores the worklist economics under XLA's static shapes:

- ``dirty`` [capP] bool marks vertices whose neighborhood changed in the
  previous cycle (computed by diffing the mesh arrays — generic, no
  per-wave bookkeeping).
- One cheap full-width pass computes the 1-ring closure ``dirty2`` and the
  ACTIVE tet set (tets holding a dirty2 vertex).  For any entity whose
  candidacy could have changed, its whole gate stencil (edge shell, ball
  of the removed/moved vertex, swap cavity) lies inside the active set —
  see the invariant below.
- The active tets are compacted into an [A]-row SUB-mesh (tet-axis arrays
  only; vertex-axis arrays are shared at full width).  The SAME wave
  kernels run on it with ``vact=dirty2`` restricting candidates; results
  scatter back.  A = capT//NARROW_DIV, so sorts and heavy passes shrink
  by the same factor.

Worklist invariant (why narrow cycles are exact): an edge/vertex whose
gate inputs did NOT change since it last failed keeps failing, so only
entities touching the previous cycle's footprint need re-evaluation.
Losers become revisitable exactly when their blocker applies (its
footprint makes them dirty).  The ONE exception is a candidate dropped
by a top-K *budget* (it failed for scheduling, not geometric, reasons):
at steady state thousands of permanently-gate-failing short edges can
pin the budget, so a strict "no deferral" entry condition would never
open (measured on the bench workload).  The full path itself never
attempts that backlog either — it re-examines the same top-K every
cycle — so narrow mode instead guarantees BOUNDED staleness: a
full-width refresh cycle runs periodically (``full_every``, default
once per block), attempting the same global top-K the full path would,
and the convergence decision in the host driver (wide check,
budget_div=2) and the polish/repair tail remain full-width — final
results keep full-path exactness.

Shell-count exactness on the sub-mesh: every shell tet of a candidate
edge contains one of its endpoints; endpoints are dirty2, so all shell
tets are active and in the sub-mesh — counts, nominations and claims are
exact.  Sub-mesh adjacency is built WITHOUT boundary tagging
(cut faces are unmatched but not surface, adjacency.build_adjacency
``set_bdy_tags=False``); swap23 skips unmatched faces, which is correct
because a pair whose twin is inactive cannot have changed status.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh
# top-level imports (NOT lazy): a module first imported inside a jit
# trace would create its module-level jnp constants as tracers, which
# then leak into every later trace (UnexpectedTracerError)
from .adapt import adapt_cycle_impl
from .adjacency import build_adjacency

# A = max(NARROW_MIN, capT // NARROW_DIV).  8 measured best on the bench
# workload (2026-08-02): equal-population morton windows hold the active
# set at ~11-16k tets, comfortably under the A=capT/8 budget at bench
# shapes, and every narrow pass (sorts, scatters, adjacency) is half the
# width of the old capT/4 sub-mesh — +30% steady-state block throughput.
NARROW_DIV = 8
NARROW_MIN = 8192


def _narrow_div() -> int:
    """Narrow-row divisor, env-overridable (PARMMG_NARROW_DIV): a larger
    divisor shrinks every narrow-cycle pass proportionally, at the cost
    of more frequent active-set overflows (which fall back to full-width
    cycles, correct but slow) — tune against the workload's steady-state
    footprint."""
    import os
    v = os.environ.get("PARMMG_NARROW_DIV", "")
    return max(2, int(v)) if v else NARROW_DIV
# fraction of A reserved for rows ALLOCATED by splits/swaps inside the
# narrow cycle; the active set itself may only fill A - A//4
NARROW_HEADROOM_DIV = 4


def narrow_rows(capT: int) -> int:
    """Narrow sub-mesh row budget, BUCKETED (compile governor): the raw
    capT//div drifts with every capacity choice and A keys the compile
    of every narrow-cycle program — bucketing from the NARROW_MIN floor
    collapses those onto a handful of variants.  The geo (1.5x) ladder,
    not pow2: a pow2 round-up can widen the tuned capT//8 narrow width
    by almost 2x, silently giving back the measured capT/4 -> capT/8
    throughput win (comment above NARROW_DIV)."""
    from ..utils.compilecache import bucket
    return bucket(max(NARROW_MIN, capT // _narrow_div()),
                  floor=NARROW_MIN, scheme="geo", cap=capT)


def dirty_from_diff(pre: Mesh, post: Mesh, pre_met=None, post_met=None):
    """[capP] bool: vertices whose neighborhood changed between two mesh
    states.  Generic footprint: vertices of any tet row whose vertex
    list / liveness / face or edge tags / face refs changed, plus moved
    vertices and vertices whose own tag/liveness changed.  Every wave's
    effect is visible in one of these arrays, so no per-wave bookkeeping
    is needed (elementwise compares are HBM-cheap)."""
    capP = pre.capP
    row = jnp.any(pre.tet != post.tet, axis=1)
    row = row | (pre.tmask != post.tmask)
    row = row | jnp.any(pre.ftag != post.ftag, axis=1)
    row = row | jnp.any(pre.fref != post.fref, axis=1)
    row = row | jnp.any(pre.etag != post.etag, axis=1)
    # vertices of changed rows (pre AND post vertex lists: a remapped
    # row must dirty both the old and the new endpoints)
    idx = jnp.where(row[:, None], pre.tet, capP)
    idx2 = jnp.where(row[:, None], post.tet, capP)
    dirty = jnp.zeros(capP + 1, bool)
    dirty = dirty.at[idx.reshape(-1)].set(True, mode="drop")
    dirty = dirty.at[idx2.reshape(-1)].set(True, mode="drop")
    dirty = dirty[:capP]
    dirty = dirty | jnp.any(pre.vert != post.vert, axis=1)
    dirty = dirty | (pre.vtag != post.vtag) | (pre.vmask != post.vmask)
    if pre_met is not None:
        dm = pre_met != post_met
        dirty = dirty | (dm if dm.ndim == 1 else jnp.any(dm, axis=1))
    return dirty


def closure_active(mesh: Mesh, dirty: jax.Array):
    """(dirty2, active): 1-ring vertex closure of ``dirty`` and the tets
    containing any dirty2 vertex.  Two [4T]-index passes — the only
    full-width work a narrow cycle pays besides the final compaction."""
    capP = mesh.capP
    touched = jnp.any(dirty[mesh.tet], axis=1) & mesh.tmask     # [T]
    idx = jnp.where(touched[:, None], mesh.tet, capP).reshape(-1)
    d2 = jnp.zeros(capP + 1, bool).at[idx].set(True, mode="drop")[:capP]
    d2 = d2 | dirty
    active = jnp.any(d2[mesh.tet], axis=1) & mesh.tmask
    return d2, active


def extract_active(mesh: Mesh, active: jax.Array, A: int):
    """Compact the active tets into an [A]-row sub-mesh.

    Returns (sub, back, n_act, ovf): ``back[r]`` is the full-mesh slot a
    sub-mesh row writes back to — active rows keep their slot, rows past
    ``n_act`` map to the full mesh's FREE rows in pool order (so in-sub
    allocations land in genuinely dead full slots, matching the
    slot-reusing allocators — edges.free_rows).  Tail rows past the full
    free count map to capT (write-back drops them; a LIVE such row is
    the alloc-overflow signal checked in auto_cycle).
    ``ovf`` = the active set does not fit the budgeted rows (caller must
    abort the narrow cycle WITHOUT applying anything)."""
    from .edges import free_rows
    capT = mesh.capT
    n_act = jnp.sum(active, dtype=jnp.int32)
    ovf = n_act > (A - A // NARROW_HEADROOM_DIV)
    ids = jnp.nonzero(active, size=A, fill_value=capT)[0].astype(jnp.int32)
    ffree, _nfree = free_rows(mesh.tmask, A)
    r = jnp.arange(A, dtype=jnp.int32)
    back = jnp.where(r < n_act, ids,
                     ffree[jnp.clip(r - n_act, 0, A - 1)])
    src = jnp.clip(ids, 0, capT - 1)
    pad = r >= n_act
    sub = dataclasses.replace(
        mesh,
        tet=jnp.where(pad[:, None], 0, mesh.tet[src]),
        tmask=jnp.where(pad, False, mesh.tmask[src]),
        tref=jnp.where(pad, 0, mesh.tref[src]),
        ftag=jnp.where(pad[:, None], 0, mesh.ftag[src]),
        fref=jnp.where(pad[:, None], 0, mesh.fref[src]),
        etag=jnp.where(pad[:, None], jnp.uint32(0), mesh.etag[src]),
        adja=jnp.full((A, 4), -1, jnp.int32),
        nelem=n_act)
    return sub, back, n_act, ovf


def writeback_active(mesh: Mesh, sub: Mesh, back: jax.Array,
                     n_act: jax.Array):
    """Scatter the sub-mesh's tet-axis rows back into the full mesh and
    adopt its (shared) vertex-axis arrays.  Rows whose target exceeds
    capT drop (they are dead pad rows past the free region)."""
    capT = mesh.capT
    tgt = jnp.where(back < capT, back, capT)
    tmask2 = mesh.tmask.at[tgt].set(sub.tmask, mode="drop",
                                    unique_indices=True)
    # exact watermark from the final liveness (free-pool targets may lie
    # below the old watermark, and pad writes may tighten nothing)
    rowsT = jnp.arange(capT, dtype=jnp.int32)
    nelem2 = jnp.max(jnp.where(tmask2, rowsT + 1, 0))
    out = dataclasses.replace(
        mesh,
        tet=mesh.tet.at[tgt].set(sub.tet, mode="drop",
                                 unique_indices=True),
        tmask=tmask2,
        tref=mesh.tref.at[tgt].set(sub.tref, mode="drop",
                                   unique_indices=True),
        ftag=mesh.ftag.at[tgt].set(sub.ftag, mode="drop",
                                   unique_indices=True),
        fref=mesh.fref.at[tgt].set(sub.fref, mode="drop",
                                   unique_indices=True),
        etag=mesh.etag.at[tgt].set(sub.etag, mode="drop",
                                   unique_indices=True),
        vert=sub.vert, vmask=sub.vmask, vtag=sub.vtag, vref=sub.vref,
        npoin=sub.npoin,
        nelem=nelem2)
    return out


def auto_cycle(mesh: Mesh, met, pending, okflag, wave, A: int,
               do_swap: bool, do_smooth: bool, do_insert: bool,
               hausd, budget_div: int = 8,
               narrow_budget_div: int = 2,
               window: int = 0):
    """One adaptation cycle that picks its own width (jit-inline).

    ``pending`` [capP] bool is the WORKLIST: vertices whose neighborhood
    changed since they were last examined.  With ``window`` > 0 each
    cycle examines only the pending vertices of the current contiguous
    morton-curve segment (``wave % window``) — and the topology waves
    restrict their candidate pools to that window too
    (split/collapse/swap ``wwin``), so each cycle's footprint is a
    compact blob.  Pending work outside the window is carried and
    re-examined when its window rotates in: staleness is bounded by
    ``window`` cycles, and the rotation attempts EVERY candidate —
    strictly better coverage than the full path's permanently-pinned
    global top-K.

    A cheap full-width closure pass sizes the active set; when
    ``okflag`` holds and the active tets fit the narrow row budget, the
    cycle runs on the compacted sub-mesh, else full-width (same
    windowed candidate masks).  Both branches live in ONE compiled
    program.

    Returns (mesh, met, pending_next, ok_next, counts[8]); counts
    column 7 is a diagnostic 1 when the narrow branch ran."""
    capP = mesh.capP
    # effective window count scales with the mesh (capT is static, so
    # this is a compile-time choice): region(~capT/nwin) + its 2-hop
    # halo must fit A - A//4 — measured on the bench workload the
    # closure covers ~the whole window region, so size regions at about
    # a THIRD of the narrow rows.  A mesh that fits the narrow rows
    # whole (A >= capT) needs no windowing at all.
    if A >= mesh.capT:
        nwin = 1
    else:
        nwin = min(window, max(2, (3 * mesh.capT) // max(1, A)))
    if window > 0 and nwin > 1:
        from .smooth import morton_window_mask
        wmask = morton_window_mask(mesh.vert, mesh.vmask, wave, nwin)
        dirty_proc = pending & wmask
    else:
        wmask = None
        dirty_proc = pending
    d2, active = closure_active(mesh, dirty_proc)
    n_act = jnp.sum(active, dtype=jnp.int32)
    fits_rows = n_act <= (A - A // NARROW_HEADROOM_DIV)
    can_narrow = okflag & fits_rows

    def _pending_next(dn):
        if wmask is None:
            return dn
        return (pending & ~wmask) | dn

    def _nar(_):
        sub0, back, n_act2, _ovf = extract_active(mesh, active, A)
        sub, met2, counts = adapt_cycle_impl(
            sub0, met, wave, do_swap=do_swap, do_smooth=do_smooth,
            do_insert=do_insert, final_rebuild=False, hausd=hausd,
            budget_div=narrow_budget_div, vact=d2, submesh=True)
        counts = counts[:8]     # the auto row has columns of its own after these
        # the sub's allocated rows land in full-mesh FREE rows via the
        # back pool; a live sub row whose back target is the capT
        # sentinel means the pool ran out and the writeback would
        # silently drop a tet (half-applied ops) — detect post-hoc and
        # discard the whole cycle instead (exact; never trips at steady
        # state where allocations are small)
        alloc_bad = jnp.any(sub.tmask & (back >= mesh.capT))

        def _apply(_):
            dn = dirty_from_diff(sub0, sub)
            mesh2 = writeback_active(mesh, sub, back, n_act2)
            # a sub CAPACITY overflow (col 4) truncated winners inside
            # the sub-mesh, or an INSERTION wave deferred at its top-K
            # (col 6 bit 0 — sizing-critical backlog): escalate to the
            # full path next cycle.  A SWAP-wave deferral (col 6 bit 1)
            # does NOT escalate: swap nomination pools routinely exceed
            # the sub top-K, escalating on them forced a ~500 ms
            # full-width cycle after most swap waves for no measured
            # quality gain, and their backlog is covered by the
            # periodic full refresh + the polish tail (the
            # bounded-staleness contract, module docstring).
            bad = (counts[4] > 0) | (counts[6] % 2 > 0)
            counts2 = counts.at[4].set(0).at[5].set(
                jnp.sum(mesh2.tmask, dtype=jnp.int32)).at[6].set(
                bad.astype(jnp.int32)).at[7].set(1)
            counts2 = jnp.concatenate(
                [counts2, n_act[None], okflag.astype(jnp.int32)[None]])
            return mesh2, met2, _pending_next(dn), ~bad, counts2

        def _discard(_):
            counts2 = jnp.zeros(8, jnp.int32).at[5].set(
                jnp.sum(mesh.tmask, dtype=jnp.int32)).at[6].set(
                1).at[7].set(1)
            counts2 = jnp.concatenate(
                [counts2, n_act[None], okflag.astype(jnp.int32)[None]])
            return mesh, met, pending, jnp.zeros((), bool), counts2

        return jax.lax.cond(~alloc_bad, _apply, _discard, None)

    def _full(_):
        mesh2, met2, counts = adapt_cycle_impl(
            mesh, met, wave, do_swap=do_swap, do_smooth=do_smooth,
            do_insert=do_insert, final_rebuild=False, hausd=hausd,
            budget_div=budget_div, wwin=wmask)
        counts = counts[:8]
        dn = dirty_from_diff(mesh, mesh2)
        # a full cycle (re)seeds the worklist when (a) capacity did not
        # overflow (the host regrows and restarts the worklist anyway)
        # and (b) the mesh is in the STEADY-STATE regime: during
        # refinement thousands of split candidates exist far from any
        # footprint, and a narrow cycle would advance only the worklist
        # region while the global frontier waits — measured as a
        # mid-protocol refinement backlog burst.  Top-K deferral does
        # NOT block narrow — see the bounded-staleness contract in the
        # module docstring.
        topo = counts[0] + counts[1] + counts[2]
        ok = (counts[4] == 0) & (topo < 512)
        counts = jnp.concatenate(
            [counts, n_act[None], okflag.astype(jnp.int32)[None]])
        return mesh2, met2, _pending_next(dn), ok, counts

    return jax.lax.cond(can_narrow, _nar, _full, None)


def adapt_cycles_auto_impl(mesh: Mesh, met, pending, okflag, wave0,
                           swap_flags: tuple,
                           full_flags: tuple | None = None,
                           hausd=None, do_smooth: bool = True,
                           do_insert: bool = True,
                           budget_div: int = 8,
                           final_rebuild: bool = True,
                           window: int = 24):
    """Fused block of self-width-selecting cycles (one dispatch).

    Thread ``pending`` [capP] bool (the worklist) and ``okflag`` scalar
    bool across blocks (start a session with zeros/False: the first
    cycles run full-width and seed the worklist).  ``full_flags``
    forces the marked positions to run full-width — the
    bounded-staleness refresh (module docstring); default: the LAST
    cycle of the block, whose morton window rotates across blocks so
    every window's backlog is refreshed periodically.  The final cycle
    restores the full-mesh adjacency/boundary-tag exit contract."""
    A = narrow_rows(mesh.capT)
    if full_flags is None:
        full_flags = tuple(c == len(swap_flags) - 1
                           for c in range(len(swap_flags)))
    counts_all = []
    for c, dosw in enumerate(swap_flags):
        okc = jnp.logical_and(okflag, not full_flags[c])
        mesh, met, pending, okflag, counts = auto_cycle(
            mesh, met, pending, okc, wave0 + c, A, dosw,
            do_smooth, do_insert, hausd, budget_div=budget_div,
            window=window)
        counts_all.append(counts)
    if final_rebuild:
        mesh = build_adjacency(mesh)
    return mesh, met, pending, okflag, jnp.stack(counts_all)


from ..utils.compilecache import governed as _governed  # noqa: E402

adapt_cycles_auto = _governed("active.adapt_cycles_auto")(
    partial(jax.jit, static_argnames=(
        "swap_flags", "full_flags", "hausd", "do_smooth", "do_insert",
        "budget_div", "final_rebuild", "window"),
        donate_argnums=(0, 1, 2))(adapt_cycles_auto_impl))
