"""Quiet-group scheduler: convergence-aware active-set compaction.

SCALE_r03 measured the grouped adapt pass at 97.6% of a 1M-tet run, and
its per-cycle op counts collapse across cycles — yet the chunked
dispatch loop of ``grouped_adapt_pass`` re-shipped EVERY group through
EVERY cycle block (host gather + device upload + compute + counter sync
+ download), even for groups that posted zero ops blocks ago.  The
per-group counts were summed away before anyone looked at them.

This module is the host-side bookkeeping that fixes that: per-group
counts mark groups *quiet*, and from then on the active group indices
are compacted into dense chunks — the SAME compiled ``[chunk, ...]``
program runs on gathered slices (zero new shapes, zero new
compile-ledger families), it just runs on fewer of them.

Exactness contract (why skipping is bit-for-bit, not approximate):

- group seams are frozen (MG_PARBDY — the split_to_shards freeze
  contract), so a group that posts zero ops cannot be re-dirtied by its
  neighbors within a pass; the reference's rank-level loop
  (libparmmg1.c:636-948) has the same convergence structure;
- every wave kernel is a deterministic function of (mesh, met) alone —
  the smoothing wave's hash rotation only permutes priorities among
  vertices that already pass the improvement gate, and the gate is
  geometry-only — so a block that posts zero split+collapse+swap+move
  leaves the group state a *fixed point*: re-running any weaker-or-equal
  block on it is byte-identity;
- "weaker-or-equal" is tracked as two quiet levels, because the cycle
  scheduler emits two block classes: prescreen-ON sizing blocks and the
  final prescreen-OFF polish blocks whose exact split veto re-evaluates
  candidates the approximate prescreen over-vetoed (ops/split.py, ADVICE
  r3).  Zero under a swap-inclusive prescreen-on block only proves the
  group inert for further prescreen-on blocks (``LEVEL_PRE``); zero
  under a swap-inclusive block containing a prescreen-off cycle proves
  it inert for everything (``LEVEL_FULL``).  Swap kernels and smoothing
  do not read the prescreen, so the pres-off proof subsumes the pres-on
  one;
- a capacity regrow invalidates every proof: the top-K wave budgets
  scale with capT, so a group whose winners were budget-truncated at the
  old capacity can post fresh ops at the new one — ``on_regrow``
  reactivates the full set (truncated winners must rerun), exactly like
  the always-dispatch path's block rerun;
- dead pad groups (the chunk-alignment padding of grouped_adapt_pass)
  are fixed points by construction (all masks False) and are never
  dispatched.

**Device-resident quiet masks** (PR 12, ROADMAP 1a): host-side
compaction makes quiet groups cost zero *dispatches*; the device mask
makes them cost ~zero *on device* too.  Every grouped block program
(`groups._group_block`, `groups._group_polish_block`, the dist-path
`dist.dist_adapt_block`) takes a per-slot bool mask and wraps its
``lax.map`` group body in ``lax.cond`` (ops/adapt.py ``active=``): an
inactive slot returns its state unchanged with zero counts instead of
running the split/collapse/swap/smooth wave math.  This is exact by the
SAME fixed-point argument as dispatch skipping — a quiet state's
recompute IS the identity — and carries the same two proof levels:
:meth:`QuietGroupScheduler.block_mask` masks ``level >= LEVEL_PRE``
slots only under prescreen-ON blocks and ``level >= LEVEL_FULL`` slots
under any block.  Three mask sources:

- **unchunked dispatches** (``PARMMG_GROUP_CHUNK=0``, where compaction
  cannot change the dispatch shape): ``block_mask`` — the only skip
  mechanism this layout has;
- **padded tail rows** of compacted chunk plans (:func:`pad_mask` via
  ``groups._pipeline_chunks``): the repeat-padded duplicate rows used
  to compute redundantly and be discarded at writeback — now they are
  cond-skipped (serving cohorts included);
- **the SPMD dist path** (``dist.run_adapt_cycles``): a per-logical-
  shard quiet level lives ON DEVICE (int8, threaded through the block
  program, updated by the same swap-inclusive zero-count rule) so the
  G>1 ``lax.map`` body skips converged groups with zero host syncs.

The mask is ALWAYS an argument of the compiled programs (an all-true
mask when disabled), so toggling it mints zero new compile families —
asserted by the ``run_tests.sh --ledger`` grouped_sched_gate.
``PARMMG_DEVICE_MASK=0`` disables the on-device skipping;
``PARMMG_GROUP_SCHED=0`` is the escape hatch back to always-dispatch
(and also forces all-true masks).
"""
from __future__ import annotations

import numpy as np

LEVEL_ACTIVE = 0   # must dispatch
LEVEL_PRE = 1      # proven zero under a swap-inclusive prescreen-ON block
LEVEL_FULL = 2     # proven zero under a swap-inclusive prescreen-OFF block


def sched_enabled() -> bool:
    """PARMMG_GROUP_SCHED knob (default on)."""
    import os
    return os.environ.get("PARMMG_GROUP_SCHED", "1") != "0"


def device_mask_enabled() -> bool:
    """PARMMG_DEVICE_MASK knob (default on): device-resident quiet
    masks — ``lax.cond``-skip the wave math for quiet/pad group slots
    (module docstring).  0 = compute every slot (masks all-true; same
    compiled programs)."""
    import os
    return os.environ.get("PARMMG_DEVICE_MASK", "1") != "0"


def pad_mask(chunk: int, nreal: int) -> np.ndarray:
    """[chunk] bool device-mask for a compacted chunk plan: the first
    ``nreal`` rows are real, the repeat-padded tail rows are masked off
    (their compute was always discarded at writeback — chunk_plans).
    All-true when PARMMG_DEVICE_MASK=0 — or under the
    PARMMG_GROUP_SCHED=0 escape hatch, which forces the full legacy
    behavior (module docstring) — so the disabled path computes
    exactly what it always did."""
    if not (sched_enabled() and device_mask_enabled()):
        return np.ones(chunk, bool)
    m = np.zeros(chunk, bool)
    m[:nreal] = True
    return m


def quiet_rows(counts: np.ndarray) -> np.ndarray:
    """Per-row fixed-point witness from a dispatched block's counts.

    ``counts``: [n, >=5], a block's (one cycle's) row per group —
    reads ONLY columns 0..4.  Row ``i`` is quiet
    when the WHOLE block was a no-op for it — zero
    split+collapse+swap+move AND zero overflow (a truncated winner set
    witnesses nothing).  Shared by
    :meth:`QuietGroupScheduler.record_block` (group granularity) and
    the serving pool (serve/pool.py, tenant granularity): one rule, one
    exactness argument (module docstring)."""
    # host-by-contract: the drain already pulled the block counters to
    # numpy ([n, >=5]) — no conversion, no possible device sync
    return counts[:, :5].sum(axis=1, dtype=np.int64) == 0


def chunk_plans(act: np.ndarray, chunk: int) -> list:
    """Compact active group indices (an ndarray) into dense
    [chunk]-sized plans.

    Returns [(idx_exec [chunk], nreal)]: a short tail plan is padded by
    repeating its last real index so every dispatch keeps the compiled
    [chunk, ...] shape; the duplicate rows are masked off on device
    (:func:`pad_mask`) and only the first ``nreal`` rows are written
    back."""
    plans = []
    for i in range(0, len(act), chunk):
        idx = act[i:i + chunk]
        nreal = len(idx)
        if nreal < chunk:
            idx = np.concatenate(
                [idx, np.repeat(idx[-1:], chunk - nreal)])
        plans.append((idx, nreal))
    return plans


class QuietGroupScheduler:
    """Active-set bookkeeping for one grouped adapt pass.

    ``g_exec`` >= ``ngroups``: the pad-aligned executable group count
    (pad groups are born quiet).  ``chunk`` = groups per dispatch
    (0 = one unchunked dispatch; compaction then cannot change the
    dispatch shape and the scheduler only records the trajectory)."""

    def __init__(self, ngroups: int, g_exec: int, chunk: int,
                 enabled: bool | None = None, tiles: int = 1):
        if enabled is None:
            enabled = sched_enabled()
        self._on = bool(enabled)
        self.dispatches = 0
        self.rows = 0           # rows of the dispatched stacks
        self.rows_dead = 0      # of them, dead pad rows (born quiet)
        self.on_recut(ngroups, g_exec, chunk, tiles)
        self.saved_dispatches = 0
        self.skipped_group_blocks = 0
        # group-slot executions skipped ON DEVICE by the lax.cond mask
        # (unchunked quiet slots + padded tail rows of chunk plans)
        self.cond_skipped = 0
        self.active_per_block: list[int] = []

    def on_recut(self, ngroups: int, g_exec: int, chunk: int,
                 tiles: int = 1) -> None:
        """A new cut of the pass's mesh (the first one included): every
        proof is void (``on_regrow``'s rule: the rows are other groups
        now), the counters run on.  ``tiles``: the dispatches an
        unchunked block makes, each over ``g_exec // tiles`` rows of the
        one program the job's first cut compiled."""
        self.ngroups = int(ngroups)
        self.g_exec = int(g_exec)
        self.chunk = int(chunk)
        self.tiles = int(tiles)
        # compaction needs per-chunk dispatches to have fewer of them
        self.enabled = self._on and self.chunk > 0
        # the device mask works at ANY chunking (including unchunked,
        # where it is the only skip mechanism — module docstring)
        self.mask_on = self._on and device_mask_enabled()
        self.level = np.zeros(self.g_exec, np.int8)
        self.level[self.ngroups:] = LEVEL_FULL     # dead pad groups

    # ---- block planning --------------------------------------------------
    def _skip_level(self, pres_all_on: bool) -> int:
        return LEVEL_PRE if pres_all_on else LEVEL_FULL

    def plan_block(self, pres_all_on: bool):
        """Plan one cycle block: returns (act, plans).

        ``act``: group indices to dispatch, in plan order.  ``plans``:
        [(idx_exec, nreal)] chunk plans (empty when every group is
        quiet).  Dispatch/saved counters and the active-group trajectory
        are accounted here; the always-dispatch baseline is
        ceil(g_exec / chunk) dispatches per block."""
        skip = self._skip_level(pres_all_on)
        if self.enabled:
            act = np.where(self.level < skip)[0]
        else:
            act = np.arange(self.g_exec)
        # level is host scheduler state (np.int8): count, then int() a
        # bound host scalar — nothing here can sync a device value
        n_active = np.count_nonzero(self.level[:self.ngroups] < skip)
        self.active_per_block.append(int(n_active))
        if self.chunk:
            base = -(-self.g_exec // self.chunk)
            plans = chunk_plans(act, self.chunk) if len(act) else []
            ndisp = len(plans)
        else:
            # unchunked: every tile of the stack is dispatched, quiet
            # and dead rows as lax.cond identities (block_mask)
            base = ndisp = self.tiles
            plans = [(act, len(act))]
        self.dispatches += ndisp
        self.rows += sum(len(idx) for idx, _ in plans)
        self.rows_dead += sum(np.count_nonzero(idx >= self.ngroups)
                              for idx, _ in plans)
        # saved vs the always-dispatch baseline, which ships the dead
        # pad groups too — skipping those IS a real dispatch saving
        self.saved_dispatches += base - ndisp
        # ...but the skipped-GROUP counter reports convergence, so it
        # counts REAL groups only (pads are dead at birth, not wins)
        n_real = np.count_nonzero(act < self.ngroups)
        self.skipped_group_blocks += self.ngroups - int(n_real)
        return act, plans

    def block_mask(self, pres_all_on: bool) -> np.ndarray:
        """[g_exec] bool device-mask for an UNCHUNKED dispatch: quiet
        slots at or above this block's skip level are cond-skipped on
        device (the only skip mechanism when compaction cannot change
        the dispatch shape).  All-true when the mask is disabled.
        Accounts the skipped slots in ``cond_skipped``."""
        if not self.mask_on:
            return np.ones(self.g_exec, bool)
        m = self.level < self._skip_level(pres_all_on)
        # lint: ok(R2) — m is the host scheduler state (numpy bool);
        # counting the masked slots syncs nothing
        self.cond_skipped += int(np.sum(~m))
        return m

    def note_plan_pads(self, plans: list) -> None:
        """Account the repeat-padded tail rows of compacted chunk plans
        that the device mask skipped (``pad_mask`` — one entry per
        padded row per dispatch).  No-op whenever ``pad_mask`` returns
        all-true (mask off, or the sched=0 escape hatch)."""
        if not (sched_enabled() and device_mask_enabled()):
            return
        for idx, nreal in plans:
            self.cond_skipped += len(idx) - nreal

    # ---- quiet marking ---------------------------------------------------
    def record_block(self, act: np.ndarray, counts: np.ndarray,
                     swap_inclusive: bool, pres_all_on: bool) -> None:
        """Mark groups quiet from a dispatched block's per-group counts.

        ``counts``: [n_act, >=5] (split, collapse, swap, moved,
        overflow, ...).  A group is quiet only when the WHOLE block was
        a no-op for it — including moves (the fixed-point requirement)
        and overflow (a truncated winner set witnesses nothing) — and
        the block was swap-inclusive (``swap_inclusive`` = any swap
        cycle, or -noswap, mirroring the global convergence rule).

        The ``deferred`` column (6) is deliberately NOT part of the
        proof: deferred marks top-K budget cuts, and the budgets are
        constant across blocks (budget_div=8; only a capacity regrow
        changes them, which reactivates everything).  Split, collapse
        and swap take no wave input, so on an unchanged state they
        re-select the identical (possibly empty) winner set every
        block — a deferred-but-zero-op state is still a fixed point.
        The only wave-rotated kernel is smoothing, and moved == 0
        proves its geometry-only improvement gate rejects every
        vertex, which no later wave's priority rotation can change."""
        if not swap_inclusive or len(act) == 0:
            return
        zero = quiet_rows(counts)
        lvl = LEVEL_PRE if pres_all_on else LEVEL_FULL
        # act comes from plan_block (np.where/arange): already host
        sel = act[zero]
        self.level[sel] = np.maximum(self.level[sel], lvl)

    def on_regrow(self) -> None:
        """Capacity regrow: every proof is stale (the top-K budgets
        scale with capT — budget-truncated winners must rerun).  Pad
        groups stay quiet (dead at any capacity)."""
        self.level[:self.ngroups] = LEVEL_ACTIVE


# ---------------------------------------------------------------------------
# PARMMG_GROUP_CHUNK auto-tune (ROADMAP item 1b, lightweight host side)
# ---------------------------------------------------------------------------
def calibrate_dispatch_overhead(acc: dict, count: dict,
                                chunk: int) -> float | None:
    """Measured per-dispatch overhead in GROUP-COMPUTE UNITS from the
    ``_pipeline_chunks`` segment timings (the PR-8 Timers spans) — the
    calibration that replaces :func:`recommend_group_chunk`'s hand-set
    ``dispatch_overhead=1.0`` default (ROADMAP 1b validation, host
    side).

    ``acc``/``count`` are the local pipeline registry's accumulators
    (keys upload/compute/download/writeback; one count per dispatch).
    overhead = (upload + download + writeback seconds per dispatch) /
    (compute seconds per GROUP) — i.e. how many groups' worth of
    compute one extra dispatch costs, exactly the unit the cost model
    ``ceil(a/c) * (c + overhead)`` wants.  Under the double-buffered
    pipeline the recorded compute segment is the RESIDUAL stall (the
    overlap hides part of it), which biases the per-group unit low and
    the overhead HIGH — i.e. toward larger chunks, the direction that
    cannot recommend pathological tiny dispatches.  Returns ``None``
    when the segments carry no signal (no dispatches, zero compute) —
    the caller keeps the hand-set default then."""
    disp = count.get("compute", 0)
    if not disp or chunk <= 0:
        return None
    over = (acc.get("upload", 0.0) + acc.get("download", 0.0)
            + acc.get("writeback", 0.0)) / disp
    comp = acc.get("compute", 0.0) / disp / chunk
    if comp <= 0.0 or over <= 0.0:
        return None
    return over / comp


def recommend_group_chunk(traj, g_exec: int,
                          dispatch_overhead: float = 1.0) -> int:
    """Recommend a PARMMG_GROUP_CHUNK from a recorded
    ``extra.active_groups_per_block`` trajectory.

    Cost model per block with ``a`` active groups at chunk ``c``:
    ``ceil(a/c) * (c + dispatch_overhead)`` in group-compute units —
    every dispatch ships a full [c, ...] slice (short tails are padded
    by repeating rows — pad_mask cond-skips their compute, but the
    transfer is still paid), plus a per-dispatch overhead (host gather
    + upload + counter sync; ~one group-block of useful work is the
    hand-set default, never measured on a local chip).  Pass the MEASURED value from
    :func:`calibrate_dispatch_overhead` when a pipeline has run — the
    grouped pass does, recording the calibration in
    ``sched_extra["chunk_overhead_units"]`` and the bench/SCALE
    artifact extras.  Smaller chunks track the decaying active set
    with less padding waste; larger chunks amortize the dispatch
    overhead — exactly the trade named in ROADMAP item 1.

    Candidates are the pow2 ladder 1..g_exec (so the recommendation
    lands on a small set of compiled [chunk, ...] shape families); ties
    prefer the LARGER chunk (fewer dispatches at equal modeled cost).
    Returns 0 (= unchunked) for an empty/degenerate trajectory or when
    the winner covers every group anyway — the group_chunk() "no
    chunking" convention."""
    a = [int(v) for v in (traj or []) if int(v) > 0]
    if not a or g_exec <= 1:
        return 0
    cands = []
    c = 1
    while c < g_exec:
        cands.append(c)
        c *= 2
    cands.append(g_exec)

    def cost(c: int) -> float:
        return sum(-(-ab // c) * (c + dispatch_overhead) for ab in a)

    best = max((c for c in cands
                if cost(c) == min(cost(x) for x in cands)))
    return 0 if best >= g_exec else best


# last recommendation computed by a grouped pass in this process
# (module-level on purpose: the steady-state loop re-enters
# grouped_adapt_pass every outer iteration, and PARMMG_GROUP_CHUNK=auto
# reads the newest trajectory-derived value at the NEXT pass — no
# behavior change unless the operator opts in with "auto").  Only the
# newest value is kept: a long-lived serving process notes one per
# pass forever, and only [-1] is ever read.
_CHUNK_RECOMMENDATION: list[int] = []


def note_chunk_recommendation(chunk: int) -> None:
    _CHUNK_RECOMMENDATION[:] = [int(chunk)]


def auto_chunk_recommendation() -> int | None:
    """Newest recorded recommendation, or None before any grouped pass
    has run (group_chunk then falls back to the backend default)."""
    return _CHUNK_RECOMMENDATION[-1] if _CHUNK_RECOMMENDATION else None
