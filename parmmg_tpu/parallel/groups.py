"""Two-level decomposition: sub-device remesh groups.

The reference splits each rank's mesh into ``-mesh-size``-element groups
and remeshes them one at a time (``PMMG_splitPart_grps`` / ``howManyGroups``
grpsplit_pmmg.c:47,1551-1614, capped at ``PMMG_REMESHER_NGRPS_MAX``); the
group is the unit that bounds the remesher's working set.  TPU-native
analogue: groups are slots of a stacked pytree traversed with ``lax.map``
— XLA compiles ONE cycle program for the group shape and executes it per
group, so peak HBM scales with the GROUP capacity, not the mesh.  Mesh
size per chip is then bounded by HBM-for-one-group x ngroups, which is
what makes the 10M-tet configuration reachable on a single chip.  (A
``vmap`` over groups would process them concurrently — same peak memory
as no groups at all; ``map`` is the memory-bounding choice.  Groups also
shorten the O(n log^2 n) TPU sorts inside each wave.)

Group interfaces are frozen exactly like rank interfaces (MG_PARBDY —
the same ``split_to_shards`` freeze contract, tag_pmmg.c:39-124) and
displaced between outer iterations with the same advancing-front
machinery, so previously-frozen group seams get remeshed later — the
two-level loop of the reference.

The MULTI-device composition of the same idea (G logical shards per
device, ``dist.distributed_adapt_multi(n_devices=...)``) shares this
module's lax.map HBM discipline and additionally keeps the
between-iteration refresh on device: grouped analysis
(analysis_dev.dist_analysis_grouped) + the grouped/packed halo exchange
(comms.halo_exchange_grouped[_packed]), all governed under the same
compile-ledger budgets as the blocks below.

``-metis-ratio`` note: the reference multiplies the group count by
``metis_ratio`` for the REDISTRIBUTION split, whose many small groups are
the METIS graph nodes (grpsplit_pmmg.c:1595-1614).  This framework
migrates interface bands directly (parallel/migrate.py) instead of
re-partitioning a group graph, so the flag has no load-bearing role; it
is parsed and validated for CLI parity only.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.mesh import Mesh
from ..core import constants as C
from ..obs import trace as otrace
from ..utils.compilecache import BLOCK_ENTRY, LEDGER
from ..utils.placement import placed_on_tpu


def how_many_groups(ne: int, target: int) -> int:
    """Group count with the reference's clamps (grpsplit_pmmg.c:47)."""
    if target <= 0:
        return 1
    return max(1, min((ne + target - 1) // target, C.REMESHER_NGRPS_MAX))


def fresh_groups(ne: int, target: int, cap_max: int = 0,
                 cap_mult: float = 3.0) -> int:
    """Groups of a job's first cut: ``how_many_groups``, and under a
    ceiling ``cap_max`` on a group's tet capacity
    (``IParam.groupCapacity``) as many more as bring the capacity a
    fresh even cut takes (``distribute.shard_capacity``) down to it:
    more groups of a shape that compiles, not a bigger group."""
    from .distribute import shard_capacity
    n = how_many_groups(ne, target)
    while cap_max > 0 and n < C.REMESHER_NGRPS_MAX and \
            shard_capacity(1, -(-ne // n), cap_mult)[1] > cap_max:
        n += 1
    return n


def fresh_cut(vert_h: np.ndarray, tet_h: np.ndarray, ngroups: int,
              contiguous: bool = False) -> np.ndarray:
    """The cut of a pass that was handed none: ``ngroups`` even parts
    along the Morton curve of the centroids, each part's stray blobs
    handed to a neighbour (``fix_contiguity``) -- unless that leaves the
    cut uneven and the caller did not ask for groups in one piece
    (``contiguous``, ``IParam.contiguousMode``).  The curve jumps
    between octants that share no face, so a part astride a jump (the
    middle one of three, two of six) is two blobs joined by a neck at
    best, and ``fix_contiguity`` then moves half a group into a
    neighbour.  Capacity follows the LARGEST group
    (``distribute.shard_capacity``), and a group half as large again is
    the next rung of the block program: 11 minutes of compile against 3
    at the benchmark's size (PERF.md section 6, PR 37).  A group in two
    blobs costs nothing: its seams are frozen faces either way."""
    from .partition import fix_contiguity, morton_partition
    even = morton_partition(vert_h[tet_h].mean(axis=1), ngroups)
    part = fix_contiguity(tet_h, even)
    if not contiguous and \
            np.bincount(part).max() > 1.04 * len(part) / ngroups:
        return even
    return part


def group_chunk(ngroups: int) -> int:
    """Groups per dispatch (0 = all in one ``lax.map``, the default on
    every backend: the stacked state stays on the device and one
    dispatch per cycle block covers every group).

    A chunk > 0 keeps the stacked state in HOST RAM and ships ``chunk``
    groups per dispatch — same compiled program per chunk, same
    results — which bounds device memory by the chunk instead of the
    mesh, at the cost of an upload/download per chunk per block.  Use
    it when the whole stacked state does not fit the device.  Returns 0
    (unchunked) when the chunk would cover every group anyway.  Set
    with PARMMG_GROUP_CHUNK; PARMMG_GROUP_CHUNK=auto adopts the newest
    trajectory-derived recommendation (sched.recommend_group_chunk,
    recorded at the end of every grouped pass) and stays unchunked
    before the first pass has produced one."""
    import os
    v = os.environ.get("PARMMG_GROUP_CHUNK", "")
    if v == "auto":
        from .sched import auto_chunk_recommendation
        c = auto_chunk_recommendation() or 0
    else:
        c = max(0, int(v)) if v else 0
    return 0 if c >= ngroups else c


def block_schedule(c: int, cycles: int, noswap: bool):
    """(swap, prescreen) of global cycle ``c`` of ``cycles``, one block
    each — THE schedule of the grouped cycle loop: swap every 3rd cycle
    plus the final-two polish cycles (swap-inclusive AND exact split
    veto via prescreen bypass — ops/split.py, ADVICE r3).  Factored
    out so the serving pool (serve/pool.py) runs byte-identical block
    sequences through the same compiled program."""
    return ((c % 3 == 2 or c >= cycles - 2) and not noswap,
            c < cycles - 2)


# lint: ok(R2) — cs is host numpy (the block's counters the drain
# already pulled); the early-exit decision is pure host bookkeeping
def block_converged(cs: np.ndarray, swap: bool, noswap: bool) -> bool:
    """The grouped loop's early-exit rule on a block's summed counts
    row ``cs`` [>=3]: a swap-inclusive cycle posting zero
    split+collapse+swap ends the sizing loop.  Shared with the serving
    pool, where it is evaluated per tenant (a tenant IS one group, so
    the per-tenant rule equals the standalone ngroups=1 rule — the
    serving parity contract)."""
    return bool(swap or noswap) and \
        int(cs[0]) + int(cs[1]) + int(cs[2]) == 0


# module-level compiled-block caches (compile governor): the builders
# below close only over hashable knobs, and jax.jit caches by function
# IDENTITY — per-pass local builders recompiled the group programs
# every outer iteration even at identical shapes.  Bounded: a handful
# of knob combos per session.
_GROUP_BLOCK_CACHE: dict = {}
_POLISH_BLOCK_CACHE: dict = {}


def _group_block(swap: bool, pre: bool, nomove: bool, noinsert: bool,
                 hausd):
    """The cycle block of one (swap, prescreen) cycle class: the
    compiled program of :func:`_group_block_program` with the two
    switches bound as device scalars."""
    run = _group_block_program(nomove, noinsert, hausd)
    sw, pr = jnp.asarray(bool(swap)), jnp.asarray(bool(pre))
    return lambda *args: run(*args, sw, pr)


def _group_block_program(nomove: bool, noinsert: bool, hausd):
    """The cycle block for the group axis: ONE adapt cycle per
    ``lax.map`` row, one dispatch + one counter pull per cycle.
    Cached by knobs so repeat passes reuse the compiled program.

    ONE program a job shape: whether the cycle swaps and whether it
    bypasses the split prescreen (:func:`block_schedule`) are the
    traced scalar bools ``sw``/``pr``, its last two arguments — the
    three cycle classes of a run (sizing, swap-inclusive sizing, final
    polish) would otherwise be three compiles of the same waves, and a
    cold compile of one costs minutes on a TPU (PERF.md, PR 26).

    The compiled program takes a per-slot ``active`` bool mask (the
    device-resident quiet mask, parallel/sched.py): inactive slots —
    quiet groups of an unchunked dispatch, repeat-padded tail rows of a
    compacted chunk plan — return their state unchanged with zero
    counts via ``lax.cond`` instead of running the wave math
    (ops/adapt.py ``active=``).  The mask is ALWAYS an argument (an
    all-true mask when masking is off), so toggling it mints zero new
    compile families — the grouped_sched_gate contract.

    Whether the cycle's surface scatters run over lists of their live
    updates (ops/surflist) is observed here, where the program is
    built, and is part of its key: they do where it is placed on a
    TPU."""
    from ..ops.adapt import adapt_cycle_impl
    from ..utils.compilecache import governed
    surf_list = placed_on_tpu()
    key = (nomove, noinsert, hausd, surf_list)
    if key in _GROUP_BLOCK_CACHE:
        return _GROUP_BLOCK_CACHE[key]

    # variant budget: one program per shape family — the chunked
    # dispatch pads every chunk to ONE shape, regrows and regrouping
    # add a few; growth past this is recompile churn
    @governed(BLOCK_ENTRY, budget=6)
    @jax.jit
    def run(stacked, met_s, wave, active, sw, pr):
        def body(args):
            m, k, wave, act = args
            # the cycle names its own phases (``cyc.*`` scopes: XLA op
            # metadata, which obs/devtime reads off the executable)
            return adapt_cycle_impl(
                m, k, wave, do_swap=sw, do_smooth=not nomove,
                do_insert=not noinsert, hausd=hausd, prescreen=pr,
                active=act, surf_list=surf_list)

        n_map = stacked.vert.shape[0]            # chunk or g_exec
        waves = jnp.full(n_map, wave, jnp.int32)
        return jax.lax.map(                      # counts [G, 11]
            body, (stacked, met_s, waves, active))

    _GROUP_BLOCK_CACHE[key] = run
    return run


def _group_polish_block(noinsert: bool, noswap: bool, nomove: bool,
                        hausd):
    """Grouped sliver-polish block (sliver_polish per group under
    lax.map), cached by knobs for the same jit-identity reason.  Takes
    the same per-slot ``active`` mask as :func:`_group_block` — the
    wave-major polish retires groups at their own collapse+swap==0
    fixed point, and a retired/pad slot's row is cond-skipped."""
    from ..ops.adapt import sliver_polish_impl
    from ..utils.compilecache import governed
    key = (noinsert, noswap, nomove, hausd)
    if key in _POLISH_BLOCK_CACHE:
        return _POLISH_BLOCK_CACHE[key]

    @governed("groups.polish_block", budget=4)
    @jax.jit
    def polish_block(stacked, met_s, wave, active):
        def body(args):
            m, k, w, act = args
            m, cnt = sliver_polish_impl(
                m, k, w, do_collapse=not noinsert,
                do_swap=not noswap, do_smooth=not nomove,
                hausd=hausd, active=act)
            return m, k, cnt
        n_map = stacked.vert.shape[0]            # chunk or g_exec
        waves = jnp.full(n_map, wave, jnp.int32)
        m, k, cnt = jax.lax.map(body, (stacked, met_s, waves, active))
        return m, k, cnt

    _POLISH_BLOCK_CACHE[key] = polish_block
    return polish_block


def _pad_groups(tree, g_new: int):
    """Pad a stacked pytree's leading group axis to ``g_new`` with dead
    groups (all-zero arrays: masks False, counts 0 — every wave kernel
    is a no-op on a fully-dead mesh)."""
    def pad(a):
        g = a.shape[0]
        if g >= g_new:
            return a
        return jnp.concatenate(
            [a, jnp.zeros((g_new - g,) + a.shape[1:], a.dtype)])
    return jax.tree.map(pad, tree)


def _tile_rows(tree, rows: int) -> list:
    """A stacked pytree of ``k * rows`` groups as ``k`` tiles of
    ``rows``: what one block program of ``rows`` rows is dispatched
    over, a tile a dispatch.  A stack of ``rows`` groups is its own
    only tile, untouched."""
    n = jax.tree.leaves(tree)[0].shape[0]
    if n == rows:
        return [tree]
    return [jax.tree.map(lambda a: a[t:t + rows], tree)
            for t in range(0, n, rows)]


def _pipeline_chunks(fn, stacked, met_s, wave, plans, tim, done=None):
    """Double-buffered chunked dispatch over gathered group-index slices.

    ``plans``: [(idx_exec [chunk], nreal)] from the quiet-group
    scheduler (parallel/sched.py); the SAME compiled [chunk, ...]
    program runs on every gathered slice, so compaction adds zero new
    shape families.  The legacy loop was a serial
    upload -> compute -> sync -> download train per chunk; here chunk
    k+1's host gather + device upload + dispatch are issued BEFORE
    blocking on chunk k, so host staging and device->host pulls overlap
    device compute (two chunks in flight, bounding peak device memory
    at 2 chunk states — the HBM discipline of the chunked mode).  The
    per-chunk counter sync is deferred into the chunk's drain: counts
    ride the same batched pull as the mesh download, after the next
    chunk is already enqueued.

    Writeback generalizes the old contiguous ``_assign`` to index
    lists: only the first ``nreal`` rows of a padded tail plan are
    scattered back.  ``tim`` (utils.timers.Timers) records the
    upload / compute-wait / download / writeback split; the compute
    wait of a drained chunk overlaps the next chunk's execution, so
    the recorded segments are the PIPELINE's residual stalls, not raw
    kernel time.

    PARMMG_GROUP_PIPELINE=0 serializes (drain each chunk before
    enqueuing the next): double-buffering holds TWO chunk states on
    device instead of the legacy loop's one, and a PARMMG_GROUP_CHUNK
    tuned against the HBM ceiling (the 16 GB-chip OOM note below) may
    need the legacy memory bound back rather than a smaller chunk.

    Fault tolerance (resilience/): a chunk whose dispatch or drain
    fails (injectable via ``PARMMG_FAULT=dispatch.chunk``) is re-run SERIALLY under the
    retry/backoff wrapper.  This is exact, not best-effort: the host
    state is only mutated by a drain's writeback (its last step, and
    idempotent), so a failed chunk's inputs are intact and a
    re-dispatch from them is bit-identical.  Retry-budget exhaustion
    raises ``RetryBudgetExhausted`` — the driver's LOWFAILURE signal.
    ``done`` (optional dict) records each plan's counts as its drain
    COMMITS (i.e. after writeback): a caller catching the exhaustion
    can tell exactly which plans already mutated the host state and
    which never ran — the serve pool's isolation fallback needs that
    to avoid re-applying a wave to already-advanced slots.

    Returns the per-plan host count arrays (trimmed to nreal), in plan
    order."""
    import os
    from ..resilience.faults import faultpoint
    from ..resilience.recover import retry_call
    from ..resilience.watchdog import deadline_knob, run_with_deadline
    from ..utils.placement import to_device
    from .sched import pad_mask
    depth = 2 if os.environ.get("PARMMG_GROUP_PIPELINE", "1") != "0" \
        else 1
    # deadline watchdog on each dispatch/drain unit (0 = off, the
    # default): a wedged device dispatch raises WatchdogTimeout into
    # the SAME except/redo/retry path as a crashed one.  The abandoned
    # monitor-thread attempt is harmless here: a drain's writeback is
    # idempotent and deterministic, so a late commit racing the retry
    # writes identical bytes (the redo contract below)
    ddl = deadline_knob("PARMMG_DEADLINE_DISPATCH_S")
    out = [None] * len(plans)

    def dispatch(pi, idx, nreal):
        with tim("upload"):
            # committed, as the unchunked pass commits its state: a
            # chunk of a shape the unchunked path has run takes the
            # executable that path built (compilecache, placement
            # variants)
            sl, kl = to_device(jax.tree.map(
                lambda a: a[idx], (stacked, met_s)))
            # device quiet mask: the repeat-padded tail rows compute
            # nothing (lax.cond identity) — their results were always
            # discarded at writeback (sched.pad_mask)
            act = jnp.asarray(pad_mask(len(idx), nreal))
        faultpoint("dispatch.chunk", key=str(pi))
        with otrace.span("grp dispatch chunk", chunk_index=pi):
            m, k, cnt = fn(sl, kl, wave, act)
        return (pi, idx, nreal, m, k, cnt)

    # lint: ok(R2) — the pipeline's ONE designed sync point: chunked
    # mode keeps the pass state host-resident, so the drain downloads
    # O(chunk) tables + [chunk,11] counters while chunk k+1 is
    # already dispatched (PR-5 double buffering; segments timed)
    def drain(p):
        pi, idx, nreal, m, k, cnt = p
        with tim("compute"):
            jax.block_until_ready(cnt)
        with tim("download"):
            mh = jax.tree.map(lambda s: np.asarray(s), m)
            kh = np.asarray(k)
            out[pi] = np.asarray(cnt)[:nreal]
        with tim("writeback"):
            rows = idx[:nreal]

            def w(d, s):
                d[rows] = s[:nreal]
                return d
            jax.tree.map(w, stacked, mh)
            met_s[rows] = kh[:nreal]
        if done is not None:
            done[pi] = out[pi]

    # the watchdog-guarded forms (inline when PARMMG_DEADLINE_DISPATCH_S
    # is 0/unset — zero threads on the default path)
    def gdispatch(pi, idx, nreal):
        return run_with_deadline(lambda: dispatch(pi, idx, nreal),
                                 ddl, "dispatch.chunk")

    def gdrain(p):
        return run_with_deadline(lambda: drain(p), ddl,
                                 "dispatch.chunk")

    def redo(pi, idx, nreal, first):
        # serial dispatch+drain re-attempt of one failed chunk; the
        # inline fast-path attempt already counted (initial_failure).
        # One deadline bounds the serial pair: a retry that ALSO wedges
        # keeps feeding the retry budget until it exhausts (LOWFAILURE)
        retry_call(lambda: run_with_deadline(
            lambda: drain(dispatch(pi, idx, nreal)), ddl,
            "dispatch.chunk"),
            site="dispatch.chunk", initial_failure=first)

    def safe_drain(p):
        try:
            gdrain(p)
        except Exception as e:
            redo(p[0], p[1], p[2], e)

    pending = None
    for pi, (idx, nreal) in enumerate(plans):
        cur = first = None
        try:
            cur = gdispatch(pi, idx, nreal)
        except Exception as e:
            first = e
        if pending is not None:
            p0, pending = pending, None
            safe_drain(p0)
        if cur is None:
            redo(pi, idx, nreal, first)
        elif depth == 1:
            safe_drain(cur)
        else:
            pending = cur
    if pending is not None:
        safe_drain(pending)
    return out


def grouped_adapt_pass(mesh: Mesh, met, ngroups: int, cycles: int = 12,
                       part: np.ndarray | None = None,
                       verbose: int = 0, stats=None,
                       noinsert: bool = False, noswap: bool = False,
                       nomove: bool = False, hausd: float | None = None,
                       polish: bool = False, cap_mult: float = 3.0,
                       timers=None, ckpt_tag: str | None = None,
                       ckpt_it: int = 0, cap_state: list | None = None,
                       contiguous: bool = False, target: int = 0,
                       cap_max: int = 0):
    """One outer pass: split into groups, run adapt cycles with lax.map
    over the group axis, merge.  Returns (mesh, met, part_of_merged).

    ``contiguous``: what a pass that is handed no ``part`` asks of its
    own cut (``fresh_cut``).

    ``cap_state``: a mutable list carried across the passes of one run
    (the ``regrow_state`` idiom of dist.run_adapt_cycles): the group
    (capP, capT) the previous pass ended with, then the rows R of the
    job's first cut.  The split keeps that shape while the new groups
    fit in it with slack (distribute.split_to_shards ``reuse_caps``),
    and a block is ``ceil(G / R)`` dispatches of the ONE ``(R, capP,
    capT)`` program over tiles of R device-resident rows, rows past G
    dead (:func:`_tile_rows`), so a later pass and a cut of another
    count run the block program the first one compiled.

    ``target``: tets a group is cut for (``-mesh-size``; 0: the count is
    the caller's and stands).  ``cap_max``: the ceiling on a group's tet
    capacity (``IParam.groupCapacity``; 0: none, the ladder is open and
    a job runs as it always did: a full group is regrown to the next
    rung, ``groups.regrows``, and the count stands).  **Under a ceiling
    the count follows the mesh**: the next rung of the capacity ladder
    is a block compile of minutes on a TPU where it compiles at all
    (ROADMAP B11), so a job that states the rung it may compile never
    leaves it.  Its first cut takes more groups (:func:`fresh_groups`);
    a block that filled a group which is over ``target`` (a mesh that
    has outgrown its count), where the regrown rung would pass the
    ceiling, is answered with MORE GROUPS OF THE SAME SHAPE, not a
    bigger group: merge, cut every group over ``target`` inside itself
    (``partition.refine_cut``), split on the kept capacity, upload, void
    the quiet proofs, run the cycle again (a ``grp recut`` span,
    ``groups.recuts``); a displaced cut whose rung would pass it is
    re-cut the same way between the passes (:func:`_recut_outgrown`);
    and a cut or a regrow that would still pass it raises
    ``MemoryError``.  A pass answers six full groups, re-cuts and
    regrows together, then raises ``MemoryError``, the driver's
    LOWFAILURE.

    The per-group program is the SAME adapt_cycle_impl as the whole-mesh
    path (frozen MG_PARBDY group seams make it correct); the map axis
    serializes groups so HBM holds one group's working set at a time.

    Quiet-group scheduler (parallel/sched.py, PARMMG_GROUP_SCHED=0 to
    disable): per-group counts mark groups quiet once a swap-inclusive
    block is a no-op for them, and subsequent chunked dispatches gather
    only the ACTIVE indices — same compiled [chunk, ...] program, fewer
    executions of it.  The quiet proof is ALSO pushed down into the
    compiled programs as a device-resident active mask
    (PARMMG_DEVICE_MASK=0 to disable): every group-block dispatch takes
    a per-slot bool mask and ``lax.cond``-skips the wave math for
    inactive slots — quiet groups of an unchunked dispatch (where
    compaction cannot change the dispatch shape) and the repeat-padded
    tail rows of chunk plans.  Skipping is bit-for-bit exact either way
    (frozen seams + deterministic waves make a zero-op state a fixed
    point; see the sched module docstring for the prescreen-level and
    regrow caveats).
    Chunked dispatches ride a double-buffered pipeline
    (:func:`_pipeline_chunks`); its upload/compute/download/writeback
    split lands in ``timers`` (driver reporting) and, with the
    skipped-group / saved-dispatch counters and the active-group
    trajectory, in ``stats.sched_extra``.
    """
    from types import SimpleNamespace
    from ..ops.adapt import (CYCLE_COLS, LISTED_COL, SURF_COLS,
                             surface_scatter_width)
    from ..utils.timers import Timers
    from .distribute import (capacity_headroom, split_to_shards,
                             merge_shards, grow_shards, regrown_capacity)
    from .partition import refine_cut
    from .sched import QuietGroupScheduler
    from ..core.mesh import mesh_to_host
    from ..obs.metrics import REGISTRY

    # The split is staged on the host CPU backend (host_staging): it
    # runs a per-shard adjacency program and stacks the result, a
    # one-shot program whose TPU compile costs far more than its run.
    # Chunked dispatch (group_chunk docstring) additionally keeps the
    # stacked state in HOST RAM between dispatches, padded so every
    # chunk runs the SAME compiled [chunk,...] program: only the
    # in-flight chunk occupies device memory — the zaldy_pmmg.c memory
    # philosophy at chip scale, for a state that does not fit the
    # device.  Unchunked, the stacked state is committed to the device
    # once a cut, in tiles, and stays there until the cut's merge.
    from ..utils.placement import host_staging, to_device
    # the rows of the pass as its newest cut staged them: ``tiles``
    # (unchunked: [(stacked, met_s)] of ``rows`` rows each, on the
    # device) or ``host`` (chunked: one (stacked, met_s) of g_exec rows)
    if cap_state is not None and not cap_state:
        cap_state[:] = [None, None]
    st = SimpleNamespace(sched=QuietGroupScheduler(0, 0, 0),
                         rows=cap_state[1] if cap_state else None)

    def stage(mesh, met, part, ngroups, caps):
        """Split ``mesh`` along ``part`` and commit the rows: the start
        of the pass and of every re-cut inside it."""
        chunk = group_chunk(ngroups)
        g_exec = ngroups        # padded to whole chunks or tiles
        with otrace.span("grp split", groups=ngroups) as sp:
            if part is None:
                vert_h, tet_h, _, _, _ = mesh_to_host(mesh)
                part = fresh_cut(vert_h, tet_h, ngroups, contiguous)
            # what the cut asks of the pass (seams, junctions, pieces),
            # as the split counts it on its way
            cut = {}
            with host_staging():
                stacked, met_s = split_to_shards(
                    mesh, met, part, ngroups, cap_mult=cap_mult,
                    reuse_caps=caps, cut=cut)
                if chunk:
                    g_exec = -(-ngroups // chunk) * chunk
                    # np.array (copy): np.asarray of a jax array can hand
                    # back a READ-ONLY buffer, and the host state is
                    # mutated in place by the per-chunk writebacks
                    stacked = jax.tree.map(
                        # lint: ok(R2) — chunked mode keeps the state in
                        # host RAM by design (the split's arrays are there)
                        lambda a: np.array(a),
                        _pad_groups(stacked, g_exec))
                    # lint: ok(R2) — the same copy, of the metric
                    met_s = np.array(_pad_groups(met_s, g_exec))
                else:
                    # R: the rows of the job's first cut, which the one
                    # block program was compiled for
                    rows = st.rows = st.rows or ngroups
                    g_exec = -(-ngroups // rows) * rows
                    if g_exec > ngroups:
                        stacked, met_s = _pad_groups((stacked, met_s),
                                                     g_exec)
            # how far the fullest group stands from the edge of the
            # capacity a later split may keep (shard_capacity ``keep``):
            # under 0 the next pass would leave this block program
            most_verts, largest = cut.pop("maxP"), cut.pop("maxT")
            capP, capT = stacked.vert.shape[1], stacked.tet.shape[1]
            if 0 < cap_max < capT:
                raise MemoryError(f"a cut of {ngroups} groups needs capT "
                                  f"{capT}, over the ceiling {cap_max}")
            headroom = capacity_headroom(most_verts, largest, capP, capT)
            sp.set(capP=capP, capT=capT, largest=largest,
                   headroom=headroom, **cut)
        otrace.log(2, f"  grp split: {ngroups} groups, largest "
                      f"{largest} tets, capacity (capP, capT) = "
                      f"({capP}, {capT})", verbose=verbose)
        # everything the cut commits to the device before its next
        # block: the stacked state (unchunked: COMMITTED, because the
        # block program hands it back committed and jax keys a lowering
        # on that; PERF.md, PR 31) and the scheduler's scalars
        with otrace.span("grp upload", chunk=chunk or 0) as sp:
            if chunk:
                st.host, st.tiles = (stacked, met_s), []
            else:
                st.host = None
                st.tiles = [to_device(t) for t in
                            _tile_rows((stacked, met_s), rows)]
                sp.set(bytes=sum(a.nbytes for a in
                                 jax.tree.leaves(st.tiles)),
                       tiles=len(st.tiles))
            st.sched.on_recut(ngroups, g_exec, chunk,
                              tiles=len(st.tiles) or 1)
        st.ngroups, st.g_exec, st.chunk = ngroups, g_exec, chunk
        st.capP, st.capT = capP, capT
        st.largest, st.headroom = largest, headroom

    # lint: ok(R2) — a cut's designed pull: ONE transfer of the stacked
    # state for the host-staged merge (merge_shards slices it per shard,
    # which on device arrays would be device programs plus a pull per
    # field per shard)
    def pull():
        """The live rows of the newest cut on the host."""
        with otrace.span("grp pull") as sp:
            if st.chunk:
                rows_h = st.host
            else:
                pulled = [jax.tree.map(np.asarray, t) for t in st.tiles]
                rows_h = pulled[0] if len(pulled) == 1 else \
                    jax.tree.map(lambda *xs: np.concatenate(xs), *pulled)
            # dead pad rows hold nothing
            rows_h = jax.tree.map(lambda a: a[:st.ngroups], rows_h)
            sp.set(bytes=sum(a.nbytes for a in jax.tree.leaves(rows_h)))
        return rows_h

    def merge(stacked_h, met_h):
        """One host mesh of the pulled rows, its metric and the row
        each tet came from.  Staged on the host like the split:
        merge_shards rebuilds adjacency at MERGED-mesh width, the widest
        one-shot program of the pass; the merged mesh stays on the host
        for the next split or the caller's merged-width tail."""
        with otrace.span("grp merge") as sp, host_staging():
            merged, met_m, part_m = merge_shards(stacked_h, met_h,
                                                 return_part=True)
            sp.set(ne=len(part_m), capT=merged.capT, capP=merged.capP)
        return merged, met_m, part_m

    # lint: ok(R2) — the pull of a block's counters, its designed sync
    def run_tiles(fn, wave, mask):
        """One dispatch of ``fn`` a tile, every tile before the first
        counter is pulled (the pulls are the block's only sync); the
        tiles' new state stays on the device.  Returns the counts rows
        [g_exec, ...] on the host."""
        rows = st.rows
        out = [fn(sl, kl, wave, jnp.asarray(mask[t * rows:(t + 1) * rows]))
               for t, (sl, kl) in enumerate(st.tiles)]
        st.tiles = [(sl, kl) for sl, kl, _ in out]
        return np.concatenate([np.asarray(cnt) for _, _, cnt in out])

    stage(mesh, met, part, ngroups,
          cap_state[0] if cap_state else None)
    sched = st.sched

    def _assign(dst_tree, src_tree, g0):
        """Write a chunk's device results back into the host state
        (contiguous-slice legacy form; the scheduler path scatters by
        index list inside :func:`_pipeline_chunks`)."""
        def w(d, s):
            d[g0:g0 + st.chunk] = np.asarray(s)
            return d
        jax.tree.map(w, dst_tree, src_tree)

    # pipeline segment timers on a LOCAL registry: folded into
    # stats.sched_extra and (prefixed) into the caller's Timers at the
    # end, so the driver report shows the transfer/compute split
    ltim = Timers()
    c = 0
    overflows = 0       # full groups the pass answered: re-cuts, regrows
    while c < cycles:
        swap, pre = block_schedule(c, cycles, noswap)
        step = _group_block(swap, pre, nomove, noinsert, hausd)
        swap_inc = swap or noswap
        wave = jnp.asarray(c, jnp.int32)
        chunk = st.chunk
        act, plans = sched.plan_block(pre)
        # one span a dispatched block, dispatch to counter pull, with
        # the operations it applied: the ratio of useful outcomes to
        # attempts is recorded where the work happens
        skipped0 = sched.cond_skipped
        with otrace.context(block=c, chunk=chunk or 0), \
                otrace.span("grp block", block=c,
                            active=len(act)) as sp:
            if chunk:
                stacked, met_s = st.host
                parts = _pipeline_chunks(step, stacked, met_s, wave,
                                         plans, ltim)
                sched.note_plan_pads(plans)
                counts_act = np.concatenate(parts) if parts else \
                    np.zeros((0, CYCLE_COLS), np.int32)
                if sched.enabled:
                    otrace.log(
                        2, f"  grp block {c}: active "
                           f"{len(act)}/{st.g_exec} groups, {len(plans)} "
                           "dispatches", verbose=verbose)
            else:
                # unchunked: compaction cannot change the dispatch
                # shape — the device-resident quiet mask is what skips
                # converged groups here (lax.cond identity rows,
                # sched.block_mask; bit-for-bit by the fixed point),
                # and the dead rows that fill the last tile
                counts_act = run_tiles(         # [g_exec, 11]
                    step, wave, sched.block_mask(pre))
            # quiet groups contribute exact zeros (that is what marked
            # them)
            cs = counts_act.sum(axis=0, dtype=np.int64)         # [11]
            # ONE host conversion for the block's counters (counts_act
            # is already host numpy — the drain pulled it)
            tot = cs.tolist()                           # python ints
            surf = {k: tot[col] for k, col in SURF_COLS.items()}
            # the rows whose surface scatters ran over lists (every row
            # that ran, or none: a mesh has boundary faces), and what
            # those scatters are at full width
            surf["listed"] = tot[LISTED_COL]
            list_full = surface_scatter_width(
                st.capT, not noinsert, not nomove, hausd
            ) * np.count_nonzero(counts_act[:, LISTED_COL])
            # quiet: the row executions the device mask skipped in this
            # dispatch (a job's sum of them is groups.cond_skipped)
            # prog: which of the block programs this process lowered
            # the dispatch ran (obs/devtime joins a capture's device ops
            # with that program's scope map)
            # tiles, rows: the dispatches the block made and the rows
            # of their stacks, the dead ones that fill the last tile
            # included
            sp.set(split=tot[0], collapse=tot[1], swap=tot[2],
                   moved=tot[3], quiet=sched.cond_skipped - skipped0,
                   prog=LEDGER.program_index(BLOCK_ENTRY),
                   tiles=len(plans) if chunk else len(st.tiles),
                   rows=sum(len(idx) for idx, _ in plans), **surf)
        if not chunk:
            # "compute" as the chunk pipeline records it: the seconds
            # from dispatch to counter pull
            ltim.add("compute", sp.dur)
        sched.record_block(act, counts_act, swap_inc, pre)
        if stats is not None:
            stats.nsplit += tot[0]
            stats.ncollapse += tot[1]
            stats.nswap += tot[2]
            stats.nmoved += tot[3]
            stats.cycles += 1
            stats.add_surface(**surf, list_full=list_full)
        otrace.log(3, f"  grp cycle {c}: split {tot[0]} "
                      f"collapse {tot[1]} swap {tot[2]} move "
                      f"{tot[3]} over {st.ngroups} groups",
                   verbose=verbose)
        if tot[4] != 0 and overflows >= 6:
            raise MemoryError("group capacity exhausted")
        if tot[4] != 0 and 0 < cap_max < regrown_capacity(
                st.capP, st.capT)[1] and \
                0 < target < counts_act[:, 5].max():
            # the ceiling refuses the next rung and the full group is
            # over ``target`` (a row reports its live tets, cond-skipped
            # or not), a group of a mesh that outgrew its count: more
            # groups of the same shape.  The block's applied winners
            # stand; the ones it dropped for want of rows run again, in
            # the cut's groups
            with otrace.span("grp recut", why="overflow",
                             g0=st.ngroups) as sp:
                merged, met_m, part_m = merge(*pull())
                vert_h, tet_h, _, _, _ = mesh_to_host(merged)
                part = refine_cut(vert_h, tet_h, part_m, target)
                # lint: ok(R2) — part is the cut's host labels
                ngroups1 = int(part.max()) + 1
                stage(merged, met_m, part, ngroups1, (st.capP, st.capT))
                sp.set(g1=st.ngroups, ne=len(part), largest=st.largest,
                       headroom=st.headroom)
            overflows += 1
            REGISTRY.counter("groups.recuts").inc()
            REGISTRY.counter("groups.recut_overflow").inc()
            continue
        if tot[4] != 0:
            with otrace.span("grp regrow") as sp:
                capP, capT = st.capP, st.capT
                newP, newT = regrown_capacity(capP, capT)
                sp.set(capT0=capT, capT1=newT)
                if 0 < cap_max < newT:
                    raise MemoryError("group capacity exhausted under "
                                      f"the ceiling {cap_max}")
                if chunk:
                    # host-resident grow (np.pad mirror of grow_shards —
                    # jnp.pad would re-materialize the state on device)
                    import dataclasses as _dc
                    stacked, met_s = st.host

                    def _padP(x, fill=0):
                        pad = [(0, 0)] * x.ndim
                        pad[1] = (0, newP - capP)
                        return np.pad(x, pad, constant_values=fill)

                    def _padT(x, fill=0):
                        pad = [(0, 0)] * x.ndim
                        pad[1] = (0, newT - capT)
                        return np.pad(x, pad, constant_values=fill)

                    stacked = _dc.replace(
                        stacked,
                        vert=_padP(stacked.vert), vref=_padP(stacked.vref),
                        vtag=_padP(stacked.vtag),
                        vmask=_padP(stacked.vmask, False),
                        vnrm=_padP(stacked.vnrm),
                        tet=_padT(stacked.tet), tref=_padT(stacked.tref),
                        tmask=_padT(stacked.tmask, False),
                        adja=_padT(stacked.adja, -1),
                        ftag=_padT(stacked.ftag), fref=_padT(stacked.fref),
                        etag=_padT(stacked.etag))
                    st.host = (stacked, _padP(met_s))
                else:
                    st.tiles = [grow_shards(sl, kl, newP, newT)
                                for sl, kl in st.tiles]
                st.capP, st.capT = newP, newT
                overflows += 1
                REGISTRY.counter("groups.regrows").inc()
                # the wave top-K budgets scale with capT: every quiet proof
                # is stale at the new capacity — reactivate the full set
                # (truncated winners must rerun)
                sched.on_regrow()
            continue        # re-run the block: truncated winners rerun
        c += 1
        if block_converged(cs, swap, noswap):
            break
    ngroups, g_exec, chunk = st.ngroups, st.g_exec, st.chunk
    if chunk:
        stacked, met_s = st.host
    pol_traj: list[int] = []
    if polish and not (noinsert and noswap and nomove):
        # grouped bad-element pass: sliver_polish per group under the
        # same lax.map regime (seams stay frozen; the outer-iteration
        # displacement exposes them to a later pass).  This is what
        # makes a >=1M-tet run report a REAL post-tail min quality
        # without a whole-mesh-width program.
        polish_block = _group_polish_block(noinsert, noswap, nomove,
                                           hausd)

        if chunk and sched.enabled:
            # quiet-group polish: wave-major over COMPACTED active
            # chunks, retiring each group at its own collapse+swap==0
            # point — the per-group form of the legacy loop's per-chunk
            # break (identical to it at chunk granularity 1; the old
            # chunk-coupled break let a chunk-mate's work extend a quiet
            # group's wave count, an artifact the compaction drops).
            # All groups re-enter here: polish ops (sliver collapses,
            # swapgen, opt-q smoothing) are a different candidate class
            # than the cycle loop, so cycle-quiet proves nothing.
            # Trade-off vs the legacy chunk-resident loop: a group
            # active for w waves is shipped w times instead of once —
            # paid back by retirement shrinking later waves and by the
            # pipeline overlapping the transfers; PARMMG_GROUP_SCHED=0
            # keeps the legacy loop.
            from .sched import chunk_plans
            from ..resilience.recover import (RetryBudgetExhausted,
                                              ladder_step)
            pol_act = np.arange(ngroups)
            try:
                for w in range(4):
                    if not len(pol_act):
                        break
                    plans = chunk_plans(pol_act, chunk)
                    sched.dispatches += len(plans)
                    parts = _pipeline_chunks(
                        polish_block, stacked, met_s,
                        jnp.asarray(2000 + w, jnp.int32), plans, ltim)
                    sched.note_plan_pads(plans)
                    cnts = np.concatenate(parts)      # [n_act, 11]
                    pol_traj.append(len(pol_act))
                    tot = cnts.sum(axis=0, dtype=np.int64).tolist()
                    otrace.log(2, f"  grp polish w{w}: collapse "
                                  f"{tot[0]} swap {tot[1]} "
                                  f"move {tot[2]} over "
                                  f"{len(pol_act)} active groups",
                               verbose=verbose)
                    pol_act = pol_act[(cnts[:, 0] + cnts[:, 1]) > 0]
            except RetryBudgetExhausted as e:
                # polish is a quality tail, not the sizing loop: a
                # persistent dispatch fault here degrades one rung
                # (remaining grouped polish skipped — the state is
                # conforming with or without it; committed chunks keep
                # their polish) instead of escalating to the driver's
                # LOWFAILURE, which would throw away the whole adapted
                # mesh (README ladder: merged_polish)
                ladder_step("merged_polish", site="dispatch.chunk",
                            detail=str(e.__cause__ or e))
                otrace.log(1, "  ## Warning: grouped polish dispatch "
                              f"kept failing ({e.__cause__ or e}); "
                              "skipping the remaining grouped polish "
                              "waves — the merged polish + repair tail "
                              "still runs.", err=True)
        elif chunk:
            # per-chunk wave loop (PARMMG_GROUP_SCHED=0 legacy): each
            # chunk polishes to ITS quiet point while resident, one
            # upload/download per chunk total
            for g0 in range(0, g_exec, chunk):
                sl, kl = to_device(jax.tree.map(
                    lambda a: a[g0:g0 + chunk], (stacked, met_s)))
                for w in range(4):
                    sl, kl, cnt = polish_block(
                        sl, kl, jnp.asarray(2000 + w, jnp.int32),
                        jnp.ones(chunk, bool))
                    # one host pull for the chunk's counters (the
                    # legacy loop's designed sync point), python ints
                    # from it without per-counter casts
                    tot = np.asarray(cnt).sum(axis=0).tolist()
                    otrace.log(2, f"  grp polish chunk {g0 // chunk} "
                                  f"w{w}: collapse {tot[0]} swap "
                                  f"{tot[1]} move {tot[2]}",
                               verbose=verbose)
                    if tot[0] == 0 and tot[1] == 0:
                        break
                _assign(stacked, sl, g0)
                met_s[g0:g0 + chunk] = np.asarray(kl)
        else:
            live = np.arange(g_exec) < ngroups
            for w in range(4):
                tot = run_tiles(
                    polish_block, jnp.asarray(2000 + w, jnp.int32),
                    live).sum(axis=0).tolist()
                otrace.log(2, f"  grp polish {w}: collapse "
                              f"{tot[0]} swap {tot[1]} move "
                              f"{tot[2]}", verbose=verbose)
                if tot[0] == 0 and tot[1] == 0:
                    break
    # fold the scheduler instrumentation: counters + the active-group
    # trajectory into AdaptStats.sched_extra (SCALE artifacts),
    # the pipeline segment times into the caller's Timers (driver
    # report) under a "grp <segment>" prefix
    # chunk auto-tune (ROADMAP 1b, lightweight): fold this pass's
    # active-group trajectory into a chunk recommendation for the NEXT
    # pass — adopted only under PARMMG_GROUP_CHUNK=auto, logged always.
    # The cost model's overhead constant is CALIBRATED from this pass's
    # measured pipeline segment timings when a chunked pipeline ran
    # (sched.calibrate_dispatch_overhead; hand-set default otherwise)
    from .sched import (calibrate_dispatch_overhead,
                        note_chunk_recommendation, recommend_group_chunk)
    overhead = calibrate_dispatch_overhead(ltim.acc, ltim.count, chunk) \
        if chunk else None
    chunk_rec = recommend_group_chunk(
        sched.active_per_block, g_exec if chunk else ngroups,
        dispatch_overhead=(1.0 if overhead is None else overhead))
    note_chunk_recommendation(chunk_rec)
    otrace.log(2, f"  grp chunk auto-tune: recommend "
                  f"PARMMG_GROUP_CHUNK={chunk_rec or 'unchunked'} "
                  f"(current {chunk or 'unchunked'}, overhead "
                  f"{'default' if overhead is None else round(overhead, 3)}"
                  " group-units)", verbose=verbose)
    # metrics spine: the pass's scheduler counters + pipeline segment
    # seconds land in the process registry regardless of whether the
    # caller threaded a stats/timers object through
    REGISTRY.counter("groups.dispatches").inc(sched.dispatches)
    # rows those dispatches ran (a block runs every row of its stack,
    # the quiet ones as lax.cond identities): what a block's seconds
    # divide by
    REGISTRY.counter("groups.rows").inc(sched.rows)
    # of them the dead rows that fill a block's last tile (or chunk)
    REGISTRY.counter("groups.rows_dead").inc(sched.rows_dead)
    # group-slot executions the device-resident quiet mask cond-skipped
    # (unchunked quiet slots + padded tail rows of chunk plans)
    REGISTRY.counter("groups.cond_skipped").inc(sched.cond_skipped)
    for k, v in ltim.acc.items():
        # lint: ok(R6) — k ranges over the fixed _pipeline_chunks
        # segment set (upload/compute/download/writeback): bounded
        REGISTRY.counter(f"groups.pipeline.{k}_s").inc(v)
    if stats is not None:
        stats.group_dispatches += sched.dispatches
        stats.group_dispatches_saved += sched.saved_dispatches
        stats.groups_skipped += sched.skipped_group_blocks
        se = stats.sched_extra
        se["cond_skipped_rows"] = se.get("cond_skipped_rows", 0) + \
            sched.cond_skipped
        se.setdefault("chunk_recommendation", []).append(chunk_rec)
        if overhead is not None:
            se.setdefault("chunk_overhead_units", []).append(
                round(overhead, 4))
        se.setdefault("active_groups_per_block", []).extend(
            sched.active_per_block)
        if pol_traj:
            se.setdefault("polish_active_per_wave", []).extend(pol_traj)
        for k, v in ltim.acc.items():
            se[f"grp_{k}_s"] = se.get(f"grp_{k}_s", 0.0) + v
    if timers is not None:
        for k, v in ltim.acc.items():
            timers.add(f"grp {k}", v, ltim.count[k])
    # pass-level durability (resilience/checkpoint.py): the pre-merge
    # stacked state doubles as the merge-free distributed-file snapshot
    # of this pass (the reference's -distributed-output checkpoint
    # role).  ckpt_due-gated: free unless PARMMG_CKPT_DIR is armed.
    stacked_h, met_h = pull()
    if ckpt_tag is not None:
        from ..resilience.checkpoint import ckpt_span, snapshot_stacked
        with ckpt_span(ckpt_it):
            snapshot_stacked(ckpt_tag, ckpt_it, stacked_h, ngroups)
    if cap_state is not None:
        cap_state[:] = [(st.capP, st.capT), st.rows]
    merged, met_m, part_m = merge(stacked_h, met_h)
    return merged, met_m, part_m


def _recut_outgrown(vert_h, tet_h, part, target: int, caps,
                    cap_max: int = 0):
    """The cut the next pass splits by: ``part``, the displaced labels,
    while the capacity their fullest group takes (``shard_capacity``'s
    ``keep`` rule: the one the job compiled its block for where the
    group fits it, every accepted job's case, else the lowest rung that
    holds it) is one the job may compile a block for.  Where that rung
    is refused (it passes the ceiling ``cap_max``,
    ``IParam.groupCapacity``) the cut takes more groups of the same
    shape instead: every group over ``target`` is cut inside itself
    (``partition.refine_cut``), so last pass's seams, which the
    displacement moved inside groups, stay there."""
    from ..obs.metrics import REGISTRY
    from .distribute import capacity_headroom, shard_capacity
    from .partition import cut_sizes, refine_cut
    if caps is None or cap_max <= 0:
        return part
    most_verts, largest = cut_sizes(tet_h, part)
    if largest <= target or \
            shard_capacity(most_verts, largest, keep=caps)[1] <= cap_max:
        return part
    with otrace.span("grp recut", why="pass",
                     g0=int(part.max()) + 1) as sp:
        part = refine_cut(vert_h, tet_h, part, target)
        most_verts, largest = cut_sizes(tet_h, part)
        sp.set(g1=int(part.max()) + 1, ne=len(part), largest=largest,
               headroom=capacity_headroom(most_verts, largest, *caps))
    REGISTRY.counter("groups.recuts").inc()
    return part


def grouped_adapt(mesh: Mesh, met, target_size: int, niter: int = 3,
                  cycles: int = 12, verbose: int = 0, stats=None,
                  noinsert: bool = False, noswap: bool = False,
                  nomove: bool = False, hausd: float | None = None,
                  ifc_layers: int = 2, timers=None,
                  resume: bool = False, ckpt_tag: str = "grouped",
                  contiguous: bool = False, cap_max: int = 0):
    """The two-level outer loop on one device: grouped passes with
    interface displacement between them (the rank-level loop of
    libparmmg1.c:636-948 collapsed onto one device, groups as the only
    level).  Engaged by the driver when ``-mesh-size`` yields >= 2
    groups.

    Durability (resilience/checkpoint.py, PARMMG_CKPT_DIR armed): the
    merged state + displaced partition are checkpointed after each
    completed outer pass; ``resume=True`` restarts from the newest
    complete pass checkpoint instead of from scratch.  Passes are
    deterministic from their input state, so a resumed run finishes
    bit-identical to an uninterrupted one (chaos-gated)."""
    from .partition import move_interfaces
    from ..core.mesh import mesh_to_host
    from ..resilience import checkpoint as ckpt

    part = None
    it0 = 0
    cap_state: list = []        # group capacities carried across passes
    # run-identity fingerprint of the ORIGINAL input: stored in every
    # checkpoint and matched at resume, so a reused PARMMG_CKPT_DIR can
    # never silently resume a stale checkpoint from a different run
    fp = None
    if resume or ckpt.ckpt_config()[0]:
        fp = ckpt.run_fingerprint(mesh, met, target_size, niter, cycles,
                                  noinsert, noswap, nomove, hausd,
                                  ifc_layers, contiguous, cap_max)
    if resume:
        found = ckpt.latest_pass_checkpoint(ckpt_tag, fingerprint=fp)
        if found is not None:
            path, k = found
            mesh, met, part, _ = ckpt.load_pass_checkpoint(path)
            it0 = k + 1
            from ..obs.metrics import REGISTRY
            REGISTRY.counter("resilience.resumes").inc()
            otrace.event("ckpt.resumed", tag=ckpt_tag, it=it0, path=path)
            otrace.log(1, f"  resume: loaded {path}; restarting at "
                          f"outer pass {it0}", err=True)
            # crash-loop breaker: resuming into the SAME pass more
            # than PARMMG_RESUME_MAX times means that pass
            # deterministically kills the run — skip past it and hand
            # the caller the last conforming checkpointed state (the
            # bounded-time contract; the driver's merged polish /
            # repair tail still runs on it).  The mh_allgather-style
            # rung for this site is the merged_polish-grade skip:
            # record it on the ladder so the run's failure story shows
            # the escalation
            _, esc = ckpt.crash_loop(ckpt_tag, fp, it0)
            if esc:
                from ..resilience.recover import ladder_step
                ladder_step("lowfailure", site="ckpt.resume",
                            detail=f"crash loop at pass {it0}: "
                                   "returning last conforming "
                                   "checkpoint")
                return mesh, met
    for it in range(it0, max(1, niter)):
        with otrace.context(**{"pass": it}):
            ne = int(np.asarray(mesh.tmask).sum())
            # a displaced partition brings its count (its labels index
            # the last pass's rows, re-cut where the mesh outgrew them:
            # _recut_outgrown); a pass handed none derives its own
            ngroups = (int(part.max()) + 1) if part is not None \
                else fresh_groups(ne, target_size, cap_max)
            if ngroups < 2:
                from ..ops.adapt import adapt_mesh
                mesh, met, st = adapt_mesh(
                    mesh, met, verbose=verbose, noinsert=noinsert,
                    noswap=noswap, nomove=nomove, hausd=hausd)
                if stats is not None:
                    stats += st
                part = None
                with ckpt.ckpt_span(it):
                    ckpt.save_pass_checkpoint(ckpt_tag, it, mesh, met,
                                              part, fingerprint=fp)
                continue
            mesh, met, part_m = grouped_adapt_pass(
                mesh, met, ngroups, cycles=cycles, part=part,
                verbose=verbose, stats=stats, noinsert=noinsert,
                noswap=noswap, nomove=nomove, hausd=hausd,
                timers=timers, ckpt_tag=ckpt_tag, ckpt_it=it,
                cap_state=cap_state, contiguous=contiguous,
                target=target_size, cap_max=cap_max)
            # a pass that re-cut ends on another count than it began on
            ngroups = int(part_m.max()) + 1
            if it + 1 < max(1, niter):
                with otrace.span("grp displace", layers=ifc_layers) as sp:
                    vert_h, tet_h, _, _, _ = mesh_to_host(mesh)
                    part = move_interfaces(tet_h, part_m, ngroups,
                                           nlayers=ifc_layers)
                    # what the displacement (and its fix_contiguity)
                    # did to the cut the next pass splits by
                    sp.set(moved=np.count_nonzero(part != part_m),
                           largest=np.bincount(part).max().tolist(),
                           mean=len(part) / ngroups)
                part = _recut_outgrown(vert_h, tet_h, part, target_size,
                                       cap_state[0], cap_max)
            else:
                # the FINAL pass checkpoints too (part=None — there is
                # no next pass to feed): a kill during the caller's
                # post-adapt tail (merged polish / repair / IO, minutes
                # at the 1M-tet scale) must not restart the whole
                # adaptation; resume with it0 == niter skips the loop
                # and hands the tail this state
                part = None
            # a checkpoint carries the DISPLACED labels: pass it+1's
            # exact input, which is what makes resume bit-identical to
            # the uninterrupted run
            with ckpt.ckpt_span(it):
                ckpt.save_pass_checkpoint(ckpt_tag, it, mesh, met, part,
                                          fingerprint=fp)
    return mesh, met
