"""Whole-mesh adaptation driver — the remesh operator.

This is the TPU-native replacement for the sequential remesher call
``MMG5_mmg3d1_delone`` that the reference invokes per group
(/root/reference/src/libparmmg1.c:737-739).  Where Mmg runs a sequential
cascade of local cavity operations, we run *batched waves*: each jitted
cycle applies one independent set of splits, collapses, swaps and smoothing
moves across the whole mesh, with adjacency rebuilt in between.  The host
loop only reads back scalar counters to decide convergence and to manage
capacity (the static-shape analogue of Mmg's realloc dance and of
``PMMG_parmesh_SetMemGloMax`` budgeting, zaldy_pmmg.c:53-254).

Frozen entities (MG_REQ / MG_PARBDY — the ParMmg interface contract,
tag_pmmg.c:39-124) are respected by every wave, so this same operator
serves both the single-chip whole-mesh path and the per-shard path with
frozen interfaces.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mesh import Mesh, with_capacity, compact
from ..core.constants import LLONG, LSHRT
from ..obs import trace as otrace
from .adjacency import build_adjacency
from .split import split_wave
from .collapse import collapse_wave
from .swap import swap_edges_wave, swap23_wave
from .smooth import smooth_wave


# a cycle's counts row (adapt_cycle_impl): its width, the columns that
# say what the surface machinery did, and the one that holds the live
# updates the cycle's surface lists held (ops/surflist; 0 where the
# scatters ran full width)
CYCLE_COLS = 11
SURF_COLS = {"bsplit": 8, "hveto": 9, "bmoved": 10}
LISTED_COL = 7


def surface_scatter_width(capT: int, insert: bool = True,
                          smooth: bool = True, hausd=None) -> int:
    """The indices a cycle's surface scatters have at full width on a
    mesh of ``capT`` rows, the ones a cycle may skip (the boundary tags
    after a collapse that changed no surface, the second form of a mesh
    with no curved patch) included: what ``counts[LISTED_COL]`` is a
    share of.  12 ``capT`` each for the vertex normals, the ridge
    tangents (two ends of 6 ``capT`` edge rows), the boundary tags and
    the smoother's surface sums, 4 ``capT`` for the second form."""
    n = 0
    if insert:
        n += 12 + (24 if hausd is not None else 0)
    if smooth:
        n += 12 + (4 if hausd is not None else 0)
    return n * capT


@dataclass
class AdaptStats:
    nsplit: int = 0
    ncollapse: int = 0
    nswap: int = 0
    nmoved: int = 0
    cycles: int = 0
    regrows: int = 0
    # what the surface machinery did (``SURF_COLS``; published as the
    # ``surf.*`` counters): splits of boundary edges, collapse
    # candidates the hausd test refused, surface vertices smoothing moved
    nbsplit: int = 0
    nhveto: int = 0
    nbmoved: int = 0
    # the live updates the cycles' surface lists held, and the indices
    # the same scatters have at full width (``surface_scatter_width`` a
    # cycle that listed); both 0 where no program listed
    nlisted: int = 0
    nlist_full: int = 0
    # PMMG_SUCCESS unless the run degraded (failed_handling contract:
    # PMMG_LOWFAILURE = something failed but a conforming mesh is saved)
    status: int = 0
    # quiet-group scheduler instrumentation (parallel/sched.py via the
    # grouped paths): chunked group-block dispatches executed / skipped
    # by the scheduler, group-block slots skipped, and the free-form
    # extra dict (active-group trajectories + pipeline segment seconds)
    # that scripts/scale_big.py surfaces in its artifacts
    group_dispatches: int = 0
    group_dispatches_saved: int = 0
    groups_skipped: int = 0
    sched_extra: dict = field(default_factory=dict)
    # serving-mode tenant isolation (serve/): stats carrying DIFFERENT
    # tenant ids refuse to merge (a per-tenant SLO must never silently
    # aggregate across tenants), and merging a tenant-tagged stats into
    # an untagged aggregate namespaces its sched_extra/timer keys under
    # "tenant:<id>/" so trajectories and segment seconds stay separable
    tenant: str | None = None

    def __iadd__(self, other):
        if (self.tenant is not None and other.tenant is not None
                and self.tenant != other.tenant):
            raise ValueError(
                f"refusing to merge AdaptStats across tenants "
                f"({self.tenant!r} += {other.tenant!r}); aggregate into "
                "an untagged AdaptStats instead")
        self.nsplit += other.nsplit
        self.ncollapse += other.ncollapse
        self.nswap += other.nswap
        self.nmoved += other.nmoved
        self.cycles += other.cycles
        self.regrows += other.regrows
        self.add_surface(other.nbsplit, other.nhveto, other.nbmoved,
                         other.nlisted, other.nlist_full)
        self.status = max(self.status, other.status)
        self.group_dispatches += other.group_dispatches
        self.group_dispatches_saved += other.group_dispatches_saved
        self.groups_skipped += other.groups_skipped
        pre = f"tenant:{other.tenant}/" \
            if self.tenant is None and other.tenant is not None else ""
        for k, v in other.sched_extra.items():
            kk = k if k.startswith("tenant:") else pre + k
            if isinstance(v, list):
                self.sched_extra.setdefault(kk, []).extend(v)
            else:
                self.sched_extra[kk] = self.sched_extra.get(kk, 0.0) + v
        return self

    def add_surface(self, bsplit=0, hveto=0, bmoved=0, listed=0,
                    list_full=0) -> None:
        self.nbsplit += bsplit
        self.nhveto += hveto
        self.nbmoved += bmoved
        self.nlisted += listed
        self.nlist_full += list_full

    def publish(self, registry=None) -> None:
        """Publish the counters into the obs metrics registry
        (obs/metrics.py): tenant-tagged stats land as tenant-namespaced
        series, the same ``tenant:<id>/`` convention as sched_extra.
        The cross-tenant isolation contract stays in ``__iadd__``."""
        from ..obs.metrics import publish_stats
        publish_stats(self, registry)


def adapt_cycle_impl(mesh: Mesh, met: jax.Array, wave: jax.Array,
                     do_swap: bool = True, do_smooth: bool = True,
                     smooth_waves: int = 1, do_insert: bool = True,
                     hausd: float | None = None,
                     budget_div: int = 8,
                     prescreen: bool = True, active=None,
                     surf_list: bool | None = None):
    """One adaptation cycle: split -> collapse -> [swap] -> [smooth].

    Pure jittable function (jitted wrapper below) — also the compile-check
    entry point exposed by ``__graft_entry__.entry``.

    Adjacency is rebuilt only where a consumer needs it (it is the most
    expensive primitive of the cycle, ~42 ms at bench shapes): swap23
    (face pairing) is the ONLY adja reader — split/collapse/edge-swaps/
    smooth run off the edge table or tets alone (collapse transfers dying
    tets' face tags with a keyed face join instead of the old adja
    lookup).  The rebuild at the end of the cycle keeps the
    every-returned-mesh-has-valid-adja contract.

    ``do_swap`` and ``prescreen`` are Python bools where the caller
    wants them compiled in or out (the jitted ``adapt_cycle`` below), or
    traced scalar bools: the swap arm then sits under ``lax.cond`` and
    the split prescreen under a mask, so one compiled program serves
    every (swap, prescreen) cycle class — the grouped and SPMD cycle
    blocks, where each class would otherwise be a compile of its own.

    Returns (mesh, met, counts) with ``counts`` = int32
    [nsplit, ncollapse, nswap, nmoved, overflow, live_tets, deferred,
    listed, bsplit, hveto, bmoved] stacked in ONE device array
    (``SURF_COLS``: of the splits those of boundary edges, the collapse
    candidates the hausd test refused, of the moves those of surface
    vertices — what the surface machinery did): the host reads all
    per-cycle counters with a single transfer (each separate scalar pull
    costs a full round trip on a remote-device transport, and an *eager*
    count op on the host would fight the donated input buffers).
    ``deferred`` = top-K budget cuts of viable candidates, encoded as
    2 bits: bit 0 = an INSERTION wave (split/collapse) deferred,
    bit 1 = a SWAP wave deferred; it has no reader (ROADMAP D4), and
    the row keeps its layout because ``SURF_COLS`` and ``LISTED_COL``
    index it.  ``listed`` (``LISTED_COL``): the live updates the cycle's
    surface lists held.

    ``surf_list``: whether the surface scatters (vertex normals, ridge
    tangents, boundary tags, the smoother's surface sums and second
    form) run over lists of their live updates (ops/surflist).  None
    observes it: they do where the program is placed on a TPU.  Static;
    the outputs are the full-width scatters' either way.

    ``active``: optional traced scalar bool — the device-resident
    quiet-mask hook of the grouped paths (parallel/sched.py).  When
    given, the WHOLE cycle is wrapped in ``lax.cond``: an inactive
    group slot returns its state unchanged with zero op counts (live
    count still reported), so a ``lax.map`` group body skips the
    split/collapse/swap/smooth wave math for slots the scheduler
    already proved quiet — exact by the frozen-seam + deterministic-
    wave fixed-point argument (re-running any weaker-or-equal block on
    a zero-op state is byte-identity, so returning the input IS the
    recompute).  ``active=None`` compiles the unconditional body — the
    whole-mesh path is untouched.
    """
    from .adjacency import boundary_edge_tags
    if active is not None:
        def _run(ops):
            m, k = ops
            return adapt_cycle_impl(
                m, k, wave, do_swap=do_swap, do_smooth=do_smooth,
                smooth_waves=smooth_waves, do_insert=do_insert,
                hausd=hausd, budget_div=budget_div,
                prescreen=prescreen, surf_list=surf_list)

        def _skip(ops):
            m, k = ops
            counts = jnp.zeros(CYCLE_COLS, jnp.int32).at[5].set(
                jnp.sum(m.tmask, dtype=jnp.int32))
            return m, k, counts
        return jax.lax.cond(active, _run, _skip, (mesh, met))
    from . import surflist
    lists = surflist.Tally(surf_list)
    defer = jnp.zeros((), bool)
    defer_sw = jnp.zeros((), bool)
    if do_insert:
        # ONE edge table + metric lengths serve both split and collapse
        # (the tables are a measured wave hot spot); the collapse defers
        # candidates whose table rows the split made stale
        from .edges import unique_edges, edge_lengths
        # slim table: split/collapse never read shell3 (only the swap
        # kernels, which build their own) — skips a [6*capT] scatter
        with otrace.scope("cyc.table"):
            et = unique_edges(mesh, shell_slots=0)
            lens = edge_lengths(mesh, et, met)
        # ridge tangents once per cycle too (same sharing rationale;
        # collapse only consults non-stale candidates, whose tangent
        # fields are identical pre/post split)
        # the vertex normals likewise: a collapse candidate has no
        # endpoint in a tet the split touched, so its endpoints' fans,
        # and the sums over them, are the pre-split mesh's
        vtan0 = vn0 = None
        if hausd is not None:
            from .analysis import boundary_vertex_normals, \
                ridge_vertex_tangents
            with otrace.scope("cyc.normals"):
                vtan0 = ridge_vertex_tangents(mesh, et=et, lists=lists)
                vn0 = boundary_vertex_normals(mesh, lists=lists)
        # ``prescreen=False`` (adapt_mesh's wide convergence check, the
        # drivers' polish cycles) disables the approximate nomination
        # prescreen so shells it over-vetoed get one exact
        # re-evaluation before convergence is accepted (split.py)
        with otrace.scope("cyc.split"):
            res = split_wave(mesh, met, hausd=hausd,
                             budget_div=budget_div,
                             et=et, lens=lens, vtan=vtan0, vn=vn0,
                             prescreen=prescreen)
        mesh, met = res.mesh, res.met
        nsplit, overflow = res.nsplit, res.overflow
        nbsplit = res.nbdy
        defer = defer | res.deferred

        with otrace.scope("cyc.collapse"):
            col = collapse_wave(mesh, met, hausd=hausd,
                                budget_div=budget_div,
                                et=et, lens=lens,
                                stale_tets=res.modified, vtan=vtan0,
                                vn=vn0)
        defer = defer | col.deferred
        # collapse rewires the surface (dying tets' face tags transfer to
        # the surviving neighbors); re-propagate MG_BDY from faces to
        # their edges and vertices so later splits/smooth treat the new
        # surface entities as boundary — without this, untagged surface
        # midpoints become "movable" and smoothing dents the surface.
        # Skipped when no dying tet donated tags (interior collapses):
        # the propagation pass costs a [12*capT]-index scatter
        with otrace.scope("cyc.bdytags"):
            if lists.on:
                mesh, ntags = jax.lax.cond(
                    col.surface_changed,
                    partial(surflist.counted, boundary_edge_tags),
                    lambda m: (m, jnp.zeros((), jnp.int32)), col.mesh)
                lists.note(ntags)
            else:
                mesh = jax.lax.cond(
                    col.surface_changed,
                    partial(boundary_edge_tags, lists=lists),
                    lambda m: m, col.mesh)
        ncol, nhveto = col.ncollapse, col.nhveto
    else:
        # -noinsert: no point insertion or deletion (Mmg contract)
        nsplit = nbsplit = jnp.zeros((), jnp.int32)
        ncol = nhveto = jnp.zeros((), jnp.int32)
        overflow = jnp.zeros((), bool)

    nswap = jnp.zeros((), jnp.int32)

    def _swap(mesh):
        from .swap import swap_facesort_enabled
        with otrace.scope("cyc.swap_edges"):
            sew = swap_edges_wave(mesh, met, hausd=hausd,
                                  budget_div=budget_div)  # 3-2 + 2-2
        with otrace.scope("cyc.swap23"):
            if swap_facesort_enabled():
                # swap23 pairs directly off the face sort (bit-identical
                # to the adja path — ops/swap._pair_fields_facesort); the
                # [capT,4] adja materialization + compare leaves the
                # cycle interior, the rebuild at the end restores the
                # adja contract
                s23 = swap23_wave(sew.mesh, met, budget_div=budget_div,
                                  facesort=True)
            else:
                # consumed by swap23
                mesh = build_adjacency(sew.mesh)
                s23 = swap23_wave(mesh, met, budget_div=budget_div)
        return (s23.mesh, sew.nswap + s23.nswap,
                sew.deferred | s23.deferred)

    if isinstance(do_swap, (bool, np.bool_)):
        if do_swap:
            mesh, nswap, defer_sw = _swap(mesh)
    else:
        # traced switch: the swap arm sits in the one compiled program
        # and a cycle that is not swap-inclusive skips it at run time
        mesh, nswap, defer_sw = jax.lax.cond(
            do_swap, _swap, lambda m: (m, nswap, defer_sw), mesh)

    nmoved = jnp.zeros((2,), jnp.int32)      # [all, of them surface]
    if do_smooth:
        for w in range(smooth_waves):
            with otrace.scope("cyc.smooth"):
                sm = smooth_wave(mesh, met, wave=wave * smooth_waves + w,
                                 hausd=hausd, lists=lists)
            mesh = sm.mesh
            nmoved = nmoved + jnp.stack([sm.nmoved, sm.nbdy])

    with otrace.scope("cyc.adjacency"):
        mesh = build_adjacency(mesh)

    return mesh, met, jnp.stack([
        nsplit, ncol, nswap, nmoved[0],
        overflow.astype(jnp.int32),
        jnp.sum(mesh.tmask, dtype=jnp.int32),
        defer.astype(jnp.int32) + 2 * defer_sw.astype(jnp.int32),
        jnp.asarray(lists.listed, jnp.int32),
        nbsplit, nhveto, nmoved[1]])


from ..utils.compilecache import governed as _governed  # noqa: E402

adapt_cycle = _governed("adapt.cycle")(
    partial(jax.jit, static_argnames=(
        "do_swap", "do_smooth", "smooth_waves", "do_insert",
        "hausd", "budget_div", "prescreen", "surf_list"),
        donate_argnums=(0, 1))(adapt_cycle_impl))


def fem_pass_impl(mesh: Mesh, met: jax.Array, topo=None):
    """One FEM-conformity wave: split interior edges whose endpoints are
    both boundary points (the configuration that lets an element touch
    the boundary with two faces or all four vertices).  This is the
    Mmg fem-mode topology fix the reference forwards per group
    (API_functions_pmmg.c:652-658, default ``info.fem`` ON :413); run
    after the sizing/polish loop until no candidate remains.

    ``topo``: the ``ops/topo_incr.TopoState`` the merged polish ended
    with (driver._finish_run hands it from round to round): the round's
    edge table and its adjacency then come off the sorts the state
    retains, as a polish wave's do (``sliver_polish_impl``), by a merge
    of the rows changed since each table's last derivation, as they are
    where none changed (the round that finds no candidate), or by the
    full sort where nothing is retained or the rows outnumber the widest
    band.  Bit-identical either way (that module's docstring).  Without
    a state the round sorts both in full: the whole-mesh path's, which
    runs on the device, where the retained sort loses (PERF.md section 6,
    PR 38).

    Returns (mesh, met, counts[3] = [nsplit, overflow, bsplit]); the
    candidates are interior edges, so ``bsplit`` (splits of boundary
    edges) reads 0 while that holds.  With ``topo``, counts[5]: then
    ``tab``, the tables the round derived (2), and ``inc``, those of
    them taken off the retained sort; the state is the last result."""
    from .adjacency import boundary_edge_tags
    et = None           # the split wave then builds its own
    if topo is not None:
        from .topo_incr import (incr_build_adjacency, incr_unique_edges,
                                mark_dirty, polish_bands)
        band = polish_bands(mesh.capT)
    with otrace.scope("fem.split"):
        if topo is not None:
            # shell_slots: what split_wave's own ``unique_edges`` asks for
            et, topo, emerged = incr_unique_edges(
                mesh, topo, shell_slots=3, band=band)
        res = split_wave(mesh, met, fem_only=True, budget_div=2, et=et)
        if topo is not None:
            topo = mark_dirty(topo, mesh.tet, mesh.tmask, res.mesh)
    with otrace.scope("fem.bdytags"):
        mesh = boundary_edge_tags(res.mesh)
    with otrace.scope("fem.adjacency"):
        if topo is None:
            mesh = build_adjacency(mesh)
        else:
            mesh, topo, fmerged = incr_build_adjacency(mesh, topo,
                                                       band=band)
    row = [res.nsplit, res.overflow.astype(jnp.int32), res.nbdy]
    if topo is None:
        return mesh, res.met, jnp.stack(row)
    inc = emerged.astype(jnp.int32) + fmerged.astype(jnp.int32)
    return mesh, res.met, jnp.stack(row + [jnp.full_like(inc, 2), inc]), topo


# governed so that the ledger keeps the signature it lowered from:
# ``obs.devtime`` reads the round's stages off its executable
fem_pass = _governed("adapt.fem_pass")(
    partial(jax.jit, donate_argnums=(0, 1))(fem_pass_impl))


def sliver_polish_impl(mesh: Mesh, met: jax.Array, wave: jax.Array,
                       sliver_q: float = 0.2, do_collapse: bool = True,
                       do_swap: bool = True, do_smooth: bool = True,
                       hausd: float | None = None, active=None,
                       budget: int | None = None, worklist=None,
                       topo=None):
    """Bad-element optimization pass (MMG3D_opttyp analogue): quality-
    targeted collapses on tets below ``sliver_q``, then swaps and a
    smoothing wave.  Run after the sizing loop converges — length-driven
    waves leave near-degenerate tets whose edges are all 'nice' lengths.
    The do_* switches mirror -noinsert/-noswap/-nomove.

    ``active``: optional traced scalar bool — same device-resident
    quiet-mask hook as :func:`adapt_cycle_impl`: an inactive group slot
    (a retired group of the wave-major grouped polish, or a padded tail
    row of a compacted chunk plan) returns its state unchanged with
    zero counts instead of running the collapse/swap/smooth math.

    ``budget``: the top-K candidate budget of each collapse and swap
    wave in ROWS (static).  None keeps the wide divisor, half the
    capacity, which suits a mesh whose capacity follows its content at
    a fixed ratio (the whole-mesh path's 3x, a group's).  A caller
    whose capacity is no measure of its content says what it wants in
    rows (driver.polish_budget for a merged mesh).

    A stage runs only when the wave can see that it has an input.  The
    collapse stage (edge table, lengths, normals, tangents, candidacy)
    is ``lax.cond``-skipped when no live tet is under ``sliver_q``: its
    candidates are edges of such tets, so it could apply and veto
    nothing.  The exit ``build_adjacency`` is skipped when ``swap23``
    applied no swap: only ``swap23`` and the smoothing wave (coordinates
    alone) run after the build ``swap23`` consumed, so that adjacency is
    still the mesh's (with the swaps off, or ``swap23`` paired off the
    face sort, the wave built none: the exit builds it).  Both are exact,
    and ``mesh.adja`` is valid on return either way.

    ``worklist``: an ``ops/worklist.PolishList`` (``all_dirty`` before
    the first wave), carried from wave to wave by a caller that runs
    several on one mesh: the ring and edge swap kernels then judge only
    the candidates whose shell changed since they last looked, and the
    wave hands the list back as a third result.  Exact: mesh and counts
    are what the wave gives without one.

    ``topo``: an ``ops/topo_incr.TopoState`` (``topo_init`` before the
    first wave), carried like the worklist: every table the wave derives
    (the collapse stage's edge table, the edge swaps' and the ring swaps',
    ``swap23``'s adjacency and the exit adjacency) then comes off the
    edge and face sorts the state retains, by a merge of the rows the
    stages dirtied since the table's last derivation, or by the full sort
    where there is no retained sort yet or the dirty rows outnumber the
    widest band (``topo_incr.polish_bands``).  Bit-identical either way
    (that module's docstring); the counts row gains two columns and the
    state is handed back as the last result.

    Returns (mesh, counts[11] = [ncollapse, nswap, nmoved, live_tets,
    hveto, bmoved, bad, col, adj, cand, wl]): ``hveto``, ``bmoved`` as
    in a cycle's ``SURF_COLS``; ``bad`` the live tets under ``sliver_q``
    at entry (0 with ``do_collapse`` off: not counted), ``col`` 1 when
    the collapse stage ran, ``adj`` 1 when the exit adjacency was
    rebuilt; ``cand`` the candidate rows the two kernels' top-K selected
    and ``wl`` those of them on the list (both 0 without a worklist: not
    counted).  With ``topo``, counts[13]: then ``tab``, the tables the
    wave derived, and ``inc``, those of them taken off the retained sort
    (merge or reuse) and not by a full sort.
    """
    from .adjacency import boundary_edge_tags
    from . import worklist as wlist
    if active is not None:
        if worklist is not None or topo is not None:
            raise ValueError("a worklist and a retained sort ride one mesh"
                             " from wave to wave: not under the quiet mask")

        def _run(m):
            return sliver_polish_impl(
                m, met, wave, sliver_q=sliver_q,
                do_collapse=do_collapse, do_swap=do_swap,
                do_smooth=do_smooth, hausd=hausd, budget=budget)

        def _skip(m):
            counts = jnp.zeros(11, jnp.int32).at[3].set(
                jnp.sum(m.tmask, dtype=jnp.int32))
            return m, counts
        return jax.lax.cond(active, _run, _skip, mesh)
    zero = jnp.zeros((), jnp.int32)
    ncol = nhveto = nbad = nswap = nmoved = nbmoved = ncand = nlist = zero
    rebuild = None      # traced bool once the wave holds an adjacency
    wl = worklist
    # [tab, inc], counted with ``topo`` only
    tables = None if topo is None else jnp.zeros(2, jnp.int32)

    # the bookkeeping between the stages (the worklist's diffs, the dirty
    # masks of the retained sorts) is a phase of its own: ``pol.list``
    def note(wl, before, after):
        if wl is None:
            return None
        with otrace.scope("pol.list"):
            return wlist.noted(wl, before, after)

    if topo is None:
        def edge_table(m, tp, tables, slots):
            return None, tp, tables     # the kernel builds its own

        def adjacency(m, tp, tables):
            return build_adjacency(m), tp, tables

        def dirtied(tp, before, after):
            return tp
    else:
        from .topo_incr import (incr_build_adjacency, incr_unique_edges,
                                mark_dirty, polish_bands)
        band = polish_bands(mesh.capT)

        def derived(tables, merged):
            return tables + jnp.stack([1, merged.astype(jnp.int32)])

        def edge_table(m, tp, tables, slots):
            et, tp, merged = incr_unique_edges(m, tp, shell_slots=slots,
                                               band=band)
            return et, tp, derived(tables, merged)

        def adjacency(m, tp, tables):
            m, tp, merged = incr_build_adjacency(m, tp, band=band)
            return m, tp, derived(tables, merged)

        def dirtied(tp, before, after):
            # the sorts carry keys of (tet, tmask) alone: a stage that
            # moves vertices or sets tags dirties nothing
            with otrace.scope("pol.list"):
                return mark_dirty(tp, before.tet, before.tmask, after)

    if do_collapse:
        from .quality import quality_from_points

        def _collapse(ops):
            # the polish widens the compaction budget (budget_div=2, or
            # the caller's ``budget`` in rows) so the quality pass covers
            # the full sliver population instead of the worst K only.
            # The budget is meant in rows of CONTENT: 1.5x the live tets
            # on a mesh at 3x; dead rows are never candidates
            m, tp, tables = ops
            et, tp, tables = edge_table(m, tp, tables, 3)
            col = collapse_wave(m, met, sliver_q=sliver_q, hausd=hausd,
                                budget_div=2, budget=budget, q_tet=q_tet,
                                et=et)
            m = jax.lax.cond(col.surface_changed, boundary_edge_tags,
                             lambda m: m, col.mesh)
            return (m, tp, tables), col.ncollapse, col.nhveto

        before = mesh
        with otrace.scope("pol.collapse"):
            q_tet = quality_from_points(
                mesh.vert[mesh.tet],
                None if met.ndim == 1 else met[mesh.tet])
            nbad = jnp.sum(mesh.tmask & (q_tet < sliver_q),
                           dtype=jnp.int32)
            (mesh, topo, tables), ncol, nhveto = jax.lax.cond(
                nbad > 0, _collapse, lambda ops: (ops, zero, zero),
                (mesh, topo, tables))
        wl = note(wl, before, mesh)
        topo = dirtied(topo, before, mesh)
    if do_swap:
        from .swapgen import swapgen_wave, RING_MAX
        from .swap import swap_facesort_enabled
        with otrace.scope("pol.swap_edges"):
            et, topo, tables = edge_table(mesh, topo, tables, 3)
            sew = swap_edges_wave(
                mesh, met, hausd=hausd, budget_div=2,
                budget=budget,      # 3-2 + 2-2
                worklist=None if wl is None else wl.edges, et=et)
        if wl is not None:
            wl = note(wl._replace(edges=wlist.looked(wl.edges, sew.keep)),
                      mesh, sew.mesh)
        topo = dirtied(topo, mesh, sew.mesh)
        # generalized degree 4-6 ring swaps: the worst surviving tets
        # are typically gate-limited for every lower-degree op — this
        # is the class that lifts the min past the 3-2/2-3 plateau
        with otrace.scope("pol.swapgen"):
            et, topo, tables = edge_table(sew.mesh, topo, tables,
                                          RING_MAX)
            sgn = swapgen_wave(
                sew.mesh, met, budget_div=2, budget=budget,
                worklist=None if wl is None else wl.rings, et=et)
        if wl is not None:
            wl = note(wl._replace(rings=wlist.looked(wl.rings, sgn.keep)),
                      sew.mesh, sgn.mesh)
            ncand, nlist = sew.ncand + sgn.ncand, sew.nlist + sgn.nlist
        topo = dirtied(topo, sew.mesh, sgn.mesh)
        with otrace.scope("pol.swap23"):
            if swap_facesort_enabled():
                mesh = sgn.mesh
                s23 = swap23_wave(mesh, met, budget_div=2, budget=budget,
                                  facesort=True)
            else:
                # consumed by swap23
                mesh, topo, tables = adjacency(sgn.mesh, topo, tables)
                s23 = swap23_wave(mesh, met, budget_div=2, budget=budget)
                # without a winner swap23 hands back the mesh it was
                # given, adjacency and all
                rebuild = s23.nswap > 0
        topo = dirtied(topo, mesh, s23.mesh)
        mesh = s23.mesh
        nswap = sew.nswap + sgn.nswap + s23.nswap
    after_rings = sgn.mesh if do_swap else mesh
    if do_smooth:
        # optimal-position mode: sliver-ball vertices ascend the height
        # of their worst incident tet instead of chasing the centroid
        with otrace.scope("pol.smooth"):
            sm = smooth_wave(mesh, met, wave=wave, opt_q=sliver_q,
                             hausd=hausd)
        mesh = sm.mesh
        nmoved, nbmoved = sm.nmoved, sm.nbdy
    with otrace.scope("pol.adjacency"):
        if rebuild is None:                         # exit contract
            mesh, topo, tables = adjacency(mesh, topo, tables)
            rebuild = jnp.ones((), bool)
        else:
            mesh, topo, tables = jax.lax.cond(
                rebuild, lambda ops: adjacency(*ops), lambda ops: ops,
                (mesh, topo, tables))
    row = [ncol, nswap, nmoved, jnp.sum(mesh.tmask, dtype=jnp.int32),
           nhveto, nbmoved, nbad, (nbad > 0).astype(jnp.int32),
           rebuild.astype(jnp.int32), ncand, nlist]
    if topo is not None:
        row += [tables[0], tables[1]]
    out = (mesh, jnp.stack(row))
    if worklist is not None:
        # swap23, the smoothing and the adjacencies' boundary tags, in one
        out += (note(wl, after_rings, mesh),)
    return out if topo is None else out + (topo,)


sliver_polish = _governed("adapt.sliver_polish")(
    partial(jax.jit, static_argnames=(
        "sliver_q", "do_collapse", "do_swap", "do_smooth", "hausd",
        "budget"), donate_argnums=(0,))(sliver_polish_impl))


def grow_mesh_met(mesh: Mesh, met, newP: int, newT: int):
    """Grow capacities, carrying the metric through compact()'s permutation."""
    vperm = np.argsort(~np.asarray(mesh.vmask), kind="stable")
    meth = np.zeros((newP,) + met.shape[1:], np.asarray(met).dtype)
    meth[: mesh.capP] = np.asarray(met)[vperm]
    mesh = with_capacity(mesh, newP, newT)
    return mesh, jnp.asarray(meth)


def adapt_mesh(mesh: Mesh, met: jax.Array, max_cycles: int = 50,
               verbose: int = 0, headroom: float = 0.85,
               swap_every: int = 3, noinsert: bool = False,
               noswap: bool = False, nomove: bool = False,
               angedg: float | None = None,
               hausd: float | None = None) -> tuple:
    """Host driver: run cycles until no topological change, manage capacity.

    Swap waves cost about as much as split+collapse+smooth combined (they
    re-derive the edge table and adjacency twice), so they run every
    ``swap_every``-th cycle — like Mmg, which interleaves swap/move passes
    between sizing passes rather than swapping continuously — and always
    once the mesh is near convergence.

    One dispatch and one counter pull per cycle.

    Returns (mesh, met, AdaptStats).
    """
    stats = AdaptStats()
    from .analysis import analyze_mesh
    from ..core.constants import ANGEDG
    # honor the caller's ridge-detection threshold (-ar / -nr): a default
    # re-analysis here would re-introduce MG_GEO tags the user disabled
    mesh = analyze_mesh(mesh, ANGEDG if angedg is None else angedg).mesh
    quiet = 0
    wide_check = False
    converged = False
    cycle = 0
    while cycle < max_cycles and not converged:
        # capacity management before the cycle (a cycle can add up to
        # 2*capT/8 tets; the overflow flag + regrow below catches a
        # shortfall, winners are only deferred)
        n_p, n_t = mesh.np_counts()
        if n_p > headroom * mesh.capP or n_t > headroom * mesh.capT:
            mesh, met = grow_mesh_met(mesh, met,
                                      max(mesh.capP, int(2 * n_p)),
                                      max(mesh.capT, int(2 * n_t)))
            stats.regrows += 1

        was_wide = wide_check
        # quiet > 0 forces a swap-inclusive cycle (convergence
        # confirmation); the wide check runs at a quarter of the
        # divisor with the split prescreen off
        do_swap = ((cycle % swap_every == swap_every - 1)
                   or quiet > 0) and not noswap
        mesh, met, counts = adapt_cycle(
            mesh, met, jnp.asarray(cycle, jnp.int32), do_swap=do_swap,
            do_smooth=not nomove, do_insert=not noinsert, hausd=hausd,
            budget_div=2 if wide_check else 8,
            prescreen=not wide_check)
        cnt = np.asarray(counts)
        ns, nc, nw, nm, ovf = (int(v) for v in cnt[:5])
        listed = int(cnt[LISTED_COL])
        stats.add_surface(
            **{k: int(cnt[col]) for k, col in SURF_COLS.items()},
            listed=listed, list_full=surface_scatter_width(
                mesh.capT, not noinsert, not nomove, hausd
            ) if listed else 0)
        stats.nsplit += ns
        stats.ncollapse += nc
        stats.nswap += nw
        stats.nmoved += nm
        stats.cycles += 1
        otrace.log(3, f"  cycle {cycle:3d}: split {ns:6d} "
                      f"collapse {nc:6d} swap {nw:6d} move {nm:6d}",
                   verbose=verbose)
        cycle += 1
        if ovf:
            # a capacity-truncated cycle cannot witness convergence
            # (its winner set was cut, not exhausted) — reset the
            # quiet state and regrow
            quiet = 0
            wide_check = False
            mesh, met = grow_mesh_met(mesh, met, 2 * mesh.capP,
                                      2 * mesh.capT)
            stats.regrows += 1
            continue
        if ns == 0 and nc == 0 and (noswap or (nw == 0 and do_swap)):
            quiet += 1
            if quiet >= 2 or nm == 0 or nomove:
                if was_wide or (noinsert and noswap):
                    # (with insertions AND swaps disabled no budget-
                    # governed op runs — a wide cycle cannot differ)
                    converged = True
                    continue
                # Verify convergence at a wider candidate budget
                # before accepting it: with top-K compaction,
                # candidates that permanently fail the
                # post-compaction geometric gates (worst shell
                # quality = always selected) can pin every budget
                # slot while viable candidates ranked past K are
                # never attempted — counts==0 would then be
                # starvation, not convergence.
                wide_check = True
                quiet = 1
        elif ns == 0 and nc == 0 and not do_swap and not noswap:
            quiet = max(quiet, 1)    # trigger a swap-inclusive cycle
        else:
            quiet = 0
            wide_check = False

    # bad-element optimization: the sizing loop leaves slivers whose edge
    # lengths are all in-range; polish until no sliver op applies
    if noinsert and noswap and nomove:
        return mesh, met, stats
    for w in range(4):
        mesh, counts = sliver_polish(mesh, met,
                                     jnp.asarray(1000 + w, jnp.int32),
                                     do_collapse=not noinsert,
                                     do_swap=not noswap,
                                     do_smooth=not nomove, hausd=hausd)
        nc, nw, nm, _, nhv, nbm = (int(v) for v in np.asarray(counts)[:6])
        stats.add_surface(hveto=nhv, bmoved=nbm)
        stats.ncollapse += nc
        stats.nswap += nw
        stats.nmoved += nm
        otrace.log(3, f"  polish {w}: collapse {nc:5d} swap {nw:5d} "
                      f"move {nm:5d}", verbose=verbose)
        if nc == 0 and nw == 0:
            break
    return mesh, met, stats
