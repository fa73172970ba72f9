"""The adaptation loop driver — PMMG_parmmglib1 analogue.

Reference flow (/root/reference/src/libparmmg1.c:550-1011): split into
groups, then per iteration: snapshot background groups, run the sequential
remesher per group with frozen interfaces, interpolate metric+fields from
the background, load-balance (split/migrate/regroup).  Here:

- single device: the whole mesh is one batched remesh operator
  (ops/adapt.py), no groups needed — the degenerate nprocs=1/ngrp=1 path
  of the reference collapses to one call;
- multi device: partition -> freeze interfaces -> SPMD waves under
  ``shard_map`` -> merge, re-partitioned every outer iteration so frozen
  interfaces land in shard interiors next time (the role of the
  ifc-displacement / graph repartitioning of loadbalancing_pmmg.c:44-161);
- fields/metric are interpolated from the ORIGINAL mesh once at the end
  (background-mesh localization, interpmesh_pmmg.c semantics) — chaining
  per-iteration interpolations only accumulates error when the background
  never changes identity.
"""
from __future__ import annotations

import numpy as np

from .core import constants as C
from .core.mesh import Mesh, mesh_to_host
from .ops.adapt import adapt_mesh, AdaptStats
from .ops.metric import metric_hsiz, metric_optim, clamp_metric, gradation
from .utils.compilecache import LEDGER


def _auto_hmin_hmax(vert: np.ndarray, info) -> tuple[float, float]:
    """Default size bounds from the bounding box (Mmg scaleMesh
    semantics: hmin/hmax resolved against the mesh scale when unset)."""
    lo, hi = vert.min(axis=0), vert.max(axis=0)
    diag = float(np.linalg.norm(hi - lo))
    hmin = info.hmin if info.hmin > 0 else 1e-3 * diag
    hmax = info.hmax if info.hmax > 0 else 2.0 * diag
    return hmin, hmax


def surface_census(mesh: Mesh) -> dict:
    """What the analysis found of the surface, as counts for its span:
    boundary faces and ridge edges of the analysed mesh (host arrays)."""
    tm = np.asarray(mesh.tmask)
    bdy = (np.asarray(mesh.ftag)[tm] & C.MG_BDY) != 0
    geo = (np.asarray(mesh.etag)[tm] & C.MG_GEO) != 0
    ridge = np.sort(np.asarray(mesh.tet)[tm][:, C.IARE][geo], axis=1)
    return {"bdy_faces": int(bdy.sum()),
            "ridges": len(np.unique(ridge, axis=0))}


def build_metric(mesh: Mesh, met, info, census: dict | None = None):
    """Metric synthesis path: -hsiz / -optim / user metric / default.
    ``census``, if given, receives ``bound_verts``, how many vertices'
    sizes or tensors the hausd bound changed, and what the bound itself
    reports (``bdy_verts``, how many regular boundary vertices it
    examined, and ``kappa_max``)."""
    import jax.numpy as jnp

    vert = np.asarray(mesh.vert)[np.asarray(mesh.vmask)]
    hmin, hmax = _auto_hmin_hmax(vert, info)
    if info.hsiz > 0:
        met = metric_hsiz(mesh, info.hsiz)
    elif met is None or info.optim or info.optimLES:
        met = metric_optim(mesh)
    met = clamp_metric(met, hmin, hmax)
    # surface-approximation size bound (Mmg defsiz -hausd route): chord
    # deviation under hausd needs h <= sqrt(8*hausd/kappa) on curved
    # boundary regions.  Requires ridge detection: without MG_GEO tags a
    # sharp edge is indistinguishable from smooth curvature and the
    # curvature estimate blows up at corners
    if info.hausd > 0 and info.angle_detection:
        from .obs import trace as otrace
        from .ops.metric import hausd_metric_bound
        seen = census if census is not None else {}
        with otrace.span("hausd bound") as sp:
            bounded = hausd_metric_bound(mesh, met, info.hausd, hmin,
                                         hmax, census=seen)
            changed = np.asarray(bounded) != np.asarray(met)
            if changed.ndim == 2:
                changed = changed.any(axis=-1)
            seen["bound_verts"] = int(np.sum(
                changed & np.asarray(mesh.vmask)))
            sp.set(**seen)
        met = bounded
    # local bounds BEFORE gradation (Mmg defsiz-then-gradsiz order) so the
    # size jump at a ref-patch boundary is smoothed by -hgrad; re-applied
    # after, since gradation only propagates smaller sizes and may pull a
    # patch below its local hmin
    if info.local_params:
        met = apply_local_params(mesh, met, info)
    if info.hgrad > 0 and met.ndim == 1:
        met = gradation(mesh, met, hgrad=info.hgrad)
        # gradation only propagates smaller sizes and may pull a patch
        # below its local hmin: re-apply the clamp (iso path only — the
        # second pass is pointless when nothing changed met)
        if info.local_params:
            met = apply_local_params(mesh, met, info)
    return met


def apply_local_params(mesh: Mesh, met, info):
    """Per-reference size bounds (MMG3D_Set_localParameter / parsop file,
    forwarded by the reference per group): vertices of the entities
    carrying reference ``ref`` get their size clamped to the local
    [hmin, hmax].  Entity kinds: 1 = triangles (surface ref patch),
    2 = tetrahedra (volume sub-domain), 3 = edges (user edge list,
    staged in ``info._user_edges`` by the API build), 0 = vertices (by
    point ref).  Per-entity hausd applies conservatively as the global
    minimum (parmmg_run); local hausd relaxation above the global value
    is not honored (documented divergence).  Iso: direct clamp; aniso:
    eigenvalue clamp of the tensor (h = 1/sqrt(lambda))."""
    import jax.numpy as jnp
    from .core.constants import IDIR, MG_BDY

    ftag = np.asarray(mesh.ftag)
    fref = np.asarray(mesh.fref)
    tet = np.asarray(mesh.tet)
    tmask = np.asarray(mesh.tmask)
    tref = np.asarray(mesh.tref)
    meth = np.array(np.asarray(met), copy=True)
    for typ, ref, lhmin, lhmax, _hausd in info.local_params:
        if typ == 1:          # triangle locals: surface reference patch
            sel_f = ((ftag & MG_BDY) != 0) & (fref == ref) & tmask[:, None]
            vids = np.unique(np.concatenate(
                [tet[sel_f[:, f]][:, IDIR[f]].reshape(-1)
                 for f in range(4)]
            )) if sel_f.any() else np.zeros(0, np.int64)
        elif typ == 2:        # tetrahedron locals: volume sub-domain
            sel_t = tmask & (tref == ref)
            vids = np.unique(tet[sel_t].reshape(-1)) if sel_t.any() \
                else np.zeros(0, np.int64)
        elif typ == 3:        # edge locals: user edges with this ref
            ue, uref = getattr(info, "_user_edges", (None, None))
            if ue is None:
                continue
            sel_e = uref == ref
            vids = np.unique(ue[sel_e].reshape(-1)) if sel_e.any() \
                else np.zeros(0, np.int64)
        elif typ == 0:        # vertex locals: points with this ref
            vm = np.asarray(mesh.vmask)
            vrf = np.asarray(mesh.vref)
            vids = np.where(vm & (vrf == ref))[0]
        else:
            continue
        if not len(vids):
            continue
        if meth.ndim == 1:
            meth[vids] = np.clip(meth[vids], lhmin, lhmax)
        else:
            from .ops.quality import unpack_sym
            m = np.asarray(unpack_sym(jnp.asarray(meth[vids])))
            w, v = np.linalg.eigh(m)
            w = np.clip(w, 1.0 / lhmax ** 2, 1.0 / lhmin ** 2)
            full = np.einsum("nij,nj,nkj->nik", v, w, v)
            meth[vids] = full[:, [0, 0, 0, 1, 1, 2], [0, 1, 2, 1, 2, 2]]
    return jnp.asarray(meth)


def parmmg_run(pm) -> tuple[Mesh, object, AdaptStats]:
    """Run the full adaptation per the staged ParMesh. Returns
    (adapted core Mesh, metric, stats)."""
    import jax
    from .api.params import check_devices, check_input_data
    from .obs import trace as otrace
    from .utils.timers import LEDGER
    info = pm.info
    check_input_data(info, met_is_aniso=(
        pm.met is not None and getattr(pm.met, "ndim", 1) == 2))
    check_devices(info, len(jax.devices()))
    # telemetry spine: fresh run context (run id + backend tag on every
    # trace record) and the process verbosity = the reference's imprim
    otrace.new_run()
    otrace.set_verbosity(info.imprim)
    LEDGER.install_listener()
    # ``run`` is the root of the job's span tree; every phase below is
    # its child.  PARMMG_PROFILE_DIR holds one capture over all of it
    with otrace.profile_capture(), \
            otrace.span("run", ne_in=int(pm.ne_)) as root:
        mesh, met, stats = _run_phases(pm)
        root.set(ne_out=int(np.asarray(mesh.tmask).sum()),
                 status=int(stats.status))
    return mesh, met, stats


def polish_budget(n_live: int) -> int:
    """Top-K candidate budget, in rows, of each collapse and swap wave
    of the merged polish: 1.5x the live tets the polish starts from,
    which is what the wide divisor gave while a merged mesh was padded
    to 3x.  Stated in rows of content it stays what it is whatever
    capacity ``merge_shards`` chooses, and the result with it."""
    from .ops.edges import wave_budget
    return wave_budget(3 * n_live, 2)


def _merged_polish(mesh, met, info, hausd, stats, tim):
    """Bad-element polish on a MERGED mesh, staged on the host (group
    and shard seams breed slivers): up to eight ``sliver_polish`` waves,
    each ended by the pull of its counts, until one applies no collapse
    and no swap.  The waves hand each other the swap kernels' worklist
    (``ops/worklist``): from the second on, a kernel judges only the
    candidates whose shell changed since it last looked; and the edge
    and face sorts behind their tables (``ops/topo_incr``): from the
    second on, a wave merges the rows the last stage changed into the
    sort it was handed where it sorted the whole mesh.  Returns (mesh,
    collapses + swaps applied, the ``TopoState`` the last wave left):
    the sorts are the mesh's own, less the rows the state marks dirty,
    so the tail's next consumer of whole-mesh tables (``_finish_run``'s
    fem rounds) merges into them where it would sort again."""
    import jax.numpy as jnp
    from .obs import trace as otrace
    from .obs.metrics import REGISTRY
    from .ops.adapt import sliver_polish
    from .ops.topo_incr import topo_init
    from .ops.worklist import all_dirty
    from .utils.placement import host_staging
    ops = col_skipped = adj_skipped = cand_rows = wl_rows = 0
    tables = tables_merged = 0
    # every program of the tail costs what its capacity is, not what its
    # content is: the two counters say how much of it is padding
    n_live = int(np.asarray(mesh.tmask).sum())
    REGISTRY.counter("tail.rows_live").inc(n_live)
    REGISTRY.counter("tail.rows_cap").inc(mesh.capT)
    budget = polish_budget(n_live)
    with tim("bad-element polish"), host_staging():
        worklist = all_dirty(mesh)
        topo = topo_init(mesh.capT)     # nothing retained: wave 0 sorts
        for w in range(8):
            with otrace.span("polish wave", wave=w) as sp:
                mesh, counts, worklist, topo = sliver_polish(
                    mesh, met, jnp.asarray(1000 + w, jnp.int32),
                    do_collapse=not info.noinsert,
                    do_swap=not info.noswap,
                    do_smooth=not info.nomove, hausd=hausd, budget=budget,
                    worklist=worklist, topo=topo)
                (ncol, nswap, nmoved, _, nhveto, nbmoved, nbad, col, adj,
                 cand, wl, tab, inc) = np.asarray(counts).tolist()
                # a polish wave splits nothing: bsplit is 0 by what it is;
                # bad: tets under the sliver threshold at the wave's
                # entry; col, adj: did the collapse stage and the exit
                # adjacency run (a stage without an input does not);
                # cand: candidate rows the ring and edge swaps' top-K
                # selected, wl: those of them whose shell changed since
                # the kernel last looked, which is what it judged;
                # tab: edge tables and adjacencies the wave derived,
                # inc: those of them merged into (or taken as) the sort
                # the last derivation left, not sorted in full;
                # prog: which of the polish programs this process
                # lowered the wave ran (obs/devtime picks its map by it)
                sp.set(collapse=ncol, swap=nswap, moved=nmoved,
                       bsplit=0, hveto=nhveto, bmoved=nbmoved,
                       bad=nbad, col=col, adj=adj, wl=wl, cand=cand,
                       tab=tab, inc=inc,
                       prog=LEDGER.program_index("adapt.sliver_polish"))
            col_skipped += int(not info.noinsert and not col)
            adj_skipped += int(not adj)
            cand_rows += cand
            wl_rows += wl
            tables += tab
            tables_merged += inc
            stats.add_surface(hveto=nhveto, bmoved=nbmoved)
            stats.ncollapse += ncol
            stats.nswap += nswap
            stats.nmoved += nmoved
            ops += ncol + nswap
            REGISTRY.counter("tail.polish_waves").inc()
            if ncol == 0 and nswap == 0:
                break
    REGISTRY.counter("tail.polish_ops").inc(ops)
    # stages the waves skipped for want of an input, zeros too
    REGISTRY.counter("tail.collapse_skipped").inc(col_skipped)
    REGISTRY.counter("tail.exit_adj_skipped").inc(adj_skipped)
    REGISTRY.counter("tail.candidate_rows").inc(cand_rows)
    REGISTRY.counter("tail.worklist_rows").inc(wl_rows)
    REGISTRY.counter("tail.tables").inc(tables)
    REGISTRY.counter("tail.tables_merged").inc(tables_merged)
    return mesh, ops, topo


def _run_phases(pm) -> tuple[Mesh, object, AdaptStats]:
    """The phases of one run, under the ``run`` span."""
    from .utils.timers import Timers
    from .obs import trace as otrace
    from .obs.metrics import REGISTRY
    from .resilience.recover import RetryBudgetExhausted, ladder_step
    info = pm.info
    tim = Timers()
    from .utils.placement import host_staging, to_device
    with tim("analysis") as sp, host_staging():
        mesh, met = pm._build_core_mesh()
        sp.set(**surface_census(mesh))
    if info.nosurf:
        # -nosurf: no surface modification — freeze every boundary entity
        # with MG_REQ (exactly how the reference freezes parallel faces,
        # and how Mmg interprets nosurf: required boundary)
        import jax.numpy as jnp
        import dataclasses
        bdy_f = (mesh.ftag & C.MG_BDY) != 0
        bdy_e = (mesh.etag & C.MG_BDY) != 0
        bdy_v = (mesh.vtag & C.MG_BDY) != 0
        mesh = dataclasses.replace(
            mesh,
            ftag=jnp.where(bdy_f, mesh.ftag | C.MG_REQ, mesh.ftag),
            etag=jnp.where(bdy_e, mesh.etag | C.MG_REQ, mesh.etag),
            vtag=jnp.where(bdy_v, mesh.vtag | C.MG_REQ, mesh.vtag))
    with tim("metric") as sp, host_staging():
        # vertices whose size the hausd bound lowered: the curvature's
        # share of the size map (0 on a flat boundary)
        census = {"bound_verts": 0, "bdy_verts": 0}
        met = build_metric(mesh, met, info, census)
        sp.set(bound_verts=census["bound_verts"])
        REGISTRY.counter("surf.bound_verts").inc(census["bound_verts"])
        REGISTRY.counter("surf.bdy_verts").inc(census["bdy_verts"])

    # background snapshot for field interpolation (PMMG_create_oldGrp
    # analogue, grpsplit_pmmg.c:207).  Deep copy: adapt_cycle donates its
    # input buffers, which would invalidate the background otherwise.
    bg_fields = [np.array(f, copy=True) for f in pm.fields]
    if bg_fields:
        import jax
        import jax.numpy as jnp
        with otrace.span("backup"):
            bg_mesh = jax.tree.map(jnp.copy, mesh)
    else:
        bg_mesh = None

    stats = AdaptStats()
    angedg = info.angedg()
    # surface-approximation tolerance: global -hausd, tightened by any
    # local-parameter hausd (per-reference hausd applies conservatively
    # as the global minimum until per-entity hausd fields land)
    hausd = info.hausd
    for _typ, _ref, _hm, _hx, _hd in info.local_params:
        if _hd and _hd > 0:
            hausd = min(hausd, _hd)
    if not info.angle_detection:
        # -nr: no ridge tags -> the Bezier lift cannot tell a sharp
        # feature from smooth curvature; fall back to piecewise-linear
        # boundary placement (conservative; Mmg with -nr instead rounds
        # features — tracked as a semantic divergence)
        hausd = None
    if info.n_devices <= 1:
        import jax
        import jax.numpy as jnp
        from .api.params import resolve_target_mesh_size
        from .parallel.groups import how_many_groups, grouped_adapt
        niter = max(1, info.niter)
        ne0 = int(np.asarray(mesh.tmask).sum())
        target = resolve_target_mesh_size(info, ne0, 1)
        if how_many_groups(ne0, target) >= 2:
            # two-level decomposition (-mesh-size below the mesh size):
            # sub-device groups traversed with lax.map so peak HBM is one
            # group's working set (grpsplit_pmmg.c:1551 role; see
            # parallel/groups.py).  Interface seams are displaced between
            # iterations like rank interfaces.  Only the group-shaped
            # cycle blocks run on the device: everything at whole-mesh
            # width (staging above, split/merge, the merged tail below)
            # stays on the host (utils/placement.host_staging) — the
            # mesh is grouped BECAUSE programs of its width are too big,
            # for the device and for its compiler alike.
            with otrace.span("backup"), host_staging():
                backup = (jax.tree.map(jnp.copy, mesh), jnp.copy(met))
            degraded = False
            try:
                with tim("adaptation"):
                    mesh, met = grouped_adapt(
                        mesh, met, target, niter=niter,
                        verbose=3 if info.imprim >= C.PMMG_VERB_ITWAVES
                        else 0, stats=stats,
                        noinsert=info.noinsert, noswap=info.noswap,
                        nomove=info.nomove, hausd=hausd,
                        ifc_layers=info.ifc_layers, timers=tim,
                        resume=getattr(info, "resume", False),
                        contiguous=info.contiguous_mode,
                        cap_max=info.group_capacity)
            except MemoryError:
                mesh, met = backup
                stats.status = C.PMMG_LOWFAILURE
                degraded = True
                ladder_step("lowfailure", site="groups.capacity")
            except RetryBudgetExhausted as e:
                # the retry rung of the ladder is spent (a chunk
                # dispatch kept failing): restore the conforming
                # backup and degrade — never die holding user data
                mesh, met = backup
                stats.status = C.PMMG_LOWFAILURE
                degraded = True
                ladder_step("lowfailure", site=e.site,
                            detail=str(e.__cause__ or e))
            except Exception as e:  # device OOM = XlaRuntimeError
                if "RESOURCE_EXHAUSTED" not in str(e) and \
                        "Out of memory" not in str(e):
                    raise
                mesh, met = backup
                stats.status = C.PMMG_LOWFAILURE
                degraded = True
                ladder_step("lowfailure", site="device.oom",
                            detail=str(e)[:200])
            # bad-element polish on the merged mesh (the same contract as
            # the other two paths — group seams breed slivers)
            topo = None
            if not degraded and not (info.noinsert and info.noswap
                                     and info.nomove):
                mesh, _, topo = _merged_polish(mesh, met, info, hausd,
                                               stats, tim)
            with host_staging():
                return _finish_run(pm, mesh, met, stats, info, tim,
                                   bg_mesh, bg_fields, hausd, topo=topo)
        # whole-mesh path: staged on the host, adapted on the device
        mesh, met = to_device((mesh, met))
        for it in range(niter):
            # the jitted cycles DONATE their input buffers, so the
            # pre-iteration binding would be dead after a failure; keep a
            # device-side copy for the degrade path (HBM-to-HBM, cheap)
            with otrace.span("backup"):
                backup = (jax.tree.map(jnp.copy, mesh), jnp.copy(met))
            try:
                with tim("adaptation"):
                    mesh, met, st = adapt_mesh(
                        mesh, met,
                        verbose=3 if info.imprim >= C.PMMG_VERB_ITWAVES
                        else 0,
                        noinsert=info.noinsert, noswap=info.noswap,
                        nomove=info.nomove, angedg=angedg, hausd=hausd)
            except MemoryError:
                # capacity exhausted mid-iteration: restore the backup
                # (conforming) and degrade, don't die (failed_handling,
                # libparmmg1.c:974-1011)
                mesh, met = backup
                stats.status = C.PMMG_LOWFAILURE
                ladder_step("lowfailure", site="adapt.capacity")
                break
            except Exception as e:  # device OOM comes as XlaRuntimeError
                if "RESOURCE_EXHAUSTED" not in str(e) and \
                        "Out of memory" not in str(e):
                    raise
                mesh, met = backup
                stats.status = C.PMMG_LOWFAILURE
                ladder_step("lowfailure", site="device.oom",
                            detail=str(e)[:200])
                break
            stats += st
    else:
        import jax
        from .parallel.dist import (distributed_adapt_multi,
                                    ShardOverflowError)
        # the SPMD path places like the grouped one: the shard-shaped
        # programs run on the devices, split, merge and the merged tail
        # below at whole-mesh width stay on the host
        part = None
        niter = max(1, info.niter)
        vrb = 3 if info.imprim >= C.PMMG_VERB_ITWAVES else 0
        # Both repartitioning modes run the shard-RESIDENT outer loop —
        # one split, niter adapt passes, ONE merge at final output
        # (the reference's migrate-only-moving-groups design,
        # loadbalancing_pmmg.c + distributegrps_pmmg.c).  The modes
        # differ only in the between-iteration labels: advancing-front
        # interface displacement (default, device flood) vs group-graph
        # repartitioning (morton clusters + weighted KL/FM — the
        # metis_pmmg.c:845-1550 gather-only-the-graph role).
        mode = "ifc" if info.repartitioning == C.REPART_IFC_DISPLACEMENT \
            else "graph"
        # distributed input stays distributed: adopt the caller's
        # partition when it matches the device count (the reference
        # preserves the input decomposition and only rebuilds comms,
        # libparmmg.c:206-329); the dedup at load time kept tet order
        in_part = getattr(pm, "_in_part", None)
        n_t0 = int(np.asarray(mesh.tmask).sum())
        # ranks x groups (grpsplit_pmmg.c:1551-1614): a rank a device,
        # and a rank cuts its share into groups of -mesh-size as one
        # device does on the grouped path, G rows of the SPMD block a
        # device.  The shard COUNT is a multiple of the device count:
        # fewer shards would leave devices permanently empty (the flood
        # never populates a shard that shares no interface)
        from .api.params import groups_per_rank
        n_shards = info.n_devices * groups_per_rank(
            n_t0, info.n_devices, info.target_mesh_size)
        if in_part is not None and (
                len(in_part) != n_t0
                or int(in_part.max()) + 1 != n_shards):
            in_part = None
        try:
            with tim("adaptation"):
                mesh, met, part = distributed_adapt_multi(
                    mesh, met, n_shards, niter=niter,
                    verbose=vrb, stats=stats,
                    noinsert=info.noinsert, noswap=info.noswap,
                    nomove=info.nomove, angedg=angedg, hausd=hausd,
                    ifc_layers=info.ifc_layers,
                    nobalancing=info.nobalancing, part=in_part,
                    mode=mode, n_devices=info.n_devices, timers=tim)
        except ShardOverflowError as e:
            # degrade to LOWFAILURE with the conforming merged state
            # (failed_handling, libparmmg1.c:974-1011)
            mesh, met, part = e.mesh, e.met, e.part
            stats.status = C.PMMG_LOWFAILURE
            ladder_step("lowfailure", site="shard.overflow")
            from .obs.trace import log as _olog
            _olog(C.PMMG_VERB_VERSION,
                  "  ## Warning: shard capacity exhausted; saving the "
                  "last conforming mesh (LOWFAILURE).",
                  verbose=info.imprim, err=True)
        # a job whose shards did not end on the devices it asked for did
        # not run where the caller was told it would: the mesh is sound,
        # the status says so
        held = stats.sched_extra.get("dist_devices", info.n_devices)
        if held < info.n_devices and jax.process_count() == 1:
            stats.status = C.PMMG_LOWFAILURE
            ladder_step("lowfailure", site="dist.devices",
                        detail=f"live shards on {held} of "
                               f"{info.n_devices} devices")
        # bad-element optimization on the merged mesh (same contract as
        # the single-device path: sliver_polish after the sizing loop)
        topo = None
        if not (info.noinsert and info.noswap and info.nomove):
            mesh, ops, topo = _merged_polish(mesh, met, info, hausd, stats,
                                             tim)
            if ops:
                part = None   # tet set changed: labels are stale
        # reused by distributed output, a file a RANK: a tet's label is
        # its shard's, and a rank holds n_shards / nDevices of them in a row
        pm._out_part = None if part is None \
            else part // (n_shards // info.n_devices)
        with host_staging():
            return _finish_run(pm, mesh, met, stats, info, tim, bg_mesh,
                               bg_fields, hausd, topo=topo)

    return _finish_run(pm, mesh, met, stats, info, tim, bg_mesh,
                       bg_fields, hausd)


def _finish_run(pm, mesh, met, stats, info, tim, bg_mesh, bg_fields,
                hausd, topo=None):
    """Common run tail: sequential sliver repair, FEM-topology
    conformity, user-field interpolation, reports.  Shared by the
    whole-mesh, grouped and distributed paths.

    ``topo``: the ``ops/topo_incr.TopoState`` the merged polish ended
    with (the grouped and the distributed path; both run this tail on
    the host).  The fem rounds then take their edge table and adjacency
    off its sorts (``ops/adapt.fem_pass_impl``) and hand it from round
    to round; what changes the mesh outside them keeps it true: a repair
    that rewrote rows marks them, a regrow (the rows are permuted and
    the capacity changes) drops it, and the next round sorts in full.
    The whole-mesh path carries none and runs the rounds as they were,
    on the device."""
    from .obs import trace as otrace
    from .obs.metrics import REGISTRY
    from .obs.trace import log as _olog
    from .ops.topo_incr import mark_dirty, topo_init
    # sequential last-resort repair: tangled sliver clusters (stacked
    # near-flat tets, typically born at former frozen interfaces) veto
    # every BATCHED fix — each parallel op inverts a neighbor — while the
    # reference's sequential remesher resolves them one op at a time;
    # ops/repair.py reproduces that freedom for the (tiny) tail only
    if not (info.noinsert and info.noswap and info.nomove):
        from .ops.repair import repair_mesh
        with tim("sequential repair"):
            before = mesh
            mesh, nrep = repair_mesh(
                mesh, met, allow_collapse=not info.noinsert,
                allow_swap=not info.noswap, allow_move=not info.nomove)
            if topo is not None and mesh is not before:
                # numpy rewrote rows: the diff the polish's stages take
                topo = mark_dirty(topo, before.tet, before.tmask, mesh)
            if nrep:
                _olog(C.PMMG_VERB_STEPS,
                      f"  sequential repair: {nrep} cluster ops",
                      verbose=info.imprim)

    # FEM-mode topology fix (default ON like the reference,
    # API_functions_pmmg.c:413; disabled by -nofem): split interior edges
    # connecting two boundary points so no element touches the boundary
    # with two faces / all four vertices (ops.split.split_wave fem_only).
    # AFTER the repair pass — a repair collapse could otherwise resurrect
    # a bdy-bdy interior edge the fem pass just removed.
    # (tables, tables_merged: the tables the rounds derived with a state
    # carried and those of them taken off its sorts, counters once a job)
    tables = tables_merged = 0
    if info.fem and not info.noinsert:
        from .ops.adapt import fem_pass, grow_mesh_met
        with tim("fem conformity"):
            nf = 0
            for w in range(8):
                with otrace.span("fem round", wave=w) as sp:
                    if topo is None:
                        mesh, met, fc = fem_pass(mesh, met)
                        nf, ovf, nbs = np.asarray(fc).tolist()
                    else:
                        mesh, met, fc, topo = fem_pass(mesh, met, topo)
                        nf, ovf, nbs, tab, inc = np.asarray(fc).tolist()
                        # tab: the round's edge table and its adjacency,
                        # inc: those of them merged into (or taken as)
                        # the sort the state carries, as a polish wave's
                        sp.set(tab=tab, inc=inc)
                        tables += tab
                        tables_merged += inc
                    # a fem round collapses and moves nothing
                    sp.set(split=nf, overflow=ovf, bsplit=nbs, hveto=0,
                           bmoved=0,
                           prog=LEDGER.program_index("adapt.fem_pass"))
                stats.nsplit += nf
                stats.add_surface(bsplit=nbs)
                if ovf:
                    mesh, met = grow_mesh_met(mesh, met, 2 * mesh.capP,
                                              2 * mesh.capT)
                    if topo is not None:
                        # rows permuted, capacity doubled: nothing of the
                        # sorts holds, the next round sorts in full
                        topo = topo_init(mesh.capT)
                    stats.regrows += 1
                    continue
                if nf == 0:
                    break
            if nf:
                _olog(C.PMMG_VERB_VERSION,
                      "  ## Warning: fem conformity pass did not "
                      f"converge ({nf} edges remain); output may "
                      "contain elements with two boundary faces.",
                      verbose=info.imprim, err=True)

    REGISTRY.counter("tail.fem_tables").inc(tables)
    REGISTRY.counter("tail.fem_tables_merged").inc(tables_merged)

    # interpolate user fields old mesh -> new mesh
    if bg_fields:
        with tim("metric and fields interpolation"):
            pm.fields = interpolate_fields(bg_mesh, bg_fields, mesh)

    # metrics spine: every run's counters land in the process registry
    # (tenant-tagged stats stay namespaced), snapshotted by the
    # artifact layer (obs/artifact.py)
    stats.publish()
    # quality report stays gated on BOTH compute and print: generating
    # it runs whole-mesh device programs, which the telemetry spine
    # must never add to a quiet run (its absence from the trace means
    # "not computed", not "suppressed" — README Observability)
    if info.imprim >= C.PMMG_VERB_QUAL:
        print_quality_report(mesh, met, info)
    # the report lines below are cheap host strings: _olog gates the
    # PRINT on imprim but always emits the trace record, so the JSONL
    # stream carries them (shown=false) even on quiet runs
    # quiet-group scheduler accounting (parallel/sched.py): the active
    # g/G trajectory + the dispatches the compaction saved on the
    # grouped path's chunked dispatch loop
    if stats.group_dispatches or stats.group_dispatches_saved:
        traj = stats.sched_extra.get("active_groups_per_block", [])
        line = (f"  -- QUIET-GROUP SCHEDULER  "
                f"{stats.group_dispatches} group-block dispatches, "
                f"{stats.group_dispatches_saved} saved "
                f"({stats.groups_skipped} group-blocks skipped)")
        if traj:
            line += "; active g/block " + \
                ",".join(str(a) for a in traj)
        _olog(C.PMMG_VERB_STEPS, line, verbose=info.imprim)
    _olog(C.PMMG_VERB_STEPS, tim.report(), verbose=info.imprim)
    # compile-churn accounting (utils/compilecache): a steady state
    # whose ledger keeps growing is recompiling, not computing
    from .utils.timers import format_ledger, ledger_snapshot
    # registration alone (import-time @governed) leaves all-zero
    # rows; only report once something was actually called/compiled
    if any(r["calls"] or r["compiles"]
           for r in ledger_snapshot().values()):
        _olog(C.PMMG_VERB_STEPS,
              "  -- COMPILE LEDGER (XLA backend compiles)\n"
              + format_ledger(), verbose=info.imprim)
    return mesh, met, stats


def print_quality_report(mesh: Mesh, met, info) -> None:
    """Quality + edge-length histograms (PMMG_qualhisto OUTQUA +
    PMMG_prilen, quality_pmmg.c:156,591 — the custom MPI_Op reductions
    become plain array reductions on the merged mesh / psums on shards)."""
    import jax.numpy as jnp
    from .obs.metrics import REGISTRY
    from .obs.trace import log as _olog
    from .ops.quality import tet_quality, quality_histogram, \
        length_histogram

    q = tet_quality(mesh, met)
    counts, qmin, qmean, nbad = quality_histogram(q, mesh.tmask)
    # quality gauges only exist when the quality report ran (imprim >=
    # VERB_QUAL at the callsite): computing them is a whole-mesh device
    # program, and the telemetry spine must never ADD device compute to
    # a quiet run — absent quality.* gauges in an artifact mean the run
    # skipped the report, not that quality regressed
    REGISTRY.gauge("quality.qmin").set(float(qmin))
    REGISTRY.gauge("quality.qmean").set(float(qmean))
    REGISTRY.gauge("quality.nbad").set(float(nbad))
    lines = [f"  -- MESH QUALITY   {int(jnp.sum(mesh.tmask))} tets ; "
             f"worst {float(qmin):.6f} ; mean {float(qmean):.6f} ; "
             f"bad {int(nbad)}"]
    c = np.asarray(counts)
    for i, n in enumerate(c):
        lo, hi = i / len(c), (i + 1) / len(c)
        lines.append(f"     {lo:.1f} < Q < {hi:.1f}   {int(n)}")
    if met is not None:
        lc, lmin, lmax, lmean = length_histogram(mesh, met)
        lines.append(f"  -- EDGE LENGTHS   min {float(lmin):.4f} ; "
                     f"max {float(lmax):.4f} ; mean {float(lmean):.4f}")
    _olog(C.PMMG_VERB_QUAL, "\n".join(lines), verbose=info.imprim)


def interpolate_fields(bg: Mesh, fields: list[np.ndarray], new: Mesh)\
        -> list[np.ndarray]:
    """Background P1 interpolation of user fields onto the new vertices
    (PMMG_interpMetricsAndFields semantics, interpmesh_pmmg.c:663).

    Boundary vertices interpolate from the background SURFACE (triangle
    walk, ops.interp.locate_points_bdy — the PMMG_locatePointBdy split of
    interpmesh_pmmg.c:535-620): a volume walk puts a curved-boundary
    point inside some tet whose P1 restriction misrepresents the surface
    field."""
    import jax.numpy as jnp
    from .core.constants import MG_BDY
    from .ops.interp import (locate_points, locate_points_bdy, interp_p1,
                             interp_p1_tri)

    vm = np.asarray(new.vmask)
    pts = np.asarray(new.vert)[vm]
    on_bdy = (np.asarray(new.vtag)[vm] & MG_BDY) != 0
    loc = locate_points(bg, jnp.asarray(pts, new.vert.dtype),
                        # lint: ok(R10) — one-shot solution-transfer
                        # boundary: the query count IS the compile
                        # family here, and locate_points retraces per
                        # point count regardless (host mesh ingest,
                        # outside the governed adapt loop)
                        jnp.zeros(len(pts), jnp.int32))
    # the surface walk runs on the boundary SUBSET only (the volume pass
    # would feed interior points through the closest-triangle machinery
    # for nothing — and its intermediates scale with the query count)
    sloc = locate_points_bdy(
        bg, jnp.asarray(pts[on_bdy], new.vert.dtype)) \
        if on_bdy.any() else None
    out = []
    for f in fields:
        full = np.zeros((bg.capP,) + f.shape[1:], f.dtype)
        full[: len(f)] = f
        vals = np.asarray(interp_p1(jnp.asarray(full), bg.tet, loc))
        if sloc is not None:
            vals = np.array(vals, copy=True)
            vals[on_bdy] = np.asarray(
                interp_p1_tri(jnp.asarray(full), bg, sloc))
        out.append(vals)
    return out
