"""SPMD distributed adaptation over a jax.sharding.Mesh.

The TPU-native replacement for ParMmg's MPI layer: where the reference runs
one MPI rank per subdomain with Sendrecv exchanges and
``MPI_Allreduce(MIN, ier)`` phase agreement (the status-agreement idiom,
/root/reference/src/libparmmg1.c:812,876,912), we run one *shard* per
device under ``shard_map``: every device executes the identical jitted
adapt program on its shard; cross-shard agreement (op counters, error
status, quality histograms) is a ``psum`` over the 'shard' axis — the
collective rides ICI instead of MPI.

During shard-local adaptation the interfaces are frozen (MG_PARBDY tags set
by distribute.py), so no halo exchange is needed *inside* the hot loop —
exactly the reference's design (interfaces remeshed only after migration).
Repartitioning/migration between outer iterations is host-side DCN
orchestration (SURVEY §5: dynamic-topology group migration stays off the
static-shape device path).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh as DeviceMesh, PartitionSpec as P, NamedSharding

from ..utils.jaxcompat import shard_map

from ..core.mesh import Mesh
from ..obs import trace as otrace
from ..obs.metrics import REGISTRY
from ..ops.quality import tet_quality, quality_histogram
from ..utils.compilecache import DIST_BLOCK_ENTRY, LEDGER, bucket, governed
from ..utils.placement import placed_on_tpu


MAX_SHARD_REGROWS = 6
# the SPMD block's counts row: columns 0-10 are the cycle's own row
# (ops/adapt: operations, overflow, live tets, deferrals, LISTED_COL,
# SURF_COLS) summed over every logical shard, then the rows whose
# surface scatters ran over lists, and the live tets and the fullest
# shard at the block's ENTRY
LISTED_ROWS_COL = 11
LIVE_IN_COL = 12
LARGEST_IN_COL = 13


class ShardOverflowError(RuntimeError):
    """Shard capacity exhausted after MAX_SHARD_REGROWS doublings.

    Carries the last CONFORMING merged state so the caller can degrade
    to PMMG_LOWFAILURE and still save a valid mesh — the reference's
    failed_handling contract (libparmmg1.c:974-1011)."""

    def __init__(self, mesh, met, part):
        super().__init__("shard capacity overflow")
        self.mesh = mesh
        self.met = met
        self.part = part


# compiled SPMD programs that close over a device mesh, a process:
# the cycle block by (device ids, knobs), the analysis refresh by
# (device ids, its static budget)
_DIST_BLOCK_CACHE: dict = {}
_ANALYSIS_CACHE: dict = {}


def _device_ids(dmesh: DeviceMesh) -> tuple:
    # lint: ok(R2) — device-id METADATA (dmesh.devices is a host numpy
    # object array), no device sync
    return tuple(d.id for d in np.asarray(dmesh.devices).flat)


def _unstack(pytree):
    return jax.tree.map(lambda x: x[0], pytree)


def _restack(pytree):
    return jax.tree.map(lambda x: x[None], pytree)


def make_device_mesh(n_devices: int | None = None) -> DeviceMesh:
    """Device mesh over the 'shard' axis.  Under an initialized
    ``jax.distributed`` runtime (parallel/multihost.py), ``jax.devices()``
    is the GLOBAL list across hosts and the same mesh spans processes —
    the MPI-communicator analogue (mpi_pmmg.h role)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return DeviceMesh(np.array(devs), ("shard",))


def shard_stacked(stacked, dmesh: DeviceMesh):
    """Place a [D, ...]-stacked pytree with leading axis over 'shard'.
    Multi-process meshes route through shard_stacked_global (each host
    uploads its addressable slices)."""
    if jax.process_count() > 1:
        from .multihost import shard_stacked_global
        return shard_stacked_global(stacked, dmesh)
    sh = NamedSharding(dmesh, P("shard"))
    return jax.tree.map(lambda x: jax.device_put(x, sh), stacked)


def dist_adapt_block(dmesh: DeviceMesh, swap: bool,
                     do_smooth: bool = True, do_insert: bool = True,
                     hausd: float | None = None, G: int = 1,
                     prescreen: bool = True,
                     swap_inclusive: bool | None = None):
    """SPMD cycle block: one adapt cycle in ONE jitted shard_map
    program, one dispatch + one psum'd counter pull.

    ``G`` > 1 is the groups x shards composition (the reference's
    rank-level x group-level two-level loop, grpsplit_pmmg.c:1551-1614,
    libparmmg1.c:597-636): the stacked leading axis holds S*G LOGICAL
    shards, G consecutive rows per device; inside the shard_map body a
    ``lax.map`` serializes the device's G groups through ONE compiled
    group-shaped cycle program, so peak HBM per chip is the G resident
    group states plus a single group's wave working set — the bound
    that makes meshes far beyond one group's HBM feasible per chip.

    Returns fn(stacked_mesh, stacked_met, wave, quiet_lvl[S*G]) ->
      (stacked_mesh, stacked_met, global_counts[14],
       active_groups, any_overflow, quiet_lvl'[S*G]); the counts row is
    the cycle's own, summed over the logical shards, and three columns
    more (``LISTED_ROWS_COL``, ``LIVE_IN_COL``, ``LARGEST_IN_COL``).

    ``active_groups`` = number of LOGICAL shards that posted a nonzero
    split+collapse+swap in the cycle (psum'd like the counters): the
    per-group convergence signal is kept instead of being summed
    away, so :func:`run_adapt_cycles` can drive its early-exit and its
    verbose "active g/G" trajectory from per-group data — the SPMD
    mirror of the quiet-group scheduler on the single-device grouped
    path (parallel/sched.py).

    ``quiet_lvl`` is that scheduler's quiet state made DEVICE-RESIDENT
    (int8 per logical shard, the sched.LEVEL_* ladder): a shard at or
    above this block's skip level has its ``lax.map`` body wrapped in
    ``lax.cond`` identity — the split/collapse/swap/smooth wave math is
    never executed for it — and a swap-inclusive block posting zero
    split+collapse+swap+move+overflow for a shard raises its level ON
    DEVICE (the same frozen-seam + deterministic-wave fixed-point
    proof, the same two prescreen levels; sched module docstring).
    Zero host syncs are added: the level array never leaves the device.
    ``swap_inclusive`` must be passed as ``swap or noswap`` by
    callers that honor -noswap (a noswap run's blocks are trivially
    swap-inclusive); it defaults to ``swap``.  The caller
    opts out of skipping by discarding the returned level and passing
    zeros each block (run_adapt_cycles under PARMMG_DEVICE_MASK=0 /
    PARMMG_GROUP_SCHED=0) — same compiled program either way.
    """
    return DistSteps(dmesh, do_smooth=do_smooth, do_insert=do_insert,
                     hausd=hausd, G=G).get(swap, prescreen,
                                           swap_inclusive)


def _dist_block_program(dmesh: DeviceMesh, do_smooth: bool,
                        do_insert: bool, hausd, G: int):
    """The compiled program behind :func:`dist_adapt_block`.  Whether
    the cycle swaps (``sw``), whether it bypasses the split prescreen
    (``pr``) and whether the block is swap-inclusive (``inc``) are
    traced, replicated scalar arguments — the cycle classes of a run
    share one multi-minute SPMD compile."""
    from ..ops.adapt import LISTED_COL, adapt_cycle_impl
    spec = P("shard")
    # observed here, where the program is built, as the grouped block
    # does: the surface scatters run over lists where it is placed on a
    # TPU (ops/surflist)
    surf_list = placed_on_tpu()
    # one program a process and knob set (the grouped block's
    # ``_GROUP_BLOCK_CACHE``): jit keeps a traced program by the
    # function's identity, so a fresh ``shard_map`` a job traced,
    # lowered and loaded the block again in every job of a process
    key = (_device_ids(dmesh), do_smooth, do_insert, hausd, G, surf_list)
    if key in _DIST_BLOCK_CACHE:
        return _DIST_BLOCK_CACHE[key]

    def one_shard(mesh: Mesh, met, wave, act, sw, pr):
        return adapt_cycle_impl(
            mesh, met, wave, do_swap=sw, do_smooth=do_smooth,
            do_insert=do_insert, smooth_waves=2, hausd=hausd,
            prescreen=pr, active=act, surf_list=surf_list)  # counts [11]

    # ``run``: the profile names the program's module after it, and a
    # capture's reduction finds a cycle block by the grouped block's
    # name (``jit_run``)
    def run(mesh_s: Mesh, met_s, wave, lvl_s, sw, pr, inc):
        # the level this block skips at == the level it can prove
        # (sched.LEVEL_PRE under a prescreen-ON cycle, LEVEL_FULL
        # once a prescreen-OFF cycle ran — numerically 1 and 2)
        skip_lvl = jnp.where(pr, jnp.int8(1), jnp.int8(2))
        act_in = lvl_s < skip_lvl                          # [G] bool
        live_in = jnp.sum(mesh_s.tmask, axis=1, dtype=jnp.int32)   # [G]
        if G == 1:
            mesh, met, cs = one_shard(_unstack(mesh_s), met_s[0],
                                      wave, act_in[0], sw, pr)
            mesh_s, met_s = _restack(mesh), met[None]
            cs_g = cs[None]                                # [1, 11]
        else:
            def body(args):
                m, k, a = args
                return one_shard(m, k, wave, a, sw, pr)
            mesh_s, met_s, cs_g = jax.lax.map(
                body, (mesh_s, met_s, act_in))
        act = jnp.sum((jnp.sum(cs_g[:, :3], axis=1) > 0
                       ).astype(jnp.int32))
        # quiet marking on device, on a swap-inclusive block —
        # sched.quiet_rows' rule: the WHOLE block a no-op (zero
        # split+collapse+swap+move AND zero overflow; a truncated
        # winner set witnesses nothing)
        blk_zero = jnp.sum(cs_g[:, :5], axis=1) == 0
        lvl_s = jnp.maximum(
            lvl_s, jnp.where(blk_zero & inc, skip_lvl, jnp.int8(0)))
        ovf = jax.lax.pmax(jnp.max(cs_g[:, 4]), "shard")
        listed_rows = jnp.sum((cs_g[:, LISTED_COL] > 0).astype(jnp.int32))
        counts = jnp.concatenate([
            jax.lax.psum(
                jnp.concatenate([jnp.sum(cs_g, axis=0), listed_rows[None],
                                 jnp.sum(live_in)[None]]), "shard"),
            jax.lax.pmax(jnp.max(live_in), "shard")[None]])
        nact = jax.lax.psum(act, "shard")
        return mesh_s, met_s, counts, nact, ovf, lvl_s

    fn = shard_map(run, mesh=dmesh,
                   in_specs=(spec, spec, P(), spec, P(), P(), P()),
                   out_specs=(spec, spec, P(), P(), P(), spec),
                   check_vma=False)
    prog = governed(DIST_BLOCK_ENTRY)(jax.jit(fn))
    _DIST_BLOCK_CACHE[key] = prog
    return prog


class DistSteps:
    """The compiled SPMD block program of one driver invocation, with
    its knobs (the program itself is cached a process:
    :func:`_dist_block_program`)."""

    def __init__(self, dmesh: DeviceMesh, do_smooth: bool = True,
                 do_insert: bool = True, hausd: float | None = None,
                 G: int = 1):
        self.do_smooth, self.do_insert, self.hausd = \
            do_smooth, do_insert, hausd
        self._prog = _dist_block_program(dmesh, do_smooth, do_insert,
                                         hausd, G)

    def get(self, swap: bool, prescreen: bool = True,
            swap_inclusive: bool | None = None):
        if swap_inclusive is None:
            swap_inclusive = swap
        prog = self._prog
        sw = jnp.asarray(bool(swap))
        pr = jnp.asarray(bool(prescreen))
        inc = jnp.asarray(bool(swap_inclusive))
        return lambda mesh_s, met_s, wave, lvl_s: prog(
            mesh_s, met_s, wave, lvl_s, sw, pr, inc)


def dist_interface_check(dmesh: DeviceMesh, G: int = 1,
                         packed_M: int | None = None):
    """On-device interface echo (PMMG_check_extNodeComm on the jittable
    exchange): every shard sends its interface vertices' coordinates +
    metric through :func:`halo_exchange` and compares against the mirror
    side; the psum'd mismatch count must be zero.  Production guard for
    the ordering contract of the comm tables — runs once per outer
    iteration in distributed_adapt.

    ``G`` > 1: groups x shards composition — the stacked leading axis is
    S*G logical shards and the exchange routes (dest_device, dest_slot)
    through :func:`comms.halo_exchange_grouped`, or the per-device-pair
    packed layout (:func:`comms.halo_exchange_grouped_packed`) when
    ``packed_M`` is set (the measured-occupancy decision of
    :func:`comms.packed_halo_rows`).

    Returns fn(stacked_mesh, stacked_met, node_idx[S,K,I], nbr[S,K],
    tol) -> global mismatch count.
    """
    from .comms import (halo_exchange, halo_exchange_grouped,
                        halo_exchange_grouped_packed)
    spec = P("shard")

    def local(mesh_s: Mesh, met_s, node_idx_s, nbr_s, tol):
        met_g = met_s[..., None] if met_s.ndim == 2 else met_s
        vals_g = jnp.concatenate(
            [mesh_s.vert, met_g.astype(mesh_s.vert.dtype)],
            axis=-1)                                     # [G, capP, 3+m]
        if G == 1:
            recv = halo_exchange(vals_g[0], node_idx_s[0],
                                 nbr_s[0])[None]          # [1,K,I,3+m]
        elif packed_M is not None:
            recv = halo_exchange_grouped_packed(
                vals_g, node_idx_s, nbr_s, G, packed_M)
        else:
            recv = halo_exchange_grouped(vals_g, node_idx_s, nbr_s, G)
        capP = mesh_s.vert.shape[1]
        g_ar = jnp.arange(G)[:, None, None]
        mine = vals_g[jnp.broadcast_to(g_ar, node_idx_s.shape),
                      jnp.clip(node_idx_s, 0, capP - 1)]
        valid = (node_idx_s >= 0)[..., None]
        bad = valid & (jnp.abs(recv - jnp.where(valid, mine, 0)) > tol)
        n_bad = jnp.sum(bad.astype(jnp.int32))
        return jax.lax.psum(n_bad, "shard")

    # lint: ok(R1) — builder: the sole caller (check_interface_echo)
    # caches in _IFC_CHECK_CACHE and wraps the product in
    # governed("dist.interface_check", budget=2)
    fn = shard_map(local, mesh=dmesh,
                   in_specs=(spec, spec, spec, spec, P()),
                   out_specs=P(), check_vma=False)
    # lint: ok(R1) — same builder contract as above
    return jax.jit(fn)


def refresh_shard_analysis_device(stacked: Mesh, comms, n_shards: int,
                                  angedg: float, glo, dmesh,
                                  pack_state: dict | None = None):
    """Device-resident analysis refresh (parallel/analysis_dev.py): the
    sort/segment reductions of the host path run jitted under shard_map,
    keyed by the persistent global numbering — no O(mesh) host pull.

    ``n_shards`` > device count dispatches the GROUPED program
    (analysis_dev.dist_analysis_grouped): G = n_shards // n_devices
    logical shards per device, per-group lax.map reductions + the
    grouped (packed when sparse) halo exchange — the G>1 loop pays the
    same zero-host-pull bill as G=1.

    Returns the updated stacked mesh, or None when the shared-record
    budget overflowed (caller falls back to the host path) — never a
    silent truncation."""
    import os
    if os.environ.get("PARMMG_HOST_ANALYSIS", "") == "1":
        return None
    # injectable KS-overflow (resilience/faults.py): the real failure
    # here is a flag, not an exception — firing takes the exact branch
    # a shared-record budget overflow takes (None -> host fallback)
    from ..resilience.faults import fault_trigger, faultpoint
    if fault_trigger("analysis.ks_overflow"):
        return None
    from .analysis_dev import dist_analysis, dist_analysis_grouped
    from .comms import packed_halo_rows
    # lint: ok(R2) — glo is the HOST-resident persistent numbering
    # (list of np arrays grown on host, distributed_adapt_multi);
    # stacking it syncs nothing — audited PR 10, no device pull here
    glo_np = np.stack([np.asarray(g) for g in glo])
    if glo_np.max() >= np.iinfo(np.int32).max:
        return None                      # int32 id budget exhausted
    capT = stacked.tet.shape[1]
    # lint: ok(R2) — device-id METADATA (dmesh.devices is a host numpy
    # object array), no device sync
    n_dev = int(np.asarray(dmesh.devices).size)
    G = max(1, n_shards // max(n_dev, 1))
    # bucketed shared-record budget (compile governor): the comm tables
    # drift between migrations and an exact KS would key a fresh
    # dist_analysis compile each outer iteration
    KS = bucket(max(1024, 4 * comms.node_idx[0].size),
                floor=1024, cap=12 * capT)
    # pack_state: sticky dense/packed layout across comm-table rebuilds
    # (hysteresis; the multi-iteration driver threads one dict through)
    Mp = packed_halo_rows(comms.nbr, G, state=pack_state) \
        if G > 1 else None
    cache = _ANALYSIS_CACHE
    ids = _device_ids(dmesh)
    key = (ids, angedg, KS, n_shards, G, Mp)
    if key in cache:
        fn = cache[key]
    else:
        if G > 1:
            fn = governed("dist.analysis_grouped", budget=2)(
                dist_analysis_grouped(dmesh, angedg, KS, G, packed_M=Mp))
        else:
            fn = governed("dist.analysis", budget=2)(
                dist_analysis(dmesh, angedg, KS))
        cache[key] = fn
    args = (stacked,
            shard_stacked(jnp.asarray(glo_np.astype(np.int32)), dmesh),
            shard_stacked(jnp.asarray(comms.node_idx), dmesh),
            shard_stacked(jnp.asarray(comms.nbr), dmesh))
    try:
        if Mp is not None:
            faultpoint("halo.exchange")
        vt, et, ovf = fn(*args)
        # sync INSIDE the guard: device dispatch is async, so a real
        # crash of the packed program surfaces at this first host pull,
        # not at the fn() call — outside the try it would bypass the
        # dense fallback entirely
        ovf_host = int(ovf)
    except Exception as e:
        if Mp is None:
            raise
        # packed halo program failed (injectable via
        # PARMMG_FAULT=halo.exchange): retry once on the DENSE layout —
        # ladder step "halo_dense".  Same governed program family
        # (dist.analysis_grouped), dense variant; the hysteresis state
        # is left alone so a healthy next iteration can re-pick packed.
        from ..resilience.recover import ladder_step
        ladder_step("halo_dense", site="halo.exchange", detail=repr(e))
        dkey = (ids, angedg, KS, n_shards, G, None)
        if dkey in cache:
            fn = cache[dkey]
        else:
            fn = governed("dist.analysis_grouped", budget=2)(
                dist_analysis_grouped(dmesh, angedg, KS, G,
                                      packed_M=None))
            cache[dkey] = fn
        vt, et, ovf = fn(*args)
        ovf_host = int(ovf)
    if ovf_host != 0:
        return None
    # the seams moved: the normals the split carried are another
    # seam's, so every normal comes from its shard's fan again
    return dataclasses.replace(stacked, vtag=vt, etag=et,
                               vnrm=jnp.zeros_like(stacked.vnrm))


def refresh_shard_analysis(stacked: Mesh, comms, n_shards: int,
                           angedg: float, glo=None, views=None):
    """Cross-shard surface analysis refresh on ADAPTED shards — the
    production PMMG_update_analys analogue (analys_pmmg.c:1571): ridge /
    corner / reference classification is recomputed with cross-interface
    dihedrals (a shard cannot see the other side's face normals), then
    written back into the stacked shard tags before the merge.

    Interface slots are stable under adaptation (frozen entities are
    never collapsed and slots are not compacted in-cycle), so the
    split-time comm tables remain valid — the reference relies on the
    same invariant between migrations.
    """
    import dataclasses
    from ..core.constants import (
        MG_BDY, MG_CRN, MG_GEO, MG_NOM, MG_PARBDY, MG_REF)
    from .analysis_par import analyze_shards, extend_numbering

    capP = stacked.vert.shape[1]
    verts, tets, ftags, frefs, tms = [], [], [], [], []
    for s in range(n_shards):
        if views is not None:
            tm = views.tmask[s]
            verts.append(views.vert[s])
            tets.append(views.tet[s][tm].astype(np.int64))
            ftags.append(views.ftag[s][tm])
            frefs.append(views.fref[s][tm])
        else:
            tm = np.asarray(stacked.tmask[s])
            verts.append(np.asarray(stacked.vert[s]))
            tets.append(np.asarray(stacked.tet[s])[tm].astype(np.int64))
            ftags.append(np.asarray(stacked.ftag[s])[tm])
            frefs.append(np.asarray(stacked.fref[s])[tm])
        tms.append(tm)
    if glo is None:
        glo = extend_numbering(comms, [capP] * n_shards)
    vtag_add, special_edges, _ = analyze_shards(
        verts, tets, ftags, frefs, comms, angedg, glo=glo)

    CLS = np.uint32(MG_GEO | MG_CRN | MG_REF | MG_NOM)
    new_vtag = []
    new_etag = []
    for s in range(n_shards):
        vt = (views.vtag[s] if views is not None
              else np.asarray(stacked.vtag[s])).copy()
        add = vtag_add[s].astype(np.uint32)
        # re-derive the classification bits; never drop freeze/user bits
        vt = (vt & ~CLS) | (add & CLS) | (add & MG_BDY)
        new_vtag.append(vt)
        # edges: clear stale classification on plain boundary edges, then
        # re-apply the global special-edge set (vectorized keyed lookup)
        from ..core.constants import IARE
        et = (views.etag[s] if views is not None
              else np.asarray(stacked.etag[s])).copy()
        tm = tms[s]
        tth = (views.tet[s] if views is not None
               else np.asarray(stacked.tet[s])).astype(np.int64)
        evl = np.sort(tth[:, IARE], axis=2)[tm]            # [nt,6,2]
        live_rows = np.where(tm)[0]
        plain_bdy = ((et[tm] & MG_BDY) != 0) & ((et[tm] & MG_PARBDY) == 0)
        cleared = et[tm] & ~np.where(plain_bdy, CLS, np.uint32(0))
        rows = special_edges[s]
        if len(rows):
            ka = np.minimum(rows[:, 0], rows[:, 1]).astype(np.int64)
            kb = np.maximum(rows[:, 0], rows[:, 1]).astype(np.int64)
            skey = ka * capP + kb
            o = np.argsort(skey, kind="stable")
            sk, sb = skey[o], rows[:, 2][o].astype(np.uint32)
            heads = np.concatenate([[True], sk[1:] != sk[:-1]])
            uk = sk[heads]
            ub = np.bitwise_or.reduceat(sb, np.where(heads)[0]) \
                if len(sk) else sb
            ekey = evl[..., 0] * capP + evl[..., 1]        # [nt,6]
            loc = np.clip(np.searchsorted(uk, ekey), 0, len(uk) - 1)
            hit = uk[loc] == ekey
            cleared |= np.where(hit, ub[loc], 0).astype(np.uint32)
        et[live_rows] = cleared
        new_etag.append(et)
    if views is not None:
        # keep the host mirrors in sync (migration reads them next)
        for s in range(n_shards):
            views.vtag[s] = new_vtag[s]
            views.etag[s] = new_etag[s]
    return dataclasses.replace(
        stacked,
        vtag=jnp.asarray(np.stack(new_vtag)),
        etag=jnp.asarray(np.stack(new_etag)),
        vnrm=jnp.zeros_like(stacked.vnrm))      # as in the device form


# compiled quality-histogram programs keyed by device ids (compile
# governor, same rationale as _IFC_CHECK_CACHE below): dist_quality used
# to hand back a FRESH jax.jit object per call, so periodic quality
# reports recompiled the shard_map reduction every time — the last
# per-call jit builder the ROADMAP governor item names
_QUALITY_CACHE: dict = {}


def dist_quality(dmesh: DeviceMesh):
    """Global quality histogram across shards (PMMG_qualhisto analogue,
    quality_pmmg.c:156 — the custom MPI_Op reduction becomes psum/pmin).
    Cached per device mesh + registered in the compile ledger."""
    spec = P("shard")
    key = tuple(d.id for d in np.asarray(dmesh.devices).flat)
    cached = _QUALITY_CACHE.get(key)
    if cached is not None:
        return cached

    def local(mesh_s: Mesh, met_s):
        mesh = _unstack(mesh_s)
        met = met_s[0]
        q = tet_quality(mesh, met)
        counts, qmin, qmean, nbad = quality_histogram(q, mesh.tmask)
        n = jnp.sum(mesh.tmask.astype(jnp.int32))
        counts = jax.lax.psum(counts, "shard")
        qmin = jax.lax.pmin(qmin, "shard")
        qsum = jax.lax.psum(qmean * n, "shard")
        ntot = jax.lax.psum(n, "shard")
        nbad = jax.lax.psum(nbad, "shard")
        return counts, qmin, qsum / jnp.maximum(ntot, 1), nbad, ntot

    fn = shard_map(local, mesh=dmesh, in_specs=(spec, spec),
                   out_specs=(P(), P(), P(), P(), P()), check_vma=False)
    fn = governed("dist.quality", budget=2)(jax.jit(fn))
    _QUALITY_CACHE[key] = fn
    return fn


# compiled interface-echo programs keyed by (device ids, G): the echo
# runs once per outer iteration and after every migration, and a fresh
# jax.jit object per call would recompile the shard_map program every
# time even at identical shapes — the cache plus the bucketed comm-table
# pads (comms.pad_comm_tables) bound it to a handful of variants
_IFC_CHECK_CACHE: dict = {}


def check_interface_echo(stacked, met_s, comms, dmesh, vert_h, G: int = 1,
                         pack_state: dict | None = None):
    """On-device interface coordinate+metric echo (the production chkcomm
    guard, chkcomm_pmmg.c:815 role); raises on an ordering-contract
    violation.  G > 1 routes the exchange through the packed grouped
    layout when the measured occupancy says it beats the dense tile
    (comms.packed_halo_rows; ``pack_state`` makes the layout decision
    sticky across comm-table rebuilds — hysteresis)."""
    from .comms import packed_halo_rows
    Mp = packed_halo_rows(comms.nbr, G, state=pack_state) \
        if G > 1 else None
    key = (tuple(d.id for d in np.asarray(dmesh.devices).flat), G, Mp)
    chk = _IFC_CHECK_CACHE.get(key)
    if chk is None:
        chk = governed("dist.interface_check", budget=2)(
            dist_interface_check(dmesh, G=G, packed_M=Mp))
        _IFC_CHECK_CACHE[key] = chk
    diag = float(np.linalg.norm(vert_h.max(0) - vert_h.min(0))) \
        if len(vert_h) else 1.0
    nbad = int(chk(
        stacked, met_s,
        shard_stacked(jnp.asarray(comms.node_idx), dmesh),
        shard_stacked(jnp.asarray(comms.nbr), dmesh),
        jnp.asarray(1e-6 * diag, stacked.vert.dtype)))
    if nbad:
        raise RuntimeError(
            f"interface comm echo mismatch: {nbad} items "
            "(ordering contract violated)")


def run_adapt_cycles(stacked, met_s, steps: DistSteps, cycles,
                     dmesh, stats=None, verbose=0, on_grow=None,
                     regrow_state=None, label="dist", noswap=False,
                     it: int = 0, tally: dict | None = None):
    """Shared SPMD cycle loop: swap cadence (every 3rd cycle + the final
    two), psum'd counter accounting, and the in-place overflow regrow
    (zaldy_pmmg.c:140-254 analogue — slot ids preserved so comm tables
    stay valid).  Past MAX_SHARD_REGROWS doublings, degrades to a
    ShardOverflowError carrying the conforming merged state
    (failed_handling, libparmmg1.c:974-1011).

    One dispatch + one counter pull per cycle.

    ``on_grow(old_capP)`` lets the caller grow its side tables (global
    numbering) in lockstep; ``regrow_state`` is a 1-element mutable list
    carried across calls so repeated passes share the regrow budget.
    ``it`` names the outer iteration on the ``dist block`` spans;
    ``tally`` (:func:`_new_tally`) takes the dispatches, their seconds
    and the live tets and fullest shard the iteration started from.
    """
    from .distribute import merge_shards, grow_shards
    from .groups import block_schedule
    from .sched import device_mask_enabled, sched_enabled
    if regrow_state is None:
        regrow_state = [0]
    # device-resident quiet levels (the sched.py proof pushed into the
    # compiled block — dist_adapt_block docstring): int8 per logical
    # shard, never pulled to host.  With masking disabled the SAME
    # program runs with an all-zeros level every block (no skipping, no
    # new compile family).
    from ..ops.adapt import LISTED_COL, SURF_COLS, surface_scatter_width
    n_logical = stacked.tmask.shape[0]
    mask_on = sched_enabled() and device_mask_enabled()
    lvl = shard_stacked(jnp.zeros(n_logical, jnp.int8), dmesh)
    c = 0
    while c < cycles:
        # the grouped path's schedule: swaps every 3rd cycle and on the
        # final two, which also bypass the split prescreen
        swap, pre = block_schedule(c, cycles, noswap)
        step = steps.get(swap, pre, swap_inclusive=swap or noswap)
        # one span a dispatched block, dispatch to counter pull, with
        # the operations it applied (the grouped path's ``grp block``)
        with otrace.span("dist block", it=it, cycle=c) as sp:
            stacked, met_s, counts, nact, ovf, lvl2 = step(
                stacked, met_s, jnp.asarray(c, jnp.int32), lvl)
            if mask_on:
                lvl = lvl2
            # ONE host pull per array per block (the blessed .tolist()
            # idiom): the per-field int() casts each forced their own
            # device sync
            cs = counts.tolist()                     # [14]
            na = nact.tolist()                       # active groups
            surf = {k: cs[col] for k, col in SURF_COLS.items()}
            surf["listed"] = cs[LISTED_COL]
            sp.set(active=na, split=cs[0], collapse=cs[1], swap=cs[2],
                   moved=cs[3], live=cs[LIVE_IN_COL],
                   largest=cs[LARGEST_IN_COL],
                   prog=LEDGER.program_index(DIST_BLOCK_ENTRY), **surf)
        if tally is not None:
            tally["dispatches"] += 1
            tally["compute_s"] += sp.dur
            if c == 0:
                # the imbalance the iteration starts from
                tally["live_tets"] = cs[LIVE_IN_COL]
                tally["largest_shard"] = cs[LARGEST_IN_COL]
        n_logical = stacked.tmask.shape[0]
        if stats is not None:        # psum'd global counters
            stats.nsplit += cs[0]
            stats.ncollapse += cs[1]
            stats.nswap += cs[2]
            stats.nmoved += cs[3]
            stats.cycles += 1
            stats.add_surface(
                **surf, list_full=cs[LISTED_ROWS_COL] * surface_scatter_width(
                    stacked.tet.shape[1], steps.do_insert, steps.do_smooth,
                    steps.hausd))
            # per-group convergence trajectory (the SPMD mirror of
            # the grouped path's active_groups_per_block)
            stats.sched_extra.setdefault(
                "active_shards_per_cycle", []).append(na)
        otrace.log(3, f"  {label} cycle {c}: split {cs[0]} "
                      f"collapse {cs[1]} swap {cs[2]} move {cs[3]} "
                      f"active {na}/{n_logical} grp",
                   verbose=verbose)
        if ovf.tolist() != 0:
            if regrow_state[0] >= MAX_SHARD_REGROWS:
                m_, k_, p_ = merge_shards(stacked, met_s,
                                          return_part=True)
                raise ShardOverflowError(m_, k_, p_)
            capP = stacked.vert.shape[1]
            capT = stacked.tet.shape[1]
            stacked, met_s = grow_shards(stacked, met_s,
                                         2 * capP, 2 * capT)
            stacked = shard_stacked(stacked, dmesh)
            met_s = shard_stacked(met_s, dmesh)
            if on_grow is not None:
                on_grow(capP)
            regrow_state[0] += 1
            # every quiet proof is stale at the new capacity (the top-K
            # wave budgets scale with capT) — sched.on_regrow's rule
            lvl = shard_stacked(jnp.zeros(n_logical, jnp.int8), dmesh)
            continue        # re-run the block: truncated winners rerun
        c += 1
        # convergence: a swap-inclusive (or noswap) cycle on which
        # EVERY logical group posted zero topological ops ends the pass
        # (active_groups == 0 is exactly the summed-zero rule, read
        # from the per-group counts instead of the psum'd total)
        if (swap or noswap) and na == 0:
            break
    return stacked, met_s


def distributed_adapt(mesh: Mesh, met, n_shards: int,
                      cycles: int = 10, dmesh: DeviceMesh | None = None,
                      partitioner: str = "morton", verbose: int = 0,
                      part: np.ndarray | None = None, stats=None,
                      noinsert: bool = False, noswap: bool = False,
                      nomove: bool = False, angedg: float | None = None,
                      hausd: float | None = None):
    """One outer remesh pass on n_shards devices (host driver).

    partition (metric-weighted, boundary-refined; or take the caller's
    displaced ``part``) -> freeze interfaces -> on-device interface echo
    check -> SPMD adapt cycles -> cross-shard surface analysis refresh ->
    merge.  Returns (merged mesh, met, part_of_merged): the partition
    labels of the NEW tets (= source shard), which the caller displaces
    with ``move_interfaces`` before the next outer iteration — the
    remesh-and-repartition scheme of PMMG_parmmglib1/loadbalancing.
    """
    from ..core.mesh import tet_volumes, mesh_to_host
    from .partition import (morton_partition, greedy_partition,
                            fix_contiguity, metric_edge_weights,
                            refine_partition)
    from .distribute import split_to_shards, merge_shards
    from .multihost import require_single_process

    # host-side split/merge orchestration is single-controller today
    require_single_process("distributed_adapt host orchestration")
    if dmesh is None:
        dmesh = make_device_mesh(n_shards)

    vert, tet, vref, tref, vtag = mesh_to_host(mesh)
    if part is None:
        cent = vert[tet].mean(axis=1)
        if partitioner == "morton":
            part = morton_partition(cent, n_shards)
        else:
            part = greedy_partition(tet, cent, n_shards)
        part = fix_contiguity(tet, part)
        # metric-aware cut refinement (PMMG_computeWgt role,
        # metis_pmmg.c:280): keep the interface out of regions whose
        # edges are far from unit metric length
        methost = np.asarray(met)[np.asarray(mesh.vmask)]
        wd = metric_edge_weights(tet, vert, methost)
        part = fix_contiguity(tet, refine_partition(
            part, n_shards, wd["pairs"], wd["w"]))

    steps = DistSteps(dmesh, do_smooth=not nomove,
                      do_insert=not noinsert, hausd=hausd)
    vert_h, tet_h = vert, tet
    s, ms, l2g = split_to_shards(mesh, met, part, n_shards,
                                 cap_mult=3.0, return_l2g=True)
    stacked = shard_stacked(s, dmesh)
    met_s = shard_stacked(ms, dmesh)
    # comm tables (communicators_pmmg.c role) + the on-device interface
    # echo: exchange interface coordinates+metric over halo_exchange and
    # require exact mirror agreement — the production chkcomm guard for
    # the ordering contract
    from .comms import build_interface_comms
    g2l = []
    for s_ in range(n_shards):
        mmap = np.full(len(vert_h), -1, np.int64)
        mmap[l2g[s_]] = np.arange(len(l2g[s_]))
        g2l.append(mmap)
    comms = build_interface_comms(tet_h, part, n_shards, l2g, g2l)
    check_interface_echo(stacked, met_s, comms, dmesh, vert_h)
    stacked, met_s = run_adapt_cycles(
        stacked, met_s, steps, cycles, dmesh,
        stats=stats, verbose=verbose, noswap=noswap)
    # cross-shard surface analysis refresh (PMMG_update_analys analogue)
    # BEFORE the merge: ridge/corner/ref classification with
    # cross-interface dihedrals, written into the shard tags so the
    # merged mesh needs no whole-mesh re-analysis.  Device-resident path
    # first (analysis_dev.py); host fallback on budget overflow.
    from ..core.constants import ANGEDG
    from .analysis_par import extend_numbering
    ang_ = ANGEDG if angedg is None else angedg
    capP_ = stacked.vert.shape[1]
    glo_ = extend_numbering(comms, [capP_] * n_shards)
    st2 = refresh_shard_analysis_device(stacked, comms, n_shards, ang_,
                                        glo_, dmesh)
    if st2 is not None:
        stacked = st2
    else:
        from ..resilience.recover import ladder_step
        ladder_step("host_analysis", site="analysis.ks_overflow")
        stacked = refresh_shard_analysis(stacked, comms, n_shards, ang_,
                                         glo=glo_)
    merged, met_m, part_new = merge_shards(stacked, met_s,
                                           return_part=True)
    return merged, met_m, part_new


def _clears_pass_tag(fn):
    """The outer loop tags every trace record with its ``pass`` in the
    process-wide context; an exception unwinding it (ShardOverflowError
    degrade, device OOM) must not leave the tag on the records of the
    caller's tail."""
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        try:
            return fn(*args, **kwargs)
        finally:
            otrace.set_context(**{"pass": None})
    return wrapper


@_clears_pass_tag
def distributed_adapt_multi(mesh: Mesh, met, n_shards: int,
                            niter: int = 3, cycles: int = 10,
                            dmesh: DeviceMesh | None = None,
                            partitioner: str = "morton", verbose: int = 0,
                            stats=None, noinsert: bool = False,
                            noswap: bool = False, nomove: bool = False,
                            angedg: float | None = None,
                            hausd: float | None = None,
                            ifc_layers: int = 2,
                            nobalancing: bool = False,
                            part: np.ndarray | None = None,
                            mode: str = "ifc",
                            n_devices: int | None = None,
                            ckpt_tag: str | None = None,
                            resume: bool = False, timers=None):
    """Shard-resident multi-iteration adaptation (host driver).

    What runs BETWEEN two iterations is staged on the host's CPU backend
    where the job is placed on a TPU, in one process, and no handoff is
    asked for (``host_between``; utils/placement.py's rule, for the same
    reason: compile time).  Each of those programs runs once an
    iteration, and
    the TPU's compiler takes 280 s for the grouped analysis, 115 s for
    the flood's three and 100-180 s for the migration's four at the
    benchmark's shard shape (PERF.md, PR 41) where XLA:CPU takes seconds
    for all of them: the shards are pulled after an iteration's blocks,
    analysed (the numpy form, ``refresh_shard_analysis``), displaced and
    migrated there by the same programs on one host device, and go back
    to their devices for the echo check and the next blocks.  Observed
    (``placed_on_tpu``, ``pod.handoff_enabled``), not asked for.

    ``timers``: the driver's Timers; every iteration folds its blocks'
    dispatch-to-pull seconds in as ``grp compute``, as a grouped pass
    does (a rank's groups are what the blocks compute here too).

    ``n_devices``: groups x shards composition (default = ``n_shards``,
    i.e. one logical shard per device).  With ``n_devices`` <
    ``n_shards``, G = n_shards // n_devices logical shards live on each
    device (leading-axis sharding, G consecutive rows per device); the
    adapt block serializes them with ``lax.map`` so peak HBM per chip is
    bounded by one group's wave working set — the reference's rank-level
    x group-level two-level decomposition (grpsplit_pmmg.c:1551-1614).
    The band-migration and flood programs already operate on the logical
    leading axis (plain jit over sharded arrays) and compose unchanged;
    the analysis refresh dispatches the grouped device program
    (analysis_dev.dist_analysis_grouped) for G > 1, host path on
    KS-budget overflow only.

    ``mode``: between-iteration label source — "ifc" = advancing-front
    interface displacement (device flood, the default repartitioning of
    the reference, libparmmgtypes.h:194); "graph" = group-graph
    repartitioning (morton clusters as the reference's redistribution
    groups, weighted KL/FM on the cluster graph —
    migrate.graph_repartition_labels, metis_pmmg.c:845-1550 role).
    Both realize the moves with the SAME band-migration machinery, so
    neither merges the world between iterations.

    The reference's outer loop re-balances by migrating only moving
    groups over the wire (loadbalancing_pmmg.c:44-161 +
    distributegrps_pmmg.c:1631-1841); the round-1 TPU path instead merged
    the WORLD through host memory every outer iteration.  This driver is
    the incremental redesign: ONE split, then per iteration

        SPMD adapt cycles (device)  ->  cross-shard analysis refresh  ->
        advancing-front labels (device flood)  ->  band migration
        (O(band) host, sparse device scatters)  ->  comm echo check

    and ONE merge at final output.  No full-mesh merge_shards happens
    between iterations — the VERDICT r1 #5 contract.

    Returns (merged mesh, met, part_of_merged).
    """
    from ..core.mesh import mesh_to_host
    from ..core.constants import ANGEDG
    from .partition import (morton_partition, greedy_partition,
                            fix_contiguity, metric_edge_weights,
                            refine_partition)
    from .distribute import split_to_shards, merge_shards
    from .comms import build_interface_comms
    from . import pod
    from .migrate import (pull_views, extend_global_ids_from_vmask,
                          flood_labels, enforce_ne_min, migrate_shards,
                          rebuild_shards, weld_shard_bands,
                          graph_repartition_labels, apply_fresh_ids,
                          kill_glo_rows)
    from .multihost import (require_single_process, pull_host as _pull,
                            is_multiprocess, hot_path, cold_io,
                            mh_uniform)

    # Multi-process contract (round 4, the mpi_pmmg.h role): every
    # process runs THIS SAME driver on the SAME input mesh (identical
    # split + comm tables — the deterministic-host-stage SPMD idiom);
    # device arrays are global ('shard'-sharded across processes via
    # shard_stacked_global), band-table host pulls replicate through
    # pull_host (DCN allgather of band-sized data), and every process
    # computes identical host decisions — the reference's
    # every-rank-agrees design (MPI_Allreduce on ier/counters).  The
    # full-view fallback paths are NOT distributed: they raise below
    # rather than silently pulling a partial world view.
    multi = is_multiprocess()
    if n_devices is None:
        n_devices = n_shards
    if n_shards % n_devices:
        raise ValueError(
            f"n_shards={n_shards} must be a multiple of "
            f"n_devices={n_devices} (G logical shards per device)")
    G = n_shards // n_devices
    if dmesh is None:
        dmesh = make_device_mesh(n_devices)
    ang = ANGEDG if angedg is None else angedg

    from ..utils.placement import host_staging
    tally = _new_tally()
    with otrace.span("dist split", shards=n_shards, G=G) as sp:
        vert_h, tet_h, vref_h, tref_h, vtag_h = mesh_to_host(mesh)
        if part is None:
            cent = vert_h[tet_h].mean(axis=1)
            if partitioner == "morton":
                part = morton_partition(cent, n_shards)
            else:
                part = greedy_partition(tet_h, cent, n_shards)
            part = fix_contiguity(tet_h, part)
            methost = np.asarray(met)[np.asarray(mesh.vmask)]
            wd = metric_edge_weights(tet_h, vert_h, methost)
            part = fix_contiguity(tet_h, refine_partition(
                part, n_shards, wd["pairs"], wd["w"]))

        # split and merge run at whole-mesh width: staged on the host
        # like the grouped pass's (utils/placement.py); the shards go to
        # their devices from there
        with host_staging():
            s0, ms0, l2g = split_to_shards(mesh, met, part, n_shards,
                                           cap_mult=3.0, return_l2g=True)
        stacked = shard_stacked(s0, dmesh)
        met_s = shard_stacked(ms0, dmesh)
        capP0 = stacked.vert.shape[1]
        g2l = []
        for s_ in range(n_shards):
            mmap = np.full(len(vert_h), -1, np.int64)
            mmap[l2g[s_]] = np.arange(len(l2g[s_]))
            g2l.append(mmap)
        comms = build_interface_comms(tet_h, part, n_shards, l2g, g2l)
        sp.set(capP=capP0, capT=stacked.tet.shape[1],
               largest=np.bincount(part, minlength=n_shards).max().tolist())
    # persistent global vertex numbering: split-time ids, extended with
    # fresh ids for adapt-created vertices each pass (the
    # PMMG_Compute_verticesGloNum role, libparmmg.c:923)
    glo = [np.full(capP0, -1, np.int64) for _ in range(n_shards)]
    for s_ in range(n_shards):
        glo[s_][: len(l2g[s_])] = l2g[s_]
    top = len(vert_h)

    # ---- per-pass checkpoint/resume (the pod restart unit) -------------
    # worker crash/stall is the EXPECTED failure mode at pod scale
    # (parallel/pod.py): the run re-launches with resume=True and
    # re-enters the loop at the pass after the newest checkpoint —
    # bit-identical to the uninterrupted run (passes are deterministic
    # functions of their input state)
    it0 = 0
    regrow0 = 0
    ckpt_fp = None
    resumed_shared = None
    if ckpt_tag is not None:
        from ..resilience.checkpoint import run_fingerprint
        ckpt_fp = run_fingerprint(
            mesh, met, "dist", n_shards, n_devices, niter, cycles,
            mode, ifc_layers, bool(noswap), bool(noinsert),
            bool(nomove), bool(nobalancing))
    if resume and ckpt_tag is not None:
        from ..obs.metrics import REGISTRY as _REG
        from ..resilience.checkpoint import (latest_dist_checkpoint,
                                             load_dist_checkpoint)
        found = latest_dist_checkpoint(ckpt_tag, ckpt_fp)
        if multi:
            # the resume point is read from each process's LOCAL
            # filesystem: ranks silently re-entering at different
            # passes would execute different collective sequences (the
            # worst failure shape — a hang or a wrong mesh, not an
            # error).  Agree loudly up front: every rank announces its
            # newest pass and they must all match, which also documents
            # the shared-storage requirement of PARMMG_CKPT_DIR.
            from jax.experimental import multihost_utils
            mine = -1 if found is None else found[1]
            # lint: ok(R7) — pre-loop resume agreement on 4 bytes per
            # rank, outside the hot path by construction
            seen = np.asarray(multihost_utils.process_allgather(
                np.asarray([mine], np.int32))).reshape(-1)
            if int(seen.min()) != int(seen.max()):
                raise RuntimeError(
                    f"dist resume diverges across processes (newest "
                    f"checkpointed pass per rank: {seen.tolist()}) — "
                    "PARMMG_CKPT_DIR must be shared storage visible "
                    "to every worker")
        if found is not None:
            payload = load_dist_checkpoint(found[0])
            stacked = shard_stacked(Mesh(
                **{k: jnp.asarray(v)
                   for k, v in payload["stacked"].items()}), dmesh)
            met_s = shard_stacked(jnp.asarray(payload["met"]), dmesh)
            glo = payload["glo"]
            top = payload["top"]
            comms = payload["comms"]
            resumed_shared = payload["shared_prev"]
            regrow0 = payload["regrow"]
            it0 = payload["it"] + 1
            _REG.counter("resilience.resumes").inc()
            otrace.log(1, f"  resuming dist loop at pass {it0} "
                          f"(checkpoint {found[0]})", verbose=verbose)
            # crash-loop breaker: a pass that deterministically kills
            # its worker must not be resumed forever.  The attempt
            # count lives next to the checkpoints (shared storage at
            # pod scale) — only rank 0 writes it, and the escalate
            # decision is agreed across ranks so every worker skips
            # the same passes.
            from ..resilience.checkpoint import crash_loop
            _, esc = crash_loop(
                ckpt_tag, ckpt_fp, it0,
                write=mh_uniform(
                    (not multi) or jax.process_index() == 0,
                    "rank-0-writes: the attempt file lives on shared "
                    "storage, so only process 0 appends; the escalate "
                    "decision itself is re-agreed right below via "
                    "process_allgather(max), every rank skips the "
                    "same passes"))
            if multi:
                from jax.experimental import multihost_utils
                # lint: ok(R7) — pre-loop resume agreement on 4 bytes
                # per rank, outside the hot path by construction
                esc_all = np.asarray(multihost_utils.process_allgather(
                    np.asarray([1 if esc else 0], np.int32))).reshape(-1)
                esc = bool(esc_all.max())
            if esc:
                from ..resilience.recover import ladder_step
                ladder_step(
                    "lowfailure", site="ckpt.resume",
                    detail=f"dist crash loop at pass {it0}: returning "
                           "last conforming checkpoint state")
                # skip the adapt loop entirely: the restored state IS
                # the last conforming answer; the post-loop merge hands
                # it back (graded-failure contract, PMMG_LOWFAILURE)
                it0 = max(1, niter)

    # sticky dense/packed halo-layout decision across comm-table
    # rebuilds (comms.packed_halo_rows hysteresis): ONE state dict
    # threaded through every packed-layout decision of this run
    pack_state: dict = {}
    # a vertex's coordinates and metric, float32, a valid slot
    echo_width = 4 * (3 + int(np.prod(met_s.shape[2:], dtype=np.int64)))
    host_between = placed_on_tpu() and not multi \
        and not pod.handoff_enabled()
    between = host_staging if host_between else contextlib.nullcontext
    host0 = jax.local_devices(backend="cpu")[0]
    # the shards staged on the host and their last copy on the devices:
    # (the staged Mesh it is a copy of, stacked, met_s)
    staged, uploaded = [False], [None]

    def on_devices():
        """``(stacked, met_s)`` on the device mesh."""
        if not staged[0]:
            return stacked, met_s
        if uploaded[0] is None or uploaded[0][0] is not stacked:
            uploaded[0] = (stacked, shard_stacked(stacked, dmesh),
                           shard_stacked(met_s, dmesh))
        return uploaded[0][1:]

    def place_between(x):
        """``x`` where the programs between two iterations run."""
        return jax.device_put(x, host0) if host_between \
            else shard_stacked(x, dmesh)

    def echo_check():
        check_interface_echo(*on_devices(), comms, dmesh, vert_h, G=G,
                             pack_state=pack_state)
        tally["exchange_bytes"] += _halo_bytes(comms, echo_width)

    echo_check()

    steps = DistSteps(dmesh, do_smooth=not nomove,
                      do_insert=not noinsert, hausd=hausd, G=G)

    def grow_glo(old_capP):
        # keep the global-numbering tables in lockstep with a device
        # regrow (slot-stable pad)
        for s_ in range(n_shards):
            glo[s_] = np.concatenate(
                [glo[s_], np.full(old_capP, -1, np.int64)])

    # ---- O(band) device path state --------------------------------------
    # the band path keeps the numbering ON DEVICE (int32 lockstep copy)
    # and replaces the full views pull + host interface rescan with
    # device-compacted band/interface tables (parallel/migrate_dev.py);
    # any budget overflow falls back to the full-view oracle path below
    import os as _os
    # both repartitioning modes ride the band path (graph mode since
    # round 4: cluster graph from device-compacted tables,
    # migrate_dev.graph_repartition_labels_band)
    use_band = _os.environ.get("PARMMG_BAND_PATH", "1") != "0"
    if multi and not use_band:
        raise NotImplementedError(
            "multi-process runs require the band path (the full-view "
            "loop is single-controller)")
    glo_d = None
    shared_prev = None

    def upload_glo():
        """The copy of the host numbering ``glo`` the migration programs
        take, COMMITTED as they hand it back (over 'shard', or to the
        host device they are staged on): an uncommitted
        upload made ``device_migrate`` lower and compile a second
        executable for the same shapes (compilecache, placement
        variants; PERF.md, PR 31)."""
        return place_between(np.stack(glo).astype(np.int32))

    if use_band:
        from .migrate_dev import (extend_ids_device, band_migrate_iteration,
                                  band_weld, session_ids_fit,
                                  dead_glo_rows)
        glo_d = upload_glo()
        # initially-shared gids: interface vertices of the initial comms
        # (a resumed run restores the exact set its checkpoint carried)
        shared_prev = resumed_shared if resumed_shared is not None \
            else _shared_gids(comms, glo, n_shards)

    regrow_state = [regrow0]
    # ---- the pod hot path -------------------------------------------
    # every iteration body runs inside multihost.hot_path(): a stray
    # process_allgather in there is metered on mh.hot_allgather_bytes
    # (run_tests.sh --multihost asserts ZERO) and raises under
    # PARMMG_MH_STRICT; pod.activate feeds pod.gather_band the device
    # topology its cached exchange programs key on
    with pod.activate(dmesh, n_shards), hot_path():
        for it in range(it0, max(1, niter)):
            # pass tag on every trace record emitted inside this outer
            # iteration (obs/trace.py)
            otrace.set_context(**{"pass": it})
            capP_before = stacked.vert.shape[1]
            done = (tally["compute_s"], tally["dispatches"])
            stacked, met_s = run_adapt_cycles(
                stacked, met_s, steps, cycles, dmesh,
                stats=stats, verbose=verbose, on_grow=grow_glo,
                regrow_state=regrow_state, label=f"dist it {it}",
                noswap=noswap, it=it, tally=tally)
            # the devices that hold a live shard: a job whose shards ended
            # on fewer than it asked for did not run there
            tally["devices"] = len({
                sh.device.id for sh in stacked.tmask.addressable_shards
                if np.asarray(sh.data).any()})
            if timers is not None:
                timers.add("grp compute", tally["compute_s"] - done[0],
                           tally["dispatches"] - done[1])
            # where the programs between two iterations run: on the
            # devices, or staged on the host (``host_between``)
            with between():
                with otrace.span("dist refresh", it=it) as span_ref:
                    if host_between:
                        # ONE pull of every shard; the copies stay
                        # committed to the host's device
                        stacked, met_s = jax.device_put(
                            jax.device_get((stacked, met_s)), host0)
                        staged[0] = True
                        span_ref.set(pull_bytes=sum(
                            a.nbytes for a in
                            jax.tree.leaves((stacked, met_s))))
                    if use_band and stacked.vert.shape[1] != capP_before:
                        glo_d = None          # regrown: rebuild the device copy
                    # extend the session numbering (device on the band path, with a
                    # band-sized fresh-id pull; vmask-pull host path otherwise),
                    # then the DEVICE analysis refresh
                    if use_band:
                        if glo_d is None:
                            glo_d = upload_glo()
                        KN = max(256, stacked.vert.shape[1] // 2)
                        # int32 numbering on device (documented migrate_dev limit):
                        # the monotone session counter must not wrap — if this
                        # iteration could hand out ids past int31, take the host
                        # path (which re-derives a compact numbering) instead of
                        # silently aliasing device ids
                        ids_fit = session_ids_fit(top, n_shards, KN)
                        oke = False
                        if ids_fit:
                            # newly-dead delta FIRST: the pre-extend numbering
                            # still carries the dying rows' ids, so (glo >= 0 &
                            # ~vmask) is exactly the band-sized kill list the host
                            # mirror needs — the O(mesh) vmask allgather of the
                            # pre-pod path is gone (migrate_dev.dead_glo_rows)
                            d_rows, d_cnt, d_ok = dead_glo_rows(
                                glo_d, stacked.vmask, KD=KN)
                            glo_d2, top_d, f_rows, f_gids, oke = extend_ids_device(
                                glo_d, stacked.vmask, jnp.asarray(top, jnp.int32),
                                KN=KN)
                            oke = bool(oke) and bool(d_ok)
                        if ids_fit and oke:
                            glo_d = glo_d2
                            top = int(top_d)
                            # ONE packed band exchange replicates the compacted
                            # fresh-id + dead-delta tables to every process
                            f_rows, f_gids, d_rows, d_cnt = pod.gather_band(
                                f_rows, f_gids, d_rows, d_cnt, what="extend")
                            apply_fresh_ids(glo, f_rows, f_gids)
                            kill_glo_rows(glo, d_rows, d_cnt)
                        else:               # fresh-id/dead budget blown: host extend
                            # lint: ok(R7) — documented escape hatch (budget
                            # overflow): the O(mesh) mask pull is metered by
                            # pull_host and visible on mh.allgather_bytes
                            vmask_h = _pull(stacked.vmask, what="host_extend")
                            top = extend_global_ids_from_vmask(glo, vmask_h, top)
                            if top >= 2 ** 31:
                                # the int32 device numbering can no longer represent
                                # the session ids: permanently leave the band path
                                # (the host path carries int64 ids) instead of
                                # wrapping them on the next device cast
                                use_band = False
                                glo_d = None
                            else:
                                glo_d = upload_glo()
                    else:
                        # lint: ok(R7) — legacy full-view path (PARMMG_BAND_PATH=0,
                        # single-controller only); metered by pull_host
                        vmask_h = _pull(stacked.vmask, what="legacy_extend")
                        top = extend_global_ids_from_vmask(glo, vmask_h, top)
                    # device analysis refresh: per-device shard_map for G=1, the
                    # grouped lax.map program for G>1 (analysis_dev) — the host
                    # path below is the KS-budget-overflow fallback ONLY, so the
                    # steady-state G>1 loop performs zero O(mesh) host pulls
                    views = None
                    st2 = None if host_between else \
                        refresh_shard_analysis_device(
                            stacked, comms, n_shards, ang, glo, dmesh,
                            pack_state=pack_state)
                    if st2 is not None:
                        stacked = st2
                    elif host_between:
                        # staged: the numpy form, by design and not as a
                        # rung of the ladder
                        views = pull_views(stacked, met_s)
                        stacked = refresh_shard_analysis(
                            stacked, comms, n_shards, ang, glo=glo,
                            views=views)
                    else:
                        if multi:
                            # no ladder event here: the fallback is NOT taken on
                            # the multi-process path — recording host_analysis and
                            # then dying would log a recovery that never happened
                            raise NotImplementedError(
                                "analysis host fallback needs a full-view pull — "
                                "not distributed; raise the KS budget or run "
                                "single-process")
                        # host fallback (shared-record budget overflow) — the
                        # "host_analysis" escalation-ladder rung
                        from ..resilience.recover import ladder_step
                        ladder_step("host_analysis", site="analysis.ks_overflow")
                        views = pull_views(stacked, met_s)
                        stacked = refresh_shard_analysis(
                            stacked, comms, n_shards, ang, glo=glo, views=views)
                    # the refresh's exchange: four words a valid slot
                    # (staged, the shards meet on the host and exchange
                    # nothing)
                    if not host_between:
                        tally["exchange_bytes"] += _halo_bytes(comms, 16)
                if it + 1 < max(1, niter) and not nobalancing:
                    nmoved = 0
                    band_done = False
                    if use_band:
                        with otrace.span("dist displace", it=it,
                                         layers=ifc_layers):
                            from .migrate_dev import (repair_flood_labels,
                                                      graph_repartition_labels_band)
                            if mode == "graph":
                                # cluster-graph rebalance from device tables (the
                                # metis_pmmg.c:845-1550 gather-only-the-graph role);
                                # depth 0 everywhere — the donor floor still bounds
                                # per-shard departures, order within a shard is
                                # immaterial for cluster moves
                                labels_d = graph_repartition_labels_band(
                                    stacked, comms, n_shards, verbose=verbose)
                                depth_d = jnp.zeros(stacked.tmask.shape, jnp.int32)
                                if labels_d is None:
                                    labels_d = jnp.broadcast_to(
                                        jnp.arange(n_shards, dtype=jnp.int32)[:, None],
                                        stacked.tmask.shape)
                            else:
                                sizes = jnp.sum(stacked.tmask, axis=1,
                                                dtype=jnp.int32)
                                labels_d, depth_d = flood_labels(
                                    stacked, jnp.asarray(comms.node_idx),
                                    jnp.asarray(comms.nbr), sizes, n_shards,
                                    nlayers=ifc_layers)
                                # contiguity/reachability repair on the displaced
                                # partition (moveinterfaces_pmmg.c:475-720 role)
                                labels_d, _nfix = repair_flood_labels(
                                    stacked, labels_d, depth_d, n_shards,
                                    verbose=verbose)
                    mh_band = REGISTRY.counter("mh.band_exchange_bytes")
                    mh0 = mh_band.value
                    with otrace.span("dist migrate", it=it) as span_mig:
                        if use_band:
                            res = band_migrate_iteration(
                                stacked, met_s, glo_d, glo, labels_d, depth_d,
                                shared_prev, n_shards, verbose=verbose)
                            # capacity/budget overflow: slot-stable grow (the full
                            # path's migrate_shards grow loop analogue) raises both
                            # the free slots AND the capacity-scaled band budgets;
                            # bounded retries before the full-view fallback
                            for _retry in range(3):
                                if res is not None:
                                    break
                                from .distribute import grow_shards
                                capP_o = stacked.vert.shape[1]
                                capT_o = stacked.tet.shape[1]
                                stacked, met_s = grow_shards(
                                    stacked, met_s, 2 * capP_o, 2 * capT_o)
                                views = None    # any pre-grow pull is shape-stale
                                grow_glo(capP_o)
                                glo_d = upload_glo()
                                me_col = jnp.arange(n_shards,
                                                    dtype=labels_d.dtype)[:, None]
                                labels_d = jnp.concatenate(
                                    [labels_d, jnp.broadcast_to(
                                        me_col, (n_shards, capT_o))], axis=1)
                                depth_d = jnp.concatenate(
                                    [depth_d, jnp.zeros((n_shards, capT_o),
                                                        depth_d.dtype)], axis=1)
                                res = band_migrate_iteration(
                                    stacked, met_s, glo_d, glo, labels_d, depth_d,
                                    shared_prev, n_shards, verbose=verbose)
                            if res is not None:
                                (stacked, met_s, glo_d, comms2, shared_prev,
                                 nmoved, arr_slots) = res
                                band_done = True
                                if nmoved:
                                    comms = comms2
                                    # weld the arrival neighborhoods (region-scoped)
                                    stacked, glo_d, nweld = band_weld(
                                        stacked, met_s, glo_d, glo, arr_slots,
                                        n_shards, verbose=verbose)
                                    if nweld < 0:     # region budget blown: full weld
                                        if multi:
                                            # fail loudly (the designed
                                            # contract) instead of the opaque
                                            # non-addressable fetch error
                                            # pull_views would raise
                                            raise NotImplementedError(
                                                "full-region weld fallback is "
                                                "single-controller; band_weld'"
                                                "s escalating probe must hold "
                                                "on a multi-process run")
                                        views_w = pull_views(stacked, met_s)
                                        stacked, _ = weld_shard_bands(
                                            stacked, views_w, glo, n_shards,
                                            verbose=verbose)
                                        # the full weld freed host-glo rows; the
                                        # device copy must drop them too (stale
                                        # gids resurrect — see band_weld)
                                        glo_d = upload_glo()
                                    stacked = rebuild_shards(stacked)
                                    echo_check()
                            else:
                                otrace.log(1, f"  it {it}: band budgets exceeded — "
                                              "falling back to the full-view path",
                                           verbose=verbose)
                        if not band_done:
                            if multi:
                                raise NotImplementedError(
                                    "full-view migration fallback is "
                                    "single-controller; band budgets must hold on "
                                    "a multi-process run")
                            if views is None:
                                views = pull_views(stacked, met_s)
                            if mode == "graph":
                                labels = graph_repartition_labels(views, glo,
                                                                  n_shards)
                                labels = enforce_ne_min(labels, views.tmask,
                                                        n_shards)
                            else:
                                from .migrate_dev import repair_flood_labels
                                sizes = jnp.asarray(
                                    views.tmask.sum(axis=1).astype(np.int32))
                                labels_d, depth_d = flood_labels(
                                    stacked, jnp.asarray(comms.node_idx),
                                    jnp.asarray(comms.nbr), sizes, n_shards,
                                    nlayers=ifc_layers)
                                labels_d, _nfix = repair_flood_labels(
                                    stacked, labels_d, depth_d, n_shards,
                                    verbose=verbose)
                                labels = np.asarray(labels_d)
                                labels = enforce_ne_min(labels, views.tmask,
                                                        n_shards,
                                                        depth=np.asarray(depth_d))
                            touched = sorted({int(r) for s_ in range(n_shards)
                                              for r in np.unique(
                                                  labels[s_][views.tmask[s_]])
                                              if int(r) != s_})
                            stacked, met_s, comms2, nmoved = migrate_shards(
                                stacked, met_s, views, glo, labels, n_shards,
                                verbose=verbose)
                            if nmoved:
                                comms = comms2
                                stacked, _ = weld_shard_bands(
                                    stacked, views, glo, n_shards,
                                    touched=touched, verbose=verbose)
                                stacked = rebuild_shards(stacked)
                                echo_check()
                            if use_band:    # resync the device numbering copy
                                glo_d = upload_glo()
                                shared_prev = _shared_gids(comms, glo, n_shards)
                        if nmoved:
                            otrace.log(2, f"  it {it}: migrated {nmoved} "
                                          "interface-band tets", verbose=verbose)
                        # host-to-host group handoff (pod runtime, opt-in knob
                        # PARMMG_MH_HANDOFF): when device loads skew past the
                        # imbalance threshold, whole logical shards move to other
                        # devices — and thereby other processes — as one compiled
                        # permutation; comm tables + numbering mirrors remap in
                        # lockstep (parallel/pod.py).  gids are unchanged under a
                        # permutation, so shared_prev needs no update.
                        if pod.handoff_enabled() and use_band and glo_d is not None:
                            (stacked, met_s, glo_d, glo, comms,
                             nmv_h) = pod.maybe_handoff(stacked, met_s, glo_d, glo,
                                                        comms, verbose=verbose)
                            if nmv_h:
                                echo_check()
                        # what crossed between shards: every moved tet with its
                        # four vertices' rows, and the band tables the hosts
                        # exchanged (pod.gather_band)
                        moved_bytes = nmoved * _moved_tet_bytes(met_s) + int(
                            mh_band.value - mh0)
                        span_mig.set(moved_tets=nmoved, bytes=moved_bytes,
                                     band_rows=int((comms.node_idx >= 0).sum()))
                        tally["migrated_tets"] += nmoved
                        tally["exchange_bytes"] += moved_bytes
                        if staged[0]:
                            # back to their devices for the next blocks
                            span_mig.set(push_bytes=sum(
                                a.nbytes for a in
                                jax.tree.leaves((stacked, met_s))))
                            stacked, met_s = on_devices()
                            staged[0] = False
                if staged[0] and it + 1 < max(1, niter):
                    # nothing migrated them back (``nobalancing``)
                    stacked, met_s = on_devices()
                    staged[0] = False
            if ckpt_tag is not None:
                from ..core.mesh import MESH_FIELDS
                from ..resilience.checkpoint import (ckpt_due,
                                                     save_dist_checkpoint)
                if ckpt_due(it):
                    # durable-output replication is the designed cost of
                    # the checkpoint path, not a stray hot-loop allgather:
                    # every process participates in the collective pull
                    # (cold_io exempts it from the hot meter), process 0
                    # writes the file
                    with cold_io():
                        # lint: ok(R7) — checkpoint IO replication under
                        # cold_io (module-documented escape hatch)
                        sh_host = {f: _pull(getattr(stacked, f))
                                   for f in MESH_FIELDS}
                        # lint: ok(R7) — same checkpoint IO section
                        met_host = _pull(met_s)
                        save_dist_checkpoint(
                            ckpt_tag, it, sh_host, met_host, glo, top,
                            comms,
                            shared_prev if shared_prev is not None
                            else np.zeros(0, np.int64),
                            regrow_state[0], fingerprint=ckpt_fp,
                            write=mh_uniform(
                                (not multi)
                                or jax.process_index() == 0,
                                "rank-0-writes: every rank computed "
                                "the identical checkpoint payload "
                                "(the cold_io collective pull above "
                                "replicated it); process 0 durably "
                                "writes, the others only needed the "
                                "agreement"))
    otrace.set_context(**{"pass": None})
    with otrace.span("dist merge") as sp:
        if multi:
            # final output: replicate the (end-state) shards to every
            # process and merge identically everywhere — the
            # centralized-output analogue of PMMG_parmmglib_centralized's
            # gather (the distributed-output entry, io.distributed, writes
            # per-process rank files instead and never pays this gather).
            # OUTSIDE the hot path: this is the one designed O(mesh)
            # replication of a centralized run, visible on
            # mh.allgather_bytes but never on the hot counter.
            # lint: ok(R7) — the documented final-output gather
            stacked = jax.tree.map(_pull, stacked)
            # lint: ok(R7) — same final-output gather
            met_s = _pull(met_s)
        else:
            # one pull per leaf (merge_shards slices per shard, which on
            # sharded device arrays is a program plus a pull per field)
            stacked, met_s = jax.tree.map(np.asarray, (stacked, met_s))
        with host_staging():
            merged, met_m, part_new = merge_shards(stacked, met_s,
                                                   return_part=True)
        sp.set(ne=len(part_new))
    _publish_tally(tally, stats)
    return merged, met_m, part_new


def _new_tally() -> dict:
    """What one SPMD job counts for its ``dist.*`` counters."""
    return {"dispatches": 0, "compute_s": 0.0, "exchange_bytes": 0,
            "migrated_tets": 0, "live_tets": 0, "largest_shard": 0,
            "devices": 0}


def _publish_tally(tally: dict, stats=None) -> None:
    """A job's ``dist.*`` counters, zeros too (the grouped path's
    ``groups.*``): block dispatches and their dispatch-to-pull seconds,
    bytes that crossed between shards (halo exchanges, band migration,
    the hosts' band tables), tets the displacement moved, the live tets
    and the fullest shard at the last iteration's entry, and the
    distinct devices that held a live shard after the last blocks."""
    REGISTRY.counter("dist.dispatches").inc(tally["dispatches"])
    REGISTRY.counter("dist.pipeline.compute_s").inc(tally["compute_s"])
    for k in ("exchange_bytes", "migrated_tets", "live_tets",
              "largest_shard", "devices"):
        # lint: ok(R6) — k ranges over the five literals above
        REGISTRY.counter(f"dist.{k}").inc(tally[k])
    if stats is not None:
        stats.sched_extra["dist_devices"] = tally["devices"]


def _halo_bytes(comms, width: int) -> int:
    """Bytes one halo exchange moves: ``width`` a valid interface slot."""
    return int((np.asarray(comms.node_idx) >= 0).sum()) * width


def _moved_tet_bytes(met_s) -> int:
    """Bytes ``device_migrate`` ships a moved tet: its global vertex
    ids, reference and face and edge tags and references (19 words),
    and the coordinates, tags, references and metric of four vertices."""
    m = int(np.prod(met_s.shape[2:], dtype=np.int64))
    return 4 * (19 + 4 * (3 + 2 + m))


def _shared_gids(comms, glo, n_shards: int) -> np.ndarray:
    """Interface-vertex gids from the comm tables (the band path's
    shared-vertex candidate seed)."""
    sh0 = []
    for s_ in range(n_shards):
        rows = np.unique(comms.node_idx[s_][comms.node_idx[s_] >= 0])
        sh0.append(glo[s_][rows])
    return np.unique(np.concatenate(sh0)) if sh0 else \
        np.zeros(0, np.int64)
