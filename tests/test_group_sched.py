"""Quiet-group scheduler tests (parallel/sched.py + groups.py wiring).

The scheduler skips chunked group-block dispatches for groups that a
swap-inclusive block proved quiet — exact because frozen MG_PARBDY
seams + deterministic waves make a zero-op group state a fixed point
(sched module docstring).  PR 12 pushes the same proof into the
compiled programs as a device-resident active mask (lax.cond group
bodies, PARMMG_DEVICE_MASK): fast tests pin the mask plumbing
(block_mask levels, pad_mask, cond_skipped accounting, the measured
chunk-overhead calibration) host-side; the slow tests pin the
end-to-end contracts: bit-for-bit parity vs always-dispatch AND vs
mask-off on the unchunked layout, the quiet fixed point, and the
strictly-fewer-dispatches acceptance gate.

The packed-halo hysteresis satellite (comms.packed_halo_rows ``state``)
is pinned here too: the dense/packed layout decision must be sticky
within the margin so borderline occupancy cannot flip-flop compiled
exchange layouts across comm-table rebuilds.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from parmmg_tpu.parallel.sched import (
    LEVEL_FULL, LEVEL_PRE, QuietGroupScheduler, chunk_plans)


# ---------------------------------------------------------------------------
# host-side state machine (tier-1: no compiles)
# ---------------------------------------------------------------------------
def _counts(n_act, at=None):
    """A block's zero counts [n_act, 8]; at={(g, col): v}."""
    c = np.zeros((n_act, 8), np.int32)
    for (g, col), v in (at or {}).items():
        c[g, col] = v
    return c


def test_sched_marks_skips_and_compacts():
    s = QuietGroupScheduler(ngroups=4, g_exec=6, chunk=2, enabled=True)
    # pad groups (4, 5) are born quiet: 2 chunks instead of 3
    act, plans = s.plan_block(pres_all_on=True)
    assert list(act) == [0, 1, 2, 3]
    assert [(list(i), n) for i, n in plans] == [([0, 1], 2), ([2, 3], 2)]
    assert s.dispatches == 2 and s.saved_dispatches == 1
    # swap-inclusive prescreen-on block: groups 1 and 3 all-zero
    s.record_block(act, _counts(4, {(0, 0): 5, (2, 2): 1}),
                   swap_inclusive=True, pres_all_on=True)
    assert list(s.level[:4]) == [0, LEVEL_PRE, 0, LEVEL_PRE]
    # prescreen-on block skips PRE groups; compaction stays dense
    act2, plans2 = s.plan_block(pres_all_on=True)
    assert list(act2) == [0, 2]
    assert [(list(i), n) for i, n in plans2] == [([0, 2], 2)]
    # a prescreen-OFF block re-dispatches PRE groups (the exact split
    # veto can produce ops the approximate prescreen vetoed)
    act3, _ = s.plan_block(pres_all_on=False)
    assert list(act3) == [0, 1, 2, 3]
    # all-zero on the pres-off swap block: everyone LEVEL_FULL
    s.record_block(act3, _counts(4), True, False)
    act4, plans4 = s.plan_block(pres_all_on=False)
    assert len(act4) == 0 and plans4 == []
    assert s.active_per_block == [4, 2, 4, 0]
    assert s.saved_dispatches == 1 + 2 + 1 + 3
    # skipped-group accounting counts REAL groups only (dead pads are
    # not scheduler wins): 0 + 2 + 0 + 4 across the four blocks
    assert s.skipped_group_blocks == 6


def test_sched_needs_swap_and_clean_overflow():
    s = QuietGroupScheduler(2, 2, 1, enabled=True)
    act, _ = s.plan_block(True)
    # zero counts on a NON-swap block prove nothing (a later swap cycle
    # could still post ops)
    s.record_block(act, _counts(2), swap_inclusive=False,
                   pres_all_on=True)
    assert (s.level[:2] == 0).all()
    # overflow (col 4) vetoes quietness: a truncated winner set is not
    # a convergence witness
    s.record_block(act, _counts(2, at={(0, 4): 1}), True, True)
    assert s.level[0] == 0 and s.level[1] == LEVEL_PRE
    # moves (col 3) veto quietness too: smoothing is part of the fixed
    # point
    s.record_block(act, _counts(2, at={(0, 3): 7}), True, True)
    assert s.level[0] == 0


def test_sched_regrow_reactivates_full_set():
    """Satellite (c): a capacity regrow invalidates every quiet proof —
    the top-K wave budgets scale with capT, so budget-truncated winners
    must rerun.  Pad groups stay dead."""
    s = QuietGroupScheduler(3, 4, 2, enabled=True)
    act, _ = s.plan_block(False)
    s.record_block(act, _counts(3), True, False)
    assert (s.level[:3] == LEVEL_FULL).all()
    s.on_regrow()
    act2, _ = s.plan_block(False)
    assert list(act2) == [0, 1, 2]          # pad group 3 stays quiet
    assert s.level[3] == LEVEL_FULL


def test_sched_disabled_always_dispatches():
    s = QuietGroupScheduler(3, 4, 2, enabled=False)
    act, plans = s.plan_block(False)
    s.record_block(act, _counts(4), True, False)
    act2, plans2 = s.plan_block(False)
    assert list(act2) == [0, 1, 2, 3]       # pads included, like legacy
    assert len(plans2) == 2 and s.saved_dispatches == 0
    assert s.skipped_group_blocks == 0      # disabled: nothing skipped
    # the trajectory still shows the would-be-active real groups
    assert s.active_per_block == [3, 0]


def test_chunk_plans_pads_tail_with_repeat():
    p = chunk_plans(np.array([1, 4, 6]), 2)
    assert [(list(i), n) for i, n in p] == [([1, 4], 2), ([6, 6], 1)]
    p1 = chunk_plans(np.array([2]), 4)
    assert [(list(i), n) for i, n in p1] == [([2, 2, 2, 2], 1)]


# ---------------------------------------------------------------------------
# device-resident quiet masks (tier-1: host-side plumbing only)
# ---------------------------------------------------------------------------
def test_pad_mask_masks_padded_tail_rows(monkeypatch):
    from parmmg_tpu.parallel.sched import pad_mask
    assert list(pad_mask(4, 2)) == [True, True, False, False]
    assert list(pad_mask(3, 3)) == [True, True, True]
    # PARMMG_DEVICE_MASK=0: all-true — the disabled path computes
    # exactly what the pre-mask code did (pad rows discarded later)
    monkeypatch.setenv("PARMMG_DEVICE_MASK", "0")
    assert list(pad_mask(4, 1)) == [True] * 4
    # PARMMG_GROUP_SCHED=0 is the FULL legacy escape hatch: it forces
    # all-true masks too, even with the mask knob on
    monkeypatch.delenv("PARMMG_DEVICE_MASK")
    monkeypatch.setenv("PARMMG_GROUP_SCHED", "0")
    assert list(pad_mask(4, 1)) == [True] * 4


def test_block_mask_levels_and_knob(monkeypatch):
    """Unchunked dispatches: the mask is the only skip mechanism —
    level >= LEVEL_PRE slots masked under prescreen-ON blocks, only
    LEVEL_FULL slots under prescreen-OFF blocks; pads born masked;
    cond_skipped accounts every masked slot."""
    s = QuietGroupScheduler(ngroups=3, g_exec=4, chunk=0, enabled=True)
    s.level[1] = LEVEL_PRE
    s.level[2] = LEVEL_FULL
    m_pre = s.block_mask(pres_all_on=True)
    assert list(m_pre) == [True, False, False, False]   # pad 3 masked
    m_full = s.block_mask(pres_all_on=False)
    # a pres-OFF block re-runs LEVEL_PRE groups (exact split veto)
    assert list(m_full) == [True, True, False, False]
    assert s.cond_skipped == 3 + 2
    # scheduler disabled: masks all-true, nothing accounted
    s2 = QuietGroupScheduler(3, 4, 0, enabled=False)
    s2.level[1] = LEVEL_FULL
    assert list(s2.block_mask(True)) == [True] * 4
    assert s2.cond_skipped == 0
    # PARMMG_DEVICE_MASK=0 forces all-true even with the scheduler on
    monkeypatch.setenv("PARMMG_DEVICE_MASK", "0")
    s3 = QuietGroupScheduler(3, 4, 0, enabled=True)
    s3.level[1] = LEVEL_FULL
    assert list(s3.block_mask(True)) == [True] * 4
    assert s3.cond_skipped == 0


def test_note_plan_pads_accounts_masked_tail(monkeypatch):
    s = QuietGroupScheduler(5, 6, 2, enabled=True)
    plans = chunk_plans(np.array([0, 2, 4]), 2)   # tail padded 1 row
    s.note_plan_pads(plans)
    assert s.cond_skipped == 1
    monkeypatch.setenv("PARMMG_DEVICE_MASK", "0")
    s.note_plan_pads(plans)                        # disabled: no-op
    assert s.cond_skipped == 1


def test_calibrate_dispatch_overhead():
    """ROADMAP 1b host-side validation: the cost model's overhead
    constant is derived from the measured pipeline segments — per-
    dispatch (upload+download+writeback) over per-GROUP compute."""
    from parmmg_tpu.parallel.sched import calibrate_dispatch_overhead
    acc = {"upload": 2.0, "download": 1.0, "writeback": 1.0,
           "compute": 8.0}
    cnt = {"upload": 4, "compute": 4, "download": 4, "writeback": 4}
    # per dispatch: overhead (2+1+1)/4 = 1.0 s; compute 8/4/chunk=2
    # = 1.0 s/group -> 1.0 group-units
    assert calibrate_dispatch_overhead(acc, cnt, 2) == 1.0
    # bigger chunk -> cheaper per-group compute -> higher overhead
    assert calibrate_dispatch_overhead(acc, cnt, 4) == 2.0
    # no signal cases keep the hand-set default (None)
    assert calibrate_dispatch_overhead({}, {}, 2) is None
    assert calibrate_dispatch_overhead(acc, cnt, 0) is None
    assert calibrate_dispatch_overhead(
        {"compute": 0.0, "upload": 1.0}, {"compute": 3}, 2) is None
    # the calibration feeds recommend_group_chunk directly
    from parmmg_tpu.parallel.sched import recommend_group_chunk
    assert recommend_group_chunk([8, 8], 8, dispatch_overhead=2.0) in \
        (2, 4, 8, 0)


# ---------------------------------------------------------------------------
# packed-halo hysteresis (comms satellite; tier-1: host numpy)
# ---------------------------------------------------------------------------
def _nbr_table(n_entries, G=4):
    """[2*G, G] logical neighbor table: device 0 carries ``n_entries``
    (group, slot) entries pointing at device 1; device 1 silent."""
    nbr = np.full((2 * G, G), -1, np.int32)
    for i in range(n_entries):
        nbr[i // G, i % G] = G + (i % G)
    return nbr


def test_packed_halo_hysteresis_sticky_layout(monkeypatch):
    from parmmg_tpu.parallel.comms import packed_halo_rows
    G = 4                      # occupancy ratio r = entries / 16
    st = {}
    # below threshold: packed, state recorded
    assert packed_halo_rows(_nbr_table(7), G, occupancy=0.5,
                            state=st) is not None
    assert st["layout"] == "packed"
    # AT the threshold (r = 0.5): a stateless call flips on the exact
    # boundary; the sticky decision keeps packed within the margin
    assert packed_halo_rows(_nbr_table(8), G, occupancy=0.5,
                            state=st) is not None
    # past threshold + margin (r = 0.5625 > 0.55): flips to dense
    assert packed_halo_rows(_nbr_table(9), G, occupancy=0.5,
                            state=st) is None
    assert st["layout"] == "dense"
    # back to r = 0.5 <= occupancy but NOT below occupancy - margin:
    # stays dense — this is the flip-flop the hysteresis kills
    assert packed_halo_rows(_nbr_table(8), G, occupancy=0.5,
                            state=st) is None
    # clearly below the lower margin (r = 0.4375 <= 0.45): re-packs
    assert packed_halo_rows(_nbr_table(7), G, occupancy=0.5,
                            state=st) is not None
    assert st["layout"] == "packed"
    # widened margin knob: r = 0.5625 <= 0.5 + 0.2 now stays packed
    monkeypatch.setenv("PARMMG_HALO_PACK_HYST", "0.2")
    assert packed_halo_rows(_nbr_table(9), G, occupancy=0.5,
                            state=st) is not None
    # stateless calls keep the legacy decide-per-call behavior
    assert packed_halo_rows(_nbr_table(8), G, occupancy=0.5) is not None
    assert packed_halo_rows(_nbr_table(9), G, occupancy=0.5) is None
    # no-traffic tables decide nothing and leave the state alone
    before = dict(st)
    assert packed_halo_rows(np.full((2 * G, G), -1, np.int32), G,
                            occupancy=0.5, state=st) is None
    assert st == before


# ---------------------------------------------------------------------------
# end-to-end contracts (slow tier: group-block XLA compiles)
# ---------------------------------------------------------------------------
def _shock_setup(n=3, h=0.6):
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.utils.fixtures import analytic_iso_metric, cube_mesh
    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    hh = analytic_iso_metric(vert, "shock", h=h)
    met = jnp.zeros(m.capP, m.vert.dtype).at[: len(hh)].set(
        jnp.asarray(hh, m.vert.dtype)).at[len(hh):].set(1.0)
    return m, met


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_sched_parity_bit_for_bit(monkeypatch):
    """Satellite (a): merged mesh + met byte-identical with the
    scheduler forced on vs off on a multi-group chunked fixture,
    polish included (at chunk granularity 1 the wave-major polish
    retirement is exactly the legacy per-chunk break)."""
    from parmmg_tpu.core.mesh import MESH_FIELDS
    from parmmg_tpu.ops.adapt import AdaptStats
    from parmmg_tpu.parallel.groups import grouped_adapt_pass

    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "1")

    def run(sched):
        monkeypatch.setenv("PARMMG_GROUP_SCHED", sched)
        m, met = _shock_setup()
        st = AdaptStats()
        out, met2, part = grouped_adapt_pass(m, met, 3, cycles=3,
                                             stats=st, polish=True)
        return out, np.asarray(met2), np.asarray(part), st

    ref, kref, pref, st0 = run("0")
    chk, kchk, pchk, st1 = run("1")
    for f in MESH_FIELDS:
        a = np.asarray(getattr(ref, f))
        b = np.asarray(getattr(chk, f))
        assert (a == b).all(), f"merged field {f} differs on/off"
    assert (kref == kchk).all(), "merged metric differs on/off"
    assert (pref == pchk).all()
    # always-dispatch accounting sanity
    assert st0.group_dispatches_saved == 0
    assert st1.group_dispatches + st1.group_dispatches_saved >= \
        st0.group_dispatches


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_device_mask_parity_unchunked(monkeypatch):
    """Device-mask bit-for-bit parity (PR 12): UNCHUNKED dispatches
    (PARMMG_GROUP_CHUNK=0) are where the lax.cond mask is the ONLY skip
    mechanism — host compaction cannot change the dispatch shape.
    Mask-on (scheduler levels -> cond identity for quiet slots) must
    merge byte-identical to sched-off (every slot computes), polish on
    (the unchunked polish loop is shared, so the cycle loop is the
    masked path under test).  The x-slab calm fixture guarantees quiet
    groups arise BEFORE convergence, so the mask demonstrably engages
    (cond_skipped > 0) rather than passing vacuously."""
    from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
    from parmmg_tpu.ops.adapt import AdaptStats
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.utils.fixtures import cube_mesh

    n = 3
    vert, tet = cube_mesh(n)
    cent = vert[tet].mean(axis=1)
    part = np.minimum((cent[:, 0] * n).astype(np.int64), n - 1)
    h = np.where(vert[:, 0] < 1e-9, 0.15, 1.3 / n)
    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "0")

    def run(sched, mask):
        monkeypatch.setenv("PARMMG_GROUP_SCHED", sched)
        monkeypatch.setenv("PARMMG_DEVICE_MASK", mask)
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.zeros(m.capP, m.vert.dtype).at[: len(h)].set(
            jnp.asarray(h, m.vert.dtype)).at[len(h):].set(1.0)
        st = AdaptStats()
        out, met2, p = grouped_adapt_pass(
            m, met, n, cycles=5, part=part, stats=st, nomove=True,
            noswap=True, polish=True)
        return out, np.asarray(met2), np.asarray(p), st

    ref, kref, pref, st0 = run("0", "0")
    chk, kchk, pchk, st1 = run("1", "1")
    for f in MESH_FIELDS:
        a = np.asarray(getattr(ref, f))
        b = np.asarray(getattr(chk, f))
        assert (a == b).all(), f"merged field {f} differs mask on/off"
    assert (kref == kchk).all(), "merged metric differs mask on/off"
    assert (pref == pchk).all()
    # the mask demonstrably skipped group-slot executions on device
    assert st1.sched_extra.get("cond_skipped_rows", 0) > 0
    assert st0.sched_extra.get("cond_skipped_rows", 0) == 0


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_sched_saves_dispatches_and_quiet_fixed_point(monkeypatch):
    """Acceptance gate: on a run where some groups converge early the
    scheduler executes strictly fewer group-block dispatches than
    cycles x ceil(G/chunk); and (satellite b) a quiet group's state is
    a fixed point — re-running the block is byte-identity.

    Fixture: x-slab partition with the refinement confined to the x=0
    boundary column, calm-region metric inside the (LSHRT, LLONG)
    band for every Kuhn edge class (h = 1.3 * spacing), -nomove/-noswap
    so groups 1 and 2 post zero everything from cycle 0 while group 0
    splits for several cycles."""
    from parmmg_tpu.core.mesh import MESH_FIELDS, make_mesh
    from parmmg_tpu.ops.adapt import AdaptStats
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.parallel.groups import _group_block, grouped_adapt_pass
    from parmmg_tpu.parallel.distribute import split_to_shards
    from parmmg_tpu.utils.fixtures import cube_mesh

    n = 3
    vert, tet = cube_mesh(n)
    cent = vert[tet].mean(axis=1)
    part = np.minimum((cent[:, 0] * n).astype(np.int64), n - 1)
    h = np.where(vert[:, 0] < 1e-9, 0.15, 1.3 / n)

    def setup():
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        m = analyze_mesh(m).mesh
        met = jnp.zeros(m.capP, m.vert.dtype).at[: len(h)].set(
            jnp.asarray(h, m.vert.dtype)).at[len(h):].set(1.0)
        return m, met

    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "1")
    monkeypatch.setenv("PARMMG_GROUP_SCHED", "1")
    cycles = 5
    m, met = setup()
    st = AdaptStats()
    out, _, _ = grouped_adapt_pass(m, met, n, cycles=cycles, part=part,
                                   stats=st, nomove=True, noswap=True)
    assert int(np.asarray(out.tmask).sum()) > 0
    # strictly fewer dispatches than the always-dispatch ceiling
    assert st.group_dispatches < cycles * n, \
        (st.group_dispatches, cycles * n)
    assert st.group_dispatches_saved > 0
    assert st.groups_skipped > 0
    traj = st.sched_extra["active_groups_per_block"]
    assert traj[0] == n and min(traj) < n, traj

    # quiet fixed point: a calm group's split state re-runs to
    # byte-identical arrays under the same compiled block (the program
    # the scheduler skipped; wave index is a traced no-op on it)
    import jax
    m2, met2 = setup()
    stacked, met_s = split_to_shards(m2, met2, part, n, cap_mult=3.0)
    calm = jax.tree.map(lambda a: a[1:2], stacked)
    kcalm = met_s[1:2]
    step = _group_block(True, False, True, False, None)
    on = jnp.ones(1, bool)
    m1, k1, c1 = step(calm, kcalm, jnp.asarray(0, jnp.int32), on)
    assert int(np.asarray(c1)[..., :5].sum()) == 0, np.asarray(c1)
    m2_, k2, c2 = step(m1, k1, jnp.asarray(1, jnp.int32), on)
    assert int(np.asarray(c2)[..., :5].sum()) == 0
    for f in MESH_FIELDS:
        a, b = np.asarray(getattr(m1, f)), np.asarray(getattr(m2_, f))
        assert (a == b).all(), f"quiet group field {f} not a fixed point"
    assert (np.asarray(k1) == np.asarray(k2)).all()
