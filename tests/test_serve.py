"""Serving subsystem tests (parmmg_tpu/serve + satellites).

Tier-1 tests pin the host-side state machines only — slot-pool
admission / recycling, AdaptStats tenant isolation, the chunk
auto-tune cost model — no XLA compiles (the 870s gate is tight).  The
slow tests pin the end-to-end serving contract: tenants packed into
one [chunk, ...] dispatch retire bit-for-bit identical to their
standalone ``grouped_adapt_pass(ngroups=1)`` runs, through queueing
and slot recycling.  (The compile-family side — a warm pool adds zero
``groups.*`` ledger families vs the batch path — is gated by
``scripts/run_tests.sh --ledger`` / ledger_check.serving_gate.)
"""
import numpy as np
import pytest

from parmmg_tpu.serve.pool import SlotPool
from parmmg_tpu.utils.compilecache import bucket


# ---------------------------------------------------------------------------
# slot-pool state machine (tier-1: host bookkeeping, no compiles)
# ---------------------------------------------------------------------------
def test_pool_admits_smallest_fitting_bucket():
    p = SlotPool(slots_per_bucket=2, chunk=1)
    st, key, slot = p.admit("a", 27, 48)
    assert st == "ok" and slot == 0
    # home bucket = the split_to_shards capacity formula (geo ladder,
    # floor 64, cap_mult 3) — what makes pool slots shape-identical to
    # the standalone grouped path
    assert key[:2] == (bucket(3 * 27, floor=64, scheme="geo"),
                       bucket(3 * 48, floor=64, scheme="geo"))
    # a same-size tenant shares the bucket; a bigger one gets its own
    st2, key2, slot2 = p.admit("b", 27, 48)
    assert st2 == "ok" and key2 == key and slot2 == 1
    st3, key3, _ = p.admit("c", 64, 162)
    assert st3 == "ok" and key3 != key
    assert p.occupancy() == {f"{key[0]}x{key[1]}": (2, 2),
                             f"{key3[0]}x{key3[1]}": (1, 2)}


def test_pool_rejects_oversize():
    p = SlotPool(slots_per_bucket=2, max_capP=500, max_capT=500)
    st, caps = p.admit("big", 400, 4000)
    assert st == "oversize" and caps[1] > 500
    assert "big" not in p._where          # nothing leaked
    # a fitting tenant is still admitted
    assert p.admit("ok", 27, 48)[0] == "ok"


def test_pool_quiet_tenant_slot_recycling():
    p = SlotPool(slots_per_bucket=2)
    p.admit("a", 27, 48)
    _, key, sb = p.admit("b", 27, 48)
    # bucket full: the next request waits (driver keeps it queued)
    assert p.admit("c", 27, 48) == ("full", key)
    # quiet tenant retires -> its slot recycles to the queued tenant
    p.release("b")
    st, key2, slot = p.admit("c", 27, 48)
    assert (st, key2, slot) == ("ok", key, sb)


def test_pool_pad_slots_born_quiet():
    """Free/pad slots are never part of the active set and a pool with
    no loaded tenants dispatches nothing (step is a no-op)."""
    p = SlotPool(slots_per_bucket=4)
    p.admit("a", 27, 48)          # admitted but never loaded
    assert p.active_tenants() == []
    assert p.step() == [] and p.dispatches == 0
    s = p.slot_of("a")
    assert not s.converged and not s.loaded


# ---------------------------------------------------------------------------
# AdaptStats tenant isolation (serving satellite)
# ---------------------------------------------------------------------------
def test_adapt_stats_refuses_cross_tenant_merge():
    from parmmg_tpu.ops.adapt import AdaptStats
    a = AdaptStats(tenant="a", nsplit=3)
    b = AdaptStats(tenant="b", nsplit=5)
    with pytest.raises(ValueError, match="across tenants"):
        a += b
    assert a.nsplit == 3                  # refused merge left a intact


def test_adapt_stats_namespaces_per_tenant_keys():
    from parmmg_tpu.ops.adapt import AdaptStats
    a = AdaptStats(tenant="a")
    a.sched_extra["ops_per_block"] = [4, 0]
    a.sched_extra["grp_upload_s"] = 0.5
    b = AdaptStats(tenant="b")
    b.sched_extra["ops_per_block"] = [7]
    agg = AdaptStats()
    agg += a
    agg += b
    # trajectories and timer keys never interleave across tenants
    assert agg.sched_extra == {"tenant:a/ops_per_block": [4, 0],
                               "tenant:a/grp_upload_s": 0.5,
                               "tenant:b/ops_per_block": [7]}
    # same-tenant accumulation stays un-namespaced (sub-pass merge)
    t = AdaptStats(tenant="a")
    t += AdaptStats(tenant="a", nswap=2)
    assert t.nswap == 2 and t.sched_extra == {}


# ---------------------------------------------------------------------------
# PARMMG_GROUP_CHUNK auto-tune (ROADMAP 1b satellite)
# ---------------------------------------------------------------------------
def test_timeout_scrubs_and_recycles_slot():
    """Regression (resilience satellite): a RUNNING request expired by
    _expire_timeouts must leave its pool slot SCRUBBED (row zeroed back
    to the dead-mesh state) and back on the bucket's free list, rentable
    by the next tenant — a timed-out tenant must never strand capacity."""
    import time
    from parmmg_tpu.serve.driver import (RUNNING, TIMEOUT, ServeDriver,
                                         ServeRequest)
    pool = SlotPool(slots_per_bucket=1)
    drv = ServeDriver(pool=pool, timeout_s=0.001)
    st, key, i = pool.admit("a", 27, 48)
    assert st == "ok"
    # fake-load the slot host-side (no XLA): a dict pytree stands in
    # for the stacked Mesh, with non-zero rows to catch the scrub
    b = pool.buckets[key]
    b.stacked = {"vert": np.ones((1, 8, 3)), "tet": np.ones((1, 16, 4))}
    b.met = np.ones((1, 8))
    b.slots[i].loaded = True
    drv.requests["a"] = ServeRequest(
        tid="a", state=RUNNING, t_submit=time.perf_counter() - 10.0)
    drv._expire_timeouts()
    r = drv.requests["a"]
    assert r.state == TIMEOUT and "exceeded" in r.reason
    # slot scrubbed: row zeroed (born-quiet dead mesh for the next
    # renter), tenant gone from the rent map, slot back on the free list
    assert (b.stacked["vert"] == 0).all() and (b.met == 0).all()
    assert "a" not in pool._where
    assert b.free_slot() == i
    # ...and actually rentable by the next tenant
    assert pool.admit("b", 27, 48) == ("ok", key, i)


def test_recommend_group_chunk_tracks_decay():
    from parmmg_tpu.parallel.sched import recommend_group_chunk
    # front-loaded decay: two full blocks then a long quiet tail —
    # chunk 2 beats both chunk 1 (dispatch overhead x8) and chunk 8
    # (pads 7 dead slots per tail block)
    assert recommend_group_chunk([8, 8, 1, 1, 1, 1], 8) == 2
    # never-converging trajectory: full chunks win (0 = unchunked)
    assert recommend_group_chunk([8] * 6, 8, dispatch_overhead=8.0) == 0
    # degenerate inputs
    assert recommend_group_chunk([], 8) == 0
    assert recommend_group_chunk([0, 0], 8) == 0
    assert recommend_group_chunk([4, 4], 1) == 0


def test_group_chunk_auto_env(monkeypatch):
    from parmmg_tpu.parallel import sched
    from parmmg_tpu.parallel.groups import group_chunk
    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "auto")
    monkeypatch.setattr(sched, "_CHUNK_RECOMMENDATION", [])
    # before any grouped pass: unchunked
    assert group_chunk(16) == 0
    sched.note_chunk_recommendation(4)
    assert group_chunk(16) == 4
    # the unchunked convention still applies when the recommendation
    # covers every group
    assert group_chunk(4) == 0
    sched.note_chunk_recommendation(2)    # newest recommendation wins
    assert group_chunk(16) == 2
    # explicit numeric values are untouched by the auto machinery
    monkeypatch.setenv("PARMMG_GROUP_CHUNK", "3")
    assert group_chunk(16) == 3


# ---------------------------------------------------------------------------
# end-to-end serving contracts (slow tier: group-block XLA compiles)
# ---------------------------------------------------------------------------
def _tenant(n=2, h=0.55):
    import jax.numpy as jnp
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.ops.analysis import analyze_mesh
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(n)
    m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
    m = analyze_mesh(m).mesh
    met = jnp.full(m.capP, h, m.vert.dtype)
    return m, met


# slow: multi-minute XLA compile on the tier-1 CPU box (tier-2 covers it)
@pytest.mark.slow
def test_serve_parity_packed_dispatch():
    """Tenants PACKED into one [chunk=2, ...] dispatch (different
    metrics, same bucket) each retire bit-for-bit identical to their
    standalone grouped_adapt_pass(ngroups=1) run — slot isolation under
    packing, through queue + slot recycling (3 tenants, 2 slots)."""
    from parmmg_tpu.core.mesh import MESH_FIELDS
    from parmmg_tpu.parallel.groups import grouped_adapt_pass
    from parmmg_tpu.serve.driver import ServeDriver

    cycles = 3
    cases = {"ta": 0.55, "tb": 0.42, "tc": 0.55}
    refs = {}
    for tid, h in cases.items():
        m, met = _tenant(2, h)
        out, met_m, _ = grouped_adapt_pass(m, met, 1, cycles=cycles)
        refs[tid] = (out, np.asarray(met_m))

    drv = ServeDriver(slots_per_bucket=2, chunk=2, cycles=cycles)
    for tid, h in cases.items():
        m, met = _tenant(2, h)
        drv.submit(mesh=m, met=met, tenant=tid)
    rep = drv.run()
    assert rep["served"] == 3 and rep["failed"] == 0
    for tid in cases:
        mesh, met_m = drv.fetch(tid)
        ref, kref = refs[tid]
        for f in MESH_FIELDS:
            a, b = np.asarray(getattr(mesh, f)), \
                np.asarray(getattr(ref, f))
            assert (a == b).all(), f"tenant {tid} field {f} differs"
        assert (np.asarray(met_m) == kref).all(), f"{tid} metric differs"
    # different metrics did different work (isolation is not no-op)
    assert rep["tenants"]["ta"]["ops"] != rep["tenants"]["tb"]["ops"]
    # every slot recycled on retirement (3 tenants through 2 home
    # slots; a capacity promotion may add a second bucket — also empty)
    occ = drv.pool.occupancy()
    assert occ and all(used == 0 for used, _ in occ.values())
