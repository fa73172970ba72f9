"""Slot pool + admission: bucketed group slots for multi-tenant serving.

A *slot* is one group position of a host-resident stacked mesh state —
exactly the unit ``parallel.groups.grouped_adapt_pass`` dispatches in
chunk mode — except the slots of one bucket hold INDEPENDENT tenant
meshes instead of slices of one mesh.  Buckets are rungs of the
capacity ladder ``utils.compilecache.bucket(cap_mult * n, floor=64,
scheme="geo")`` — the SAME formula ``parallel.distribute
.split_to_shards`` uses for group capacities, so a tenant admitted into
its home bucket runs at byte-identical static shapes to the standalone
``grouped_adapt_pass(ngroups=1)`` path: same cached ``_group_block``
program, same wave indices, same top-K budgets.  That is the whole
compile story of serving: after one warmup per bucket (which any batch
user pays anyway), every request is served by already-compiled
programs — zero new ``groups.*`` compile-ledger families (gated by
``scripts/run_tests.sh --ledger`` / ``ledger_check.serving_gate``).

Scheduling: per step, active (admitted, unconverged) slots of each
bucket are cohorted by cycle index — slots in the same cohort share
``(swap, prescreen, wave)`` and are compacted into dense ``[chunk, ...]``
dispatches with ``parallel.sched.chunk_plans``, ridden through the
double-buffered ``groups._pipeline_chunks`` pipeline.  A tenant
retires at its own fixed point (``groups.block_converged`` — the
per-tenant form of the batch loop's early exit, which at one group per
tenant is exactly the standalone rule) and frees its slot for the next
queued request: the quiet-group scheduler's skip (parallel/sched.py)
becomes slot recycling.  Free/pad slots are born quiet (all-zero dead
meshes, ``groups._pad_groups`` convention) and are never dispatched.

Capacity overflow mirrors the batch regrow: the overflowed post-run
state is promoted to a ``(2*capP, 2*capT)`` bucket and the SAME block
re-runs (the batch path's ``on_regrow`` + block-rerun semantics, at
tenant granularity).

The admission state machine (admit / full / oversize, slot recycling)
is pure host bookkeeping — tests drive it without touching XLA; array
storage is allocated lazily on the first ``load``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ..utils.compilecache import bucket

BUCKET_FLOOR = 64          # split_to_shards' geo-ladder floor


def _env_int(name: str, default: int) -> int:
    import os
    v = os.environ.get(name, "")
    return int(v) if v else default


@dataclasses.dataclass
class Slot:
    """One bucketed group slot: bookkeeping for the tenant renting it."""
    tenant: str | None = None
    c: int = 0                 # cycle index (block boundary)
    converged: bool = False
    failed: str = ""           # non-empty = terminal failure reason
    regrows: int = 0
    loaded: bool = False
    stats: object = None       # AdaptStats(tenant=...)
    faults: int = 0            # dispatch faults (quarantine ladder)


class Bucket:
    """One capacity rung: ``nslots`` group slots at (capP, capT).

    ``stacked``/``met`` are host numpy trees [nslots, ...] in the
    chunk-mode layout of grouped_adapt_pass (allocated on first load);
    free slots stay all-zero = dead meshes (born quiet)."""

    def __init__(self, capP: int, capT: int, nslots: int):
        self.capP = capP
        self.capT = capT
        self.nslots = nslots
        self.slots = [Slot() for _ in range(nslots)]
        self.stacked = None
        self.met = None

    def free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s.tenant is None:
                return i
        return None

    def occupancy(self) -> tuple[int, int]:
        return (sum(1 for s in self.slots if s.tenant is not None),
                self.nslots)


class SlotPool:
    """Bucketed slot pool: admission + chunked multi-tenant dispatch.

    Knobs (constructor arg wins over env): ``slots_per_bucket``
    (PARMMG_SERVE_SLOTS, default 4), ``chunk`` groups/dispatch
    (PARMMG_SERVE_CHUNK, default 1 — every dispatch reuses the
    standalone ``[1, ...]`` program; larger chunks pack tenants
    per dispatch at the cost of one ``[chunk, ...]`` warmup),
    ``max_capT``/``max_capP`` admission ceilings
    (PARMMG_SERVE_MAX_CAPT / _CAPP, default 1<<22 — *oversize*
    rejection), ``cap_mult`` growth headroom (the split_to_shards
    default 3.0), and the remesh parameters shared by every tenant of
    the pool (one kernel, many meshes — the paper's model)."""

    def __init__(self, slots_per_bucket: int | None = None,
                 chunk: int | None = None, cap_mult: float = 3.0,
                 max_capP: int | None = None, max_capT: int | None = None,
                 cycles: int = 6, noinsert: bool = False,
                 noswap: bool = False, nomove: bool = False,
                 hausd: float | None = None,
                 max_slot_retries: int | None = None):
        self.slots_per_bucket = slots_per_bucket if slots_per_bucket \
            else _env_int("PARMMG_SERVE_SLOTS", 4)
        # fault-isolation budget (PARMMG_SERVE_MAX_RETRIES): a tenant
        # whose slot dispatch faults this many times is quarantined —
        # retired FAILED, slot scrubbed and recycled — never aborting
        # cohort-mates sharing the chunk
        self.max_slot_retries = max(1, max_slot_retries
                                    if max_slot_retries is not None
                                    else _env_int(
                                        "PARMMG_SERVE_MAX_RETRIES", 2))
        self.quarantined: list[str] = []
        self.chunk = max(1, chunk if chunk
                         else _env_int("PARMMG_SERVE_CHUNK", 1))
        self.cap_mult = float(cap_mult)
        self.max_capP = max_capP if max_capP \
            else _env_int("PARMMG_SERVE_MAX_CAPP", 1 << 22)
        self.max_capT = max_capT if max_capT \
            else _env_int("PARMMG_SERVE_MAX_CAPT", 1 << 22)
        self.cycles = int(cycles)
        self.noinsert = noinsert
        self.noswap = noswap
        self.nomove = nomove
        self.hausd = hausd
        self.buckets: dict[tuple, Bucket] = {}
        self._where: dict[str, tuple] = {}      # tenant -> (key, slot)
        self.dispatches = 0
        self.steps = 0
        # active-slot trajectory per (step, bucket) — the serving-side
        # analogue of extra.active_groups_per_block, feeding the same
        # chunk auto-tune cost model
        self.active_per_step: list[int] = []
        # pipeline segment timers (upload/compute/download/writeback),
        # folded across every dispatch of the pool's lifetime
        from ..utils.timers import Timers
        self.timers = Timers()

    # ---- admission state machine (pure host bookkeeping) -----------------
    def home_caps(self, n_vert: int, n_tet: int) -> tuple[int, int]:
        """Smallest ladder rung fitting a tenant of ``n_tet`` live tets
        referencing ``n_vert`` vertices — the exact capacities
        split_to_shards computes for a one-part split (its maxP counts
        TET-REFERENCED vertices, not vmask: callers must pass that, or
        an orphan vertex inflates the bucket past the rung the split
        produces and load() rejects the mismatch)."""
        return (bucket(int(self.cap_mult * n_vert), floor=BUCKET_FLOOR,
                       scheme="geo"),
                bucket(int(self.cap_mult * n_tet), floor=BUCKET_FLOOR,
                       scheme="geo"))

    def admit(self, tenant: str, n_vert: int, n_tet: int,
              met_width: int = 0):
        """Try to admit a tenant: ("ok", key, slot) | ("full", key) |
        ("oversize", caps).  "full" tenants stay queued at the caller
        (the driver) until a converged tenant recycles its slot."""
        from ..obs.metrics import REGISTRY
        if tenant in self._where:
            raise ValueError(f"tenant {tenant!r} already admitted")
        capP, capT = self.home_caps(n_vert, n_tet)
        if capP > self.max_capP or capT > self.max_capT:
            REGISTRY.counter("serve.admit_oversize").inc()
            return ("oversize", (capP, capT))
        key = (capP, capT, int(met_width))
        b = self.buckets.get(key)
        if b is None:
            b = self.buckets[key] = Bucket(capP, capT,
                                           self.slots_per_bucket)
        i = b.free_slot()
        if i is None:
            REGISTRY.counter("serve.admit_full").inc()
            return ("full", key)
        from ..ops.adapt import AdaptStats
        b.slots[i] = Slot(tenant=tenant, stats=AdaptStats(tenant=tenant))
        self._where[tenant] = (key, i)
        REGISTRY.counter("serve.admit_ok").inc()
        return ("ok", key, i)

    @staticmethod
    def _zero_row(b: Bucket, i: int) -> None:
        """Reset a slot row to the dead-mesh state (all-zero — the
        _pad_groups pad-group convention: born quiet)."""
        if b.stacked is not None:
            import jax

            def z(a):
                a[i] = 0            # broadcasts over the row
                return a
            jax.tree.map(z, b.stacked)
            b.met[i] = 0

    def release(self, tenant: str) -> None:
        """Free a tenant's slot (slot recycling): the row is zeroed
        back to a dead mesh — born quiet for the next renter."""
        key, i = self._where.pop(tenant)
        b = self.buckets[key]
        if b.slots[i].loaded:
            self._zero_row(b, i)
        b.slots[i] = Slot()

    @staticmethod
    def bucket_label(key: tuple) -> str:
        """Report/metric spelling of a bucket key: ``capPxcapT`` plus a
        metric-width suffix keeping scalar- and tensor-metric buckets
        of equal caps from colliding on one report/gauge key."""
        return f"{key[0]}x{key[1]}" + (f"m{key[2]}" if key[2] else "")

    def labels(self) -> dict:
        """{report label: bucket key} — the autoscale actuator's map
        from metric-series bucket names back to pool buckets."""
        return {self.bucket_label(k): k for k in self.buckets}

    def occupancy(self) -> dict:
        return {self.bucket_label(k): b.occupancy()
                for k, b in sorted(self.buckets.items())}

    def resize_bucket(self, key: tuple, nslots: int) -> int:
        """Autoscale actuator: grow/shrink one bucket's slot count.

        Growth appends born-quiet dead rows (all-zero, the _pad_groups
        convention) — compiled shapes are untouched because dispatches
        gather ``[chunk, ...]`` row slices, never the whole
        ``[nslots, ...]`` array, so resizing adds zero compile
        families.  Shrink drops TRAILING FREE slots only (never evicts
        or renumbers a tenant: ``_where`` holds live slot indices), so
        the result may stay larger than requested.  Returns the actual
        new slot count."""
        b = self.buckets[key]
        want = max(1, int(nslots))
        if want > b.nslots:
            add = want - b.nslots
            b.slots.extend(Slot() for _ in range(add))
            if b.stacked is not None:
                import jax
                b.stacked = jax.tree.map(
                    lambda a: np.concatenate(
                        [a, np.zeros((add,) + a.shape[1:], a.dtype)]),
                    b.stacked)
                b.met = np.concatenate(
                    [b.met, np.zeros((add,) + b.met.shape[1:],
                                     b.met.dtype)])
            b.nslots = want
        elif want < b.nslots:
            keep = b.nslots
            while keep > want and b.slots[keep - 1].tenant is None:
                keep -= 1
            if keep < b.nslots:
                b.slots = b.slots[:keep]
                if b.stacked is not None:
                    import jax
                    b.stacked = jax.tree.map(
                        lambda a: np.ascontiguousarray(a[:keep]),
                        b.stacked)
                    b.met = np.ascontiguousarray(b.met[:keep])
                b.nslots = keep
        return b.nslots

    def active_tenants(self) -> list[str]:
        return [t for t, (k, i) in self._where.items()
                if self.buckets[k].slots[i].loaded
                and not self.buckets[k].slots[i].converged
                and not self.buckets[k].slots[i].failed]

    def slot_of(self, tenant: str) -> Slot:
        key, i = self._where[tenant]
        return self.buckets[key].slots[i]

    # ---- mesh attach / detach --------------------------------------------
    def load(self, tenant: str, mesh, met) -> None:
        """Split the tenant mesh into its slot (one-part
        split_to_shards, staged on the CPU backend exactly like the
        chunked grouped path) and write the row into the bucket's host
        state."""
        import jax
        from ..parallel.distribute import split_to_shards

        key, i = self._where[tenant]
        b = self.buckets[key]
        ntet = int(np.asarray(mesh.tmask).sum())
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            stacked1, met1 = split_to_shards(
                mesh, met, np.zeros(ntet, np.int32), 1,
                cap_mult=self.cap_mult)
        if stacked1.vert.shape[1] != b.capP or \
                stacked1.tet.shape[1] != b.capT:
            raise ValueError(
                f"tenant {tenant!r} split caps "
                f"{stacked1.vert.shape[1]}x{stacked1.tet.shape[1]} != "
                f"admitted bucket {b.capP}x{b.capT}")
        if b.stacked is None:
            # allocate the bucket's host state from the first tenant's
            # split as a template; free rows all-zero = dead meshes
            b.stacked = jax.tree.map(
                lambda a: np.zeros((b.nslots,) + a.shape[1:], a.dtype),
                stacked1)
            b.met = np.zeros((b.nslots,) + met1.shape[1:], met1.dtype)
        from ..core.mesh import MESH_FIELDS
        for f in MESH_FIELDS:
            getattr(b.stacked, f)[i] = np.asarray(getattr(stacked1, f)[0])
        b.met[i] = np.asarray(met1[0])
        b.slots[i].loaded = True

    def slot_state(self, tenant: str):
        """(bucket, slot index) — the raw stacked row accessors for the
        merge-free writers (driver.write_distributed)."""
        key, i = self._where[tenant]
        return self.buckets[key], i

    def merge(self, tenant: str):
        """Merge the tenant's single-slot state back to one Mesh + met
        (the same merge_shards call grouped_adapt_pass makes, staged on
        the CPU backend)."""
        import jax
        import jax.numpy as jnp
        from ..parallel.distribute import merge_shards

        b, i = self.slot_state(tenant)
        one = jax.tree.map(lambda a: jnp.asarray(a[i:i + 1]), b.stacked)
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            return merge_shards(one, jnp.asarray(b.met[i:i + 1]))

    # ---- fault isolation (resilience ladder, serving form) ----------------
    def _note_slot_fault(self, s: Slot, exc) -> bool:
        """Account one slot-dispatch fault.  Returns True when the
        tenant just crossed ``max_slot_retries`` and is quarantined:
        terminal FAILED, slot scrubbed + recycled at retirement.
        Below the threshold the slot simply stays at its cycle index
        and is re-dispatched next step — its state is untouched
        (writeback only happens on a successful drain), so the retry
        is exact."""
        from ..obs import trace as otrace
        from ..obs.metrics import REGISTRY
        s.faults += 1
        REGISTRY.counter("serve.slot_faults").inc()
        if s.tenant is not None:
            REGISTRY.counter("serve.slot_faults", tenant=s.tenant).inc()
        if s.faults >= self.max_slot_retries:
            s.failed = (f"quarantined after {s.faults} slot fault(s): "
                        + repr(exc)[:200])
            self.quarantined.append(s.tenant)
            REGISTRY.counter("serve.quarantined").inc()
            otrace.event("serve.quarantine", tenant=s.tenant,
                         faults=s.faults, detail=repr(exc)[:300])
            otrace.log(1, f"serve: QUARANTINED {s.tenant} after "
                          f"{s.faults} slot fault(s)", err=True)
            return True
        otrace.event("serve.slot_fault", tenant=s.tenant,
                     faults=s.faults, detail=repr(exc)[:300])
        return False

    def _dispatch_cohort(self, b: Bucket, fn, wave, ids, done) -> list:
        """Dispatch one cohort with per-tenant fault isolation.

        Fast path: one compacted multi-slot dispatch (the packed
        serving path).  If it faults (a poisoned tenant's dispatch —
        injectable via ``PARMMG_FAULT=serve.slot_step;key=<tenant>``),
        fall back to per-slot dispatches so cohort-mates are never
        aborted by the faulting tenant: a single-slot plan pads to the
        SAME compiled ``[chunk, ...]`` program (``chunk_plans``) and
        ``lax.map`` rows are independent, so the mates' results stay
        bit-identical to the packed dispatch.  Plans whose drain
        already COMMITTED during the fast path (the ``done`` contract
        of ``_pipeline_chunks``) keep their results — their slots
        advanced, and re-dispatching them would apply the cycle wave
        twice.  Returns [(slot index, the cycle's counts row [11])] for
        slots that ran; faulting slots are accounted via
        :meth:`_note_slot_fault` (retried next step, or quarantined
        into ``done``)."""
        from ..obs import trace as otrace
        from ..obs.metrics import REGISTRY
        from ..parallel.groups import _pipeline_chunks
        from ..parallel.sched import chunk_plans
        from ..resilience.faults import FAULTS, faultpoint
        plans = chunk_plans(np.asarray(ids), self.chunk)
        committed: dict = {}
        try:
            if FAULTS.armed():
                for i in ids:
                    faultpoint("serve.slot_step", key=b.slots[i].tenant)
            parts = _pipeline_chunks(fn, b.stacked, b.met, wave, plans,
                                     self.timers, done=committed)
            self.dispatches += len(plans)
            REGISTRY.counter("serve.dispatches").inc(len(plans))
            return list(zip(ids, np.concatenate(parts)))
        except Exception as e:
            REGISTRY.counter("resilience.serve_cohort_faults").inc()
            otrace.event("serve.cohort_fault", detail=repr(e)[:300])
        out = []
        for pi, (idx, nreal) in enumerate(plans):
            rows = [int(v) for v in idx[:nreal]]
            if pi in committed:
                # this plan's drain COMMITTED during the fast path (its
                # writeback advanced the slots): honor its results —
                # re-dispatching would apply the cycle wave twice
                self.dispatches += 1
                REGISTRY.counter("serve.dispatches").inc()
                out.extend(zip(rows, committed[pi]))
                continue
            for i in rows:
                s = b.slots[i]
                try:
                    faultpoint("serve.slot_step", key=s.tenant)
                    plans1 = chunk_plans(np.asarray([i]), self.chunk)
                    parts1 = _pipeline_chunks(fn, b.stacked, b.met,
                                              wave, plans1, self.timers)
                    self.dispatches += len(plans1)
                    REGISTRY.counter("serve.dispatches").inc(len(plans1))
                    out.append((i, np.concatenate(parts1)[0]))
                except Exception as e:
                    if self._note_slot_fault(s, e):
                        done.append(s.tenant)
        return out

    # ---- the serving step -------------------------------------------------
    def _grow_tenant(self, tenant: str) -> None:
        """Promote an overflowed tenant to the (2*capP, 2*capT) bucket
        (the batch regrow geometry: np.pad by the old capacity on the
        capacity axis, slot ids preserved) and re-rent a slot there.
        Raises MemoryError past the regrow limit — the caller marks the
        tenant failed, it does NOT kill the pool."""
        key, i = self._where[tenant]
        b = self.buckets[key]
        s = b.slots[i]
        if s.regrows >= 6:
            raise MemoryError(f"tenant {tenant!r}: slot capacity "
                              "exhausted after 6 regrows")
        capP, capT = b.capP, b.capT
        row = {f: np.asarray(getattr(b.stacked, f)[i])
               for f in ("vert", "vref", "vtag", "vmask", "tet", "tref",
                         "tmask", "adja", "ftag", "fref", "etag")}
        npoin = np.asarray(b.stacked.npoin[i])
        nelem = np.asarray(b.stacked.nelem[i])
        met_row = np.asarray(b.met[i])

        def padP(x, fill=0):
            pad = [(0, 0)] * x.ndim
            pad[0] = (0, capP)
            return np.pad(x, pad, constant_values=fill)

        def padT(x, fill=0):
            pad = [(0, 0)] * x.ndim
            pad[0] = (0, capT)
            return np.pad(x, pad, constant_values=fill)

        nkey = (2 * capP, 2 * capT, key[2])
        nb = self.buckets.get(nkey)
        if nb is None:
            nb = self.buckets[nkey] = Bucket(2 * capP, 2 * capT,
                                             self.slots_per_bucket)
        j = nb.free_slot()
        if j is None:
            # a full promotion bucket grows by one slot rather than
            # deadlocking the overflowed tenant (it already paid the
            # regrow; queueing it cannot make progress)
            self.resize_bucket(nkey, nb.nslots + 1)
            j = nb.nslots - 1
        if nb.stacked is None:
            import jax
            nb.stacked = jax.tree.map(
                lambda a: np.zeros(
                    (nb.nslots,) + ((2 * capP,) + a.shape[2:]
                                    if a.shape[1:2] == (capP,)
                                    else (2 * capT,) + a.shape[2:]
                                    if a.shape[1:2] == (capT,)
                                    else a.shape[1:]), a.dtype),
                b.stacked)
            nb.met = np.zeros((nb.nslots, 2 * capP) + b.met.shape[2:],
                              b.met.dtype)
        for f, fill in (("vert", 0), ("vref", 0), ("vtag", 0),
                        ("vmask", False)):
            getattr(nb.stacked, f)[j] = padP(row[f], fill)
        for f, fill in (("tet", 0), ("tref", 0), ("tmask", False),
                        ("adja", -1), ("ftag", 0), ("fref", 0),
                        ("etag", 0)):
            getattr(nb.stacked, f)[j] = padT(row[f], fill)
        nb.stacked.npoin[j] = npoin
        nb.stacked.nelem[j] = nelem
        nb.met[j] = padP(met_row)
        # hand the slot over: bookkeeping moves, old slot recycles
        nb.slots[j] = dataclasses.replace(s, regrows=s.regrows + 1)
        self._zero_row(b, i)
        b.slots[i] = Slot()
        self._where[tenant] = (nkey, j)
        if s.stats is not None:
            s.stats.regrows += 1

    def step(self, verbose: int = 0, on_retire=None) -> list[str]:
        """Advance every active tenant by one cycle block.  Returns the
        tenants that reached a terminal state (converged/failed) this
        step.

        Slots of one bucket at the same cycle index share (swap,
        prescreen, wave) and ride compacted [chunk, ...] dispatches of
        the SAME cached compiled program the batch grouped path uses.

        ``on_retire`` (streaming admission, serve/admission.py): when
        given, it is called with each cohort's newly-retired tenants AS
        THEY RETIRE, while the step is still in flight.  The callback
        may release slots and admit+load queued tenants into them; the
        step then RE-SCANS for tenants it has not yet dispatched this
        step and picks the re-rented slots up at their own cycle 0 — a
        freed slot is re-rented without waiting for the cohort (or the
        step) to drain.  Each TENANT dispatches at most once per step
        (a regrown tenant re-runs its block next step, either mode), so
        existing tenants advance exactly one block either way.
        Per-tenant parity with the between-steps path is exact: a
        tenant's block sequence is a function of its own cycle index
        alone (groups.block_schedule) and ``lax.map`` rows are
        independent, so WHEN a tenant is admitted never changes WHAT it
        computes (pinned by tests/test_serve_daemon.py)."""
        import jax.numpy as jnp
        from ..obs import trace as otrace
        from ..obs.metrics import REGISTRY
        from ..parallel.groups import (_group_block, block_converged,
                                       block_schedule)

        self.steps += 1
        done: list[str] = []
        stepped: set[str] = set()       # tenants dispatched this step
        while True:
            progressed = False
            # sorted() snapshots the key list: a regrow or a streaming
            # re-rent may add buckets mid-scan (picked up on re-scan)
            for key in sorted(self.buckets):
                b = self.buckets[key]
                occ, nslots = b.occupancy()
                label = self.bucket_label(key)
                # lint: ok(R6) — label is a capacity-ladder bucket (geo
                # ladder from bucket(), capped by PARMMG_SERVE_MAX_CAP*):
                # O(log cap) distinct series, not unbounded
                REGISTRY.gauge(f"serve.occupancy.{label}").set(occ)
                # lint: ok(R6) — same capacity-ladder cardinality bound
                REGISTRY.gauge(f"serve.slots.{label}").set(nslots)
                act = [(i, s) for i, s in enumerate(b.slots)
                       if s.tenant is not None and s.loaded
                       and not s.converged and not s.failed
                       and s.tenant not in stepped]
                if not act:
                    continue
                self.active_per_step.append(len(act))
                cohorts: dict[int, list[int]] = {}
                for i, s in act:
                    cohorts.setdefault(s.c, []).append(i)
                for c in sorted(cohorts):
                    ids = cohorts[c]
                    n_done0 = len(done)
                    swap, pre = block_schedule(c, self.cycles,
                                               self.noswap)
                    fn = _group_block(swap, pre, self.nomove,
                                      self.noinsert, self.hausd)
                    stepped.update(b.slots[i].tenant for i in ids)
                    progressed = True
                    rows = self._dispatch_cohort(
                        b, fn, jnp.asarray(c, jnp.int32), ids, done)
                    for i, crow in rows:
                        s = b.slots[i]
                        cs = crow.astype(np.int64)           # [11]
                        st = s.stats
                        st.nsplit += int(cs[0])
                        st.ncollapse += int(cs[1])
                        st.nswap += int(cs[2])
                        st.nmoved += int(cs[3])
                        st.cycles += 1
                        st.group_dispatches += 1
                        st.sched_extra.setdefault(
                            "ops_per_block", []).append(
                            int(cs[:4].sum()))
                        if int(cs[4]) != 0:
                            # batch regrow semantics: promote the
                            # post-run state, re-run the SAME block
                            # next step
                            try:
                                self._grow_tenant(s.tenant)
                            except MemoryError as e:
                                s.failed = str(e)
                                done.append(s.tenant)
                            continue
                        s.c = c + 1
                        if block_converged(cs, swap, self.noswap) \
                                or s.c >= self.cycles:
                            s.converged = True
                            done.append(s.tenant)
                    otrace.log(2, f"  serve step {self.steps} bucket "
                                  f"{key[0]}x{key[1]} c{c}: {len(ids)} "
                                  f"tenants, {len(rows)} dispatched",
                               verbose=verbose, err=True)
                    if on_retire is not None and len(done) > n_done0:
                        # mid-step retirement hook: slots freed by this
                        # cohort may be re-rented before the step ends
                        on_retire(done[n_done0:])
            if on_retire is None or not progressed:
                break
        return done

    def run_to_completion(self, max_steps: int = 1000) -> list[str]:
        """Drive step() until no tenant is active (pool-only loop; the
        request-queue front-end lives in serve/driver.py)."""
        out = []
        for _ in range(max_steps):
            if not self.active_tenants():
                break
            out.extend(self.step())
        return out

    def chunk_recommendation(self) -> int:
        """Trajectory-derived PARMMG_GROUP_CHUNK recommendation for the
        pool's dispatch loop (satellite of ROADMAP 1b): feed the
        active-slot counts per step into the same cost model the batch
        path logs."""
        from ..parallel.sched import recommend_group_chunk
        return recommend_group_chunk(self.active_per_step,
                                     self.slots_per_bucket)
