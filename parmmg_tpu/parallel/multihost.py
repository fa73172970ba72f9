"""Multi-host SPMD backend (jax.distributed over ICI/DCN).

The reference scales across nodes with MPI ranks (mpi_pmmg.h; rank
discovery + shared-memory budget split in zaldy_pmmg.c:53-96).  The
JAX-native equivalent is ``jax.distributed.initialize``: each host
process owns its local TPU devices, ``jax.devices()`` becomes the GLOBAL
device list, and the same ``shard_map`` programs of parallel/dist.py run
unchanged — XLA lowers the 'shard' axis collectives onto ICI within a
pod slice and DCN across slices (gloo on the CPU dev backend, knob
PARMMG_MH_COLLECTIVES).

What runs multi-host (the pod runtime, parallel/pod.py):
- the SPMD adapt blocks, quality reductions, the on-device interface
  echo and the whole band-migration pipeline — device arrays are
  global ('shard'-sharded via :func:`shard_stacked_global`);
- the band-path host stages: every process executes the identical host
  driver (the reference's "all ranks agree via Allreduce" idiom) on
  compacted band tables replicated through ``pod.gather_band`` — ONE
  cached shard_map collective per table family, never a per-leaf
  ``process_allgather``;
- the persistent compile cache is SHARED across workers
  (PARMMG_MH_CACHE_DIR): a warmed cache means worker N+1 deserializes
  executables instead of re-paying the multi-minute SPMD compiles —
  the scripts/multihost_run.py phase structure.

What stays single-host: the full-view fallback stages (split, merge,
full-mesh migration oracle) assert single-process via
:func:`require_single_process` rather than silently computing on a
partial device view.

:func:`pull_host` remains as the METERED escape hatch: every
process_allgather it performs counts ``mh.allgather_bytes``, and one
reached inside a :func:`hot_path` section additionally counts
``mh.hot_allgather_bytes`` (the ``--multihost`` gate asserts that
counter is ZERO) and raises under PARMMG_MH_STRICT — a stray allgather
on the hot path fails the gate, it does not just slow the run.  The
static mirror of the same tripwire is lint rule R7
(parmmg_tpu/lint/rules_hostsync.py).
"""
from __future__ import annotations

import contextlib
import os

import numpy as np


def mh_uniform(value, why: str):
    """Identity marker asserting ``value`` is SPMD-safe: either agreed
    across ranks (same value everywhere) or deliberately rank-scoped
    with the agreement protocol described in ``why``.

    The flagship use is the rank-0-writes idiom::

        write=mh_uniform((not multi) or jax.process_index() == 0,
                         "rank 0 durably writes; every rank computed "
                         "the identical predicate shape")

    Lint rule R8 (parmmg_tpu/lint/rules_spmd.py) taints everything
    derived from ``jax.process_index()`` and flags collectives or side
    effects that depend on the taint; ``mh_uniform``'s RESULT is
    untainted, so wrapping a value here is the in-code, reasoned
    alternative to a ``# lint: ok(R8)`` comment.  ``why`` is mandatory
    for the same reason suppression reasons are: the assertion is only
    as good as its argument.
    """
    if not why or not why.strip():
        raise ValueError("mh_uniform() requires a non-empty 'why' "
                         "describing the cross-rank agreement")
    return value


def init_multihost(coordinator: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None) -> bool:
    """Initialize jax.distributed from args or the standard env vars
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID).

    Returns True if a multi-process runtime was initialized; False for
    the single-process degenerate case (no-op — the NP=1 column of the
    reference CI matrix).  Safe to call twice.

    Pod wiring performed here, BEFORE the backend client exists:
    cross-process CPU collectives (jax refuses multiprocess CPU
    computations without an implementation; PARMMG_MH_COLLECTIVES,
    default gloo) and the shared persistent compile cache
    (PARMMG_MH_CACHE_DIR — the explicit opt-in path of
    ``set_cache_env``, so the pinned-CPU dev pod behaves like the chip
    pod: one worker compiles, the others deserialize).
    """
    import jax

    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("JAX_PROCESS_ID", "0"))
    cache = os.environ.get("PARMMG_MH_CACHE_DIR", "")
    if cache:
        # cache even the sub-second programs: the pod pays hundreds of
        # small eager-op compiles whose sum dwarfs any deserialize cost
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
        from ..utils.compilecache import set_cache_env
        set_cache_env(cache)
    if not coordinator or num_processes <= 1:
        # single-process degenerate pod: still wire the shared cache
        # (the 1-process parity reference of multihost_run warms its
        # own program family once per scenario)
        if cache:
            from ..utils.compilecache import enable_persistent_cache
            enable_persistent_cache(cache)
        return False
    impl = os.environ.get("PARMMG_MH_COLLECTIVES", "gloo")
    if impl and impl != "none":
        try:
            jax.config.update("jax_cpu_collectives_implementation", impl)
        except Exception:
            pass            # other jax versions: backend handles it
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id)
    except RuntimeError as e:
        if "already initialized" in str(e).lower():
            return True
        raise
    if cache:
        from ..utils.compilecache import enable_persistent_cache
        enable_persistent_cache(cache)
    return True


def is_multiprocess() -> bool:
    import jax
    return jax.process_count() > 1


# ---------------------------------------------------------------------------
# hot-path metering (the pull_host escape hatch's tripwire)
# ---------------------------------------------------------------------------
_HOT_DEPTH = [0]


@contextlib.contextmanager
def hot_path():
    """Mark a section as the multi-host HOT PATH: any process_allgather
    ``pull_host`` performs inside it is counted on
    ``mh.hot_allgather_bytes`` (gate-asserted zero) and raises under
    PARMMG_MH_STRICT.  The per-iteration body of
    ``distributed_adapt_multi`` runs inside one.  Entering a hot
    section also beats this rank's heartbeat file (throttled by
    PARMMG_HEARTBEAT_S) so the pod supervisor's lease
    (scripts/multihost_run.py --lease) sees liveness exactly where
    wedging matters."""
    from ..resilience.watchdog import beat
    beat()
    _HOT_DEPTH[0] += 1
    try:
        yield
    finally:
        _HOT_DEPTH[0] -= 1


@contextlib.contextmanager
def cold_io():
    """Exempt a nested IO section (checkpoint write, artifact dump)
    from hot-path metering: replicating state for durable output is the
    designed cost of that path, not a stray hot-loop allgather."""
    d, _HOT_DEPTH[0] = _HOT_DEPTH[0], 0
    try:
        yield
    finally:
        _HOT_DEPTH[0] = d


def in_hot_path() -> bool:
    return _HOT_DEPTH[0] > 0


def _note_allgather(nbytes: int, what: str = "") -> None:
    """Meter one escape-hatch allgather (factored for host-only tests):
    total bytes always; hot-path bytes + trace event + the
    PARMMG_MH_STRICT tripwire when inside :func:`hot_path`."""
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    REGISTRY.counter("mh.allgather_bytes").inc(float(nbytes))
    if in_hot_path():
        REGISTRY.counter("mh.hot_allgather_bytes").inc(float(nbytes))
        otrace.event("mh.hot_allgather", nbytes=int(nbytes),
                     what=str(what))
        if os.environ.get("PARMMG_MH_STRICT", "") == "1":
            raise RuntimeError(
                f"hot-path process_allgather of {nbytes} bytes"
                + (f" ({what})" if what else "")
                + " — the pod band path must route through "
                "pod.gather_band [PARMMG_MH_STRICT]")


# cached resharding identities keyed by the target sharding (compile
# governor): the non-addressable branch below used to build a FRESH
# ``jax.jit(lambda a: a)`` per call — one recompile per leaf per upload
# on multi-process runs (the io.distributed writers and every band-table
# pull route through here).  One cached object per (devices, spec) pair
# + ledger registration, the check_interface_echo caching pattern.
_RESHARD_CACHE: dict = {}


def _reshard_identity(sh):
    # lint: ok(R2) — device-id METADATA (sharding.mesh.devices is a
    # host numpy object array), no device sync
    key = (tuple(d.id for d in np.asarray(sh.mesh.devices).flat),
           str(sh.spec))
    fn = _RESHARD_CACHE.get(key)
    if fn is None:
        import jax
        from ..utils.compilecache import governed
        fn = governed("multihost.reshard", budget=4)(
            jax.jit(lambda a: a, out_shardings=sh))
        _RESHARD_CACHE[key] = fn
    return fn


def shard_stacked_global(stacked_host, dmesh):
    """Place a [D, ...]-stacked HOST pytree onto a (possibly multi-host)
    device mesh: each process uploads only the shard slices that live on
    its addressable devices, then the global array is assembled with
    ``jax.make_array_from_single_device_arrays`` — the multi-host
    replacement for a plain ``jax.device_put`` (which requires all
    devices addressable).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(dmesh, P("shard"))
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sh),
                            stacked_host)

    devs = list(dmesh.devices.reshape(-1))

    def put(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # already a multi-process global array (e.g. the output of
            # grow_shards' pad on a sharded input): np.asarray would
            # raise on non-addressable shards — reshard with the cached
            # jitted identity instead (XLA inserts the collectives)
            return _reshard_identity(sh)(x)
        # lint: ok(R2) — input is the HOST-resident stacked pytree
        # (numpy or addressable upload staging), never a device pull
        x = np.asarray(x)
        if x.shape[0] % len(devs):
            raise ValueError(
                f"leading axis {x.shape[0]} not divisible by "
                f"{len(devs)} devices (groups x shards requires "
                "G whole rows per device)")
        g = x.shape[0] // len(devs)   # logical shards per device (G)
        pieces = []
        for i, d in enumerate(devs):
            if d.process_index == jax.process_index():
                # lint: ok(R8) — rank-scoped BY DESIGN: each process
                # uploads exactly its addressable shard slices; every
                # rank runs this identical loop over the global device
                # list, and make_array_from_single_device_arrays below
                # is the agreement that assembles the pieces
                pieces.append(jax.device_put(x[i * g:(i + 1) * g], d))
        return jax.make_array_from_single_device_arrays(
            x.shape, sh, pieces)

    return jax.tree.map(put, stacked_host)


def require_single_process(what: str) -> None:
    """Guard for host-orchestration stages not yet distributed across
    processes (split/merge/migration packaging) — fail loudly instead of
    silently computing on a partial device view."""
    import jax
    if jax.process_count() > 1:
        raise NotImplementedError(
            f"{what} is single-controller today; run it on one host or "
            "use the per-process distributed I/O entry "
            "(io.distributed) — multi-process host orchestration is the "
            "next step documented in parallel/multihost.py")


def pull_host(x, what: str = "") -> np.ndarray:
    """Device -> host pull that is correct on a multi-process runtime —
    and METERED: the band path's hot-loop stages must ride
    ``pod.gather_band`` instead, this is the escape hatch.

    Single-process (or an already fully-addressable / fully-replicated
    array): plain ``np.asarray``.  Multi-process with a 'shard'-sharded
    global array: every process holds only its addressable slices, so
    the pull is a ``process_allgather`` — each process receives the
    full value and the host stages compute identically everywhere (the
    reference's every-rank-agrees idiom, distributegrps_pmmg.c:1631).
    Every such allgather bumps ``mh.allgather_bytes``; inside a
    :func:`hot_path` section it additionally bumps
    ``mh.hot_allgather_bytes`` (asserted ZERO by ``run_tests.sh
    --multihost``) and raises under PARMMG_MH_STRICT."""
    import jax
    if isinstance(x, np.ndarray):
        return x
    if jax.process_count() == 1 or not isinstance(x, jax.Array) \
            or x.is_fully_addressable or x.is_fully_replicated:
        return np.asarray(x)
    _note_allgather(int(np.prod(x.shape)) * x.dtype.itemsize, what)
    from jax.experimental import multihost_utils
    # lint: ok(R7) — pull_host IS the metered escape hatch (module
    # docstring): the allgather is counted above and trips the
    # PARMMG_MH_STRICT / gate assertions when reached hot
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
