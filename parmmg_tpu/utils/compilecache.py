"""Compile governor: shape bucketing + a process-wide compile ledger.

The steady-state loop of this system re-runs the SAME programs every
migration iteration and every adapt wave (the libparmmg1.c remesh/
repartition cycle), but jitted entry points whose static shapes track
exact per-iteration sizes recompile forever: the retag KF2/KN widths,
the interface comm-table pads, group capacities and narrow-row budgets
all drift by a few entries between iterations, and each drift is a
fresh multi-second XLA compile (ADVICE round 3).  A serving
stack bounds and observes its compile count; this module is that layer:

- :func:`bucket` — the ONE shape-rounding policy every dynamic
  static-shape site routes through (next-pow2 with a floor, or a
  geometric 1.5x scheme for wide tables where pow2 doubling wastes
  memory), so repeat iterations land on a small fixed set of shapes;
- :func:`governed` — an explicit registry decorator for jitted entry
  points.  Paired with a ``jax.monitoring`` duration listener on the
  backend-compile event, it maintains a process-wide **compile
  ledger**: per entry point, the distinct static-shape variants that
  actually compiled, the compile count, cumulative compile seconds and
  the last static shapes — printed by scripts/scale_big.py
  so churn regressions are visible in every SCALE artifact, and
  enforced by ``scripts/run_tests.sh --ledger`` via per-entry variant
  budgets;
- :func:`set_cache_env` / :func:`enable_persistent_cache` — the
  persistent-cache wiring (JAX_COMPILATION_CACHE_DIR) shared by the
  CLI, bench and scale drivers so repeat runs and pod workers reuse
  compiled executables instead of starting cold.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time

# the jax.monitoring event recorded around every XLA backend compile
# (jax._src.dispatch.BACKEND_COMPILE_EVENT); a hit in the persistent
# cache fires it too, with the retrieval inside it
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the other events jax 0.9.0 emits on the way to an executable: tracing
# to a jaxpr, lowering it to MLIR, and loading from the persistent cache
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration", LOWER_EVENT)
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# the entry whose executables ``compile.block_programs`` counts: the
# grouped cycle block, minutes of compile and 115 MB of cache apiece
BLOCK_ENTRY = "groups.adapt_block"
# the same cycle under ``shard_map`` (parallel/dist): as long a compile
DIST_BLOCK_ENTRY = "dist.adapt_block"


# ---------------------------------------------------------------------------
# shape bucketing
# ---------------------------------------------------------------------------
def bucket(n: int, floor: int = 256, scheme: str = "pow2",
           cap: int | None = None) -> int:
    """Round ``n`` up to a bucketed static size.

    ``scheme="pow2"``: next power-of-two multiple of ``floor`` — the
    default for index tables and compaction budgets (at most 2x
    overshoot, very few distinct shapes).
    ``scheme="geo"``: geometric 1.5x ladder from ``floor`` — for WIDE
    tables (comm item axes, group capacities) where a pow2 jump can
    waste a large absolute amount of memory; overshoot <= 1.5x while
    still collapsing drifting sizes onto O(log n) shapes.

    ``cap`` clamps the result (capacity ceilings like capT); a capped
    bucket may be smaller than ``n`` — callers that cannot truncate
    must check, exactly as they would for any static budget.
    """
    n = max(int(n), 1)
    b = max(int(floor), 1)
    if scheme == "pow2":
        while b < n:
            b *= 2
    elif scheme == "geo":
        while b < n:
            b = b * 3 // 2 + 1
    else:
        raise ValueError(f"unknown bucket scheme {scheme!r}")
    if cap is not None:
        b = min(b, int(cap))
    return b


# ---------------------------------------------------------------------------
# the compile ledger
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EntryStats:
    """Per-entry-point compile accounting (mutated under the ledger lock)."""
    budget: int | None = None      # max allowed compiled variants (None = untracked)
    calls: int = 0
    compiles: int = 0              # backend-compile events attributed here
    compile_secs: float = 0.0
    keys_seen: set = dataclasses.field(default_factory=set)
    # static-shape key -> backend-compile seconds credited to calls of
    # that key (its keys are the keys that compiled)
    keys_compiled: dict = dataclasses.field(default_factory=dict)
    last_key: tuple = ()
    # (program, static-shape key) -> the placement each argument leaf
    # had when the program was first lowered for that key
    # (:meth:`CompileLedger._on_lowering`)
    lowered: dict = dataclasses.field(default_factory=dict)
    placement_variants: int = 0
    # static-shape key -> (the entry's function, the abstract signature
    # of the call a program was lowered inside, the default device it
    # was lowered under), in the order the keys were first lowered: what
    # ``obs.devtime.scope_map`` lowers again to reach the executable
    # that ran.  Kept once a lowering, never on a later call
    signatures: dict = dataclasses.field(default_factory=dict)

    @property
    def variants(self) -> int:
        """Distinct static-shape keys that triggered >= 1 compile."""
        return len(self.keys_compiled)


class CompileLedger:
    """Process-wide registry: entry point -> EntryStats.

    Attribution: :meth:`track` pushes the entry name on a thread-local
    stack; the ``jax.monitoring`` listener credits every backend-compile
    event to the innermost governed entry on the calling thread (XLA
    compiles synchronously inside the dispatching call).  Events firing
    outside any governed scope land in the ``(ungoverned)`` aggregate,
    so total compile time stays visible even for unregistered programs.

    The same listener feeds the metrics spine (``obs.metrics.REGISTRY``)
    with what the process paid on the way to its executables —
    ``compile.backend_n`` / ``compile.backend_s`` (backend-compile
    events), ``compile.cache_load_s`` (retrieval from the persistent
    cache), ``compile.trace_lower_s`` (jaxpr tracing + lowering to
    MLIR), each in seconds no other of them counts
    (:meth:`_exclusive`), and ``compile.cache_hits`` — and emits one
    ``compile`` trace event per backend compile, with the program's
    ``fun`` name, its ``dur`` and the span open at the time as
    ``parent``: a compile inside a steady job names the step that paid.

    Placement variants.  jax keys a lowering on its arguments' shardings
    and an UNCOMMITTED argument (a fresh ``jnp.zeros``, a numpy array)
    lowers with an unspecified one, so a governed program that takes the
    same avals once uncommitted and once committed (what a program's own
    output is, when any of its inputs was) is lowered, compiled and
    cached TWICE: same jaxpr, two executables (PERF.md, PR 31: the
    grouped cycle block did, 115 MB and minutes of compile each).  The
    listener sees every lowering: one of a program it has lowered before
    under the same entry, for the same shapes with another placement,
    counts in ``compile.placement_variants``, and the ``compile`` event
    of its executable carries ``variant="placement"`` and ``leaves``,
    the paths of the arguments that differ.  ``compile.block_programs``
    counts the executables of :data:`BLOCK_ENTRY` and
    :data:`DIST_BLOCK_ENTRY`, built or loaded.

    Kept signatures.  At a lowering, and never on a later call, the
    entry keeps the call's abstract signature under its static-shape key
    (:func:`_abstract`: shapes, dtypes, shardings, statics) beside the
    function it called: ``obs.devtime.scope_map`` lowers from it again
    to reach the executable that ran, and :meth:`program_index` says
    which of an entry's programs a call ran (a ``grp block`` span's
    ``prog``).
    """

    UNGOVERNED = "(ungoverned)"

    def __init__(self):
        self._entries: dict[str, EntryStats] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._listener_installed = False

    # -- registration / listener -------------------------------------------
    def register(self, name: str, budget: int | None = None) -> None:
        with self._lock:
            e = self._entries.setdefault(name, EntryStats())
            if budget is not None:
                e.budget = budget
        self.install_listener()

    def install_listener(self) -> None:
        if self._listener_installed:
            return
        try:
            from jax import monitoring
        except Exception:       # pragma: no cover - jax always present
            return
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_event_listener(self._on_count)
        self._listener_installed = True

    def _on_count(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            from ..obs.metrics import REGISTRY
            REGISTRY.counter("compile.cache_hits").inc()

    def _exclusive(self, duration: float) -> float:
        """Seconds of an event that the events nested inside it have
        not counted already.  jax reports an inner jit's trace inside
        its caller's, a cache retrieval inside the backend-compile event
        of its program, an eager op's compile inside a trace: each fires
        before the event round it, on the same thread, so the newer
        intervals an event covers are taken off it and the counters
        add up to the time spent, not to a multiple of it."""
        now = time.time()
        start = now - duration
        ivals = getattr(self._tls, "ivals", None)
        if ivals is None:
            ivals = self._tls.ivals = []
        inner = 0.0
        while ivals and ivals[-1][0] >= start - 1e-4:
            s, e = ivals.pop()
            inner += e - s
        ivals.append((start, now))
        del ivals[:-4096]
        return max(0.0, duration - inner)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event in TRACE_LOWER_EVENTS:
            series = "compile.trace_lower_s"
        elif event == CACHE_LOAD_EVENT:
            series = "compile.cache_load_s"
        elif event == BACKEND_COMPILE_EVENT:
            series = "compile.backend_s"
        else:
            return
        from ..obs.metrics import REGISTRY
        duration = float(duration)
        # lint: ok(R6) — series is one of the three literals above
        REGISTRY.counter(series).inc(self._exclusive(duration))
        stack = getattr(self._tls, "stack", None)
        fun = str(kw.get("fun_name", "?"))
        if event == LOWER_EVENT and stack:
            self._on_lowering(stack[-1], fun)
        if event != BACKEND_COMPILE_EVENT:
            return
        from ..obs import trace as otrace
        REGISTRY.counter("compile.backend_n").inc()
        variant = {}
        if stack and stack[-1].variant and stack[-1].variant[0] == fun:
            variant = {"variant": "placement",
                       "leaves": stack[-1].variant[1]}
            stack[-1].variant = None
        otrace.event("compile", fun=fun, dur=round(duration, 6), **variant)
        name = stack[-1].name if stack else self.UNGOVERNED
        if name in (BLOCK_ENTRY, DIST_BLOCK_ENTRY):
            REGISTRY.counter("compile.block_programs").inc()
        with self._lock:
            e = self._entries.setdefault(name, EntryStats())
            e.compiles += 1
            e.compile_secs += float(duration)
            if stack:
                key = stack[-1].key
                e.keys_compiled[key] = \
                    e.keys_compiled.get(key, 0.0) + float(duration)

    def _on_lowering(self, scope: "_TrackScope", fun: str) -> None:
        """A program was lowered inside the governed call ``scope``: if
        the entry lowered ``fun`` for this static-shape key before and
        the leaves' placement differs, this lowering exists for the
        placement alone."""
        import jax
        places = _placement(scope.call)
        # the default device is part of what jax lowers for: a program
        # staged on the host (utils/placement.host_staging) takes
        # uncommitted arguments and runs where the context says
        kept = (scope.fn, _abstract(scope.call),
                jax.config.jax_default_device)
        with self._lock:
            e = self._entries.setdefault(scope.name, EntryStats())
            e.signatures[scope.key] = kept
            first = e.lowered.setdefault((fun, scope.key), places)
            differ = [i for i, (a, b) in enumerate(zip(first, places))
                      if a != b]
            if not differ:
                return
            e.placement_variants += 1
        import jax
        paths = jax.tree_util.tree_flatten_with_path(scope.call)[0]
        scope.variant = (fun, [
            f"{jax.tree_util.keystr(paths[i][0])}: "
            f"{first[i] or 'uncommitted'} -> {places[i] or 'uncommitted'}"
            for i in differ])
        from ..obs.metrics import REGISTRY
        REGISTRY.counter("compile.placement_variants").inc()

    # -- call tracking ------------------------------------------------------
    def track(self, name: str, key: tuple, call=None,
              fn=None) -> "_TrackScope":
        """``call``: the call's ``(args, kwargs)``, and ``fn`` the
        function it calls: read only if a program is lowered inside the
        scope (:meth:`_on_lowering`)."""
        return _TrackScope(self, name, key, call, fn)

    def signature(self, name: str, key: tuple | None = None):
        """(key, function, abstract ``(args, kwargs)``, default device)
        kept when ``name`` lowered a program for ``key`` (its last
        call's key by default, the last lowered one if that call lowered
        nothing)."""
        with self._lock:
            e = self._entries.get(name)
            if e is None or not e.signatures:
                raise KeyError(f"{name}: no program lowered in this process")
            if key is None:
                key = e.last_key if e.last_key in e.signatures \
                    else next(reversed(e.signatures))
            return (key,) + e.signatures[key]

    def compile_seconds(self, name: str, key: tuple) -> float:
        """Backend-compile seconds credited to ``name``'s calls of
        ``key``: what building that program again would cost."""
        with self._lock:
            e = self._entries.get(name)
            return e.keys_compiled.get(key, 0.0) if e is not None else 0.0

    def lowered_keys(self, name: str) -> list:
        """The static-shape keys ``name`` lowered a program for, in the
        order :meth:`program_index` counts them."""
        with self._lock:
            e = self._entries.get(name)
            return list(e.signatures) if e is not None else []

    def program_index(self, name: str) -> int | None:
        """Which of the programs ``name`` lowered its last call ran: the
        index of that call's key among the lowered keys, in the order
        they were first lowered (None: the call lowered none)."""
        with self._lock:
            e = self._entries.get(name)
            if e is None or e.last_key not in e.signatures:
                return None
            return list(e.signatures).index(e.last_key)

    @contextlib.contextmanager
    def ungoverned(self):
        """No governed entry on this thread for the block: a compile
        inside it is credited to none (``obs.devtime.scope_map``)."""
        stack = getattr(self._tls, "stack", None)
        self._tls.stack = []
        try:
            yield
        finally:
            self._tls.stack = stack if stack is not None else []

    # -- reporting ----------------------------------------------------------
    def snapshot(self) -> dict:
        """{entry: {calls, variants, shapes_seen, compiles, compile_s,
        last_shapes, budget}} — JSON-serializable."""
        with self._lock:
            out = {}
            for name, e in sorted(self._entries.items()):
                out[name] = {
                    "calls": e.calls,
                    "variants": e.variants,
                    "shapes_seen": len(e.keys_seen),
                    "compiles": e.compiles,
                    "compile_s": round(e.compile_secs, 3),
                    "last_shapes": repr(e.last_key) if e.last_key else "",
                    "budget": e.budget,
                    "placement_variants": e.placement_variants,
                }
            return out

    def violations(self) -> list[str]:
        """Entries whose compiled-variant count exceeds their budget."""
        bad = []
        with self._lock:
            for name, e in sorted(self._entries.items()):
                if e.budget is not None and e.variants > e.budget:
                    bad.append(f"{name}: {e.variants} compiled variants "
                               f"> budget {e.budget}")
        return bad

    def format(self, min_compiles: int = 0) -> str:
        rows = [f"{'entry point':36s} {'calls':>6s} {'vars':>5s} "
                f"{'compiles':>8s} {'secs':>8s}"]
        for name, rec in self.snapshot().items():
            # hide rows that were only registered (import-time @governed)
            # but never called or compiled; min_compiles raises the bar
            if rec["calls"] == 0 and rec["compiles"] < max(min_compiles, 1):
                continue
            rows.append(f"{name:36s} {rec['calls']:6d} "
                        f"{rec['variants']:5d} {rec['compiles']:8d} "
                        f"{rec['compile_s']:8.2f}")
        return "\n".join(rows)

    def reset(self) -> None:
        with self._lock:
            for e in self._entries.values():
                e.calls = 0
                e.compiles = 0
                e.compile_secs = 0.0
                e.keys_seen.clear()
                e.keys_compiled.clear()
                e.last_key = ()
                e.lowered.clear()
                e.placement_variants = 0
                # ``signatures`` stay: they say what jax's caches hold,
                # which a reset of the accounting does not drop, and a
                # later call of a program lowers nothing to keep anew


class _TrackScope:
    """Context manager crediting backend compiles inside the scope to a
    governed entry (one instance per call — the steady-state loop calls
    governed entries every iteration, so no per-call class creation)."""

    __slots__ = ("_ledger", "name", "key", "call", "fn", "variant")

    def __init__(self, ledger: CompileLedger, name: str, key: tuple,
                 call=None, fn=None):
        self._ledger = ledger
        self.name = name
        self.key = key
        self.call = call
        self.fn = fn
        # (program, differing leaves) of a placement-only lowering whose
        # executable's ``compile`` event is still to come
        self.variant = None

    def __enter__(self):
        led = self._ledger
        if not hasattr(led._tls, "stack"):
            led._tls.stack = []
        led._tls.stack.append(self)
        with led._lock:
            e = led._entries.setdefault(self.name, EntryStats())
            e.calls += 1
            e.keys_seen.add(self.key)
            e.last_key = self.key
        return self

    def __exit__(self, *exc):
        self._ledger._tls.stack.pop()
        return False


LEDGER = CompileLedger()


def _static_key(args, kwargs) -> tuple:
    """Hashable static-shape key of a call: array leaves contribute
    (shape, dtype); hashable non-array leaves contribute their value
    (jit static args); everything else its type name."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            try:
                hash(leaf)
                parts.append(leaf)
            except TypeError:
                parts.append(type(leaf).__name__)
    return tuple(parts)


def _placement(call) -> tuple:
    """The placement jax keys a lowering on, leaf by leaf of a call's
    ``(args, kwargs)``: the sharding of a committed array, and "" for
    everything that lowers unspecified (an uncommitted array, numpy, a
    Python scalar)."""
    import jax
    return tuple(str(leaf.sharding)
                 if getattr(leaf, "_committed", False) else ""
                 for leaf in jax.tree_util.tree_leaves(call))


def _abstract(call):
    """A call's ``(args, kwargs)`` with every array leaf as what jax keys
    a lowering on (shape, dtype, weak type, and the sharding of a
    committed array); static leaves stay what they are."""
    import jax

    def leaf(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=x.sharding if getattr(x, "_committed", False) else None,
            weak_type=bool(getattr(x, "weak_type", False)))
    return jax.tree_util.tree_map(leaf, call)


def governed(name: str, budget: int | None = None, key_fn=None):
    """Register a (usually jitted) entry point with the compile ledger.

    Every call records its static-shape key; backend compiles occurring
    inside the call are attributed to ``name``.  ``budget`` declares
    the allowed number of compiled variants (enforced by
    ``scripts/run_tests.sh --ledger`` and checkable in tests via
    :func:`ledger_violations`); ``key_fn(*args, **kwargs)`` overrides
    the default shapes-and-statics key.
    """
    def deco(fn):
        LEDGER.register(name, budget)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            key = key_fn(*args, **kwargs) if key_fn is not None \
                else _static_key(args, kwargs)
            with LEDGER.track(name, key, (args, kwargs), fn):
                return fn(*args, **kwargs)

        wrapper.__wrapped__ = fn
        return wrapper
    return deco


def ledger_diff(old: dict, new: dict) -> list[str]:
    """Compile-ledger regression check between two snapshots: entry
    points present in BOTH whose compiled-variant count grew.

    ``old``/``new`` accept either a flat snapshot ({entry: {variants,
    ...}}) or the nested per-worker shape scale_big emits ({"pass0":
    {entry: ...}, "host": ...}) — nested levels are flattened with a
    "<worker>/" prefix and compared per worker.  Entries only in
    ``new`` are NOT regressions (fresh programs carry their own
    budgets); a grown variant count on a shared entry is the churn
    signature scripts/scale_big.py flags against the previous SCALE
    artifact."""
    def flatten(d: dict, prefix: str = "") -> dict:
        out = {}
        for k, v in (d or {}).items():
            if isinstance(v, dict) and "variants" not in v:
                out.update(flatten(v, prefix + str(k) + "/"))
            elif isinstance(v, dict):
                out[prefix + str(k)] = v
        return out

    fo, fn_ = flatten(old), flatten(new)
    bad = []
    for name in sorted(set(fo) & set(fn_)):
        vo = int(fo[name].get("variants", 0))
        vn = int(fn_[name].get("variants", 0))
        if vn > vo:
            bad.append(f"{name}: {vo} -> {vn} compiled variants")
    return bad


def extract_artifact_ledger(doc) -> dict:
    """Pull the compile-ledger dict out of any artifact shape we emit:
    a plain snapshot, bench JSON ({extra: {compile_ledger}}), or the
    round wrapper ({parsed: {extra: {compile_ledger}}})."""
    if not isinstance(doc, dict):
        return {}
    for path in (("parsed", "extra", "compile_ledger"),
                 ("extra", "compile_ledger"),
                 ("compile_ledger",)):
        d = doc
        for k in path:
            d = d.get(k) if isinstance(d, dict) else None
            if d is None:
                break
        if isinstance(d, dict):
            return d
    return doc


def regressions_vs_latest_artifact(root: str, pattern: str,
                                   ledger: dict) -> list[str]:
    """Diff ``ledger`` against the NEWEST round artifact matching
    ``pattern`` (e.g. "SCALE_r*.json") under ``root`` — the shared
    regression check of scripts/scale_big.py and scripts/serve_bench.py.
    Artifacts without a ledger compare clean (the first governed round
    seeds the baseline)."""
    import glob
    import json
    import re

    def rnum(p: str) -> int:
        m = re.search(r"r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    def has_rows(d: dict) -> bool:
        return any(isinstance(v, dict) and
                   ("variants" in v or has_rows(v)) for v in d.values())

    for path in sorted(glob.glob(os.path.join(root, pattern)),
                       key=rnum, reverse=True):
        try:
            with open(path) as f:
                doc = json.load(f)
        except Exception:
            continue
        prev = extract_artifact_ledger(doc)
        if prev and has_rows(prev):
            return ledger_diff(prev, ledger)
    return []


# module-level conveniences (re-exported by utils.timers)
def ledger_snapshot() -> dict:
    return LEDGER.snapshot()


def variants_by_prefix(prefix: str) -> dict:
    """{entry: compiled-variant count} for ledger entries under a name
    prefix — the compile-family comparison unit of the zero-new-family
    gates (ledger_check grouped_sched_gate / serving_gate) and of
    scripts/serve_bench.py's batch-vs-serve diff: snapshot before,
    snapshot after, equality == no new compiled shape families."""
    return {k: r["variants"] for k, r in LEDGER.snapshot().items()
            if k.startswith(prefix)}


def format_ledger(min_compiles: int = 0) -> str:
    return LEDGER.format(min_compiles)


def reset_ledger() -> None:
    LEDGER.reset()


def ledger_violations() -> list[str]:
    return LEDGER.violations()


# ---------------------------------------------------------------------------
# persistent-cache wiring
# ---------------------------------------------------------------------------
def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the one default.  The path is part
    of the cache key, so it never moves with a pid, a time or a temp
    directory."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def set_cache_env(cache_dir: str | None = None) -> str:
    """The one cache rule, as env vars, WITHOUT importing jax (safe
    before backend selection; child processes inherit it): a
    JAX_COMPILATION_CACHE_DIR the caller set is used and no other is
    set in code; otherwise ``cache_dir`` or :func:`default_cache_dir`.

    Skipped (returns "") on the pinned CPU backend (JAX_PLATFORMS=cpu)
    unless the caller opted in with ``cache_dir`` or the env var: the
    XLA:CPU AOT cache is unreliable on this image (its serializer
    intermittently aborts — tests/conftest.py rationale)."""
    if ("JAX_COMPILATION_CACHE_DIR" not in os.environ
            and cache_dir is None
            and os.environ.get("JAX_PLATFORMS", "") == "cpu"):
        return ""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          cache_dir or default_cache_dir())
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def enable_persistent_cache(cache_dir: str | None = None) -> str:
    """set_cache_env + push the values into an already-imported jax
    config (covers callers that imported jax before the env was set)."""
    path = set_cache_env(cache_dir)
    if not path:
        return ""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
    return path


def disable_persistent_cache() -> None:
    """Turn the persistent cache off for this process (cold-compile
    gates) without touching what the caller placed in the env."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def backend_or_fail() -> dict:
    """Resolve the backend at an entry point and describe it
    ({platform, kind, count}).  Without the explicit JAX_PLATFORMS=cpu
    pin a run that landed on the CPU means the accelerator is missing:
    that is an error, not a quieter run."""
    import jax
    d = jax.devices()[0]
    if d.platform == "cpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        raise RuntimeError(
            "no accelerator: jax resolved to the CPU backend; set "
            "JAX_PLATFORMS=cpu to run there on purpose")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
