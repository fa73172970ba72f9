"""Central registry of every ``PARMMG_*`` environment knob.

The env surface grew one knob at a time across the governor, scheduler,
halo, obs, resilience and serve layers; until this module the only
inventory was grep.  Every knob the tree reads MUST be declared here —
``scripts/lint_check.py`` (rule R4) cross-checks the registry against
the actual ``os.environ`` / ``getenv`` read sites AND against the
README knob tables, in both directions: an unregistered read fails the
lint, and so does a registered knob nothing reads (dead knob) or one
the README never mentions.

This module is import-light on purpose (stdlib only, no jax, no
numpy): the linter and host-only tests consume it, and the readers in
the hot layers keep their existing direct ``os.environ`` reads — the
registry is the *contract*, not a call-path rewrite.

``python -m parmmg_tpu.api.knobs`` prints the canonical markdown table
(the README "Environment knobs" section is generated from it; R4
verifies the two never drift).

NOTE for the R4 linter: ``KNOBS`` below must stay a single dict literal
of ``"NAME": Knob(type, default, doc)`` entries — the linter reads it
with ``ast`` (no import) so it can run jax-free in <10 s.
"""
from __future__ import annotations

import dataclasses
import os

__all__ = ["KNOBS", "Knob", "get", "knob_table_md", "registered"]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One env knob: coarse value type ("int" | "float" | "str" |
    "flag" | "path" | "spec"), the default the reader applies when the
    variable is unset/empty (as the string the env would carry; "" =
    off/auto), and a one-line doc."""
    type: str
    default: str
    doc: str


KNOBS: dict[str, Knob] = {
    "PARMMG_BAND_PATH": Knob(
        "flag", "1",
        "device band-migration path; 0 = legacy host full-mesh migrate"),
    "PARMMG_CKPT_DIR": Knob(
        "path", "",
        "pass-checkpoint directory (resilience/checkpoint.py); unset "
        "= checkpointing off"),
    "PARMMG_CKPT_EVERY": Knob(
        "int", "1", "checkpoint every Nth outer pass"),
    "PARMMG_COLLAPSE_BAND": Knob(
        "flag", "1",
        "donor-scoped collapse apply: run the collapse tag/ref join "
        "scatters on a geo-bucketed donor band instead of full [capT] "
        "width, bit-identical by the band coverage proof "
        "(ops/collapse.py); 0 = always full width"),
    "PARMMG_DEADLINE_DISPATCH_S": Knob(
        "float", "0",
        "watchdog deadline on each grouped chunk dispatch/drain "
        "(resilience/watchdog.py; 0 = off); expiry enters the retry "
        "ladder as WatchdogTimeout"),
    "PARMMG_DEADLINE_EXCHANGE_S": Knob(
        "float", "0",
        "watchdog deadline on each single-process gather_band "
        "exchange attempt (0 = off; cross-process hangs are the "
        "heartbeat lease's job)"),
    "PARMMG_DEADLINE_GRACE_S": Knob(
        "float", "300",
        "extra seconds granted to a site's FIRST guarded call so a "
        "cold XLA compile is not misread as a wedged warm step"),
    "PARMMG_DEADLINE_SERVE_S": Knob(
        "float", "0",
        "watchdog deadline on each serve daemon loop step (0 = off); "
        "expiry flips /healthz to wedged until the step returns"),
    "PARMMG_DEVICE_MASK": Knob(
        "flag", "1",
        "device-resident quiet masks: lax.cond-skip the wave math for "
        "quiet/pad group slots on the grouped and dist paths "
        "(parallel/sched.py); 0 = compute every slot"),
    "PARMMG_FAULT": Knob(
        "spec", "",
        "arm fault-injection sites: site[:trigger][,site...] "
        "(resilience/faults.py grammar)"),
    "PARMMG_GROUP_CHUNK": Knob(
        "int", "",
        "groups per dispatch on the grouped path (0 = one lax.map; "
        "auto = adopt sched.recommend_group_chunk; empty = 0)"),
    "PARMMG_GROUP_PIPELINE": Knob(
        "flag", "1",
        "double-buffer the chunk dispatches; 0 = serialize (one chunk "
        "in flight)"),
    "PARMMG_GROUP_SCHED": Knob(
        "flag", "1",
        "quiet-group scheduler on the grouped adapt path; 0 = legacy "
        "always-dispatch"),
    "PARMMG_HALO_PACK_HYST": Knob(
        "float", "0.05",
        "hysteresis margin around the packed-halo occupancy threshold "
        "(layout flips only past threshold +/- margin)"),
    "PARMMG_HALO_PACK_OCC": Knob(
        "float", "0.75",
        "measured-occupancy threshold under which the grouped halo "
        "uses the packed per-device-pair layout instead of dense"),
    "PARMMG_HEARTBEAT_LEASE_S": Knob(
        "float", "0",
        "pod supervisor default for scripts/multihost_run.py --lease: "
        "seconds without a worker heartbeat after which the pack is "
        "killed and relaunched with resume (0 = leases off)"),
    "PARMMG_HEARTBEAT_S": Knob(
        "float", "2",
        "worker heartbeat interval: minimum seconds between per-rank "
        "heartbeat touches inside hot_path sections"),
    "PARMMG_HOST_ANALYSIS": Knob(
        "flag", "",
        "1 = skip the device analysis-refresh path and always use the "
        "host fallback"),
    "PARMMG_MH_CACHE_DIR": Knob(
        "path", "",
        "shared persistent compile-cache dir for multi-host pod "
        "workers (parallel/multihost.init_multihost): worker 0 warms, "
        "workers N+1 deserialize instead of recompiling"),
    "PARMMG_MH_COLLECTIVES": Knob(
        "str", "gloo",
        "cross-process CPU collectives implementation for the dev pod "
        "(gloo | mpi | none); ignored on real chip interconnects"),
    "PARMMG_MH_HANDOFF": Knob(
        "flag", "",
        "1 = host-to-host group handoff: rebalance logical shards "
        "across devices/processes between iterations (parallel/pod.py;"
        " off by default — reordering arrivals breaks bit-parity with "
        "the no-handoff run)"),
    "PARMMG_MH_HEARTBEAT_DIR": Knob(
        "path", "",
        "internal supervisor->worker heartbeat directory (per-rank "
        "hb.N files; scripts/multihost_run.py sets it under --lease); "
        "never set by hand"),
    "PARMMG_MH_IMBALANCE": Knob(
        "float", "0.25",
        "device load skew (max/mean - 1) above which the group "
        "handoff re-plans placement"),
    "PARMMG_MH_STRICT": Knob(
        "flag", "",
        "1 = raise on any hot-path process_allgather instead of only "
        "metering it (mh.hot_allgather_bytes tripwire)"),
    "PARMMG_PALLAS_SCORE": Knob(
        "flag", "1",
        "Pallas candidate-scoring kernels for the split/collapse/swap "
        "top-k budget prep (ops/pallas_kernels.py; dispatched on TPU "
        "only — CPU always uses the bit-identical jnp reference); "
        "0 = jnp reference everywhere"),
    "PARMMG_PROFILE_DIR": Knob(
        "path", "",
        "hold one jax.profiler capture over each whole run "
        "(driver.parmmg_run, staging to tail), written into this "
        "directory"),
    "PARMMG_RESUME_MAX": Knob(
        "int", "3",
        "crash-loop breaker: resume attempts into the SAME pass of "
        "the same run fingerprint before escalating to lowfailure "
        "instead of resuming again (resilience/checkpoint.crash_loop)"),
    "PARMMG_RETRY_BASE_S": Knob(
        "float", "0.05",
        "retry backoff base seconds, doubled per attempt"),
    "PARMMG_RETRY_DEADLINE_S": Knob(
        "float", "0",
        "wall-clock cap on retrying (0 = no deadline)"),
    "PARMMG_RETRY_MAX": Knob(
        "int", "2",
        "retries after the first failure on retry_call sites (0 = "
        "fail fast)"),
    "PARMMG_SERVE_AUTOSCALE": Knob(
        "flag", "1",
        "SLO-driven autoscale controller on the serving loop (bucket "
        "resizing + admission deferral); 0 = off"),
    "PARMMG_SERVE_CHUNK": Knob(
        "int", "1", "serve pool: tenants per packed cohort dispatch"),
    "PARMMG_SERVE_MAX_CAPP": Knob(
        "int", "4194304",
        "serve admission ceiling on the vertex capacity (oversize "
        "requests rejected)"),
    "PARMMG_SERVE_MAX_CAPT": Knob(
        "int", "4194304",
        "serve admission ceiling on the tet capacity"),
    "PARMMG_SERVE_MAX_INFLIGHT": Knob(
        "int", "0",
        "serve driver: max requests admitted concurrently (0 = "
        "unbounded)"),
    "PARMMG_SERVE_MAX_QUEUE": Knob(
        "int", "0",
        "admission backpressure: try_submit / daemon submits are "
        "deferred (HTTP 429) at this queue depth (0 = unbounded)"),
    "PARMMG_SERVE_MAX_RETRIES": Knob(
        "int", "2",
        "slot faults before a serve tenant is quarantined (retired "
        "FAILED, slot scrubbed)"),
    "PARMMG_SERVE_MAX_SLOTS": Knob(
        "int", "16",
        "autoscale growth ceiling on any bucket's slot count"),
    "PARMMG_SERVE_PORT": Knob(
        "int", "8077",
        "serve daemon: HTTP bind port (scripts/serve_daemon.py; 0 = "
        "ephemeral)"),
    "PARMMG_SERVE_SLO_QMIN": Knob(
        "float", "0",
        "per-tenant qmin SLO floor; retirement records an slo_ok / "
        "slo_violation verdict (0 = off)"),
    "PARMMG_SERVE_SLOTS": Knob(
        "int", "4", "serve pool: slots per capacity bucket"),
    "PARMMG_SERVE_STREAM": Knob(
        "flag", "1",
        "streaming admission: re-rent slots freed MID-STEP to queued "
        "tenants; 0 = admit between steps only"),
    "PARMMG_SERVE_STREAM_RATE": Knob(
        "float", "2",
        "serve_bench.py --stream open-loop arrival rate (tenants/sec)"),
    "PARMMG_SERVE_TARGET_P99_S": Knob(
        "float", "0",
        "autoscale latency SLO: defer admissions while observed p99 "
        "exceeds this with work queued (0 = off)"),
    "PARMMG_SERVE_TIMEOUT_S": Knob(
        "float", "0",
        "serve driver: per-request wall-clock timeout; the slot is "
        "reclaimed (0 = off)"),
    "PARMMG_SOAK_RUNS": Knob(
        "int", "8",
        "scripts/chaos_soak.py default campaign length (seeded runs "
        "with randomized fault schedules)"),
    "PARMMG_SOAK_SEED": Knob(
        "int", "20260804",
        "scripts/chaos_soak.py campaign seed: the fault schedule is a "
        "pure function of (seed, runs)"),
    "PARMMG_SWAP_FACESORT": Knob(
        "flag", "",
        "pair swap23 candidates directly off the face-sort records, "
        "skipping the cycle-interior build_adjacency rebuild "
        "(ops/swap.py); bit-identical pairing by the argmin/argmax2 "
        "tie-break equivalence; unset = on where the program is placed "
        "on a TPU, off elsewhere, so off for the tail a TPU process "
        "stages on its host (the CPU sort costs more than the rebuild "
        "it replaces); "
        "1/0 force either path on any backend"),
    "PARMMG_TEST_CACHE": Knob(
        "flag", "",
        "1 = opt the test processes into the persistent compile cache "
        "(tests/conftest.py; default off — the XLA:CPU AOT cache is "
        "unreliable on this image)"),
    "PARMMG_TPU_PALLAS": Knob(
        "flag", "",
        "1 = force the Pallas TPU kernels (interpret mode off-TPU); "
        "0 = disable even on TPU"),
    "PARMMG_TRACE": Knob(
        "path", "",
        "append structured trace records (JSONL) to this file; unset "
        "= ring buffer only"),
    "PARMMG_TRACE_RING": Knob(
        "int", "4096", "trace ring-buffer capacity in records"),
    "PARMMG_VERBOSE": Knob(
        "int", "1",
        "process verbosity (the reference's imprim scale) gating "
        "obs.trace.log output"),
}


def registered() -> tuple[str, ...]:
    """All declared knob names, sorted."""
    return tuple(sorted(KNOBS))


def get(name: str, default: str | None = None) -> str:
    """Registry-checked ``os.environ.get``: raises ``KeyError`` on an
    undeclared knob so ad-hoc env surface cannot creep back in; falls
    back to the declared default when no override is given."""
    if name not in KNOBS:
        raise KeyError(f"undeclared PARMMG knob {name!r} — declare it "
                       "in parmmg_tpu/api/knobs.py")
    return os.environ.get(
        name, KNOBS[name].default if default is None else default)


def knob_table_md() -> str:
    """The canonical markdown knob table (README 'Environment knobs'
    section body; R4 verifies every registered name appears in README)."""
    rows = ["| knob | type | default | purpose |",
            "|---|---|---|---|"]
    for name in registered():
        k = KNOBS[name]
        rows.append(f"| `{name}` | {k.type} | "
                    f"{('`' + k.default + '`') if k.default else 'unset'}"
                    f" | {k.doc} |")
    return "\n".join(rows)


if __name__ == "__main__":
    # lint: ok(R3) — the table dump IS this module's stdout contract
    # (README generation channel)
    print(knob_table_md())
