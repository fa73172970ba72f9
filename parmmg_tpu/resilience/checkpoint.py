"""Pass-level checkpoint/resume for the grouped outer loop.

A killed 1M-tet grouped run used to restart from scratch: every pass
is minutes of wall time, and a run can die mid-pass.  This module makes the outer pass the unit of
durability:

- after each completed outer pass the loop saves the merged state
  (mesh fields + metric + the DISPLACED partition + the pass index)
  as one ``.npz`` under ``PARMMG_CKPT_DIR`` — the exact-resume payload
  (npz round-trips float64 bit-for-bit, which the Medit ASCII writer's
  ``%.15g`` does not);
- the pre-merge STACKED state of a checkpointed pass is additionally
  snapshotted through ``io.distributed.stacked_to_distributed_files``
  (merge-free per-group ``name.<rank>.mesh`` shard files — the
  reference's ``-distributed-output`` checkpoint contract), so a
  checkpoint is also inspectable/loadable by any Medit consumer;
- ``PARMMG_CKPT_EVERY`` (default 1) thins the cadence;
- resume (``cli.py -resume`` / ``scale_big.py --resume`` /
  ``grouped_adapt(resume=True)``) loads the NEWEST complete pass
  checkpoint and re-enters the loop at the next pass.  Passes are
  deterministic functions of their input state (the quiet-group
  fixed-point argument, parallel/sched.py), so a resumed run finishes
  bit-identical to an uninterrupted one — asserted by
  ``scripts/chaos_check.py``.

Checkpoint IO must never kill the run it is protecting: every write is
atomic (tmp + ``os.replace``) and every failure — including the
injected ``io.checkpoint`` OSError — is swallowed into a
``resilience.checkpoint_failures`` counter + trace event; the run
continues unprotected rather than dying.
"""
from __future__ import annotations

import json
import os
import re

import numpy as np

from .faults import faultpoint

__all__ = [
    "ckpt_config", "ckpt_due", "crash_loop", "latest_dist_checkpoint",
    "latest_pass_checkpoint", "load_dist_checkpoint",
    "load_pass_checkpoint", "resume_max", "save_dist_checkpoint",
    "save_pass_checkpoint", "snapshot_stacked",
]

_CKPT_RE = re.compile(r"\.pass(\d+)\.npz$")
_DCKPT_RE = re.compile(r"\.dpass(\d+)\.npz$")


def ckpt_config() -> tuple[str, int]:
    """(checkpoint dir, pass cadence); dir == "" disables."""
    d = os.environ.get("PARMMG_CKPT_DIR", "")
    every = int(os.environ.get("PARMMG_CKPT_EVERY", "1") or 1)
    return d, max(1, every)


def ckpt_due(it: int) -> bool:
    """Whether outer pass ``it`` (0-based) should checkpoint."""
    d, every = ckpt_config()
    return bool(d) and (it + 1) % every == 0


def ckpt_span(it: int):
    """The ``grp checkpoint`` span round a pass's checkpoint writes when
    pass ``it`` is due; nothing is emitted when unarmed."""
    from contextlib import nullcontext
    from ..obs.trace import span
    return span("grp checkpoint") if ckpt_due(it) else nullcontext()


def _ckpt_path(d: str, tag: str, it: int) -> str:
    return os.path.join(d, f"{tag}.pass{it}.npz")


# ---------------------------------------------------------------------------
# crash-loop breaker
# ---------------------------------------------------------------------------
def resume_max() -> int:
    """Resume attempts into the SAME (fingerprint, pass) before the
    breaker escalates past the failing rung (PARMMG_RESUME_MAX)."""
    try:
        return max(1, int(os.environ.get("PARMMG_RESUME_MAX", "3")
                          or 3))
    except ValueError:
        return 3


def crash_loop(tag: str, fingerprint: str | None, it: int,
               write: bool = True) -> tuple[int, bool]:
    """The crash-loop breaker decision, taken at resume time.

    Checkpoint/resume made a crash survivable; it also made a
    DETERMINISTIC crash eternal — a pass that reliably kills its
    worker resumes into the identical state and kills it again, and
    the supervisor relaunch loop never terminates (the unbounded-time
    failure the LOWFAILURE contract forbids).  This records a
    per-(fingerprint, pass) resume-attempt count in a small JSON file
    next to the checkpoints and returns ``(attempts, escalate)``:
    ``escalate`` turns True on the attempt AFTER ``resume_max()`` is
    reached, the caller's signal to skip past the failing pass (the
    last conforming checkpointed state IS the bounded-time answer —
    the driver's merged-polish/LOWFAILURE tail still runs on it).

    Escalation is emitted as a ``resilience.crash_loop`` event + a
    ``resilience.crash_loops`` counter.  ``write=False`` computes the
    decision without persisting the bump (non-zero pod ranks: only
    rank 0 writes to the shared checkpoint dir, and the ranks agree
    on the final decision collectively — parallel/dist.py).  Like all
    checkpoint bookkeeping, IO failure here is absorbed, never
    raised."""
    d, _ = ckpt_config()
    key = f"{fingerprint or ''}:{int(it)}"
    counts: dict = {}
    path = os.path.join(d, f"{tag}.resume.json") if d else ""
    if path:
        try:
            with open(path) as fh:
                counts = dict(json.load(fh))
        except Exception:
            counts = {}
    n = int(counts.get(key, 0)) + 1
    if path and write:
        counts[key] = n
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(counts, fh)
            os.replace(tmp, path)
        except OSError:
            pass
    mx = resume_max()
    esc = n > mx
    if esc:
        from ..obs import trace as otrace
        from ..obs.metrics import REGISTRY
        REGISTRY.counter("resilience.crash_loops").inc()
        otrace.event("resilience.crash_loop", tag=tag, it=int(it),
                     attempts=n, max=mx)
        otrace.log(1, f"  ## resilience: crash loop — pass {it} "
                      f"resumed {n}x (PARMMG_RESUME_MAX={mx}); "
                      "escalating past the failing pass: the last "
                      "conforming checkpointed state is the "
                      "bounded-time answer.", err=True)
    return n, esc


def run_fingerprint(mesh, met, *knobs) -> str:
    """Run-identity digest of a loop's ORIGINAL input (mesh bytes +
    metric + the loop knobs).  Stored in every pass checkpoint and
    required to match at resume: a checkpoint dir is often reused
    across runs, and silently resuming a stale checkpoint from a
    DIFFERENT input would deliver the wrong mesh."""
    import hashlib
    h = hashlib.blake2b(digest_size=16)
    for a in (mesh.vert, mesh.tet, mesh.tmask, met):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    h.update(repr(knobs).encode())
    return h.hexdigest()


def save_pass_checkpoint(tag: str, it: int, mesh, met, part,
                         fingerprint: str | None = None) -> str | None:
    """Atomically write pass ``it``'s resume payload.  Returns the path,
    or None when disabled / not due / the write failed (failure is
    counted + traced, never raised — see module docstring)."""
    from ..core.mesh import MESH_FIELDS
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    if not ckpt_due(it):
        return None
    d, _ = ckpt_config()
    path = _ckpt_path(d, tag, it)
    try:
        faultpoint("io.checkpoint")
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        # file handle, not a path: np.savez would append ".npz" to the
        # tmp name and break the atomic-replace pairing
        with open(tmp, "wb") as fh:
            np.savez(fh, it=np.asarray(it, np.int64),
                     fp=np.asarray(fingerprint or ""),
                     met=np.asarray(met),
                     part=np.asarray(part if part is not None else []),
                     **{f: np.asarray(getattr(mesh, f))
                        for f in MESH_FIELDS})
        os.replace(tmp, path)
    except Exception as e:
        # drop the partial .tmp: on the disk-full failure mode every
        # pass would otherwise leave another mesh-sized partial behind
        try:
            os.unlink(path + ".tmp")
        except OSError:
            pass
        REGISTRY.counter("resilience.checkpoint_failures").inc()
        otrace.event("ckpt.failed", tag=tag, it=it, detail=repr(e)[:300])
        otrace.log(1, f"  ## Warning: pass checkpoint failed ({e!r}); "
                      "run continues unprotected.", err=True)
        return None
    REGISTRY.counter("resilience.checkpoints").inc()
    otrace.event("ckpt.saved", tag=tag, it=it, path=path)
    return path


def snapshot_stacked(tag: str, it: int, stacked, n_groups: int) -> list:
    """Merge-free shard snapshot of a checkpointed pass's stacked state
    (``stacked_to_distributed_files``, no communicator sections: group
    seams are frozen, not parallel interfaces).  Best-effort like the
    npz write: failures are counted, never raised."""
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    if not ckpt_due(it):
        return []
    d, _ = ckpt_config()
    try:
        faultpoint("io.checkpoint")
        from ..io.distributed import stacked_to_distributed_files
        os.makedirs(d, exist_ok=True)
        outs = stacked_to_distributed_files(
            os.path.join(d, f"{tag}.pass{it}.mesh"), stacked, None,
            None, n_groups, shards=range(n_groups))
    except Exception as e:
        REGISTRY.counter("resilience.checkpoint_failures").inc()
        otrace.event("ckpt.snapshot_failed", tag=tag, it=it,
                     detail=repr(e)[:300])
        return []
    REGISTRY.counter("resilience.checkpoint_shards").inc(len(outs))
    return outs


def save_dist_checkpoint(tag: str, it: int, stacked_host: dict,
                         met_s, glo: list, top: int, comms,
                         shared_prev, regrow: int,
                         fingerprint: str | None = None,
                         write: bool = True) -> str | None:
    """Per-pass durability for the SHARD-RESIDENT distributed loop
    (``distributed_adapt_multi``) — the pod runtime's restart unit:
    worker crash/stall at pod scale is the EXPECTED failure mode, and
    the survivors re-launch from here instead of re-paying the whole
    adaptation (parallel/pod.py module docstring).

    ``stacked_host``: {field: [S, ...] host array} of the stacked mesh
    (the caller replicates via pull_host under ``multihost.cold_io`` —
    every process participates in the collective, only process 0
    passes ``write=True``).  The payload carries the full loop state:
    stacked fields + metric, the host numbering mirror + session
    counter, the comm tables (incl. per-shard owner rows) and the
    shared-gid / regrow scalars.  Atomic + fault-absorbed exactly like
    :func:`save_pass_checkpoint`."""
    from ..obs import trace as otrace
    from ..obs.metrics import REGISTRY
    if not ckpt_due(it):
        return None
    d, _ = ckpt_config()
    path = os.path.join(d, f"{tag}.dpass{it}.npz")
    if not write:
        return path
    try:
        faultpoint("io.checkpoint")
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        payload = {f"m_{k}": np.asarray(v)
                   for k, v in stacked_host.items()}
        payload.update(
            it=np.asarray(it, np.int64),
            fp=np.asarray(fingerprint or ""),
            met=np.asarray(met_s),
            glo=np.stack([np.asarray(g) for g in glo]),
            top=np.asarray(int(top), np.int64),
            nbr=comms.nbr, node_idx=comms.node_idx,
            node_cnt=comms.node_cnt, face_idx=comms.face_idx,
            face_cnt=comms.face_cnt,
            shared_prev=np.asarray(shared_prev),
            regrow=np.asarray(int(regrow), np.int64))
        for s, ow in enumerate(comms.owner):
            payload[f"owner_{s}"] = np.asarray(ow)
        with open(tmp, "wb") as fh:
            np.savez(fh, **payload)
        os.replace(tmp, path)
    except Exception as e:
        try:
            os.unlink(path + ".tmp")
        except OSError:
            pass
        REGISTRY.counter("resilience.checkpoint_failures").inc()
        otrace.event("ckpt.failed", tag=tag, it=it, detail=repr(e)[:300])
        otrace.log(1, f"  ## Warning: dist pass checkpoint failed "
                      f"({e!r}); run continues unprotected.", err=True)
        return None
    REGISTRY.counter("resilience.checkpoints").inc()
    otrace.event("ckpt.saved", tag=tag, it=it, path=path)
    return path


def latest_dist_checkpoint(tag: str, fingerprint: str | None = None
                           ) -> tuple[str, int] | None:
    """Newest complete dist-loop (path, pass index) for ``tag``; same
    staleness/partial-file rules as :func:`latest_pass_checkpoint`."""
    from ..obs import trace as otrace
    d, _ = ckpt_config()
    if not d or not os.path.isdir(d):
        return None
    found = []
    for name in os.listdir(d):
        if not name.startswith(tag + ".dpass"):
            continue
        m = _DCKPT_RE.search(name)
        if m:
            found.append((int(m.group(1)), os.path.join(d, name)))
    for it, path in sorted(found, reverse=True):
        try:
            with np.load(path) as z:
                if "m_vert" not in z.files or int(z["it"]) != it:
                    continue
                if fingerprint is not None:
                    stored = str(z["fp"]) if "fp" in z.files else ""
                    if stored != fingerprint:
                        otrace.log(1, f"  ## Warning: checkpoint "
                                      f"{path} belongs to a different "
                                      "run (input fingerprint "
                                      "mismatch); skipped.", err=True)
                        continue
                return path, it
        except Exception:
            continue
    return None


def load_dist_checkpoint(path: str) -> dict:
    """Dist checkpoint -> {stacked: {field: array}, met, glo (list),
    top, comms: InterfaceComms, shared_prev, regrow, it}."""
    from ..parallel.comms import InterfaceComms
    z = np.load(path)
    stacked = {k[2:]: z[k] for k in z.files if k.startswith("m_")}
    S = z["glo"].shape[0]
    owner = [z[f"owner_{s}"] for s in range(S)]
    comms = InterfaceComms(z["nbr"], z["node_idx"], z["node_cnt"],
                           z["face_idx"], z["face_cnt"], owner)
    return dict(stacked=stacked, met=z["met"],
                glo=[g.copy() for g in z["glo"]], top=int(z["top"]),
                comms=comms, shared_prev=z["shared_prev"],
                regrow=int(z["regrow"]), it=int(z["it"]))


def latest_pass_checkpoint(tag: str, fingerprint: str | None = None
                           ) -> tuple[str, int] | None:
    """Newest complete (path, pass index) for ``tag`` under the ckpt
    dir, or None.  ``.tmp`` partials from a kill mid-write are ignored
    (the atomic-replace contract), unloadable files are skipped.
    With ``fingerprint`` set, checkpoints whose stored run identity
    differs (a STALE checkpoint from a previous run on different
    input) are skipped with a warning instead of silently resumed."""
    from ..obs import trace as otrace
    d, _ = ckpt_config()
    if not d or not os.path.isdir(d):
        return None
    found = []
    for name in os.listdir(d):
        if not name.startswith(tag + ".pass"):
            continue
        m = _CKPT_RE.search(name)
        if m:
            found.append((int(m.group(1)), os.path.join(d, name)))
    for it, path in sorted(found, reverse=True):
        try:
            with np.load(path) as z:
                if "vert" not in z.files or int(z["it"]) != it:
                    continue
                if fingerprint is not None:
                    stored = str(z["fp"]) if "fp" in z.files else ""
                    if stored != fingerprint:
                        otrace.log(1, f"  ## Warning: checkpoint "
                                      f"{path} belongs to a different "
                                      "run (input fingerprint "
                                      "mismatch); skipped.", err=True)
                        continue
                return path, it
        except Exception:
            continue
    return None


def load_pass_checkpoint(path: str):
    """Checkpoint -> (Mesh of host arrays, met, part, pass index)."""
    from ..core.mesh import MESH_FIELDS, Mesh
    z = np.load(path)
    mesh = Mesh(**{f: z[f] for f in MESH_FIELDS})
    part = z["part"]
    return mesh, z["met"], (part if part.size else None), int(z["it"])
