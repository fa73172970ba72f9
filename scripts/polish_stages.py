"""Where a merged polish wave's seconds go, stage by stage.

Runs one job of a benchmark cell through ``ParMesh.run``, keeps the merged
mesh as it stands at the entry of ``driver._merged_polish``, and replays
the polish on it as the wave now is (``ops/adapt.sliver_polish_impl`` with
the worklist and the retained sorts), a program a stage, each timed from
dispatch to ``block_until_ready``, on the host's CPU backend where the
driver stages the tail.  A stage the wave skips for want of an input (the
collapse stage with no tet under the threshold, the exit adjacency when
``swap23`` applied nothing) is skipped here too and reads 0.

The tables are stages of their own (``*_table``, ``adjacency``,
``exit_adjacency``): each is derived off the sort the polish carries
(``ops/topo_incr``: a merge of the rows dirtied since its last
derivation, the retained sort as it is when there are none, the full
sort where nothing is retained yet or the rows outnumber the band) and
handed to its kernel (``et=``); ``dirty`` says how many rows it met,
``merged`` whether it came off the retained sort, and ``full_s`` what
the same table costs by the full sort on the same mesh (timed beside the
wave, not part of it).  The two swap kernels that take the polish's
worklist (``ops/worklist``) are split at the compaction line: ``*_head``
is the kernel on an EMPTY list with its table given (qualities, top-K,
then claims and apply over K rows with no candidate: what neither the
list nor the merge shortens), ``*_rows`` the rest of its seconds on the
list the waves have kept (the candidate stage, as wide as the list);
``list`` is the bookkeeping between the stages, the lists' and the dirty
masks'.  Each wave's row says how many candidate rows the kernels' top-K
selected (``cand``) and how many of them were on the list (``wl``).

After the waves, the tail's other consumer of whole-mesh tables the same
way (PR 44): the repair, then the fem rounds of ``driver._finish_run`` on
the mesh and the sorts the waves left, a program a stage (``edge_table``
and ``adjacency`` off the carried sort with the dirty rows each met and
whether it was merged, ``full_s`` the same table by the full sort, which
is what a round without the state runs; ``split`` the split wave with its
table given, ``bdytags``, ``list`` the dirty marks), until a round finds
no candidate.  The rounds are run twice from the same state and the
second run is the one reported, so no round's seconds hold a compile.  A
diagnostic, not a contract: it mirrors the wave's composition as of
PR 38 and the round's as of PR 44.

    python scripts/polish_stages.py --cell iso-growth --seed 21 \
        [--save DIR] [--from DIR] [--out FILE.json]

``--save DIR`` also writes the captured mesh (``DIR/<cell>-<seed>.npz``);
``--from DIR`` replays such a file without running a job, so a mesh made
on one machine can be timed on another's host.  The job's line holds the
sha256 of the output's vertices, tets and metric: two checkouts that
print the same three made the same mesh.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

import numpy as np  # noqa: E402

TABLES = ("collapse_table", "swap_edges_table", "swapgen_table",
          "adjacency", "exit_adjacency")
FEM_STAGES = ("edge_table", "split", "bdytags", "adjacency", "list")
STAGES = ("collapse_table", "collapse", "swap_edges_table",
          "swap_edges_head", "swap_edges_rows", "swapgen_table",
          "swapgen_head", "swapgen_rows", "adjacency", "swap23", "smooth",
          "exit_adjacency", "list")
# what each stage applied is counted under these (the driver's loop ends
# on a wave whose first four apply nothing)
APPLIED = ("collapse", "swap_edges", "swapgen", "swap23", "smooth")


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_config(name: str) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    return load_json(ROOT, next(
        c for c in bench["configs"] if c["name"] == cell["config"])["file"])


def capture_job(cell: str, seed: int) -> tuple[dict, dict]:
    """One job of ``cell``; returns (what the merged polish was handed,
    the job's digest)."""
    import jax
    import inputs
    import job as jobmod
    from parmmg_tpu import driver
    from parmmg_tpu.obs.trace import TRACER
    config = cell_config(cell)
    seen = {}
    inner = driver._merged_polish

    def spy(mesh, met, info, hausd, stats, tim):
        seen.update(mesh=jax.tree.map(np.array, mesh), met=np.array(met),
                    hausd=hausd)
        return inner(mesh, met, info, hausd, stats, tim)

    driver._merged_polish = spy
    try:
        res = jobmod.run_job(inputs.build_input(config, seed),
                             config["options"])
    finally:
        driver._merged_polish = inner
    sha = {k: hashlib.sha256(np.ascontiguousarray(res[k]).tobytes())
           .hexdigest() for k in ("vert", "tet", "met")}
    def spans(name, *keys):
        return [{k: r[k] for k in keys if k in r}
                for r in TRACER.ring if r.get("name") == name]

    waves = spans("polish wave", "collapse", "swap", "moved", "bad", "col",
                  "adj", "wl", "cand", "tab", "inc", "dur")
    fem = spans("fem round", "split", "overflow", "tab", "inc", "dur")
    digest = {"rc": res["rc"], "seconds": res["seconds"],
              "ntets": len(res["tet"]), "sha256": sha, "waves": waves,
              "fem": fem,
              "counters": {k: v for k, v in res["counters"].items()
                           if k.startswith("tail.")}}
    return seen, digest


def save(seen: dict, path: str) -> None:
    import dataclasses
    leaves = {f"mesh.{f.name}": getattr(seen["mesh"], f.name)
              for f in dataclasses.fields(seen["mesh"])
              if isinstance(getattr(seen["mesh"], f.name), np.ndarray)}
    np.savez_compressed(
        path, met=seen["met"], hausd=np.float64(
            np.nan if seen["hausd"] is None else seen["hausd"]), **leaves)


def restore(path: str) -> dict:
    import dataclasses
    from parmmg_tpu.core.mesh import Mesh
    z = np.load(path)
    fields = {f.name: z[f"mesh.{f.name}"] for f in dataclasses.fields(Mesh)
              if f"mesh.{f.name}" in z}
    hausd = float(z["hausd"])
    return {"mesh": Mesh(**fields), "met": z["met"],
            "hausd": None if np.isnan(hausd) else hausd}


def timed(fn, *a):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*a))
    return out, time.perf_counter() - t0


def table_programs(capT: int):
    """``edges(slots)`` -> (an edge table off the carried sort: (table,
    state, merged?, dirty rows met); the same table by the full sort),
    ``adjacency`` the same pair for the face sort, ``mark`` the dirty
    marks of the rows a stage changed; at the bands of ``capT``."""
    import jax
    import jax.numpy as jnp
    from parmmg_tpu.ops import topo_incr as ti
    from parmmg_tpu.ops.adjacency import build_adjacency
    from parmmg_tpu.ops.edges import unique_edges
    band = ti.polish_bands(capT)

    def edges(slots):
        def merged(m, tp):
            nd = jnp.sum(tp.edirty, dtype=jnp.int32)
            return ti.incr_unique_edges(m, tp, shell_slots=slots,
                                        band=band) + (nd,)
        return jax.jit(merged), jax.jit(
            lambda m: unique_edges(m, shell_slots=slots))

    def faces(m, tp):
        nd = jnp.sum(tp.fdirty, dtype=jnp.int32)
        return ti.incr_build_adjacency(m, tp, band=band) + (nd,)

    mark = jax.jit(lambda tp, before, after: ti.mark_dirty(
        tp, before.tet, before.tmask, after))
    return edges, (jax.jit(faces), jax.jit(build_adjacency)), mark


def replay(seen: dict, waves: int = 8, sliver_q: float = 0.2):
    """The polish on ``seen``, stage by stage; per wave, each stage's
    seconds and what it applied, the tets under ``sliver_q`` at the
    wave's entry, the two listed kernels' ``cand`` and ``wl`` rows, and
    per table the dirty rows it met, whether it came off the retained
    sort, and the full sort's seconds.  Wave 0 pays the compiles.
    Returns (the rows, what the waves left: mesh, metric, state)."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from parmmg_tpu.driver import polish_budget
    from parmmg_tpu.ops import topo_incr as ti
    from parmmg_tpu.ops import worklist as wlist
    from parmmg_tpu.ops.adjacency import boundary_edge_tags
    from parmmg_tpu.ops.collapse import collapse_wave
    from parmmg_tpu.ops.quality import quality_from_points
    from parmmg_tpu.ops.smooth import smooth_wave
    from parmmg_tpu.ops.swap import swap23_wave, swap_edges_wave
    from parmmg_tpu.ops.swapgen import RING_MAX, swapgen_wave
    from parmmg_tpu.utils.placement import host_staging
    hausd = seen["hausd"]
    n_live = int(seen["mesh"].tmask.sum())
    budget = polish_budget(n_live)
    band = ti.polish_bands(int(seen["mesh"].tmask.shape[0]))
    kw = dict(budget_div=2, budget=budget)

    def collapse(m, k, et):
        col = collapse_wave(m, k, sliver_q=sliver_q, hausd=hausd, et=et,
                            **kw)
        m = jax.lax.cond(col.surface_changed, boundary_edge_tags,
                         lambda m: m, col.mesh)
        return m, col.ncollapse

    def count_bad(m, k):
        q = quality_from_points(
            m.vert[m.tet], None if k.ndim == 1 else k[m.tet])
        return jnp.sum(m.tmask & (q < sliver_q), dtype=jnp.int32)

    def of(wave_fn, count, **kws):
        def run(m, k, *a):
            r = wave_fn(m, k, *a, **kws)
            return r.mesh, getattr(r, count)
        return jax.jit(run)

    def listed(wave_fn, **kws):
        """A kernel that takes a list and its table: (mesh, applied,
        keep, cand, wl)."""
        def run(m, k, dirty, et):
            r = wave_fn(m, k, worklist=dirty, et=et, **kws)
            return r.mesh, r.nswap, r.keep, r.ncand, r.nlist
        return jax.jit(run)

    edges, adjacency, mark = table_programs(
        int(seen["mesh"].tmask.shape[0]))
    tables = {"collapse_table": edges(3), "swap_edges_table": edges(3),
              "swapgen_table": edges(RING_MAX), "adjacency": adjacency,
              "exit_adjacency": adjacency}
    programs = {
        "collapse": jax.jit(collapse),
        "swap23": of(swap23_wave, "nswap", **kw),
        "smooth": of(partial(smooth_wave, opt_q=sliver_q, hausd=hausd),
                     "nmoved"),
    }
    kernels = {     # stage -> (its program, its field of the PolishList)
        "swap_edges": (listed(swap_edges_wave, hausd=hausd, **kw), "edges"),
        "swapgen": (listed(swapgen_wave, **kw), "rings"),
    }
    count_bad = jax.jit(count_bad)
    noted = jax.jit(wlist.noted)
    rows = []

    with host_staging():
        mesh = jax.tree.map(jnp.asarray, seen["mesh"])
        met = jnp.asarray(seen["met"])
        wl = wlist.all_dirty(mesh)
        topo = ti.topo_init(mesh.capT)
        nothing = jax.tree.map(jnp.zeros_like, wl.edges)
        say(f"replay: {n_live} live tets at capT {mesh.capT}, budget "
            f"{budget}, band {band}, hausd {hausd}")
        for w in range(waves):
            row = {"wave": w, "bad": int(count_bad(mesh, met)),
                   "s": dict.fromkeys(STAGES, 0.0), "n": {},
                   "cand": {}, "wl": {}, "dirty": {}, "merged": {},
                   "full_s": {}}

            def table(name, m, tp):
                """The table ``name`` of ``m``: (table or mesh with its
                adjacency, state)."""
                merged, full = tables[name]
                _, row["full_s"][name] = timed(full, m)
                (out, tp, inc, nd), row["s"][name] = timed(merged, m, tp)
                row["dirty"][name], row["merged"][name] = int(nd), bool(inc)
                return out, tp

            def note(fn, state, before, after):
                """``noted`` on the lists, ``mark`` on the sorts."""
                state, s = timed(fn, state, before, after)
                row["s"]["list"] += s
                return state

            row["n"] = dict.fromkeys(APPLIED, 0)
            if row["bad"] > 0:      # the wave's own rule
                et, topo = table("collapse_table", mesh, topo)
                (after, n), row["s"]["collapse"] = timed(
                    programs["collapse"], mesh, met, et)
                row["n"]["collapse"] = int(n)
                wl = note(noted, wl, mesh, after)
                topo = note(mark, topo, mesh, after)
                mesh = after
            for name, (fn, field) in kernels.items():
                et, topo = table(name + "_table", mesh, topo)
                _, row["s"][name + "_head"] = timed(
                    fn, mesh, met, nothing, et)
                (after, n, keep, cand, nl), s = timed(
                    fn, mesh, met, getattr(wl, field), et)
                row["s"][name + "_rows"] = s - row["s"][name + "_head"]
                row["cand"][name], row["wl"][name] = int(cand), int(nl)
                row["n"][name] = int(n)
                wl = note(noted, wl._replace(**{field: wlist.looked(
                    getattr(wl, field), keep)}), mesh, after)
                topo = note(mark, topo, mesh, after)
                mesh = after
            before, topo = table("adjacency", mesh, topo)
            (mesh, n), row["s"]["swap23"] = timed(
                programs["swap23"], before, met)
            row["n"]["swap23"] = int(n)
            topo = note(mark, topo, before, mesh)
            (mesh, n), row["s"]["smooth"] = timed(
                programs["smooth"], mesh, met,
                jnp.asarray(1000 + w, jnp.int32))
            row["n"]["smooth"] = int(n)
            if row["n"]["swap23"] > 0:      # the wave's own rule
                mesh, topo = table("exit_adjacency", mesh, topo)
            # swap23, the smoothing and the adjacencies' tags, in one
            wl = note(noted, wl, before, mesh)
            say(f"  wave {w}: bad {row['bad']:5d}  " + "  ".join(
                f"{k} {row['s'][k]:.3f}s" for k in STAGES) + "  applied "
                + "/".join(str(row["n"][k]) for k in APPLIED) + "  wl/cand "
                + "  ".join(f"{k} {row['wl'][k]}/{row['cand'][k]}"
                            for k in kernels) + "  tables "
                + "  ".join(f"{k} {row['dirty'][k]}"
                            f"{'m' if row['merged'][k] else 'F'}"
                            f" {row['full_s'][k]:.3f}s"
                            for k in row["dirty"]))
            rows.append(row)
            if sum(row["n"][k] for k in APPLIED[:4]) == 0:
                break       # the driver's loop ends here too
    return rows, (mesh, met, topo)


def replay_fem(left, rounds: int = 8) -> dict:
    """The repair and the fem rounds of ``driver._finish_run`` on what
    the waves ``left`` (mesh, metric, state), stage by stage: per round
    each stage's seconds, the splits it applied, and per table the dirty
    rows it met, whether it came off the carried sort, and the full
    sort's seconds.  Run twice from the same state; the second run is
    the one returned."""
    import jax
    import jax.numpy as jnp
    from parmmg_tpu.ops import topo_incr as ti
    from parmmg_tpu.ops.adapt import grow_mesh_met
    from parmmg_tpu.ops.adjacency import boundary_edge_tags
    from parmmg_tpu.ops.repair import repair_mesh
    from parmmg_tpu.ops.split import split_wave
    from parmmg_tpu.utils.placement import host_staging

    def split(m, k, et):
        r = split_wave(m, k, fem_only=True, budget_div=2, et=et)
        return r.mesh, r.met, r.nsplit, r.overflow

    split, bdytags = jax.jit(split), jax.jit(boundary_edge_tags)
    made = {}

    def programs(capT):
        """The table makers at ``capT``, the same ones in both runs."""
        if capT not in made:
            edges, (faces, full_faces), mark = table_programs(capT)
            # 3: the slots of split_wave's own table
            made[capT] = edges(3) + (faces, full_faces, mark)
        return made[capT]

    def once(mesh, met, topo):
        table, full_table, faces, full_faces, mark = programs(mesh.capT)
        out = {"rounds": []}
        (after, nrep), out["repair_s"] = timed(
            lambda: repair_mesh(mesh, met))
        out["repaired"] = int(nrep)
        if after is not mesh:
            topo = mark(topo, mesh, after)
        mesh = after
        for w in range(rounds):
            row = {"round": w, "s": dict.fromkeys(FEM_STAGES, 0.0),
                   "dirty": {}, "merged": {}, "full_s": {}}
            _, row["full_s"]["edge_table"] = timed(full_table, mesh)
            (et, topo, inc, nd), row["s"]["edge_table"] = timed(
                table, mesh, topo)
            row["dirty"]["edge_table"] = int(nd)
            row["merged"]["edge_table"] = bool(inc)
            (after, met, n, ovf), row["s"]["split"] = timed(
                split, mesh, met, et)
            topo, row["s"]["list"] = timed(mark, topo, mesh, after)
            mesh, row["s"]["bdytags"] = timed(bdytags, after)
            _, row["full_s"]["adjacency"] = timed(full_faces, mesh)
            (mesh, topo, inc, nd), row["s"]["adjacency"] = timed(
                faces, mesh, topo)
            row["dirty"]["adjacency"] = int(nd)
            row["merged"]["adjacency"] = bool(inc)
            row["split"], row["overflow"] = int(n), bool(ovf)
            out["rounds"].append(row)
            if row["overflow"]:     # the driver's rule: regrow, no state
                mesh, met = grow_mesh_met(mesh, met, 2 * mesh.capP,
                                          2 * mesh.capT)
                topo = ti.topo_init(mesh.capT)
                table, full_table, faces, full_faces, mark = programs(
                    mesh.capT)
                continue
            if row["split"] == 0:
                break
        return out

    with host_staging():
        mesh, met, topo = jax.tree.map(jnp.asarray, left)
        once(mesh, met, topo)       # the compiles
        out = once(mesh, met, topo)
    for row in out["rounds"]:
        say(f"  fem round {row['round']}: split {row['split']:4d}  "
            + "  ".join(f"{k} {row['s'][k]:.4f}s" for k in FEM_STAGES)
            + "  tables " + "  ".join(
                f"{k} {row['dirty'][k]}{'m' if row['merged'][k] else 'F'}"
                f" {row['full_s'][k]:.4f}s" for k in row["dirty"]))
    # a job's rounds with the state carried, and with both tables of
    # every round sorted in full (what the rounds cost without one)
    out["rounds_s"] = sum(sum(r["s"].values()) for r in out["rounds"])
    out["rounds_full_s"] = out["rounds_s"] + sum(
        r["full_s"][k] - r["s"][k] for r in out["rounds"]
        for k in r["full_s"])
    out["tables"] = sum(len(r["dirty"]) for r in out["rounds"])
    out["tables_merged"] = sum(sum(r["merged"].values())
                               for r in out["rounds"])
    return out


def summary(rows: list[dict]) -> dict:
    """Mean seconds and share of each stage over the waves after the
    first (which pays the compiles); per table, over those waves, what
    it cost off the carried sort and by the full sort in the waves that
    derived it."""
    warm = rows[1:] or rows
    mean = {k: sum(r["s"][k] for r in warm) / len(warm)
            for k in warm[0]["s"]}
    total = sum(mean.values())

    def over(name, field):
        vals = [r[field][name] for r in warm if name in r["dirty"]]
        return sum(vals) / len(vals) if vals else None

    return {"waves_meaned": len(warm), "wave_s": total,
            "mean_s": mean,
            "share_pct": {k: 100.0 * v / total for k, v in mean.items()},
            "table_s": {k: over(k, "s") for k in TABLES},
            "table_full_s": {k: over(k, "full_s") for k in TABLES},
            "tables": sum(len(r["dirty"]) for r in rows),
            "tables_merged": sum(sum(r["merged"].values()) for r in rows),
            "dirty_by_wave": {k: [r["dirty"].get(k) for r in rows]
                              for k in TABLES},
            "merged_by_wave": {k: [r["merged"].get(k) for r in rows]
                               for k in TABLES},
            "bad_by_wave": [r["bad"] for r in rows],
            "applied_by_wave": {k: [r["n"][k] for r in rows]
                                for k in rows[0]["n"]},
            "cand_by_wave": {k: [r["cand"][k] for r in rows]
                             for k in rows[0]["cand"]},
            "wl_by_wave": {k: [r["wl"][k] for r in rows]
                           for k in rows[0]["wl"]},
            "worklist_share_pct": 100.0 * sum(
                sum(r["wl"].values()) for r in rows) / max(1, sum(
                    sum(r["cand"].values()) for r in rows))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--save", help="directory to write the captured mesh to")
    ap.add_argument("--from", dest="src",
                    help="directory holding a captured mesh: run no job")
    ap.add_argument("--no-replay", action="store_true",
                    help="run the job and print its digest only")
    ap.add_argument("--out", help="write the result here as well")
    args = ap.parse_args()
    name = f"{args.cell}-{args.seed}.npz"
    result = {"cell": args.cell, "seed": args.seed}
    if args.src:
        seen = restore(os.path.join(args.src, name))
    else:
        seen, result["job"] = capture_job(args.cell, args.seed)
        say(f"job: {json.dumps(result['job'])}")
        if args.save:
            os.makedirs(args.save, exist_ok=True)
            save(seen, os.path.join(args.save, name))
    if not args.no_replay:
        import jax
        result["platform"] = jax.default_backend()
        result["host_cores"] = os.cpu_count()
        result["rows_live"] = int(seen["mesh"].tmask.sum())
        result["rows_cap"] = int(seen["mesh"].tmask.shape[0])
        result["waves"], left = replay(seen)
        result["summary"] = summary(result["waves"])
        result["fem"] = replay_fem(left)
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
