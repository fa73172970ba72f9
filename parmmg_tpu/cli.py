"""Command-line interface — the ``parmmg_O3`` executable analogue.

Flag surface mirrors the reference CLI (usage list
/root/reference/src/libparmmg_tools.c:101-170; main flow parmmg.c:60-446):
load (centralized file, or per-shard ``name.<rank>.mesh`` fallback probe
like parmmg.c:161-188), adapt, save (mesh/meshb/vtu/pvtu, centralized or
distributed).  Device parallelism replaces MPI ranks: ``-ndev N`` shards
the mesh over N devices of the JAX mesh.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from .api import ParMesh, IParam, DParam
from .core import constants as C
from .obs import trace as otrace


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="parmmg_tpu", add_help=True, prefix_chars="-",
        description="TPU-native parallel tetrahedral remesher "
                    "(ParMmg capability surface)")
    a = p.add_argument
    a("-in", dest="inp", metavar="file", help="input mesh")
    a("-out", dest="out", metavar="file", help="output mesh")
    a("-sol", "-met", dest="sol", metavar="file", help="metric file")
    a("-field", dest="field", metavar="file", help="input fields to "
      "interpolate")
    a("-noout", action="store_true", help="no output mesh")
    a("-v", dest="verbose", type=int, default=1, help="verbosity")
    a("-mmg-v", dest="mmg_verbose", type=int, default=-1,
      help="remesh-kernel verbosity")
    a("-m", dest="mem", type=int, default=-1, help="memory budget MB")
    a("-d", dest="debug", action="store_true", help="debug mode")
    a("-niter", type=int, default=C.NITER_DEFAULT,
      help="adaptation iterations")
    a("-mesh-size", dest="mesh_size", type=int,
      default=C.TARGET_MESH_SIZE_SENTINEL, help="target shard mesh size")
    a("-metis-ratio", dest="metis_ratio", type=int,
      default=C.RATIO_MMG_METIS_SENTINEL,
      help="ratio of migration groups to remesh groups")
    a("-nlayers", type=int, default=C.MVIFCS_NLAYERS,
      help="interface displacement layers")
    a("-groups-ratio", dest="groups_ratio", type=float, default=C.GRPS_RATIO,
      help="allowed group imbalance")
    a("-nobalance", action="store_true", help="no load balancing")
    a("-ndev", type=int, default=1, help="number of devices (shards)")
    a("-hmin", type=float, default=-1.0)
    a("-hmax", type=float, default=-1.0)
    a("-hsiz", type=float, default=-1.0, help="constant target size")
    a("-hausd", type=float, default=C.HAUSD_DEFAULT)
    a("-hgrad", type=float, default=C.HGRAD_DEFAULT)
    a("-hgradreq", type=float, default=C.HGRADREQ_DEFAULT)
    a("-ar", dest="angle", type=float, default=C.ANGEDG_DEG,
      help="ridge detection angle (deg)")
    a("-nr", dest="noridge", action="store_true",
      help="no ridge detection")
    a("-A", dest="aniso", action="store_true",
      help="anisotropic metric computation (reference -A flag)")
    a("-mmg-d", dest="mmg_debug", action="store_true",
      help="remesh-kernel debug mode")
    a("-optim", action="store_true", help="preserve current sizing")
    a("-optimLES", action="store_true")
    a("-noinsert", action="store_true")
    a("-noswap", action="store_true")
    a("-nomove", action="store_true")
    a("-nosurf", action="store_true")
    a("-nofem", action="store_true")
    a("-opnbdy", action="store_true", help="preserve open boundaries")
    a("-octree", type=int, default=-1, help="(accepted, unused on TPU)")
    a("-rn", type=int, default=-1, help="(renumbering: n/a on TPU)")
    a("-centralized-output", dest="cent_out", action="store_true")
    a("-distributed-output", dest="dist_out", action="store_true")
    a("-resume", action="store_true",
      help="resume a killed grouped run from the newest "
           "PARMMG_CKPT_DIR pass checkpoint (resilience/checkpoint.py)")
    a("-val", action="store_true", help="print default values and exit")
    a("-bench-json", dest="bench_json", action="store_true",
      help="print one JSON line with timing/quality stats")
    return p


def default_values() -> str:
    """PMMG_defaultValues analogue (libparmmg_tools.c:61)."""
    from .api.params import Info
    info = Info()
    lines = ["default parameter values:"]
    for f, v in sorted(vars(info).items()):
        lines.append(f"  {f:24s} {v}")
    return "\n".join(lines)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # the CLI's -v flag IS the process imprim: align obs.trace.log's
    # gate with it up front so pre-run errors/warnings follow the flag
    # (not a stray PARMMG_VERBOSE inherited from the environment);
    # fatal diagnostics are level 0, silenced only by an explicit
    # negative -v — the reference's imprim semantics
    otrace.set_verbosity(args.verbose)
    if args.val:
        print(default_values())   # lint: ok(R3) — -val stdout contract
        return 0
    if not args.inp:
        otrace.log(0, "missing -in <mesh>", err=True)
        return 1
    # persistent compile cache (compile governor): the adapt programs
    # take minutes to compile cold and are identical across runs —
    # default the cache dir (env JAX_COMPILATION_CACHE_DIR wins) so
    # repeat CLI invocations start warm.  Then resolve the backend: a
    # missing accelerator without the JAX_PLATFORMS=cpu pin is an
    # error here, not a quieter run on the CPU.
    from .utils.compilecache import backend_or_fail, set_cache_env
    set_cache_env()
    try:
        backend_or_fail()
    except RuntimeError as e:
        otrace.log(0, str(e), err=True)
        return 1

    from .io import medit
    from .io.distributed import probe_distributed, load_distributed_mesh

    t0 = time.perf_counter()
    pm = ParMesh()
    inp = Path(args.inp)
    vtu_met = vtu_fields = None
    if inp.suffix == ".vtu":
        # centralized VTK input (PMMG_loadVtuMesh_centralized role,
        # inoutcpp_pmmg.cpp:44); point fields named metric/sol become
        # the metric unless -sol overrides
        from .io.vtk import read_vtu_medit
        if not inp.exists():
            otrace.log(0, f"cannot open {inp}", err=True)
            return 1
        m, vtu_met, vtu_fields = read_vtu_medit(inp)
        distributed_in = False
    else:
        if inp.suffix not in (".mesh", ".meshb"):
            inp = inp.with_suffix(".mesh")
        distributed_in = not inp.exists() and probe_distributed(inp, 0)
    if distributed_in:
        # reassemble shards (the centralized entry of a distributed
        # checkpoint; parmmg.c's probe order reversed but equivalent)
        parts = []
        r = 0
        while probe_distributed(inp, r):
            parts.append(load_distributed_mesh(inp, r)[0])
            r += 1
        m = _concat_shards(parts)
        # distributed input stays distributed: the run adopts the
        # caller's decomposition (libparmmg.c:206-329 semantics) when the
        # device count matches the shard count
        pm._in_part = getattr(m, "src_part", None)
    elif inp.suffix == ".vtu":
        pass                                  # loaded above
    elif inp.exists():
        m = medit.read_mesh(inp)
    else:
        otrace.log(0, f"cannot open {inp}", err=True)
        return 1

    pm.set_mesh_size(np_=len(m.vert), ne=len(m.tetra), nt=len(m.tria),
                     na=len(m.edges))
    pm.set_vertices(m.vert, m.vref)
    pm.set_tetrahedra(m.tetra + 1, m.tref)
    if len(m.tria):
        pm.set_triangles(m.tria + 1, m.triaref)
    if len(m.edges):
        pm.set_edges(m.edges + 1, m.edgeref)
    for c in m.corners:
        pm.set_corner(int(c) + 1)
    for rv in m.required_vert:
        pm.set_required_vertex(int(rv) + 1)
    for rid in m.ridges:
        pm.set_ridge(int(rid) + 1)

    if args.sol:
        vals, types = medit.read_sol(args.sol)
        typ = types[0]
        pm.set_met_size(3 if typ == medit.SOL_TENSOR else 1, len(m.vert))
        if typ == medit.SOL_TENSOR:
            pm.set_tensor_mets(vals.reshape(len(m.vert), 6))
        else:
            pm.set_scalar_mets(vals.reshape(len(m.vert)))
    elif vtu_met is not None:
        # metric carried in the VTU point data (the VTK-solution ingest
        # of the reference's loadVtu path)
        if vtu_met.ndim == 2 and vtu_met.shape[1] == 6:
            pm.set_met_size(3, len(m.vert))
            pm.set_tensor_mets(vtu_met)
        else:
            pm.set_met_size(1, len(m.vert))
            pm.set_scalar_mets(np.asarray(vtu_met).reshape(len(m.vert)))
    if args.field:
        vals, types = medit.read_sol(args.field)
        pm.set_sols_at_vertices_size(len(types), types)
        off = 0
        ncomp = {1: 1, 2: 3, 3: 6}
        vals2 = vals.reshape(len(m.vert), -1)
        for i, t in enumerate(types):
            w = ncomp[t]
            chunk = vals2[:, off:off + w]
            pm.set_ith_sol_in_sols_at_vertices(
                i + 1, chunk if w > 1 else chunk[:, 0])
            off += w
    elif vtu_fields:
        # non-metric VTU point fields ride along as solution fields
        # (the reference's loadVtu path carries them; losing them
        # silently would strand the user's data) — scalar and
        # 3-component fields map to the Medit sol types, anything else
        # is skipped with a warning
        from .io.medit import SOL_SCALAR, SOL_VECTOR, SOL_TENSOR
        carried, types = [], []
        for nm, arr in vtu_fields.items():
            a = np.asarray(arr, np.float64).reshape(len(m.vert), -1)
            if a.shape[1] == 1:
                carried.append(a[:, 0])
                types.append(SOL_SCALAR)
            elif a.shape[1] == 3:
                carried.append(a)
                types.append(SOL_VECTOR)
            elif a.shape[1] == 6:
                carried.append(a)
                types.append(SOL_TENSOR)
            else:
                otrace.log(0, f"warning: dropping VTU point field "
                              f"'{nm}' ({a.shape[1]} components)",
                           err=True)
        if carried:
            pm.set_sols_at_vertices_size(len(types), types)
            for i, chunk in enumerate(carried):
                pm.set_ith_sol_in_sols_at_vertices(i + 1, chunk)

    # parameters
    info = pm.info
    info.imprim = args.verbose
    info.mmg_imprim = args.mmg_verbose
    info.debug = args.debug
    info.niter = args.niter
    info.target_mesh_size = args.mesh_size
    info.metis_ratio = args.metis_ratio
    info.ifc_layers = args.nlayers
    info.grps_ratio = args.groups_ratio
    info.nobalancing = args.nobalance
    pm.set_iparameter(IParam.nDevices, args.ndev)
    info.hmin, info.hmax = args.hmin, args.hmax
    info.hsiz = args.hsiz
    info.hausd = args.hausd
    info.hgrad = args.hgrad
    info.hgradreq = args.hgradreq
    info.angle_deg = args.angle
    info.angle_detection = not args.noridge
    info.optim = args.optim
    info.optimLES = args.optimLES
    info.anisosize = args.aniso
    info.mmg_debug = args.mmg_debug
    info.noinsert = args.noinsert
    info.noswap = args.noswap
    info.nomove = args.nomove
    info.nosurf = args.nosurf
    info.fem = not args.nofem
    info.opnbdy = args.opnbdy
    info.mem_budget_mb = args.mem
    info.centralized_output = not args.dist_out
    info.noout = args.noout
    info.resume = args.resume

    # local-parameter file (<mesh>.mmg3d, MMG3D_parsop format; the
    # reference delegates parsing to Mmg at libparmmg_tools.c:573)
    parfile = Path(args.inp).with_suffix(".mmg3d")
    if parfile.exists():
        try:
            parsed = _parse_parfile(parfile)
        except (IndexError, ValueError) as e:
            # the file is discovered implicitly by name — a stale or
            # malformed one must not abort the run
            otrace.log(0, f"  ## Warning: unable to parse {parfile} "
                          f"({e}); local parameters ignored.", err=True)
            parsed = []
        for typ, ref, hmin_l, hmax_l, hausd_l in parsed:
            pm.set_local_parameter(typ, ref, hmin_l, hmax_l, hausd_l)
        otrace.log(1, f"  %% {parfile} read: "
                      f"{len(pm.info.local_params)} local parameter(s)",
                   verbose=args.verbose)

    ret = pm.run()
    dt = time.perf_counter() - t0
    if ret == C.PMMG_LOWFAILURE:
        # a conforming mesh was produced despite the partial failure —
        # save it and exit nonzero (the reference CLI's LOWFAILURE path)
        otrace.log(0, "adaptation INCOMPLETE (low failure): saving "
                      "the last conforming mesh", err=True)
        if not args.noout:
            _save_outputs(pm, args)
        return ret
    if ret != C.PMMG_SUCCESS:
        otrace.log(0, f"adaptation FAILED ({ret})", err=True)
        return ret

    if args.verbose >= C.PMMG_VERB_QUAL or args.bench_json:
        _report(pm, dt, args.bench_json)

    if not args.noout:
        _save_outputs(pm, args)
    return 0


def _parse_parfile(path):
    """Parse an Mmg local-parameter file:

        Parameters
        <n>
        <ref> <Triangle|Vertex|...> <hmin> <hmax> <hausd>

    Returns [(typ, ref, hmin, hmax, hausd)]: typ 1 = triangles (surface
    reference patch), typ 2 = tetrahedra (volume sub-domain by tref),
    typ 3 = edges (user edge list by ref), typ 0 = vertices (by point
    ref); other entity types warn and are skipped."""
    typ_map = {"triangle": 1, "triangles": 1,
               "tetrahedron": 2, "tetrahedra": 2, "tetrahedrons": 2,
               "edge": 3, "edges": 3, "ridge": 3,
               "vertex": 0, "vertices": 0}
    out = []
    lines = [ln.strip() for ln in path.read_text().splitlines()
             if ln.strip() and not ln.strip().startswith("#")]
    i = 0
    while i < len(lines):
        if lines[i].lower().startswith("parameters"):
            n = int(lines[i + 1].split()[0])
            for j in range(n):
                tok = lines[i + 2 + j].split()
                typ = typ_map.get(tok[1].lower())
                if typ is None:
                    otrace.log(0, "  ## Warning: unsupported local-"
                                  f"parameter type '{tok[1]}' in "
                                  f"{path}; entry skipped.", err=True)
                    continue
                out.append((typ, int(tok[0]),
                            float(tok[2]), float(tok[3]), float(tok[4])))
            i += 2 + n
        else:
            i += 1
    return out


def _concat_shards(parts):
    """Reassemble distributed shard files into one mesh + the per-tet
    source-shard labels.  The labels preserve the CALLER'S partition so
    the distributed run adopts it instead of re-partitioning from
    scratch — the reference's distributed entry keeps the input
    decomposition and only rebuilds communicators (libparmmg.c:206-329).
    """
    from .io.medit import MeditMesh
    m = MeditMesh()
    off = 0
    vs, vr, ts, tr, src = [], [], [], [], []
    for k, p in enumerate(parts):
        vs.append(p.vert); vr.append(p.vref)
        ts.append(p.tetra + off); tr.append(p.tref)
        src.append(np.full(len(p.tetra), k, np.int32))
        off += len(p.vert)
    m.vert = np.concatenate(vs)
    m.vref = np.concatenate(vr)
    m.tetra = np.concatenate(ts)
    m.tref = np.concatenate(tr)
    m.src_part = np.concatenate(src)
    # duplicate interface vertices are deduplicated by the core merge on
    # exact coordinates at run() time via analysis; cheap dedup here:
    uniq, inv = np.unique(m.vert.round(12), axis=0, return_inverse=True)
    if len(uniq) < len(m.vert):
        first = np.zeros(len(uniq), np.int64)
        seen = np.full(len(uniq), -1, np.int64)
        for i, k in enumerate(inv):
            if seen[k] < 0:
                seen[k] = i
        m.tetra = seen[inv[m.tetra]].astype(np.int32)
        keep = np.zeros(len(m.vert), bool)
        keep[seen] = True
        newid = np.cumsum(keep) - 1
        m.tetra = newid[m.tetra].astype(np.int32)
        m.vert = m.vert[keep]
        m.vref = m.vref[keep]
    return m


def _save_distributed_shards(pm, m, out, ndev):
    """True distributed output: split the adapted mesh into ndev shards
    and write ``name.<rank>.mesh`` files with ParallelVertex/Triangle
    communicator sections (inout_pmmg.c:74-486 format) — the
    checkpoint/resume contract of the reference's -distributed-output."""
    from .io.medit import MeditMesh
    from .io.distributed import save_distributed_mesh, ShardComm
    from .parallel.partition import greedy_partition, fix_contiguity
    from .parallel.comms import build_interface_comms

    tet0 = np.asarray(m.tetra, np.int64)
    # reuse the partition the distributed run just produced (it indexes
    # the compacted output tets, same order as m.tetra); fall back to a
    # fresh partition for single-device runs or mismatched shapes
    part = getattr(pm, "_out_part", None)
    if part is None or len(part) != len(tet0) or part.max() >= ndev:
        cent = m.vert[tet0].mean(axis=1)
        part = fix_contiguity(tet0, greedy_partition(tet0, cent, ndev))
    l2g = [np.unique(tet0[part == s]) for s in range(ndev)]
    g2l = []
    for s in range(ndev):
        mp = np.full(len(m.vert), -1, np.int64)
        mp[l2g[s]] = np.arange(len(l2g[s]))
        g2l.append(mp)
    comms = build_interface_comms(tet0, part, ndev, l2g, g2l)

    # boundary-triangle ownership: a triangle belongs to the shard that
    # owns its adjacent tetrahedron (vertex membership alone can assign a
    # fully-on-interface surface triangle to a shard with no matching tet
    # face)
    tglob = np.asarray(m.tria, np.int64) if len(m.tria) else \
        np.zeros((0, 3), np.int64)
    tri_tet = getattr(m, "tria_tet", None)
    if tri_tet is not None and len(tri_tet) == len(tglob):
        tri_owner = part[np.asarray(tri_tet, np.int64)]
    else:
        tri_owner = np.full(len(tglob), -1, np.int64)
        for s in range(ndev):
            inside = (g2l[s][tglob] >= 0).all(axis=1) if len(tglob) else \
                np.zeros(0, bool)
            tri_owner[inside] = s

    for s in range(ndev):
        sh = MeditMesh()
        sh.vert = m.vert[l2g[s]]
        sh.vref = m.vref[l2g[s]]
        sel = part == s
        sh.tetra = g2l[s][tet0[sel]].astype(np.int32)
        sh.tref = m.tref[sel]
        # shard triangle list: interface faces (from the comm tables, in
        # table order so comm items can reference them by position),
        # then the shard's share of the true boundary triangles
        tris, trefs = [], []
        face_comms, node_comms = [], []
        from .core.constants import IDIR
        for k in range(comms.nbr.shape[1]):
            b = int(comms.nbr[s, k])
            if b < 0:
                continue
            nf = int(comms.face_cnt[s, k])
            fidx = comms.face_idx[s, k, :nf]        # 4*local_tet+face
            lt, lf = fidx // 4, fidx % 4
            fv = sh.tetra[lt][np.arange(nf)[:, None], np.asarray(IDIR)[lf]]
            first = sum(len(t) for t in tris)
            tris.append(fv)
            trefs.append(np.zeros(nf, np.int32))
            local_ids = np.arange(first + 1, first + nf + 1)
            # global face id: stable across both sides = sorted global
            # vertex triple encoded
            gfv = np.sort(l2g[s][fv], axis=1)
            gid = (gfv[:, 0] << 42) | (gfv[:, 1] << 21) | gfv[:, 2]
            face_comms.append(ShardComm(b, local_ids, gid))
            nn = int(comms.node_cnt[s, k])
            nidx = comms.node_idx[s, k, :nn]
            node_comms.append(ShardComm(
                b, nidx + 1, l2g[s][nidx] + 1))
        if len(tglob):
            # true boundary triangles owned by this shard
            mine = tri_owner == s
            tl = g2l[s][tglob[mine]].astype(np.int32)
            tris.append(tl)
            trefs.append(m.triaref[mine])
        if tris:
            sh.tria = np.concatenate(tris).astype(np.int32)
            sh.triaref = np.concatenate(trefs)
        save_distributed_mesh(out, s, sh, face_comms, node_comms)


def _report(pm, dt, as_json):
    from .ops.quality import tet_quality
    import jax.numpy as jnp
    q = np.asarray(tet_quality(pm._out, pm._out_met))
    tm = np.asarray(pm._out.tmask)
    st = pm.stats
    rec = {
        "ntets": int(tm.sum()),
        "qmin": float(q[tm].min()) if tm.any() else 0.0,
        "qmean": float(q[tm].mean()) if tm.any() else 0.0,
        "nsplit": st.nsplit if st else 0,
        "ncollapse": st.ncollapse if st else 0,
        "nswap": st.nswap if st else 0,
        "wall_s": round(dt, 3),
    }
    if as_json:
        # lint: ok(R3) — -bench-json stdout contract (machine-readable
        # record consumed by bench tooling; must not be gated)
        print(json.dumps(rec))
    else:
        otrace.log(0, f"  #tets {rec['ntets']}  quality min "
                      f"{rec['qmin']:.4f} mean {rec['qmean']:.4f}  "
                      f"ops s/c/w {rec['nsplit']}/{rec['ncollapse']}"
                      f"/{rec['nswap']}  {rec['wall_s']}s")


def _save_outputs(pm, args):
    from .io.medit import MeditMesh, write_mesh, write_sol, SOL_SCALAR, \
        SOL_TENSOR
    from .io.vtk import write_vtu, write_pvtu
    out = Path(args.out) if args.out else \
        Path(args.inp).with_name(Path(args.inp).stem + ".o.mesh")

    vert, vref = pm.get_vertices()
    tet, tref = pm.get_tetrahedra()
    tris, trefs = pm.get_triangles()

    if out.suffix in (".vtu", ".pvtu"):
        vtu = write_vtu(out.with_suffix(".vtu"), vert, tet - 1)
        if out.suffix == ".pvtu":
            write_pvtu(out, [vtu])
        return

    m = MeditMesh()
    m.vert, m.vref = vert, vref
    m.tetra, m.tref = tet - 1, tref
    m.tria, m.triaref = tris - 1, trefs
    m.tria_tet = pm._out_triangles()[3]     # adjacent-tet provenance
    # boundary entity sections (Edges/Ridges/Corners/RequiredVertices),
    # rebuilt from the adapted tags like the reference bdryBuild output
    edges, erefs, eridge, ereq = pm.get_edges()
    if len(edges):
        m.edges, m.edgeref = edges - 1, erefs
        m.ridges = np.flatnonzero(eridge).astype(np.int32)
        m.required_edges = np.flatnonzero(ereq).astype(np.int32)
    _, _, _, _, vtag = pm._out_host()
    m.corners = np.flatnonzero(vtag & C.MG_CRN).astype(np.int32)
    m.required_vert = np.flatnonzero(
        ((vtag & C.MG_REQ) != 0) & ((vtag & C.MG_PARBDY) == 0)
    ).astype(np.int32)
    if args.dist_out:
        from .io.distributed import save_distributed_mesh
        ndev = pm.info.n_devices
        if ndev > 1:
            _save_distributed_shards(pm, m, out, ndev)
        else:
            save_distributed_mesh(out, 0, m)
    else:
        write_mesh(out, m)
    met = pm.get_metric()
    if met is not None:
        write_sol(out.with_suffix(".sol"),
                  met.reshape(len(vert), -1),
                  [SOL_TENSOR if met.ndim == 2 and met.shape[1] == 6
                   else SOL_SCALAR])


if __name__ == "__main__":
    sys.exit(main())
