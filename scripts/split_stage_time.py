"""Cumulative per-stage timing inside split_wave / collapse_wave.

Each timed program replays the wave's pipeline UP TO stage k and returns
a value data-dependent on everything computed so far (so XLA cannot DCE
earlier stages); differencing consecutive timings attributes cost to
each stage.  Mirrors the ops/split.py + ops/collapse.py structure as of
round 3 — a diagnostic, not a contract.

Run: python scripts/split_stage_time.py [N]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from parmmg_tpu.utils.compilecache import set_cache_env  # noqa: E402
set_cache_env()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

import jax
import jax.numpy as jnp
import numpy as np

from parmmg_tpu.core.mesh import make_mesh
from parmmg_tpu.core.constants import (IARE, LLONG, MG_REQ, MG_PARBDY,
                                       QUAL_FLOOR, EPSD, LSHRT)
from parmmg_tpu.ops.analysis import analyze_mesh
from parmmg_tpu.ops.edges import (unique_edges, edge_lengths,
                                  claim_channels, scatter_argmax2,
                                  wave_budget, NEG_INF, PRI_MIN)
from parmmg_tpu.ops.quality import quality_from_points
from parmmg_tpu.utils.fixtures import cube_mesh, analytic_iso_metric

K = int(os.environ.get("ST_REPS", "10"))
_IARE_J = jnp.asarray(IARE)


def timed(name, fn, *args):
    f = jax.jit(fn)
    r = f(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(K):
        r = f(*args)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / K * 1e3
    print(f"  {name:30s} {dt:9.2f} ms cumulative")
    return dt


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    vert, tet = cube_mesh(n)
    mesh = make_mesh(vert, tet, capP=3 * len(vert), capT=3 * len(tet))
    mesh = analyze_mesh(mesh).mesh
    h = analytic_iso_metric(vert, "shock", h=1.5 / n)
    met = jnp.zeros(mesh.capP, mesh.vert.dtype).at[: len(h)].set(
        jnp.asarray(h, mesh.vert.dtype)).at[len(h):].set(1.0)
    capT, capP = mesh.capT, mesh.capP
    print(f"N={n} capT={capT} device={jax.default_backend()}")

    # ---- split stages ----------------------------------------------------
    def s_table(mesh, met):
        et = unique_edges(mesh)
        return et.edge_id.sum() + et.nshell.sum() + et.etag.sum().astype(
            jnp.int32) + et.shell_rank.sum() + et.shell3.sum()

    def s_lens(mesh, met):
        et = unique_edges(mesh)
        lens = edge_lengths(mesh, et, met)
        return s_table(mesh, met) + lens.sum().astype(jnp.int32)

    def _prep(mesh, met):
        et = unique_edges(mesh)
        lens = edge_lengths(mesh, et, met)
        va = jnp.clip(et.ev[:, 0], 0, capP - 1)
        vb = jnp.clip(et.ev[:, 1], 0, capP - 1)
        frozen = (et.etag & (MG_REQ | MG_PARBDY)) != 0
        cand = et.emask & (lens > LLONG) & ~frozen
        return et, lens, va, vb, cand

    def s_nom(mesh, met):
        et, lens, va, vb, cand = _prep(mesh, met)
        s, t = claim_channels(lens, cand)
        tes = jnp.where(mesh.tmask[:, None], s[et.edge_id], NEG_INF)
        best_s = jnp.max(tes, axis=1)
        at_best = (tes == best_s[:, None]) & jnp.isfinite(best_s)[:, None]
        tet_t = jnp.where(at_best, t[et.edge_id], PRI_MIN)
        best_t = jnp.max(tet_t, axis=1)
        nominate = at_best & (tet_t == best_t[:, None])
        return s_lens(mesh, met) + nominate.sum().astype(jnp.int32)

    def _nom(mesh, met):
        et, lens, va, vb, cand = _prep(mesh, met)
        s, t = claim_channels(lens, cand)
        tes = jnp.where(mesh.tmask[:, None], s[et.edge_id], NEG_INF)
        best_s = jnp.max(tes, axis=1)
        at_best = (tes == best_s[:, None]) & jnp.isfinite(best_s)[:, None]
        tet_t = jnp.where(at_best, t[et.edge_id], PRI_MIN)
        best_t = jnp.max(tet_t, axis=1)
        nominate = at_best & (tet_t == best_t[:, None])
        return et, lens, va, vb, cand, nominate

    def s_veto(mesh, met):
        et, lens, va, vb, cand, nominate = _nom(mesh, met)
        ar0 = jnp.arange(capT)
        loc_n = jnp.argmax(nominate, axis=1)
        e_n = et.edge_id[ar0, loc_n]
        i_n = _IARE_J[loc_n, 0]
        j_n = _IARE_J[loc_n, 1]
        mid_n = 0.5 * (mesh.vert[va[e_n]] + mesh.vert[vb[e_n]])
        pts = mesh.vert[mesh.tet]
        q1 = quality_from_points(pts.at[ar0, j_n].set(mid_n))
        q2 = quality_from_points(pts.at[ar0, i_n].set(mid_n))
        nominate = nominate & ((q1 > QUAL_FLOOR) &
                               (q2 > QUAL_FLOOR))[:, None]
        return s_nom(mesh, met) + nominate.sum().astype(jnp.int32)

    def _win(mesh, met):
        et, lens, va, vb, cand, nominate = _nom(mesh, met)
        ar0 = jnp.arange(capT)
        loc_n = jnp.argmax(nominate, axis=1)
        e_n = et.edge_id[ar0, loc_n]
        i_n = _IARE_J[loc_n, 0]
        j_n = _IARE_J[loc_n, 1]
        mid_n = 0.5 * (mesh.vert[va[e_n]] + mesh.vert[vb[e_n]])
        pts = mesh.vert[mesh.tet]
        q1 = quality_from_points(pts.at[ar0, j_n].set(mid_n))
        q2 = quality_from_points(pts.at[ar0, i_n].set(mid_n))
        nominate = nominate & ((q1 > QUAL_FLOOR) &
                               (q2 > QUAL_FLOOR))[:, None]
        capE = et.ev.shape[0]
        nom_count = jnp.zeros(capE, jnp.int32).at[
            et.edge_id.reshape(-1)].add(
            nominate.reshape(-1).astype(jnp.int32))
        win = cand & (nom_count == et.nshell) & (et.nshell > 0)
        return et, lens, win

    def s_win(mesh, met):
        _, _, win = _win(mesh, met)
        return s_veto(mesh, met) + win.sum().astype(jnp.int32)

    def s_budget(mesh, met):
        et, lens, win = _win(mesh, met)
        capE = et.ev.shape[0]
        win_i = win.astype(jnp.int32)
        new_off = jnp.cumsum(win_i) - win_i
        nwin = jnp.sum(win_i)
        fits_p = new_off < (capP - mesh.npoin)
        shell_add = jnp.where(win & fits_p, et.nshell, 0)
        tet_off = jnp.cumsum(shell_add) - shell_add
        fits_t = (tet_off + shell_add) <= (capT - mesh.nelem)
        win_cap = win & fits_p & fits_t
        KW = min(wave_budget(capT, 8), capE)
        KH = min(2 * wave_budget(capT, 8), capT)
        bord = jnp.argsort(jnp.where(win_cap, -lens, jnp.inf))
        win_srt = win_cap[bord]
        off_srt = jnp.cumsum(win_srt.astype(jnp.int32)) - win_srt
        sh_srt = jnp.where(win_srt & (off_srt < KW), et.nshell[bord], 0)
        toff_srt = jnp.cumsum(sh_srt) - sh_srt
        ok_srt = win_srt & (off_srt < KW) & ((toff_srt + sh_srt) <= KH)
        win2 = jnp.zeros_like(win_cap).at[bord].set(ok_srt,
                                                    unique_indices=True)
        win_i2 = win2.astype(jnp.int32)
        new_off2 = jnp.cumsum(win_i2) - win_i2
        shell_add2 = jnp.where(win2, et.nshell, 0)
        tet_off2 = jnp.cumsum(shell_add2) - shell_add2
        return (s_win(mesh, met) + new_off2.sum() + tet_off2.sum()
                + win2.sum().astype(jnp.int32))

    print("split_wave stages:")
    timed("table", s_table, mesh, met)
    timed("+lengths", s_lens, mesh, met)
    timed("+nomination", s_nom, mesh, met)
    timed("+degeneracy veto", s_veto, mesh, met)
    timed("+whole-shell win", s_win, mesh, met)
    timed("+budget/offsets", s_budget, mesh, met)
    from parmmg_tpu.ops.split import split_wave
    timed("full split_wave", lambda m, k: split_wave(m, k).mesh.tet.sum(),
          mesh, met)

    # ---- collapse stages -------------------------------------------------
    def c_prep(mesh, met):
        et = unique_edges(mesh)
        lens = edge_lengths(mesh, et, met)
        va_f = jnp.clip(et.ev[:, 0], 0, capP - 1)
        vb_f = jnp.clip(et.ev[:, 1], 0, capP - 1)
        frozen = (et.etag & (MG_REQ | MG_PARBDY)) != 0
        short = et.emask & (lens < LSHRT) & ~frozen
        from parmmg_tpu.ops.collapse import _removable
        ta_f, tb_f = mesh.vtag[va_f], mesh.vtag[vb_f]
        rem_b = _removable(tb_f, ta_f, et.etag)
        rem_a = _removable(ta_f, tb_f, et.etag)
        pre = short & (rem_a | rem_b)
        return et, lens, va_f, vb_f, pre, rem_b

    def c_sel(mesh, met):
        et, lens, va_f, vb_f, pre, rem_b = c_prep(mesh, met)
        Kb = min(et.ev.shape[0], wave_budget(capT, 8))
        sel = jnp.argsort(jnp.where(pre, lens, jnp.inf))[:Kb]
        return (sel.sum() + pre.sum().astype(jnp.int32))

    def _c_top(mesh, met):
        et, lens, va_f, vb_f, pre, rem_b = c_prep(mesh, met)
        Kb = min(et.ev.shape[0], wave_budget(capT, 8))
        sel = jnp.argsort(jnp.where(pre, lens, jnp.inf))[:Kb]
        lens_c = lens[sel]
        va = va_f[sel]
        vb = vb_f[sel]
        cand = pre[sel]
        del_b = rem_b[sel]
        rm = jnp.where(del_b, vb, va)
        kp = jnp.where(del_b, va, vb)
        s, t = claim_channels(-lens_c, cand)
        is_top, v_s, v_t = scatter_argmax2(rm, s, t, cand, capP)
        kept_of = jnp.zeros(capP, jnp.int32).at[
            jnp.where(is_top, rm, capP)].set(kp, mode="drop",
                                             unique_indices=True)
        return v_s, v_t, kept_of, is_top

    def c_top(mesh, met):
        v_s, v_t, kept_of, is_top = _c_top(mesh, met)
        return (c_sel(mesh, met) + kept_of.sum()
                + is_top.sum().astype(jnp.int32))

    def c_valid(mesh, met):
        v_s, v_t, kept_of, is_top = _c_top(mesh, met)
        tv = mesh.tet
        vpos = mesh.vert[tv]
        vs_c = v_s[tv]
        has_c = jnp.isfinite(vs_c)
        kept = kept_of[tv]
        kept_pos = mesh.vert[kept]
        contains_kept = jnp.zeros((capT, 4), bool)
        for k in range(4):
            hit = jnp.zeros((capT,), bool)
            for j in range(4):
                hit = hit | ((tv[:, j] == kept[:, k]) & (j != k))
            contains_kept = contains_kept.at[:, k].set(hit)
        from parmmg_tpu.core.constants import IDIR
        from parmmg_tpu.ops.quality import edge_length_iso
        idx_act = []
        bad_all = []
        for k in range(4):
            active = has_c[:, k] & mesh.tmask & ~contains_kept[:, k]
            p = vpos.at[:, k].set(kept_pos[:, k])
            d1 = p[:, 1] - p[:, 0]
            d2 = p[:, 2] - p[:, 0]
            d3 = p[:, 3] - p[:, 0]
            vol = jnp.einsum("ti,ti->t", d1, jnp.cross(d2, d3)) / 6.0
            bad = vol <= EPSD
            for f in range(4):
                if k == f:
                    continue
                idx = IDIR[f]
                n_old = jnp.cross(vpos[:, idx[1]] - vpos[:, idx[0]],
                                  vpos[:, idx[2]] - vpos[:, idx[0]])
                n_new = jnp.cross(p[:, idx[1]] - p[:, idx[0]],
                                  p[:, idx[2]] - p[:, idx[0]])
                isb = (mesh.ftag[:, f] & 2) != 0
                flip = jnp.sum(n_old * n_new, -1) <= 0
                bad = bad | (isb & flip)
            for j in range(4):
                if j == k:
                    continue
                lnew = edge_length_iso(kept_pos[:, k], p[:, j],
                                       met[kept[:, k]], met[tv[:, j]])
                bad = bad | (lnew > LLONG)
            idx_act.append(jnp.where(active, tv[:, k], capP))
            bad_all.append(bad)
        idx_act = jnp.concatenate(idx_act)
        geombad = jnp.zeros(capP + 1, bool).at[idx_act].max(
            jnp.concatenate(bad_all), mode="drop")[:capP]
        return c_top(mesh, met) + geombad.sum().astype(jnp.int32)

    def c_ballq(mesh, met):
        v_s, v_t, kept_of, is_top = _c_top(mesh, met)
        tv = mesh.tet
        vpos = mesh.vert[tv]
        kept = kept_of[tv]
        kept_pos = mesh.vert[kept]
        has_c = jnp.isfinite(v_s[tv])
        q_ball = quality_from_points(vpos)
        idx4c = jnp.concatenate(
            [jnp.where(mesh.tmask, tv[:, k], capP) for k in range(4)])
        ballq_old = jnp.full(capP + 1, jnp.inf).at[idx4c].min(
            jnp.tile(jnp.where(mesh.tmask, q_ball, jnp.inf), 4),
            mode="drop")
        variants = jnp.concatenate(
            [vpos.at[:, k].set(kept_pos[:, k]) for k in range(4)])
        qv = quality_from_points(variants)
        act4 = jnp.concatenate([has_c[:, k] & mesh.tmask
                                for k in range(4)])
        idx_act = jnp.concatenate(
            [jnp.where(has_c[:, k] & mesh.tmask, tv[:, k], capP)
             for k in range(4)])
        ballq_new = jnp.full(capP + 1, jnp.inf).at[idx_act].min(
            jnp.where(act4, qv, jnp.inf), mode="drop")
        return (c_valid(mesh, met) +
                (ballq_new[:capP] > 0.3 * ballq_old[:capP]).sum()
                .astype(jnp.int32))

    print("collapse_wave stages:")
    timed("prep+candidacy", lambda m, k: c_prep(m, k)[4].sum()
          .astype(jnp.int32), mesh, met)
    timed("+topK sel", c_sel, mesh, met)
    timed("+top-remover claims", c_top, mesh, met)
    timed("+tet validity", c_valid, mesh, met)
    timed("+ball quality", c_ballq, mesh, met)
    from parmmg_tpu.ops.collapse import collapse_wave
    timed("full collapse_wave",
          lambda m, k: collapse_wave(m, k).mesh.tet.sum(), mesh, met)


if __name__ == "__main__":
    main()
