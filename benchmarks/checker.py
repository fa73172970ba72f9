"""The plain reference: is this tetrahedral mesh what the user asked for?

numpy only, float64, independent of the code under test (started from
``chip_smoke.check_output_mesh``).  ``measure`` turns one job's output
into numbers; ``judge`` holds each number against the limit the
configuration's file states under ``guarantees``.  Edges and aniso quality are measured in the metric
the job returns with its mesh (the driver grades an iso size map by
``hgrad`` before it adapts, so the request's formula is not the target);
that metric is itself held to being there, finite and positive.
"""
from __future__ import annotations

import numpy as np

from byname import load

# vol / (sum of squared edge lengths)^1.5 of the regular tetrahedron:
# Mmg's quality (MMG5_caltet) is scaled to 1 there
Q_REGULAR = 1.0 / (6.0 * 2.0 ** 0.5 * 6.0 ** 1.5)
# the remesher's own band (Mmg LSHRT / LLONG): it collapses edges
# shorter than 1/sqrt 2 and splits edges longer than sqrt 2 in the metric
LEN_LO, LEN_HI = 0.5 ** 0.5, 2.0 ** 0.5
_PAIRS = ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3))


def _sym(m6):
    """[n, 6] packed (m11 m12 m13 m22 m23 m33) -> [n, 3, 3]."""
    return m6[:, [0, 1, 2, 1, 3, 4, 2, 4, 5]].reshape(-1, 3, 3)


def edge_lengths(p0, p1, m0, m1):
    """Length of each edge in the metric, by Mmg's rules: iso, the exact
    integral of 1/h for h linear along the edge; aniso, the Simpson-like
    mean of the two endpoint lengths."""
    e = p1 - p0
    if m0.ndim == 1:
        d = np.sqrt((e * e).sum(-1))
        r0, r1 = 1.0 / m0, 1.0 / m1
        same = np.abs(r0 - r1) < 1e-9 * np.maximum(r0, r1)
        with np.errstate(divide="ignore", invalid="ignore"):
            lm = np.where(same, 0.5 * (r0 + r1), (r1 - r0) / np.log(m0 / m1))
        return d * lm
    l0 = np.sqrt(np.einsum("ni,nij,nj->n", e, _sym(m0), e))
    l1 = np.sqrt(np.einsum("ni,nij,nj->n", e, _sym(m1), e))
    return (2.0 / 3.0) * (l0 * l0 + l0 * l1 + l1 * l1) / (l0 + l1)


def volumes(p):
    """Signed volumes of tets p [n, 4, 3]."""
    d1, d2, d3 = p[:, 1] - p[:, 0], p[:, 2] - p[:, 0], p[:, 3] - p[:, 0]
    return np.einsum("ij,ij->i", d1, np.cross(d2, d3)) / 6.0


def face_counts(tet):
    """The distinct faces of ``tet`` [n, 4] (sorted vertex triples) and
    how many tets have each: 1 on the boundary, 2 inside a conforming
    mesh."""
    faces = np.sort(np.stack([tet[:, [1, 2, 3]], tet[:, [0, 2, 3]],
                              tet[:, [0, 1, 3]], tet[:, [0, 1, 2]]],
                             axis=1).reshape(-1, 3), axis=1)
    return np.unique(faces, axis=0, return_counts=True)


def quality(p, met_t=None):
    """Mmg quality of tets p [n, 4, 3], 1 on the regular tet, <= 0 when
    flat or inverted: Euclidean, or in the tet's mean tensor
    ``met_t`` [n, 6]."""
    vol = volumes(p)
    ed = np.stack([p[:, j] - p[:, i] for i, j in _PAIRS], 1)
    if met_t is None:
        return vol / (ed * ed).sum((1, 2)) ** 1.5 / Q_REGULAR
    m = _sym(met_t)
    l2 = np.einsum("nei,nij,nej->n", ed, m, ed)
    return vol * np.sqrt(np.linalg.det(m)) / l2 ** 1.5 / Q_REGULAR


def coord_bits(coords) -> float:
    """Median number of significand bits the float32 form of each
    coordinate needs.  Coordinates a float32 program has moved fill
    their 24 bits; carried in bfloat16 they need at most 8, in float16
    at most 11."""
    u = np.asarray(coords, np.float32).ravel().view(np.uint32)
    frac = (u & 0x7FFFFF) | 0x800000            # the 24-bit significand
    low = frac & -frac.astype(np.int64)         # its lowest set bit
    return float(np.median(24 - np.log2(low)))


def _metric_ok(met, nvert: int):
    if met is None or len(met) != nvert or not np.isfinite(met).all():
        return False
    if met.ndim == 1:
        return bool((met > 0).all())
    return met.shape[1] == 6 and bool(
        (np.linalg.eigvalsh(_sym(met))[:, 0] > 0).all())


def measure(vert, tet, met, domain: dict) -> dict:
    """Numbers of one output: vertices, tets (0-based) and the metric at
    the vertices ([n] sizes or [n, 6] tensors).  Never raises on a bad
    mesh: one that cannot be measured gets ``broken`` = 1."""
    vert = np.asarray(vert, np.float64)
    tet = np.asarray(tet, np.int64)
    met = None if met is None else np.asarray(met, np.float64)
    out = {"broken": 0, "ntets": int(len(tet)), "nvert": int(len(vert))}
    if (len(tet) == 0 or tet.min() < 0 or tet.max() >= len(vert)
            or not np.isfinite(vert).all() or not _metric_ok(met, len(vert))):
        out["broken"] = 1
        return out
    p = vert[tet]
    vol = volumes(p)
    out["inverted_tets"] = int((vol <= 0).sum())
    out["volume_rel_err"] = float(abs(vol.sum() - domain["volume"])
                                  / domain["volume"])
    # manifold conformity: a face has one tet (boundary) or two
    uniq, cnt = face_counts(tet)
    out["overfull_faces"] = int((cnt > 2).sum())
    # an unmatched face off the domain's surface borders a hole; the
    # volume sum bounds a hole's size, this sees the flat kind too
    lone = vert[uniq[cnt == 1]]
    out["unmatched_interior_faces"] = int((~load(
        "domains", domain["kind"]).on_surface(lone, domain, 1e-9)).sum())
    q = quality(p, met[tet].mean(axis=1) if met.ndim == 2 else None)
    out["qmin"], out["qmean"] = float(q.min()), float(q.mean())
    edges = np.unique(np.sort(np.concatenate(
        [tet[:, [i, j]] for i, j in _PAIRS]), axis=1), axis=0)
    ln = edge_lengths(vert[edges[:, 0]], vert[edges[:, 1]],
                      met[edges[:, 0]], met[edges[:, 1]])
    out["len_ok_share"] = float(
        100.0 * ((ln >= LEN_LO) & (ln <= LEN_HI)).mean())
    # vertices off the boundary are where the program chose coordinates
    inner = np.ones(len(vert), bool)
    inner[uniq[cnt == 1].ravel()] = False
    out["coord_bits"] = coord_bits(vert[inner]) if inner.any() else 0.0
    return out


def judge(numbers: dict, guarantees: dict) -> list[dict]:
    """Each number compared beside its limit:
    [{"name", "value", "limit", "ok"}].  ``guarantees`` maps a number to
    {"max": x}, {"min": x} or {"band": [lo, hi]} (and a "reason")."""
    rows = []
    for name, rule in guarantees.items():
        val = numbers.get(name)
        if "band" in rule:
            lo, hi = rule["band"]
            ok = val is not None and lo <= val <= hi
            limit = [lo, hi]
        elif "max" in rule:
            ok, limit = val is not None and val <= rule["max"], rule["max"]
        else:
            ok, limit = val is not None and val >= rule["min"], rule["min"]
        rows.append({"name": name, "value": val, "limit": limit,
                     "ok": bool(ok)})
    return rows
