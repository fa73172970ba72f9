"""Tag vocabulary, error codes and default parameters.

TPU-native re-design of the ParMmg constant surface:
- entity tag bits mirror the Mmg ``MG_*`` vocabulary referenced throughout the
  reference (see /root/reference/src/tag_pmmg.c:39-107 for how parallel
  interface entities are tagged ``MG_PARBDY + MG_BDY + MG_REQ + MG_NOSURF`` so
  the remesher treats them as frozen), because the freeze/ownership contract is
  behavioral API we must reproduce;
- error codes mirror PMMG_SUCCESS/LOWFAILURE/STRONGFAILURE
  (/root/reference/src/libparmmgtypes.h:45-66);
- default knobs mirror PMMG_Init_parameters
  (/root/reference/src/API_functions_pmmg.c:400-426) and parmmg.h:70,209-227.

Here the tags live in dense per-entity uint32 arrays (points, tet faces, tet
edges) instead of sparse xtetra/xpoint side structures: dense arrays are the
vectorizable representation on TPU.
"""
from __future__ import annotations

import numpy as np

# ---------------------------------------------------------------------------
# Entity tag bits (uint32). Names follow the Mmg vocabulary for parity.
# ---------------------------------------------------------------------------
MG_NOTAG = 0
MG_REF = 1 << 0       # entity lies on a reference (multi-material) surface
MG_BDY = 1 << 1       # entity lies on the domain boundary
MG_REQ = 1 << 2       # required: the remesher must not touch it
MG_CRN = 1 << 3       # corner point (geometric singularity)
MG_GEO = 1 << 4       # ridge (sharp edge by dihedral angle)
MG_NOM = 1 << 5       # non-manifold entity
MG_NOSURF = 1 << 6    # REQ was set by us, not the user (can be relaxed)
MG_OPNBDY = 1 << 7    # open boundary face (hanging surface inside volume)
MG_PARBDY = 1 << 8    # entity on a parallel (inter-shard) interface
MG_PARBDYBDY = 1 << 9 # true domain boundary that also lies on an interface
MG_OLDPARBDY = 1 << 10  # was a parallel interface at the previous iteration

# Frozen-interface contract: everything on a parallel interface is required +
# boundary + "not a real surface" (reference tag_pmmg.c:39-124).
PARBDY_TAGS = MG_PARBDY | MG_BDY | MG_REQ | MG_NOSURF

# ---------------------------------------------------------------------------
# Return codes (libparmmgtypes.h:45-66)
# ---------------------------------------------------------------------------
PMMG_SUCCESS = 0
PMMG_LOWFAILURE = 1      # something failed but a conforming mesh can be saved
PMMG_STRONGFAILURE = 2   # unrecoverable
PMMG_FAILURE = 4

# ---------------------------------------------------------------------------
# Default parameters (API_functions_pmmg.c:400-426, parmmg.h:70,209-227)
# ---------------------------------------------------------------------------
NITER_DEFAULT = 3                 # parmmg.h:70
TARGET_MESH_SIZE_SENTINEL = -30_000_000   # parmmg.h:209 (negative => default)
REMESHER_NGRPS_MAX = 100          # parmmg.h:212
RATIO_MMG_METIS_SENTINEL = -100   # parmmg.h:215
REDISTR_NGRPS_MAX = 1000          # parmmg.h:218
REDISTR_NELEM_MIN = 6             # parmmg.h:221
GRPS_RATIO = 2.0                  # parmmg.h:224
MVIFCS_NLAYERS = 2                # parmmg.h:227 (interface displacement waves)
IFC_EDGE_WEIGHT = 1.0e6           # metis_pmmg.h:64 (keep old ifcs off cuts)
WGT_ALPHA = 28.0                  # metis_pmmg.c:280 metric-aware edge weight
PARMETIS_UBVEC = 1.05             # metis_pmmg.h:72

# Repartitioning modes (libparmmgtypes.h:173-194)
REPART_GRAPH = 0
REPART_IFC_DISPLACEMENT = 1       # reference default
# Load-balancing partitioners
LB_METIS = 0   # reference: sequential METIS on gathered group graph
LB_SPECTRAL = 1  # ours: on-device spectral partitioner

# API modes for distributed input (libparmmg.h APImode)
APIDISTRIB_FACES = 0
APIDISTRIB_NODES = 1

# ---------------------------------------------------------------------------
# Remesh thresholds (Mmg kernel constants, mmg3d.h). Edge lengths are in
# metric space where the ideal length is 1.
# ---------------------------------------------------------------------------
LLONG = 1.4142135623730951   # split edges longer than sqrt(2)
LSHRT = 0.7071067811865476   # collapse edges shorter than 1/sqrt(2)
LOPTL = 1.3                  # target long threshold used in later passes
LOPTS = 0.6                  # target short threshold used in later passes
ANGEDG_DEG = 45.0            # dihedral angle for ridge detection (Mmg default)
ANGEDG = np.cos(ANGEDG_DEG * np.pi / 180.0)
EPSD = 1e-30
# Normalisation so an equilateral tet has quality 1:
#   Q = ALPHA_TET * vol / (sum_of_squared_edge_lengths)^{3/2}
# (Mmg MMG5_caltet_iso semantics, reference quality_pmmg.c:720 calls it per
# group; 36*sqrt(12) = 124.707...)
ALPHA_TET = 36.0 * np.sqrt(12.0)

# Minimal acceptable quality for an operator to be applied (Mmg uses a
# relative criterion; we keep an absolute floor plus no-worsening rules).
QUAL_FLOOR = 1e-9
# What a child of a sizing split must keep in a tet that holds a frozen
# group seam edge (ops/split.py; every other tet keeps QUAL_FLOOR, as
# upstream has no such floor).  A midpoint split halves a tet's quality
# at worst, and a tet on a frozen edge whose other edges the size map
# wants several times shorter is halved cycle after cycle: 0.56 ->
# 0.04 in five cycles, 1e-7 by the end of two passes of a job that grows
# its mesh 4.8x, thousands of tets under the tail's 1e-3 (ROADMAP B1).
# Under this floor the edge waits: the seam moves between passes and a
# later pass splits it.  Five times the floor the tail repairs to, so
# that one collapse (which may take a ball to 0.3 of its worst) stays
# above it.  Euclidean, like the tail's floor, so it holds under a
# scalar size map only: a tet that is well shaped in a tensor metric
# stretched 100:1 reads under it, and a job with a tensor metric keeps
# QUAL_FLOOR everywhere (a floor in the job's own metric is owed there,
# ROADMAP B1)
SPLIT_CHILD_FLOOR = 5e-3

# Default Hausdorff / gradation values (Mmg defaults, forwarded per group by
# PMMG_Set_dparameter, API_functions_pmmg.c:735)
HAUSD_DEFAULT = 0.01
HGRAD_DEFAULT = 1.3
HGRADREQ_DEFAULT = 2.3

# Verbosity levels (parmmg.h:128-163)
PMMG_VERB_NO = -1
PMMG_VERB_VERSION = 0
PMMG_VERB_QUAL = 1
PMMG_VERB_STEPS = 2
PMMG_VERB_ITWAVES = 3
PMMG_VERB_DETQUAL = 4

# ---------------------------------------------------------------------------
# Local tet topology tables (canonical, same conventions as Mmg where the
# reference relies on them for face/edge encodings, libparmmg1.c:132-140).
# Face f of a tet is opposite vertex f; MMG5_idir lists its 3 vertices.
# ---------------------------------------------------------------------------
# faces: IDIR[f] = the 3 local vertex indices of face f (opposite vertex f)
IDIR = np.array([[1, 2, 3], [0, 3, 2], [0, 1, 3], [0, 2, 1]], dtype=np.int32)
# edges: IARE[e] = the 2 local vertex indices of edge e
IARE = np.array(
    [[0, 1], [0, 2], [0, 3], [1, 2], [1, 3], [2, 3]], dtype=np.int32
)
# IFAR[e] = the 2 faces NOT containing edge e ; faces containing edge e:
EDGE_FACES = np.array(
    [[2, 3], [1, 3], [1, 2], [0, 3], [0, 2], [0, 1]], dtype=np.int32
)
# For face f (vertices IDIR[f]), the local edge indices of its 3 edges
FACE_EDGES = np.array(
    [[3, 5, 4], [2, 5, 1], [0, 4, 2], [1, 3, 0]], dtype=np.int32
)
