"""``partition.cut_pieces`` labels a cut's face-connected pieces with
numpy (PR 43), and ``fix_contiguity`` takes its pieces from it where it
searched tet by tet in Python (0.5 s at 83k tets, twice a job).  The
search is kept HERE as the reference: same pieces in the same order, so
the same blob keeps a colour on a tie and the same neighbour takes the
others, on cuts with a few blobs (the Morton cut's) and with hundreds."""
from collections import deque

import numpy as np
import pytest

from parmmg_tpu.parallel.partition import (build_dual_graph, cut_pieces,
                                           fix_contiguity, morton_partition)
from parmmg_tpu.utils.fixtures import cube_mesh


def pieces_by_search(tet, part):
    """The search ``fix_contiguity`` ran until PR 43."""
    n = len(tet)
    xadj, adj = build_dual_graph(tet)
    comp = np.full(n, -1, np.int64)
    ncomp = 0
    for s in range(n):
        if comp[s] != -1:
            continue
        comp[s] = ncomp
        dq = deque([s])
        while dq:
            t = dq.popleft()
            for v in adj[xadj[t]:xadj[t + 1]]:
                if comp[v] == -1 and part[v] == part[t]:
                    comp[v] = ncomp
                    dq.append(v)
        ncomp += 1
    return comp, xadj, adj


def fix_contiguity_by_search(tet, part):
    """``fix_contiguity`` as it stood at 7527801."""
    part = part.copy()
    comp, xadj, adj = pieces_by_search(tet, part)
    ncomp = int(comp.max()) + 1
    sizes = np.bincount(comp, minlength=ncomp)
    keep = {}
    for cid in range(ncomp):
        col = part[np.argmax(comp == cid)]
        if col not in keep or sizes[cid] > sizes[keep[col]]:
            keep[col] = cid
    keepset = set(keep.values())
    for cid in range(ncomp):
        if cid in keepset:
            continue
        idx = np.where(comp == cid)[0]
        votes = {}
        for t in idx:
            for v in adj[xadj[t]:xadj[t + 1]]:
                if comp[v] != cid:
                    votes[part[v]] = votes.get(part[v], 0) + 1
        if votes:
            part[idx] = max(votes, key=votes.get)
    return part


def cuts():
    """(name, tet, part): the even Morton cuts the grouped path makes,
    and cuts in many blobs with ties everywhere."""
    out = []
    for n, nparts in ((4, 6), (6, 3), (6, 6), (8, 6)):
        vert, tet = cube_mesh(n)
        out.append((f"morton-{n}-{nparts}", tet,
                    morton_partition(vert[tet].mean(axis=1), nparts)))
    vert, tet = cube_mesh(5)
    rng = np.random.default_rng(43)
    for k, nparts in enumerate((2, 3, 6)):
        out.append((f"noise-{nparts}", tet,
                    rng.integers(0, nparts, len(tet)).astype(np.int32)))
        # a Morton cut with a tenth of its tets handed to other parts
        part = morton_partition(vert[tet].mean(axis=1), nparts)
        flip = rng.random(len(tet)) < 0.1
        part[flip] = rng.integers(0, nparts, int(flip.sum()))
        out.append((f"speckled-{nparts}", tet, part))
    return out


CUTS = cuts()


@pytest.mark.parametrize("name,tet,part", CUTS, ids=[c[0] for c in CUTS])
def test_pieces_are_the_searchs_in_the_searchs_order(name, tet, part):
    comp, _, _ = pieces_by_search(tet, part)
    got = cut_pieces(tet, part)
    assert np.array_equal(got, comp)


@pytest.mark.parametrize("name,tet,part", CUTS, ids=[c[0] for c in CUTS])
def test_fix_contiguity_hands_back_what_the_search_did(name, tet, part):
    want = fix_contiguity_by_search(tet, part)
    got = fix_contiguity(tet, part)
    assert np.array_equal(got, want)
    if name.startswith("morton"):
        assert (got != part).any() or cut_pieces(tet, part).max() + 1 \
            == part.max() + 1


def test_a_cut_in_one_piece_a_part_comes_back_as_it_was():
    vert, tet = cube_mesh(4)
    part = (vert[tet].mean(axis=1)[:, 0] > 0.5).astype(np.int32)
    assert cut_pieces(tet, part).max() + 1 == 2
    got = fix_contiguity(tet, part)
    assert np.array_equal(got, part) and got is not part
