"""A swap kernel judges only what changed since it last looked.

The merged polish (driver._merged_polish) runs up to eight whole-mesh
waves, and from the second on a wave applies a few dozen operations: the
ring and edge swap kernels re-judge tens of thousands of candidates the
previous wave refused on the very same inputs.  A candidate's verdict is
a function of its SHELL: the live tet rows that hold the edge, those
rows' tags and references, and the coordinates and metric of their
vertices.  So the polish carries, per kernel, which rows changed since
that kernel last judged the mesh (:class:`Dirty`), a kernel evaluates
only the candidates with a changed shell, and its candidate stage runs
as wide as that list (:func:`staged`).

What makes it exact (the output is the full evaluation's, to the bit):

* a row is dirty when any of its row data changed (vertex ids, live bit,
  reference, face and edge tags: one elementwise diff across a stage, as
  ``ops/topo_incr.mark_dirty`` does for the tables) or when one of its
  vertices moved.  A tet that LEFT an edge's shell is no slot of the new
  shell, so the vertices such a row held are flagged too, and an edge
  with both ends flagged is on the list whatever its shell;
* a candidate comes off the list only when a gate that reads nothing but
  its shell refused it.  One that passed those gates and did not apply
  (a claim loser, one cut for want of free rows, one the same-wave
  duplicate veto dropped, a 2-2 candidate whose flipped diagonal exists
  elsewhere in the mesh) is put back by the kernel (``keep``), and a wave
  whose candidates outnumber its budget puts every row back;
* ``edges.tie_hash`` hashes a candidate's position in the compacted
  array and is not monotone, so the listed candidates move to the front
  by a STABLE partition (allocation order and "first winner a key" are
  relative orders) and carry the position they had for their tie hash.

By induction over the waves an unlisted candidate is one the kernel would
refuse again, and a refused candidate takes no part in claims, allocation
or the vetoes: every wave's winners are the full evaluation's.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.mesh import Mesh

# the candidate stage runs over the compacted rows an eighth at a time
CHUNKS = 8
# per-tet data a swap kernel's verdict reads (``adja`` is read by neither)
ROW_FIELDS = ("tet", "tmask", "tref", "ftag", "fref", "etag")


class Dirty(NamedTuple):
    """What changed since ONE kernel last judged the mesh."""
    rows: jax.Array     # [capT] bool: row data changed, or a vertex moved
    verts: jax.Array    # [capP] bool: vertices of a changed row, as it was


class PolishList(NamedTuple):
    """The merged polish's state, one :class:`Dirty` a swap kernel."""
    edges: Dirty        # ops/swap.swap_edges_wave
    rings: Dirty        # ops/swapgen.swapgen_wave


def all_dirty(mesh: Mesh) -> PolishList:
    """Before the first wave no kernel has judged anything."""
    def one():
        return Dirty(jnp.ones(mesh.capT, bool), jnp.ones(mesh.capP, bool))
    return PolishList(one(), one())


def changes(before: Mesh, after: Mesh) -> Dirty:
    """The rows and vertices a stage dirtied, ``before`` -> ``after``.
    A field the stage handed through (the same array object) is not
    compared."""
    rows = jnp.zeros(before.capT, bool)
    for name in ROW_FIELDS:
        a, b = getattr(before, name), getattr(after, name)
        if a is not b:
            d = a != b
            rows = rows | (d if d.ndim == 1 else jnp.any(d, axis=1))
    verts = jnp.zeros(before.capP + 1, bool).at[
        jnp.where(rows[:, None], before.tet, before.capP)].set(
        True, mode="drop")[:before.capP]
    if after.vert is not before.vert:
        moved = jnp.any(after.vert != before.vert, axis=1)
        rows = rows | jnp.any(moved[after.tet], axis=1)
    return Dirty(rows, verts)


def noted(wl: PolishList, before: Mesh, after: Mesh) -> PolishList:
    """Every kernel's list takes what a stage changed."""
    c = changes(before, after)
    return PolishList(*(Dirty(d.rows | c.rows, d.verts | c.verts)
                        for d in wl))


def looked(dirty: Dirty, keep) -> Dirty:
    """A kernel's look empties its list but for the rows it puts back
    (``keep``, its result's)."""
    return Dirty(keep, jnp.zeros_like(dirty.verts))


def on_list(dirty: Dirty, shells, valid, a, b):
    """[K] bool: the edge (a, b) with shell rows ``shells`` [K, S]
    (``valid`` slots) has a dirty slot, or lost a tet."""
    return jnp.any(valid & dirty.rows[shells], axis=1) | \
        (dirty.verts[a] & dirty.verts[b])


def listed_first(listed):
    """Stable partition of [K] rows, the listed ones first.  Returns
    (perm [K]: the position each row of the new order had, count of
    listed rows)."""
    k = listed.shape[0]
    li = listed.astype(jnp.int32)
    nl = jnp.sum(li)
    dest = jnp.where(listed, jnp.cumsum(li) - 1,
                     nl + jnp.cumsum(1 - li) - 1)
    perm = jnp.zeros(k, jnp.int32).at[dest].set(
        jnp.arange(k, dtype=jnp.int32), unique_indices=True)
    return perm, nl


def staged(stage, sel, nl, chunks: int = CHUNKS):
    """``stage(sel)`` over the first ``nl`` rows of ``sel`` [K], a chunk
    of K / ``chunks`` rows at a time, stopping after the last chunk that
    holds one: the program holds the stage once, at a chunk's width (it
    is traced a second time, abstractly, for the shapes of its rows), and
    a wave with an empty list runs none.  ``stage`` maps candidate ids to a
    pytree of per-row arrays, row by row independently; rows of chunks
    not run read zero (False), and the last chunk may overlap the one
    before it (same rows, same values)."""
    k = sel.shape[0]
    c = -(-k // chunks)
    shapes = jax.eval_shape(stage, sel[:c])
    init = jax.tree.map(
        lambda s: jnp.zeros((k,) + s.shape[1:], s.dtype), shapes)

    def body(i, out):
        start = jnp.minimum(i * c, k - c)
        part = stage(jax.lax.dynamic_slice_in_dim(sel, start, c))
        return jax.tree.map(
            lambda o, p: jax.lax.dynamic_update_slice_in_dim(
                o, p, start, 0), out, part)

    return jax.lax.fori_loop(0, (nl + c - 1) // c, body, init)


def keep_rows(keep, slot0, npre, capT: int):
    """[capT] bool, the rows a kernel puts back after its look: the
    first shell row of every candidate that passed its shell's gates and
    did not apply; every row when more candidates stood than the budget
    ``keep.shape[0]`` let through."""
    rows = jnp.zeros(capT + 1, bool).at[
        jnp.where(keep, slot0, capT)].set(True, mode="drop")[:capT]
    return rows | (npre > keep.shape[0])
