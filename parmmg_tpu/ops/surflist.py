"""A scatter whose updates are mostly dropped runs over the live ones.

The surface machinery of a cycle sums over boundary faces and special
edges into vertex-wide arrays, and it does so as full-width scatters:
12 x ``capT`` (face, corner) records, 2 x ``capE`` edge ends, of which
all but the surface's own are sent to the drop row.  A group of 12k-20k
live tets in 43,118 rows has 2k-5k boundary faces of 172,472 face slots,
a cube a few hundred special edges of 258,708: 1-3 % of the updates are
live.  The chip charges a gather or a scatter by the index, not by the
live one (17 ns an index; PERF.md, PR 39), and the gathers that feed
such a scatter are as wide as it is.

So where the live updates are known by a mask before the scatter, the
program lists their positions in ascending order (:class:`Live`: one
single-key sort) and runs gather -> geometry -> scatter over that
list a chunk at a time until the count is covered
(:func:`staged_scatter`, in the manner of ``ops/worklist.staged``).  No
second arm and no overflow: a list longer than a chunk takes more trips,
an empty one none.  Ascending positions keep every row's contributions
in the full-width scatter's order of addition, and a dropped update
changes nothing, so on a backend that adds in index order (XLA:CPU) the
result is the full-width scatter's to the bit; a scatter-max is exact on
any.

Where it engages is observed, not set (:class:`Tally`): XLA:CPU drops an
out-of-range update for a nanosecond or two and sorts slowly, so a
program placed on the host keeps the full-width scatter, and its lowered
text is what it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.constants import IDIR
from ..utils import placement

# a chunk is this fraction of the scatter's full width
CHUNK_DIV = 64


class Tally:
    """Whether the program being traced runs its surface scatters over
    lists (``on``: where it is placed on a TPU, unless the caller says),
    and the live updates its lists held so far (``listed``: a Python 0
    until a list is counted, so a program that lists nothing gains no
    operation).  One a traced scope: a ``lax.cond`` arm counts into a
    tally of its own and hands ``listed`` out as a result
    (:func:`counted`)."""

    def __init__(self, on: bool | None = None):
        self.on = placement.placed_on_tpu() if on is None else bool(on)
        self.listed = 0

    def note(self, n) -> None:
        self.listed = self.listed + n


def counted(site, *args):
    """``site(*args)`` over its lists, and the updates they held: the
    body of a ``lax.cond`` arm, which counts into a tally of its own."""
    arm = Tally(True)
    return site(*args, lists=arm), arm.listed


class Live:
    """The positions where ``mask`` [N] holds: ``pos`` [N + chunk] int32,
    the live ones ascending, then N (padded so that every chunk a count
    can ask for is in range); ``count`` of them; ``n`` = N and ``chunk``,
    the rows a trip takes, static.  One single-key sort."""

    def __init__(self, mask: jax.Array):
        self.n = n = mask.shape[0]
        self.chunk = max(1, -(-n // CHUNK_DIV))
        key = jnp.where(mask, jnp.arange(n, dtype=jnp.int32), n)
        self.pos = jnp.concatenate(
            [jnp.sort(key), jnp.full(self.chunk, n, jnp.int32)])
        self.count = jnp.sum(mask, dtype=jnp.int32)


def staged_scatter(acc: jax.Array, live: Live, updates,
                   op: str = "add") -> jax.Array:
    """``acc`` [rows + 1, ...] with the listed updates applied, the drop
    row last.  ``updates(p, ok)`` maps a chunk of positions ``p`` [c] (in
    range; ``ok`` [c] is False past the list's end) to (indices [c * m]
    into ``acc``, the drop row where not ``ok``; payload [c * m, ...]);
    ``op`` is ``add`` or ``max``.  The program holds ``updates`` once, at
    a chunk's width."""
    c, n = live.chunk, live.n

    def body(i, acc):
        p = jax.lax.dynamic_slice_in_dim(live.pos, i * c, c)
        idx, pay = updates(jnp.minimum(p, n - 1), p < n)
        at = acc.at[idx]
        return at.add(pay, mode="drop") if op == "add" \
            else at.max(pay, mode="drop")

    return jax.lax.fori_loop(0, (live.count + c - 1) // c, body, acc)


def take(rows: jax.Array, col: jax.Array) -> jax.Array:
    """``rows[i, col[i]]`` for ``rows`` [c, W, ...] with a narrow static W
    and ``col`` [c]: W selects, not a gather (which the chip charges by
    the index)."""
    col = col.reshape(col.shape + (1,) * (rows.ndim - 2))
    out = rows[:, 0]
    for j in range(1, rows.shape[1]):
        out = jnp.where(col == j, rows[:, j], out)
    return out


def table_rows(table, row: jax.Array) -> jax.Array:
    """``table[row]`` [c, m] for a small constant ``table`` [R, m] and
    ``row`` [c], by selects."""
    table = jnp.asarray(table)
    return take(jnp.broadcast_to(table, row.shape + table.shape), row)


def face_vertices(rows: jax.Array, f: jax.Array) -> jax.Array:
    """[c, 3] vertex ids of face ``f`` [c] of the tets ``rows`` [c, 4]:
    ``rows[i, IDIR[f[i]]]``, by selects."""
    loc = table_rows(IDIR, f)                              # [c,3]
    return jnp.stack([take(rows, loc[:, m]) for m in range(3)], axis=1)
