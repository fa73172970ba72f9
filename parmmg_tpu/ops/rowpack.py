"""A vertex's scalars ride in one row.

The chip has two prices for a gather (PERF.md section 5, PR 42;
``scripts/tpu_microbench.py gathers``): ONE scalar out of a 1-D
per-vertex table costs 6.7-7.6 ns an index, a ROW of a ``[capP, k]``
table 2.1 ns at the same indices, whatever it holds up to sixteen words.
A stage of the cycle reads several per-vertex columns through one index
array (a tag word, a flag, a score, a size, beside the coordinates), each
through a gather of its own.

:func:`pack` stacks such columns as one ``[n, k]`` table of 32-bit words
(``lax.bitcast_convert_type`` to one carrier: the table has a few
thousand rows, making it costs nothing); ``bool`` flags are folded into
the bits of shared words.  :meth:`RowPack.take` gathers ROWS and hands
each column back in its own type and trailing shape.  A gather copies
bits and a bitcast keeps them, so every column is its own gather's to the
bit on any backend: NaN payloads, -0.0 and denormals included.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

CARRIER = jnp.uint32
FLAGS_PER_WORD = 32


class _Column(NamedTuple):
    dtype: object       # the column's own type
    start: int          # first word of the row (a flag's: its word)
    width: int | None   # trailing width; None: a 1-D column
    bit: int            # a flag's bit in its word


class RowPack(NamedTuple):
    """Per-vertex columns as rows of words; see :func:`pack`."""
    table: jax.Array    # [n, k] CARRIER
    columns: dict       # name -> _Column

    def take(self, idx: jax.Array) -> dict:
        """The columns at ``idx`` (any shape), by ONE row gather: each
        what ``column[idx]`` is, to the bit, under its name."""
        rows = self.table[idx]                       # idx.shape + [k]
        out = {}
        for name, c in self.columns.items():
            if c.dtype == jnp.bool_:
                word = rows[..., c.start]
                out[name] = ((word >> CARRIER(c.bit)) & CARRIER(1)) != 0
                continue
            words = rows[..., c.start] if c.width is None \
                else rows[..., c.start:c.start + c.width]
            out[name] = words if c.dtype == CARRIER \
                else jax.lax.bitcast_convert_type(words, c.dtype)
        return out


def pack(**cols: jax.Array) -> RowPack:
    """Stack named per-vertex columns, ``[n]`` or ``[n, w]`` of any
    32-bit type (``f32``, ``s32``, ``u32``) and ``[n]`` ``bool`` flags,
    into one table of rows.  The flags share words, 32 a word, after the
    other columns."""
    words, columns = [], {}
    flags = [name for name, col in cols.items() if col.dtype == jnp.bool_]
    k = 0
    for name, col in cols.items():
        if name in flags:
            continue
        if col.dtype.itemsize != 4 or col.ndim not in (1, 2):
            raise TypeError(
                f"{name}: a packed column is [n] or [n, w] of a 32-bit "
                f"type, not {col.dtype}{list(col.shape)}")
        c = col[:, None] if col.ndim == 1 else col
        words.append(c if c.dtype == CARRIER
                     else jax.lax.bitcast_convert_type(c, CARRIER))
        columns[name] = _Column(col.dtype, k, None if col.ndim == 1
                                else col.shape[1], 0)
        k += c.shape[1]
    for first in range(0, len(flags), FLAGS_PER_WORD):
        word = CARRIER(0)
        for bit, name in enumerate(flags[first:first + FLAGS_PER_WORD]):
            if cols[name].ndim != 1:
                raise TypeError(f"{name}: a flag column is 1-D, not "
                                f"{list(cols[name].shape)}")
            word = word | (cols[name].astype(CARRIER) << CARRIER(bit))
            columns[name] = _Column(jnp.bool_, k, None, bit)
        words.append(word[:, None])
        k += 1
    if k == 1:
        # a row of ONE word is fetched at the scalar's price, a row of two
        # to sixteen at the row's (scripts/tpu_microbench.py gathers:
        # 1.73 ms against 0.54 at 258,708 indices): a lone column rides
        # beside its own copy
        words.append(words[0])
    return RowPack(jnp.concatenate(words, axis=1), columns)


def take(col: jax.Array, idx: jax.Array) -> jax.Array:
    """``col[idx]`` of ONE per-vertex column, through a row gather."""
    return pack(col=col).take(idx)["col"]
