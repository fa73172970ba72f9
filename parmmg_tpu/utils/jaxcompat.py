"""The one place that names the jax symbols lint rule R5 guards.

``shard_map``, ``axis_size`` and ``platform_dependent`` are plain
aliases of the installed jax's own (``jax.shard_map(...,
check_vma=)``, ``jax.lax.axis_size``, ``jax.lax.platform_dependent``);
callers import them from here so a future rename is a one-file edit.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma=True, **kw):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


axis_size = jax.lax.axis_size
platform_dependent = jax.lax.platform_dependent
