"""Where a program runs: the accelerator or the host's CPU backend.

One rule, used by the driver and the grouped pass: the group-shaped
cycle blocks run on the accelerator; the one-shot programs at
whole-mesh width around them (analysis, metric, group split and merge,
and in the grouped path the merged polish / repair / fem tail) run on
the host.  The mesh is grouped BECAUSE programs of its width are too
big, for the device and for its compiler alike: on a v5e host one
grouped pass spent 709 s compiling such programs against seconds on
XLA:CPU (PERF.md, PR 26).  They are placed there for their COMPILE
time; their run time is not small: on a v5e host the merged tail was
47 % of an iso job and 32 % of an aniso one, with the chip idle all
through it (ledger, PR 28), and it costs what the merged mesh's
capacity is, not what its content is (PERF.md, PR 29).
``chip_smoke.py`` reports the host and device shares of a run.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def host_staging():
    """Run the enclosed programs on the host's CPU backend and leave
    their results there.  The results are placed, not pinned: a program
    run on them outside the context moves them to the default device,
    so every whole-mesh step of the grouped path sits inside one
    (tests/test_placement.py).  A no-op when the default backend
    already is the CPU."""
    import jax
    if jax.default_backend() == "cpu":
        yield
        return
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        yield


def placed_on_tpu() -> bool:
    """Whether the program being traced is PLACED on a TPU: the default
    device where a context set one (``host_staging`` in a process that
    holds a chip), else the process's default backend.  What decides
    between two renderings of the same result whose costs differ by
    backend (``ops/swap.swap_facesort_enabled``, ``ops/surflist.Tally``):
    a TPU process places its whole-mesh tail on the host, where the
    process default chose wrongly (PERF.md, PRs 26, 33)."""
    import jax
    dev = jax.config.jax_default_device or jax.default_backend()
    return getattr(dev, "platform", dev) == "tpu"


def to_device(tree):
    """Commit a staged pytree to the first device of the default
    backend (the inverse of :func:`host_staging`)."""
    import jax
    return jax.device_put(tree, jax.devices()[0])
