"""utils/placement.py: which backend a staged program runs on.

The accelerator is played by virtual CPU device 5 (the suite runs on
eight virtual CPU devices): ``jax.default_backend`` is patched to name
a non-CPU platform and the default device is moved off device 0, so the
branch a TPU run takes is the one exercised.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parmmg_tpu.utils import placement


def _ids(tree):
    return {d.id for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


@pytest.fixture
def fake_accelerator(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_device(jax.devices()[5]):
        yield jax.devices()[5]


def test_host_staging_is_a_no_op_on_the_cpu_backend():
    with jax.default_device(jax.devices()[3]), placement.host_staging():
        assert _ids(jnp.zeros(4)) == {3}


def test_host_staging_commits_to_the_host(fake_accelerator):
    assert _ids(jnp.zeros(4)) == {5}             # the "chip" by default
    with placement.host_staging():
        a = jnp.arange(8.0)
        b = jax.jit(lambda x: 2 * x)(a)          # programs follow arrays
    assert _ids((a, b)) == {0}
    assert _ids(jnp.zeros(4)) == {5}             # and back after it
    # staging places, it does not pin: a program run on a staged array
    # OUTSIDE the context goes to the default device — which is why every
    # whole-mesh step of the grouped path sits inside one (driver.py)
    assert _ids(jax.jit(jnp.cumsum)(a)) == {5}


def test_grouped_split_is_staged_then_committed(fake_accelerator):
    """The grouped pass's placement: the split's stacked state lands on
    the host, to_device hands every leaf to the default backend's first
    device (where the cycle blocks then run)."""
    from parmmg_tpu.core.mesh import make_mesh
    from parmmg_tpu.parallel.distribute import split_to_shards
    from parmmg_tpu.utils.fixtures import cube_mesh
    vert, tet = cube_mesh(2)
    part = (vert[tet].mean(axis=1)[:, 0] > 0.5).astype(np.int32)
    with placement.host_staging():
        m = make_mesh(vert, tet, capP=4 * len(vert), capT=4 * len(tet))
        staged = split_to_shards(m, jnp.full(m.capP, 0.3, m.vert.dtype),
                                 part, 2)
    assert _ids(staged) == {0}
    assert _ids(placement.to_device(staged)) == {jax.devices()[0].id}
