"""Ring attention vs dense over an 8-device sequence-parallel mesh."""
import pytest

pytestmark = pytest.mark.jax

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from skypilot_tpu.ops import attention, ring_attention


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('hq,hkv', [(4, 4), (4, 2)])
def test_ring_matches_dense(causal, hq, hkv):
    devs = jax.devices()
    assert len(devs) == 8
    mesh = Mesh(np.array(devs), ('sp',))
    b, s, d = 2, 8 * 16, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, hq, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, s, d), jnp.float32)

    with jax.default_matmul_precision('float32'):
        ref = attention.dense_attention(q, k, v, causal=causal)
        ring = shard_map(
            lambda q_, k_, v_: ring_attention.ring_attention(
                q_, k_, v_, axis_name='sp', causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, 'sp', None),) * 3,
            out_specs=P(None, None, 'sp', None),
        )
        out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_grads_finite():
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ('sp',))
    b, h, s, d = 1, 2, 8 * 8, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d))

    ring = shard_map(
        lambda q_, k_, v_: ring_attention.ring_attention(
            q_, k_, v_, axis_name='sp', causal=True),
        mesh=mesh,
        in_specs=(P(None, None, 'sp', None),) * 3,
        out_specs=P(None, None, 'sp', None),
    )
    g = jax.grad(lambda x: jnp.sum(jax.jit(ring)(x, x, x) ** 2))(q)
    assert bool(jnp.all(jnp.isfinite(g)))
