"""The Mamba-2 decode-state kernel (``ops/mamba2.ssd_decode_live``)
against the plain form it stands for (``ssd_decode_step``), interpreted
on the CPU at both families' head, width, state and group sizes with a
few slots.

What the kernel promises beyond the plain form's numbers: it moves the
live slots' state and no other, so a slot that is not active keeps its
state BIT FOR BIT, reads zeros for ``y``, and nothing of it (a NaN in
its inputs or its state) reaches a live slot.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import mamba2

pytestmark = pytest.mark.jax

SLOTS = 5
# H, P, N, G as served: Falcon-H1-34B, Nemotron-3-Nano.
FAMILIES = {'falcon_h1': (32, 128, 256, 2), 'nemotron_h': (64, 64, 128, 8)}
LIVE = {
    'none': (0, 0, 0, 0, 0),
    'one': (0, 0, 1, 0, 0),
    'all': (1, 1, 1, 1, 1),
    'scattered': (1, 0, 1, 1, 0),
    'last_only': (0, 0, 0, 0, 1),
}


def _inputs(family, seed=0):
    H, P, N, G = FAMILIES[family]
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (SLOTS, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (SLOTS, H))),
        a=-jnp.exp(jax.random.normal(k[2], (H,))),
        b=jax.random.normal(k[3], (SLOTS, G, N)),
        c=jax.random.normal(k[4], (SLOTS, G, N)),
        d_skip=jax.random.normal(k[5], (H,)),
        state=jax.random.normal(k[6], (SLOTS, H, P, N)))


@pytest.fixture(scope='module', params=sorted(FAMILIES))
def family(request):
    """(inputs, the plain form's y and state', the jitted kernel)."""
    i = _inputs(request.param)
    return i, mamba2.ssd_decode_step(**i), jax.jit(mamba2.ssd_decode_live)


@pytest.mark.parametrize('pattern', sorted(LIVE))
def test_live_rows_advance_and_dead_rows_are_not_touched(family, pattern):
    i, (y_ref, state_ref), kernel = family
    live = np.asarray(LIVE[pattern], bool)
    # Whatever a dead slot holds, NaN included, stays where it is.
    dead = jnp.asarray(~live)
    poisoned = dict(
        i, x=jnp.where(dead[:, None, None], jnp.nan, i['x']),
        dt=jnp.where(dead[:, None], jnp.nan, i['dt']),
        state=jnp.where((dead & (jnp.arange(SLOTS) % 2 == 0))
                        [:, None, None, None], jnp.nan, i['state']))
    y, state = kernel(**poisoned, active=jnp.asarray(live))
    assert y.dtype == state.dtype == jnp.float32
    y, state = np.asarray(y), np.asarray(state)
    np.testing.assert_allclose(y[live], np.asarray(y_ref)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state[live], np.asarray(state_ref)[live],
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(y).all()
    assert (y[~live] == 0).all()
    np.testing.assert_array_equal(
        state[~live].view(np.uint32),
        np.asarray(poisoned['state'])[~live].view(np.uint32))


@pytest.mark.parametrize('name', sorted(FAMILIES))
def test_tile_follows_the_shapes_under_the_budget(name):
    """A tile is whole heads that divide H and four of them fit the
    budget: both served shapes take more than one head a copy."""
    H, P, N, _ = FAMILIES[name]
    hb = mamba2._state_tile_heads(H, P, N)
    assert H % hb == 0 and hb > 1
    assert 4 * hb * P * N * 4 <= mamba2._STATE_VMEM_BUDGET
    assert mamba2._state_tile_heads(7, P, N) in (1, 7)
    assert mamba2._state_tile_heads(H, 1024, 1024) == 1


def test_steps_chain_in_place_as_the_engine_runs_them():
    """Three steps with a changing live set, the state donated each
    time: equal to the plain form under ``jnp.where`` step by step."""
    i = _inputs('nemotron_h', seed=3)
    step = jax.jit(mamba2.ssd_decode_live, donate_argnums=(6,))
    state, want = i['state'], i['state']
    args = [i[k] for k in ('x', 'dt', 'a', 'b', 'c', 'd_skip')]
    for pattern in ('scattered', 'last_only', 'all'):
        live = jnp.asarray(LIVE[pattern], bool)
        _, new = mamba2.ssd_decode_step(*args, want)
        want = jnp.where(live[:, None, None, None], new, want)
        _, state = step(*args, state, live)
        np.testing.assert_allclose(np.asarray(state), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
