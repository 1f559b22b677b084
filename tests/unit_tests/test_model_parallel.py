"""Llama forward/grad on CPU; sharded train step on the 8-device CPU mesh."""
import pytest

pytestmark = pytest.mark.jax

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel import sharding as sharding_lib
from skypilot_tpu.train import trainer


@pytest.fixture(scope='module')
def tiny():
    return llama.LlamaConfig.tiny()


def test_forward_shapes_and_finite(tiny):
    params = llama.init_params(tiny, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(tiny, params, tokens)
    assert logits.shape == (2, 16, tiny.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    params = llama.init_params(tiny, jax.random.PRNGKey(0))
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(5)
    l1 = llama.forward(tiny, params, t1)
    l2 = llama.forward(tiny, params, t2)
    np.testing.assert_allclose(np.asarray(l1[0, :10]),
                               np.asarray(l2[0, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_loss_decreases_single_device(tiny):
    opt = trainer.make_optimizer(learning_rate=1e-2, warmup_steps=1,
                                 total_steps=100)
    state = trainer.init_train_state(tiny, jax.random.PRNGKey(0), opt)
    step = trainer.make_train_step(tiny, opt)
    batch = trainer.synthetic_batch(tiny, 4, 32, jax.random.PRNGKey(1))
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics['loss']))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_num_params_matches(tiny):
    params = llama.init_params(tiny, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(p.shape))
                 for p in jax.tree_util.tree_leaves(params))
    assert actual == tiny.num_params


def test_sharded_train_step_2x2x2(tiny):
    """Full dp2 x fsdp2 x tp2 train step on the virtual 8-device CPU mesh —
    the multi-chip path the driver dry-runs."""
    assert len(jax.devices()) == 8, 'conftest must force 8 CPU devices'
    mesh = mesh_lib.make_mesh(dp=2, fsdp=2, tp=2)
    opt = trainer.make_optimizer(warmup_steps=1, total_steps=10)
    state = trainer.init_train_state(tiny, jax.random.PRNGKey(0), opt)
    state = trainer.shard_train_state(state, mesh)

    # Params actually sharded: wq [L, d, heads*hd] split over fsdp x tp.
    wq = state.params['layers']['wq']
    assert len(wq.sharding.device_set) == 8
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[1] == wq.shape[1] // 2   # fsdp
    assert shard_shape[2] == wq.shape[2] // 2   # tp

    step = trainer.make_train_step(tiny, opt, mesh=mesh)
    batch = trainer.synthetic_batch(tiny, 8, 32, jax.random.PRNGKey(1))
    batch = {k: jax.device_put(v, sharding_lib.batch_sharding(mesh))
             for k, v in batch.items()}
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics['loss']))
    state, metrics2 = step(state, batch)
    assert float(metrics2['loss']) < float(metrics['loss']) + 1.0
    assert int(metrics2['step']) == 2


@pytest.mark.parametrize('impl,seq', [('auto', 16), ('flash', 128)])
def test_sharded_matches_unsharded(impl, seq):
    """Same seed, same batch: mesh execution must match single-device
    numerics (within bf16-free f32 tolerance). With the flash kernels
    the mesh step runs them per shard inside a shard_map (XLA cannot
    partition a Mosaic kernel — on a real four-chip host the sharded
    step did not lower at all without it), forward and backward."""
    tiny = llama.LlamaConfig.tiny(attention_impl=impl)
    opt = trainer.make_optimizer(warmup_steps=1, total_steps=10)
    with jax.default_matmul_precision('float32'):
        s_single = trainer.init_train_state(tiny, jax.random.PRNGKey(0), opt)
        step1 = trainer.make_train_step(tiny, opt)
        batch = trainer.synthetic_batch(tiny, 8, seq, jax.random.PRNGKey(1))
        _, m_single = step1(s_single, batch)

        mesh = mesh_lib.make_mesh(dp=2, fsdp=2, tp=2)
        s_mesh = trainer.init_train_state(tiny, jax.random.PRNGKey(0), opt)
        s_mesh = trainer.shard_train_state(s_mesh, mesh)
        step2 = trainer.make_train_step(tiny, opt, mesh=mesh)
        sharded_batch = {
            k: jax.device_put(v, sharding_lib.batch_sharding(mesh))
            for k, v in batch.items()}
        _, m_mesh = step2(s_mesh, sharded_batch)
    assert float(m_single['loss']) == pytest.approx(
        float(m_mesh['loss']), rel=1e-4)
    assert float(m_single['grad_norm']) == pytest.approx(
        float(m_mesh['grad_norm']), rel=1e-4)


def test_attention_spec_keeps_gqa_groups_whole():
    mesh = mesh_lib.make_mesh(dp=2, fsdp=2, tp=2)
    P = jax.sharding.PartitionSpec
    assert sharding_lib.attention_spec(mesh, 16, 8) == P(
        ('dp', 'fsdp'), 'tp', None, None)
    # tp does not divide the KV heads: every tp shard takes all heads.
    assert sharding_lib.attention_spec(mesh, 16, 1) == P(
        ('dp', 'fsdp'), None, None, None)


def test_mesh_validation():
    with pytest.raises(ValueError):
        mesh_lib.make_mesh(dp=3, fsdp=1, tp=1)  # 3 != 8
    m = mesh_lib.auto_mesh(tp=2)
    assert m.shape == {'dp': 1, 'fsdp': 4, 'tp': 2}


def test_mesh_from_slice():
    from skypilot_tpu import topology
    s = topology.parse_tpu('v5e-16')
    # 16 chips but only 8 local devices — build over fake devices list.
    with pytest.raises(ValueError):
        mesh_lib.mesh_from_slice(s, tp=3)
