"""Training entrypoints + ResNet: the runnables behind the baseline
configs, smoke-run at tiny scale on the CPU mesh."""
import pytest

pytestmark = pytest.mark.jax

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import skypilot_tpu as sky
from skypilot_tpu.models import resnet
from skypilot_tpu.train import run as train_run
from skypilot_tpu.train import run_vision


def test_resnet_forward_and_train_step():
    cfg = resnet.ResNetConfig.tiny()
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    labels = jnp.array([1, 3])
    logits = resnet.forward(cfg, params, images)
    assert logits.shape == (2, cfg.num_classes)
    assert np.isfinite(np.asarray(logits)).all()

    loss, grads = jax.value_and_grad(
        lambda p: resnet.loss_fn(cfg, p, images, labels))(params)
    assert np.isfinite(float(loss))
    gnorm = jax.tree_util.tree_reduce(
        lambda a, g: a + float(jnp.sum(jnp.abs(g))), grads, 0.0)
    assert gnorm > 0


@pytest.fixture
def _restore_compile_cache_config():
    """train.run attaches the persistent compile cache process-wide;
    the rest of the session must not inherit it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update('jax_compilation_cache_dir', before)


def test_train_run_entry_with_checkpoint_resume(
        tmp_path, caplog, _restore_compile_cache_config):
    ckpt = str(tmp_path / 'ckpts')
    args = ['--model', 'llama-tiny', '--steps', '4', '--batch', '4',
            '--seq', '16', '--fsdp', '4', '--tp', '2',
            '--checkpoint-dir', ckpt, '--checkpoint-every', '2',
            '--log-every', '2']
    with caplog.at_level('INFO'):
        train_run.main(args)
    # The boot line says what the run computes on and caches in.
    assert ('platform=cpu device_kind=cpu devices=8 mesh dp=1 fsdp=4 tp=2'
            in caplog.text)
    assert 'attention=dense compile_cache=' in caplog.text
    saved = glob.glob(os.path.join(ckpt, '*'))
    assert saved, 'no checkpoints written'
    # Resume: start_step comes from the checkpoint; finishes instantly.
    train_run.main(args)


def test_run_vision_entry():
    run_vision.main(['--model', 'tiny', '--steps', '3', '--batch', '8',
                     '--image-size', '32', '--log-every', '1'])


def test_baseline_example_yamls_parse():
    here = os.path.join(os.path.dirname(__file__), '..', '..', 'examples')
    for name in ('minimal.yaml', 'resnet_ddp.yaml', 'serve_llm.yaml',
                 'llama_finetune_fsdp.yaml', 'pretrain_70b_spot.yaml'):
        task = sky.Task.from_yaml(os.path.join(here, name))
        assert task.run
        assert task.resources.accelerators
        if name == 'pretrain_70b_spot.yaml':
            assert task.resources.use_spot
        if name == 'serve_llm.yaml':
            assert task.is_service


def test_remat_policies_numerically_identical():
    """Remat must never change values — only the recompute schedule."""
    import jax
    import numpy as np
    from skypilot_tpu.models import llama
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 100)
    tgts = jax.numpy.roll(toks, -1, axis=1)
    grads = {}
    for pol in ('full', 'save_attn', 'dots'):
        cfg = llama.LlamaConfig.tiny(remat_policy=pol)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        g = jax.grad(lambda p: llama.loss_fn(cfg, p, toks, tgts))(params)
        grads[pol] = np.asarray(g['embed'])
    np.testing.assert_allclose(grads['full'], grads['save_attn'],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(grads['full'], grads['dots'],
                               rtol=1e-5, atol=1e-6)
