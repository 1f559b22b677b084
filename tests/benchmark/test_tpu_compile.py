"""The main path's kernels, at this model's real shapes, compiled for a
described (not attached) ``v5e:2x2``: what the chip's compiler would
refuse, a test refuses here, at no chip time. Nothing runs, so nothing
here is a time or a result.

The topology is described inside a module-scoped fixture, never while a
module is imported (only one process may load the TPU's library, and
every worker imports every test file), and the tests are skipped where
it cannot be described. All such tests stay in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest

CFG = manifest.cell(manifest.load(), 'mistral7b-serve.chat')['config']
HQ, HKV, HD = (CFG['num_attention_heads'], CFG['num_key_value_heads'],
               CFG['head_dim'])
ENG = CFG['engine']
PAGE, SLOTS, N_PAGES = ENG['page_size'], ENG['n_slots'], ENG['n_pages']
MAXP = ENG['max_seq_len'] // PAGE


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module', autouse=False)
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it. And
    compile at the chip's own default matmul precision."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    # Another test file sets 'highest' for the whole process as it is
    # imported; the chip runs the kernels at the default, and Mosaic
    # refuses the library kernel's f32 x bf16 product at fp32.
    with jax.default_matmul_precision('default'):
        yield
    jax.config.update('jax_enable_compilation_cache', before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pages(one_chip):
    kv = _shape((HKV, N_PAGES, PAGE, HD), jnp.bfloat16, one_chip)
    return kv, kv


def _has_kernel(compiled) -> bool:
    return 'tpu_custom_call' in compiled.as_text()


def test_paged_decode_kernel_compiles_at_the_cells_shapes(one_chip, no_cache):
    from skypilot_tpu.ops import paged_attention as pa
    k, v = _pages(one_chip)
    q = _shape((SLOTS, HKV, HQ // HKV, HD), jnp.bfloat16, one_chip)
    tables = _shape((SLOTS, MAXP), jnp.int32, one_chip)
    lengths = _shape((SLOTS,), jnp.int32, one_chip)
    for impl in ('jax', 'native'):   # 'auto' picks 'jax' on a real TPU
        fn = jax.jit(lambda q, k, v, t, n, impl=impl:
                     pa.paged_decode_attention(q, k, v, t, n, impl=impl,
                                               interpret=False))
        assert _has_kernel(fn.lower(q, k, v, tables, lengths).compile())


def test_paged_prefill_kernel_compiles_at_the_cells_shapes(one_chip,
                                                           no_cache):
    from skypilot_tpu.ops import paged_attention as pa
    k, v = _pages(one_chip)
    row = _shape((MAXP,), jnp.int32, one_chip)
    scalar = _shape((), jnp.int32, one_chip)
    for chunk in (64, 128, ENG['prefill_chunk']):
        q = _shape((chunk, HKV, HQ // HKV, HD), jnp.bfloat16, one_chip)
        fn = jax.jit(lambda q, k, v, t, off, n: pa.paged_prefill_attention(
            q, k, v, t, off, n, interpret=False))
        assert _has_kernel(fn.lower(q, k, v, row, scalar, scalar).compile())


def test_flash_forward_and_backward_compile_at_the_models_heads(one_chip,
                                                                no_cache):
    from skypilot_tpu.ops import attention as att
    seq = 4096
    q = _shape((1, HQ, seq, HD), jnp.bfloat16, one_chip)
    kv = _shape((1, HKV, seq, HD), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = att.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    fwd = jax.jit(lambda q, k, v: att.flash_attention(
        q, k, v, causal=True, interpret=False))
    assert _has_kernel(fwd.lower(q, kv, kv).compile())
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    assert _has_kernel(bwd.lower(q, kv, kv).compile())
