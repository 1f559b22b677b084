"""The dots3 family's two WHOLE step programs at the cell's shapes,
compiled for a described (not attached) ``v5e:2x2``: what the chip's
compiler would refuse, or would do to the pools, a test sees here at no
chip time (PERF.md section 7(7) asked for a whole step, where
``test_tpu_compile.py`` compiles kernels alone). Nothing runs, so
nothing here is a time or a result.

What this guards beside "it compiles and fits": the pools ride the
programs IN PLACE. With pool rows of the latents' own widths (576,
1,088: not whole 128-lane tiles) the compiler chose a layout of its own
for each pool and relaid all of it into and out of every step (1.4 GB
of temporaries, found by this compile before any chip run); with rows
padded to whole tiles (``latent_cache.lanes``) no operation copies a
pool.

The topology is described inside a module-scoped fixture, never while
a module is imported, and the tests are skipped where it cannot be
described (``test_tpu_compile.py`` says why).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest

CFG = manifest.cell(manifest.load(), 'dots3-serve.longctx-mixed')['config']
ENG = CFG['engine']
PAGE, SLOTS = ENG['page_size'], ENG['n_slots']
MAXP = ENG['max_seq_len'] // PAGE
HBM = 16 * 2**30


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def no_cache():
    """As ``test_tpu_compile.py``'s: such a compile cannot be read back
    from the persistent cache; and the chip's own matmul precision."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision('default'):
        yield
    jax.config.update('jax_enable_compilation_cache', before)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def shapes(one_chip):
    """(config, params, cache) as shapes on the described chip, built
    as the engine builds them from the cell's configuration."""
    from skypilot_tpu.infer import latent_cache, paged_cache
    from skypilot_tpu.models import dots3

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=one_chip), tree)
    config = dots3.Dots3Config.note_prev_ep8()
    params = on_chip(jax.eval_shape(
        lambda: config.init_params(jax.random.PRNGKey(0))))
    window = paged_cache.WindowAllocator(PAGE, SLOTS, MAXP, config.window,
                                         ENG['prefill_chunk'])
    cache = on_chip(jax.eval_shape(
        lambda: latent_cache.init_latent_cache(
            config.cache_spec(), SLOTS, ENG['n_pages'], PAGE, jnp.bfloat16,
            window_pages=window.n_pages)))
    return config, params, cache


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _compile(fn, monkeypatch, *args):
    # ``moe_dropless.grouped_matmul`` asks the backend which grouped
    # product to use; here jax sees the CPU, and the chip's is wanted.
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()


def _pool_copies(compiled, cache):
    """Operations that copy or relay a whole pool."""
    text = compiled.as_text()
    found = []
    for pool in (cache.full, cache.index, cache.window):
        dims = ','.join(map(str, pool.shape))
        found += re.findall(
            rf'= bf16\[{dims}\]\{{[^}}]*\}} (?:copy|transpose)\(', text)
    return found


def _fits(compiled, cache):
    m = compiled.memory_analysis()
    pools = sum(p.size * 2 for p in (cache.full, cache.index, cache.window))
    # the donated pools are the outputs: counted once
    assert m.alias_size_in_bytes >= pools
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM, total
    return m


def test_the_prefill_chunk_program_compiles_in_place(one_chip, no_cache,
                                                     shapes, monkeypatch):
    from skypilot_tpu.infer import latent_steps
    config, params, cache = shapes
    chunk = ENG['prefill_chunk']
    compiled = _compile(
        functools.partial(latent_steps.prefill_chunk, config), monkeypatch,
        params, cache, _i32(one_chip),
        (_i32(one_chip, MAXP), _i32(one_chip, MAXP)), _i32(one_chip, chunk),
        _i32(one_chip), _i32(one_chip))
    assert 'tpu_custom_call' in compiled.as_text()   # the grouped products
    assert _pool_copies(compiled, cache) == []
    m = _fits(compiled, cache)
    assert m.temp_size_in_bytes < 3 * 2**30


def test_the_decode_program_compiles_in_place(one_chip, no_cache, shapes,
                                              monkeypatch):
    from skypilot_tpu.infer import latent_steps
    config, params, cache = shapes
    compiled = _compile(
        functools.partial(latent_steps.decode_step, config), monkeypatch,
        params, cache,
        (_i32(one_chip, SLOTS, MAXP), _i32(one_chip, SLOTS, MAXP)),
        _i32(one_chip, SLOTS),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip))
    assert _pool_copies(compiled, cache) == []
    m = _fits(compiled, cache)
    assert m.temp_size_in_bytes < 2**30
