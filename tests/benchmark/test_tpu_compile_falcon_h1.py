"""The Falcon-H1 family's two WHOLE step programs at the published
widths and the cell's engine sizes, compiled for a described (not
attached) ``v5e:2x2`` with the Pallas kernels forced: what the chip's
compiler would refuse, or would do to the caches and the weights, a
test sees here at no chip time. Nothing runs, so nothing here is a time
or a result.

Two blocks stand for the cell's nine: the body repeats. What this
guards beside "it compiles and fits":

- both paged kernels lower at a GROUP OF 5 query heads a KV head (5
  rows are not a multiple of a sublane tile; only 4 and 16 had ever
  been lowered for the chip before PR 33);
- the 256-wide state's update lowers as plain ``jax.numpy`` and rides
  the program IN PLACE: no operation copies a state array, the page
  pool or a weight matrix.

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
from benchmark.families import falcon_h1 as family

CFG = manifest.cell(manifest.load(),
                    'falcon-h1-serve.reasoning-steady')['config']
ENG = CFG['engine']
PAGE, SLOTS = ENG['page_size'], ENG['n_slots']
MAXP = ENG['max_seq_len'] // PAGE
BLOCKS = 2
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


def shapes(one_chip, blocks=BLOCKS):
    """(config, steps, params, cache) as shapes on the described chip,
    built as the engine builds them from the cell's configuration."""
    from skypilot_tpu.infer import model as model_lib
    from skypilot_tpu.models import falcon_h1

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=one_chip), tree)
    config = falcon_h1.FalconH1Config.h1_34b_pp8(
        n_layers=blocks, max_seq_len=ENG['max_seq_len'])
    assert (config.dim, config.vocab_size) == (CFG['hidden_size'],
                                               CFG['vocab_size'])
    steps = model_lib.paged_steps(config)
    params = on_chip(jax.eval_shape(
        lambda: config.init_params(jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: steps.init_cache(
        config.cache_spec(), SLOTS, ENG['n_pages'], PAGE, jnp.bfloat16)))
    return config, steps, params, cache


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def compile_for_chip(fn, monkeypatch, *args):
    # ``ops/paged_attention`` interprets its kernels unless the backend
    # is a TPU; here jax sees the CPU, and the chip's lowering is wanted.
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()


def big_copies(compiled, params, cache):
    """Operations that copy or relay an SSM state array (268 MB a
    block), a page pool or a weight matrix whole. The convolution's
    window (``[64, 3, 5120]`` bfloat16, 2 MB a block) is not held to
    this: the decode step shifts it by a row, and the compiler relays
    it for that, microseconds at the chip's bandwidth."""
    text = compiled.as_text()
    found = []
    arrays = [*cache.ssm, cache.kv.k_pages, cache.kv.v_pages,
              *(v for v in jax.tree_util.tree_leaves(params) if v.ndim >= 2)]
    for shape, dtype in {(a.shape, a.dtype) for a in arrays}:
        kind = {'float32': 'f32', 'bfloat16': 'bf16'}[jnp.dtype(dtype).name]
        dims = ','.join(map(str, shape))
        found += re.findall(
            rf'= {kind}\[{dims}\]\{{[^}}]*\}} (?:copy|transpose)\(', text)
    return found


def fits(compiled, cache):
    m = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in
               (*cache.ssm, *cache.conv, cache.kv.k_pages, cache.kv.v_pages))
    # the donated caches are the outputs: counted once
    assert m.alias_size_in_bytes >= held
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM, total
    return m


def test_the_servers_preset_is_the_cells_configuration():
    """``infer.server --model falcon-h1-34b-pp8`` and the benchmark's
    configuration file name the same model: every field the family
    builds its configuration from, but the engine's length."""
    import dataclasses
    from skypilot_tpu.models import falcon_h1
    built = family.config_of(CFG)
    preset = falcon_h1.FalconH1Config.h1_34b_pp8(
        max_seq_len=ENG['max_seq_len'])
    assert dataclasses.asdict(built) == dataclasses.asdict(preset)
    assert (preset.in_proj, preset.conv_dim, preset.d_inner) == (
        9248, 5120, CFG['mamba_d_ssm'])


def test_the_prefill_chunk_program_compiles_in_place(one_chip, no_cache,
                                                     monkeypatch):
    config, steps, params, cache = shapes(one_chip)
    compiled = compile_for_chip(
        functools.partial(steps.prefill_chunk, config), monkeypatch,
        params, cache, _i32(one_chip), _i32(one_chip, MAXP),
        _i32(one_chip, ENG['prefill_chunk']), _i32(one_chip),
        _i32(one_chip))
    assert 'tpu_custom_call' in compiled.as_text()   # the prefill kernel
    assert big_copies(compiled, params, cache) == []
    m = fits(compiled, cache)
    assert m.temp_size_in_bytes < 2**30


def test_the_decode_program_compiles_in_place(one_chip, no_cache,
                                              monkeypatch):
    config, steps, params, cache = shapes(one_chip)
    compiled = compile_for_chip(
        functools.partial(steps.decode, config), monkeypatch,
        params, cache, _i32(one_chip, SLOTS, MAXP), _i32(one_chip, SLOTS),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip))
    assert 'tpu_custom_call' in compiled.as_text()   # the decode kernel
    assert big_copies(compiled, params, cache) == []
    m = fits(compiled, cache)
    assert m.temp_size_in_bytes < 2**30
