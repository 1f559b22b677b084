"""The dense block's two WHOLE paged step programs at the Mistral cell's
shapes, compiled for a described (not attached) ``v5e:2x2``: what the
chip's compiler would do to a layer's weights, a test sees here, at no
chip time (PERF.md section 7(7) asked for the dense whole steps, where
``tests/benchmark/test_tpu_compile.py`` compiles kernels alone; that
file is the benchmark's, so these cases live here). Nothing runs, so
nothing here is a time or a result.

Compiled at the configuration's widths, slots and pages with the
attention kernels forced (``jax.default_backend`` answers 'tpu' while
the program is traced: under ``JAX_PLATFORMS=cpu`` the attention ops
would otherwise take their ``jax.numpy`` path, and the chunk program
then asks 30 GB) and with 4 of the 32 layers: the layer loop's body,
which is what these tests read, is the same at any depth, and 4 layers
compile in seconds.

The topology is described inside a module-scoped fixture, never while
a module is imported, and the tests are skipped where it cannot be
described (``tests/benchmark/test_tpu_compile.py`` says why).
"""
import functools
import math
import os
import re

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
CHUNK = ENG['prefill_chunk']
DENSE_LAYERS = 4


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
    """As ``tests/benchmark/test_tpu_compile.py``'s: such a compile
    cannot be read back from the persistent cache; and the chip's own
    matmul precision."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision('default'):
        yield
    jax.config.update('jax_enable_compilation_cache', before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return 'tpu_custom_call' in compiled.as_text()


def _dense_shapes(one_chip, quantized):
    """(config, params, pool) as shapes on the described chip."""
    from skypilot_tpu.infer import paged_cache
    from skypilot_tpu.models import llama
    from skypilot_tpu.ops import quant

    config = llama.LlamaConfig(
        vocab_size=CFG['vocab_size'], dim=CFG['hidden_size'],
        n_layers=DENSE_LAYERS, n_heads=HQ, n_kv_heads=HKV,
        ffn_dim=CFG['intermediate_size'],
        max_seq_len=CFG['max_position_embeddings'],
        rope_theta=CFG['rope_theta'], norm_eps=CFG['rms_norm_eps'],
        dtype='bfloat16')

    def params():
        tree = llama.init_params(config, jax.random.PRNGKey(0))
        return quant.quantize_params(tree) if quantized else tree

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda v: _shape(v.shape, v.dtype, one_chip), tree)
    pool = on_chip(jax.eval_shape(lambda: paged_cache.init_paged_cache(
        DENSE_LAYERS, SLOTS, N_PAGES, PAGE, HKV, HD, jnp.bfloat16)))
    return config, on_chip(jax.eval_shape(params)), pool


def _compile_dense_step(program, one_chip, quantized):
    from skypilot_tpu.infer import model
    config, params, pool = _dense_shapes(one_chip, quantized)

    def i32(*shape):
        return _shape(shape, jnp.int32, one_chip)
    if program == 'decode':
        fn, args = model.paged_decode_step, (
            i32(SLOTS, MAXP), i32(SLOTS),
            _shape((SLOTS,), jnp.bool_, one_chip))
    else:
        fn, args = model.paged_prefill_chunk, (
            i32(), i32(MAXP), i32(CHUNK), i32(), i32())
    return jax.jit(functools.partial(fn, config), donate_argnums=(1,)
                   ).lower(params, pool, *args).compile()


def _loop_body(text):
    """The instructions of the layer loop's body, one line each."""
    (body,) = set(re.findall(r' while\(.*?body=%?([\w.\-]+)', text))
    lines, inside = [], False
    for line in text.splitlines():
        if re.match(rf'%?{re.escape(body)} \(', line):
            inside = True
        elif inside and line.startswith('}'):
            break
        elif inside:
            lines.append(line)
    assert lines, body
    return lines


def _relaid_weights(compiled, quantized):
    """Instructions of the layer loop's body that YIELD an array of a
    layer weight's type and size, other than a read in place: a copy, a
    transpose, a slice into fast memory (``S(1)``)."""
    sizes = {CFG['hidden_size'] * n for n in (
        HQ * HD, HKV * HD, CFG['intermediate_size'])}
    dtype = 's8' if quantized else 'bf16'
    found = []
    for line in _loop_body(compiled.as_text()):
        m = re.match(r'\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]+)\]\S* '
                     r'([\w\-]+)\(', line)
        if not m or m.group(1) != dtype:
            continue
        size = math.prod(int(dim) for dim in m.group(2).split(','))
        if size in sizes and m.group(3) not in (
                'dynamic-slice', 'bitcast', 'get-tuple-element'):
            found.append(line.strip()[:120])
    return found


def _qkv_as_it_was(config, h, layer, cos, sin, positions):
    """The four lines every dense-block layer function held before PR
    32, kept here so that the test shows what it guards: with the
    consumer free to fuse into the dot's output, the compiler re-lays
    the weight."""
    from skypilot_tpu.ops import quant, rope
    B, T, _ = h.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    q = quant.qdot(h, layer['wq']).reshape(B, T, hq, hd)
    k = quant.qdot(h, layer['wk']).reshape(B, T, hkv, hd)
    v = quant.qdot(h, layer['wv']).reshape(B, T, hkv, hd)
    q = rope.apply_rope(q, cos, sin, positions)
    k = rope.apply_rope(k, cos, sin, positions)
    return q, k, v


@pytest.mark.parametrize('quantized', [True, False], ids=['int8', 'plain'])
@pytest.mark.parametrize('program', ['decode', 'chunk'])
@pytest.mark.parametrize('formulation', ['helper', 'as_it_was'])
def test_dense_step_reads_its_projection_weights_in_place(
        one_chip, no_cache, monkeypatch, formulation, program, quantized):
    """``paged_decode_step`` and a 256-token ``paged_prefill_chunk`` at
    the Mistral cell's shapes, kernels forced AND 4 of the 32 layers
    (the module docstring says why): no instruction of the layer loop's body
    yields a weight-sized array of the weights' type but a read in
    place (PR 32); and with the old formulation in the helper's place
    the same check finds the re-laid weights, so it can fail."""
    from skypilot_tpu.infer import model
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    if formulation == 'as_it_was':
        monkeypatch.setattr(model, '_qkv', _qkv_as_it_was)
    compiled = _compile_dense_step(program, one_chip, quantized)
    assert _has_kernel(compiled)
    relaid = _relaid_weights(compiled, quantized)
    if formulation == 'helper':
        assert relaid == []
        # And the pool rides in place on the chip's compiler too
        # (test_pool_in_place.py reads XLA's CPU backend): both pools
        # aliased to their outputs, temporaries under ONE layer's slab.
        slab = HKV * N_PAGES * PAGE * HD * 2
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= 2 * DENSE_LAYERS * slab
        assert m.temp_size_in_bytes < slab, m.temp_size_in_bytes
    else:
        # wq and wk in a decode step, wv too in a chunk: each sliced
        # into fast memory and then copied transposed.
        assert len(relaid) == (4 if program == 'decode' else 6), relaid
        assert any(' copy(' in line for line in relaid)
