"""The dense block's q/k/v projections behind one helper
(``infer/model._qkv``, PR 32): the same values as the four lines every
layer function held before it, and nothing of another family reaches
it.

The helper holds the projections' results as the dot leaves them (an
``optimization_barrier``) so that the TPU compiler re-lays the
activation and not the weight; ``test_tpu_compile_dense.py``
holds what that does to the compiled program. Here: values. The old
formulation is written out below and put in the helper's place; every
step program that reaches the helper (the six layer functions through
the six single-purpose programs; the two mixed steps call the same
layer functions) must give the same logits and the same cache.
"""
import dataclasses
import functools
import inspect

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import cache as cache_lib
from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.models import llama
from skypilot_tpu.ops import quant as quant_lib
from skypilot_tpu.ops import rope as rope_lib

pytestmark = pytest.mark.jax

CFG = llama.LlamaConfig.tiny()
SLOTS, PAGE, P, MAXP, CHUNK, RUN = 3, 16, 12, 4, 32, 3
TABLES = np.array([[5, 2, 7, 0], [9, 3, 4, 6], [1, 8, 0, 0]], np.int32)
LENGTHS = np.array([37, 50, 16], np.int32)
ACTIVE = np.array([True, True, False])
PROGRAMS = ('prefill_chunk', 'decode_step', 'verify_step',
            'paged_prefill_chunk', 'paged_decode_step',
            'paged_verify_step')
# Where the two formulations may round differently (bfloat16: the old
# one may carry the dot's float32 result into rope; on XLA's CPU
# backend today they agree to the last bit too), the paged tests'
# tolerance scaled to the type; float32 programs must agree exactly.
TOLERANCE = {'float32': 0.0, 'bfloat16': 2e-2}


def _qkv_as_it_was(config, h, layer, cos, sin, positions):
    _qkv_as_it_was.traced += 1
    B, T, _ = h.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    q = quant_lib.qdot(h, layer['wq']).reshape(B, T, hq, hd)
    k = quant_lib.qdot(h, layer['wk']).reshape(B, T, hkv, hd)
    v = quant_lib.qdot(h, layer['wv']).reshape(B, T, hkv, hd)
    q = rope_lib.apply_rope(q, cos, sin, positions)
    k = rope_lib.apply_rope(k, cos, sin, positions)
    return q, k, v


_qkv_as_it_was.traced = 0


def _old_formulation(monkeypatch):
    """Puts the old formulation in the helper's place; returns a check
    that it was traced since (a cached trace would compare the helper
    with itself)."""
    before = _qkv_as_it_was.traced
    monkeypatch.setattr(model_lib, '_qkv', _qkv_as_it_was)
    return lambda: _qkv_as_it_was.traced > before


@functools.lru_cache(maxsize=None)
def _params(weights, dtype):
    config = dataclasses.replace(CFG, dtype=dtype)
    params = llama.init_params(config, jax.random.PRNGKey(3))
    if weights == 'int8':
        params = quant_lib.quantize_params(params)
    return config, params


def _filled(cache, key):
    """The cache with random rows everywhere and the test's lengths."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 8))

    def fill(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        return jax.random.normal(next(keys), leaf.shape).astype(leaf.dtype)
    cache = jax.tree_util.tree_map(fill, cache)
    return dataclasses.replace(cache, lengths=jnp.asarray(LENGTHS))


def _tokens(n, *shape):
    return jax.random.randint(jax.random.PRNGKey(n), shape, 0,
                              CFG.vocab_size, jnp.int32)


def _run(name, config, params):
    """One call of the step program ``name``; every array it returns."""
    paged = name.startswith('paged_')
    if paged:
        cache = _filled(paged_cache_lib.init_paged_cache(
            config.n_layers, SLOTS, P, PAGE, config.n_kv_heads,
            config.head_dim, dtype=config.dtype), 1)
        tables = (jnp.asarray(TABLES),)
        row = (jnp.asarray(TABLES[2]),)
    else:
        cache = _filled(cache_lib.init_cache(
            config.n_layers, SLOTS, config.max_seq_len, config.n_kv_heads,
            config.head_dim, dtype=config.dtype), 1)
        tables = row = ()
    args = {
        'prefill_chunk': (jnp.int32(2), *row, _tokens(2, CHUNK),
                          jnp.int32(16), jnp.int32(29)),
        'decode_step': (*tables, _tokens(3, SLOTS), jnp.asarray(ACTIVE)),
        'verify_step': (*tables, _tokens(4, SLOTS, RUN)),
    }[name.removeprefix('paged_')]
    # A function of its own every time: jax keeps traces by function,
    # and a second run must see the helper that is in place by then.
    program = getattr(model_lib, name)
    out = jax.jit(lambda *a: program(config, *a))(params, cache, *args)
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(out)]


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('weights', ['plain', 'int8'])
@pytest.mark.parametrize('name', PROGRAMS)
def test_step_program_matches_the_old_formulation(monkeypatch, name,
                                                  weights, dtype):
    config, params = _params(weights, dtype)
    got = _run(name, config, params)
    traced = _old_formulation(monkeypatch)
    want = _run(name, config, params)
    assert traced() and len(got) == len(want)
    tol = TOLERANCE[dtype]
    for g, w in zip(got, want):
        if tol:
            np.testing.assert_allclose(g, w, atol=tol, rtol=tol)
        else:
            np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize('quantize', [False, True], ids=['plain', 'int8'])
@pytest.mark.parametrize('paged', [False, True], ids=['dense', 'paged'])
def test_greedy_tokens_are_the_old_formulations(monkeypatch, paged,
                                                quantize):
    """A short greedy run through the engine (several prompts, one of
    them over a chunk) gives the tokens the old formulation gives."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    prompts = [[5, 17, 101, 7], [9, 8, 7, 6, 5, 4, 3],
               [(i * 7 + 3) % 250 for i in range(40)]]
    extra = dict(paged=True, page_size=16) if paged else {}

    def tokens():
        engine = engine_lib.InferenceEngine(
            CFG, params, engine_lib.EngineConfig(
                n_slots=3, max_seq_len=128, prefill_buckets=(16, 32),
                eos_id=None, prefill_chunk=32, quantize=quantize, **extra))
        return [r.output_tokens
                for r in engine.generate(prompts, max_new_tokens=8)]
    got = tokens()
    traced = _old_formulation(monkeypatch)
    assert got == tokens() and traced()


# ---------- who reaches the helper -----------------------------------------
DENSE_LAYER_FUNCTIONS = ('_chunk_layer', '_paged_chunk_layer',
                         '_decode_layer', '_paged_decode_layer',
                         '_verify_layer', '_paged_verify_layer')


def test_the_projection_text_is_held_once():
    """Each dense-block layer function calls the helper, and the
    projections of wq / wk / wv are written nowhere else in the file."""
    for name in DENSE_LAYER_FUNCTIONS:
        assert '_qkv(' in inspect.getsource(getattr(model_lib, name)), name
    source = inspect.getsource(model_lib)
    helper = inspect.getsource(model_lib._qkv)
    for weight in ("'wq'", "'wk'", "'wv'"):
        assert source.count(weight) == helper.count(weight) == 1, weight
    assert source.count('apply_rope(') == helper.count('apply_rope(') == 2


def _family_programs(family):
    """``(program, args)`` of both step programs of another family's
    tiny preset, as shapes."""
    slots, page, n_pages, maxp, chunk = 2, 16, 40, 16, 32

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    if family == 'hybrid':
        from skypilot_tpu.models import nemotron_h
        config = nemotron_h.NemotronHConfig.tiny()
        steps = model_lib.paged_steps(config)
        cache = jax.eval_shape(lambda: steps.init_cache(
            config.cache_spec(), slots, n_pages, page, jnp.float32))
        row, tables = i32(maxp), i32(slots, maxp)
    else:
        from skypilot_tpu.infer import latent_cache
        from skypilot_tpu.models import dots3
        config = dots3.Dots3Config.tiny()
        steps = model_lib.paged_steps(config)
        window = paged_cache_lib.WindowAllocator(page, slots, maxp,
                                                 config.window, chunk)
        cache = jax.eval_shape(lambda: latent_cache.init_latent_cache(
            config.cache_spec(), slots, n_pages, page, jnp.float32,
            window_pages=window.n_pages))
        row = (i32(maxp), i32(maxp))
        tables = (i32(slots, maxp), i32(slots, maxp))
    params = jax.eval_shape(
        lambda: config.init_params(jax.random.PRNGKey(0)))
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    return config, (
        (steps.prefill_chunk,
         (params, cache, i32(), row, i32(chunk), i32(), i32())),
        (steps.decode, (params, cache, tables, i32(slots), active)))


@pytest.mark.parametrize('family', ['hybrid', 'dots3'])
def test_the_other_families_do_not_reach_the_helper(monkeypatch, family):
    """Both step programs of the hybrid and of the dots3 family trace
    with the helper refusing every call (their StableHLO is the
    parent's, byte for byte: PERF.md section 6, PR 32)."""
    def refuse(*args, **kwargs):
        raise AssertionError('_qkv reached from another family')
    monkeypatch.setattr(model_lib, '_qkv', refuse)
    config, programs = _family_programs(family)
    for program, args in programs:
        jax.eval_shape(lambda *a, f=program: f(config, *a), *args)


def test_the_refusing_helper_does_stop_a_dense_program(monkeypatch):
    """The test above can fail: the dense block's program does call
    the helper."""
    def refuse(*args, **kwargs):
        raise AssertionError('reached')
    monkeypatch.setattr(model_lib, '_qkv', refuse)
    config, params = _params('plain', 'float32')
    with pytest.raises(AssertionError, match='reached'):
        _run('paged_decode_step', config, params)
