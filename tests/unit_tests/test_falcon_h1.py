"""Falcon-H1 (attention and a Mamba-2 mixer side by side in every
block) through the serving path, tiny and on the CPU: the shared
hybrid step programs against the plain float32 reference on seeded
weights (logits, not tokens), the two traps of recurrent state in a
block that ALSO pages K/V, rope at the model's theta, and the mixer
shared with the Nemotron-H family (the engine's refusals are
``test_nemotron_h.py``'s, parametrised over both families).

The tiny preset keeps what the published model forces: a group of 5
query heads a KV head, 2 SSM groups, a state (16) wider than the
mixer's head (8), the published multipliers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import falcon_h1 as fam
from benchmark.reference import falcon_h1 as ref
from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import state_cache
from skypilot_tpu.models import falcon_h1
from skypilot_tpu.models import mamba_mixer
from skypilot_tpu.models import nemotron_h
from skypilot_tpu.ops import rope as rope_lib

TINY = falcon_h1.FalconH1Config.tiny()
CFG = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=10,
    num_key_value_heads=2, head_dim=16, intermediate_size=96,
    mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=16,
    mamba_rms_norm=True, mamba_norm_before_gate=False, mamba_use_mlp=True,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    mlp_bias=False, projectors_bias=False, rope_scaling=None,
    hidden_act='silu', tie_word_embeddings=False, rope_theta=1e11,
    rms_norm_eps=1e-5, vocab_size=512, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4,
    **{k: getattr(TINY, k) for k in ref.MULTIPLIERS},
    ssm_multipliers=list(TINY.ssm_multipliers),
    mlp_multipliers=list(TINY.mlp_multipliers),
    engine={'max_seq_len': 256}, precision={'activations': 'float32'})
SEED = 2**31 + 33
PAGE = 16


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda v: v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v,
        tree)


@pytest.fixture(scope='module')
def model():
    config, params = fam.program(CFG, SEED)
    assert config == dataclasses.replace(TINY, dtype='float32')
    return config, _f32(params), fam.reference_weights(CFG, SEED)


@pytest.fixture(scope='module')
def steps(model):
    config = model[0]
    programs = model_lib.paged_steps(config)
    assert programs.stats == ('ssm_slot_steps',)
    return (jax.jit(lambda p, c, s, row, t, o, n:
                    programs.prefill_chunk(config, p, c, s, row, t, o, n)),
            jax.jit(lambda p, c, tb, t, a:
                    programs.decode(config, p, c, tb, t, a)))


def _cache(config, slots=2):
    return state_cache.init_hybrid_cache(config.cache_spec(), slots, 40,
                                         PAGE, jnp.float32)


def _tables(slots=2, pages=8):
    # slot s owns pages 1 + s*pages ..: page 0 is the sink
    return jnp.asarray(1 + np.arange(slots * pages).reshape(slots, pages),
                       jnp.int32)


def _prefill(steps, params, cache, slot, tables, tokens, chunk=32,
             upto=None, start=0):
    """Chunks of ``chunk`` (the tail padded to a 16 bucket), as the
    engine would dispatch them, from ``start`` up to ``upto`` tokens.
    Returns (cache, last logits)."""
    off, logits = start, None
    n = len(tokens) if upto is None else upto
    while off < n:
        tl = min(chunk, n - off)
        pad = np.zeros(-(-tl // PAGE) * PAGE, np.int32)
        pad[:tl] = tokens[off:off + tl]
        cache, logits = steps[0](params, cache, jnp.int32(slot),
                                 tables[slot], jnp.asarray(pad),
                                 jnp.int32(off), jnp.int32(tl))
        off += tl
    return cache, logits


def _close(got, want, what):
    # float32 throughout on both sides: what is left is the order of
    # the sums (the chunked SSD form against the step-by-step scan, the
    # paged kernels' blocks against one softmax), a few units in the
    # last place of logits that spread by tens.
    err = float(jnp.abs(got - want).max())
    assert err < 2e-5 * float(jnp.abs(want).max()) + 1e-5, (what, err)


def test_every_layer_keeps_both_pages_and_state():
    spec = TINY.cache_spec()
    assert spec.kv_layers == spec.state.layers == TINY.n_layers == 3
    assert spec.state.ssm_shape == (4, 8, 16)
    assert spec.state.conv_shape == (3, TINY.conv_dim) == (3, 96)
    assert (TINY.n_heads // TINY.n_kv_heads, TINY.n_groups) == (5, 2)
    assert TINY.ssm_state > TINY.mamba_head_dim
    full = falcon_h1.FalconH1Config()
    # d_ssm is heads x head width as given, not expand x hidden
    assert (full.d_inner, full.conv_dim, full.in_proj) == (4096, 5120, 9248)
    assert full.cache_spec().state.ssm_shape == (32, 128, 256)
    pp8 = falcon_h1.FalconH1Config.h1_34b_pp8()
    assert (pp8.n_layers * 8, pp8.vocab_size * 8) == (72, 261_120)


def test_prefill_in_chunks_then_decode_equals_the_reference(model, steps):
    """A prompt whose length is no multiple of the chunk bucket: the
    padded tail must advance neither the SSM state nor the
    convolution's window, and its K/V rows must not be attended."""
    config, params, W = model
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 512, (45 + 6,))
    want = ref.forward(CFG, W, jnp.asarray(toks))
    tables = _tables()
    cache, logits = _prefill(steps, params, _cache(config), 1, tables,
                             toks[:45])
    _close(logits, want[44], 'prefill')
    active = jnp.asarray([False, True])
    for i in range(45, 51):
        last = jnp.asarray([0, toks[i]], jnp.int32)
        out, cache, stats = steps[1](params, cache, tables, last, active)
        _close(out[1], want[i], f'decode {i}')
    assert int(cache.lengths[1]) == 51 and int(cache.lengths[0]) == 0
    assert [int(v) for v in stats] == [1]      # one live slot a step


def test_a_padded_tail_moves_no_state(model, steps):
    """The same 21 tokens as a chunk of 21 padded to 32 and as one
    padded to 48: the slot's state is the same, bit for bit."""
    config, params, _ = model
    toks = np.random.default_rng(5).integers(0, 512, (21,))
    tables, states = _tables(), []
    for width in (32, 48):
        pad = np.full(width, 7, np.int32)
        pad[:21] = toks
        cache, _ = steps[0](params, _cache(config), jnp.int32(0), tables[0],
                            jnp.asarray(pad), jnp.int32(0), jnp.int32(21))
        states.append([np.asarray(s[0]) for s in cache.ssm + cache.conv])
    for a, b in zip(*states):
        assert np.array_equal(a, b)


def test_a_slot_mid_prefill_is_not_advanced_by_decode_steps(model, steps):
    """Slot 1 is half-way through its prompt while slot 0 decodes: the
    decode steps run over every slot, and a recurrent state, unlike a
    K/V row, is never overwritten. Bit for bit."""
    config, params, W = model
    rng = np.random.default_rng(1)
    first, second = rng.integers(0, 512, (20 + 5,)), rng.integers(
        0, 512, (70,))
    want0 = ref.forward(CFG, W, jnp.asarray(first))
    want1 = ref.forward(CFG, W, jnp.asarray(second))
    tables = _tables()
    cache, _ = _prefill(steps, params, _cache(config), 0, tables, first[:20])
    cache, _ = _prefill(steps, params, cache, 1, tables, second, upto=32)
    frozen = [np.asarray(s[1]) for s in cache.ssm + cache.conv]
    active = jnp.asarray([True, False])
    for i in range(20, 25):
        last = jnp.asarray([first[i], 7], jnp.int32)
        out, cache, _ = steps[1](params, cache, tables, last, active)
        _close(out[0], want0[i], f'slot 0 decode {i}')
    for before, after in zip(frozen, cache.ssm + cache.conv):
        assert np.array_equal(before, np.asarray(after[1]))
    cache, logits = _prefill(steps, params, cache, 1, tables, second,
                             start=32)
    _close(logits, want1[69], 'slot 1 after the interleaved decodes')


def test_a_prefill_from_offset_0_resets_the_slots_state(model, steps):
    """Preempt-and-re-prefill at the level of the programs: the slot's
    old state and pages are whatever the evicted request left; a
    prefill that starts at offset 0 gives the same logits as in a fresh
    cache."""
    config, params, W = model
    rng = np.random.default_rng(2)
    old, new = rng.integers(0, 512, (40,)), rng.integers(0, 512, (33,))
    want = ref.forward(CFG, W, jnp.asarray(new))
    tables = _tables()
    cache, _ = _prefill(steps, params, _cache(config), 0, tables, old)
    cache = state_cache.free_slot(cache, jnp.int32(0))
    cache, logits = _prefill(steps, params, cache, 0, tables, new)
    _close(logits, want[32], 'after reuse of the slot')
    _, fresh = _prefill(steps, params, _cache(config), 0, tables, new)
    assert np.array_equal(np.asarray(logits), np.asarray(fresh))


def _engine(config, params, **kw):
    base = dict(n_slots=4, max_seq_len=128, paged=True, page_size=16,
                prefill_chunk=32, prefill_buckets=(16, 32), n_pages=40,
                cache_dtype='float32')
    base.update(kw)
    return engine_lib.InferenceEngine(config, params,
                                      engine_lib.EngineConfig(**base))


def test_engine_preempt_and_resume_serves_the_same_tokens(model):
    config, params, _ = model
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(0, 512, (n,))))
               for n in (37, 20, 45, 9)]
    roomy = _engine(config, params)
    want = [r.output_tokens for r in roomy.generate(prompts,
                                                    max_new_tokens=24)]
    # 9 pages of 16 (one the sink) cannot hold 4 requests of ~60 tokens
    tight = _engine(config, params, n_pages=9)
    got = [r.output_tokens for r in tight.generate(prompts,
                                                   max_new_tokens=24)]
    m = tight.metrics()
    assert m['preemptions'] > 0
    assert got == want
    assert m['state_bytes'] == tight.cache.state_bytes > 0
    assert 'state_slots' in m and m['ssm_slot_steps'] >= 24
    assert not any(k.startswith('moe_') for k in m)     # no expert layer
    assert set(tight.compiled_counts()) == {'prefill', 'decode', 'free'}


def test_the_engines_tokens_are_the_references_greedy_tokens(model):
    """Through the engine's own caches, scheduler and sampler: every
    served token is the reference's first choice at its position (the
    logits' agreement, checked above, leaves no room for another)."""
    config, params, W = model
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(0, 512, (n,)))) for n in (50, 12)]
    served = [r.output_tokens for r in _engine(config, params).generate(
        prompts, max_new_tokens=10)]
    for prompt, out in zip(prompts, served):
        logits = ref.forward(CFG, W, jnp.asarray(prompt + out[:-1]))
        first = np.asarray(jnp.argmax(logits[len(prompt) - 1:], -1))
        assert list(first) == out


# ---- what the model forces on shared code -----------------------------------

def test_rope_tables_hold_at_theta_1e11():
    """The lowest frequency is 1.5e-11 a position: the float32 table is
    finite, and equals the float64 one to float32's rounding at the
    last position the model declares."""
    hd, theta = 128, 1e11
    pos = jnp.asarray([0, 1, 2303, 262_143], jnp.int32)
    cos, sin = rope_lib.rope_at(hd, theta, pos)
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.asarray(pos, np.float64)[:, None] * inv[None]
    assert np.isfinite(np.asarray(cos)).all() and inv.min() < 2e-11
    # a float32 angle of up to 262143 radians carries an error of up to
    # 2**-7 radians: the fast pairs at the far end, nowhere else
    np.testing.assert_allclose(cos[:3], np.cos(ang[:3]), atol=2e-4)
    np.testing.assert_allclose(sin[:3], np.sin(ang[:3]), atol=2e-4)
    np.testing.assert_allclose(cos[3, 16:], np.cos(ang[3, 16:]), atol=2e-4)
    table = rope_lib.rope_frequencies(hd, 2304, theta)
    for got, want in zip((cos, sin), table):
        assert np.array_equal(np.asarray(got[:3]),
                              np.asarray(want[np.asarray(pos[:3])]))


def test_rope_pairs_channel_i_with_i_plus_half():
    """The half-split pairing, as the reference writes it out."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 3, 16))
    cos, sin = rope_lib.rope_at(16, 1e11, jnp.arange(6))
    got = rope_lib.apply_rope(x, cos, sin)
    np.testing.assert_allclose(got, ref.rope(x, 1e11), atol=1e-6)


@pytest.mark.parametrize('family', ['falcon_h1', 'nemotron_h'])
def test_one_mixer_serves_both_families(family):
    """``mamba_mixer.decode`` token by token equals ``mamba_mixer.chunk``
    over the same tokens, from a carried state, at either family's
    tiny shapes; Falcon-H1's with its five-part multiplier on W_in."""
    if family == 'falcon_h1':
        config = dataclasses.replace(TINY, dtype='float32')
        layer = falcon_h1.init_layer(config, jax.random.PRNGKey(1))
        mult = config.in_mult()
        assert mult.shape == (config.in_proj,) and len(set(
            np.asarray(mult).tolist())) == 4     # z and dt share a value
    else:
        config = nemotron_h.NemotronHConfig.tiny(dtype='float32')
        layer, mult = nemotron_h.init_layer(config, 'M',
                                            jax.random.PRNGKey(1)), None
    h = jax.random.normal(jax.random.PRNGKey(2), (32, config.dim))
    shape = config.cache_spec().state
    ssm0 = jax.random.normal(jax.random.PRNGKey(3), shape.ssm_shape)
    conv0 = jax.random.normal(jax.random.PRNGKey(4), shape.conv_shape)
    y, ssm, conv = mamba_mixer.chunk(config, layer, h, ssm0, conv0,
                                     jnp.int32(27), mult)
    s, c = ssm0[None], conv0[None]
    for t in range(27):
        yt, s, c = mamba_mixer.decode(config, layer, h[t][None], s, c,
                                      jnp.ones((1,), bool), mult)
        np.testing.assert_allclose(yt[0], y[t], atol=3e-5)
    np.testing.assert_allclose(s[0], ssm, atol=1e-5)
    np.testing.assert_allclose(c[0], conv, atol=1e-6)
    if mult is not None:
        plain, _, _ = mamba_mixer.chunk(config, layer, h, ssm0, conv0,
                                        jnp.int32(27))
        assert float(jnp.abs(plain - y).max()) > 1e-3


def test_the_multipliers_are_the_models_not_the_weights(model, steps):
    """All eleven at 1 is another model: the same weights served
    without them give other logits."""
    config, params, _ = model
    ones = dataclasses.replace(
        config, ssm_multipliers=(1.0,) * 5, mlp_multipliers=(1.0, 1.0),
        **{k: 1.0 for k in ref.MULTIPLIERS})
    toks = jnp.asarray(np.random.default_rng(6).integers(0, 512, (32,)),
                       jnp.int32)
    args = (params, _cache(config), jnp.int32(0), _tables()[0], toks,
            jnp.int32(0), jnp.int32(32))
    _, with_mup = steps[0](*args)
    _, without = jax.jit(lambda *a: model_lib.hybrid_prefill_chunk(
        ones, *a))(*args)
    assert float(jnp.abs(with_mup - without).max()) > 1.0


# ---- refusals: tests/unit_tests/test_nemotron_h.py, parametrised over
# both families whose slots hold recurrent state ------------------------------

def test_the_server_names_both_presets():
    from skypilot_tpu.infer import server
    assert server.MODELS['falcon-h1-tiny']() == TINY
    assert (server.MODELS['falcon-h1-34b-pp8']()
            == falcon_h1.FalconH1Config.h1_34b_pp8())
