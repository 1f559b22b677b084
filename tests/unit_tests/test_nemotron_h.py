"""The Nemotron-H hybrid through the serving path, tiny and on the
CPU: the step programs against the plain float32 reference on seeded
weights (logits, not tokens), the two traps of recurrent state, the
expert layer's share, the chunked scan, and the engine's refusals."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import nemotron_h as fam
from benchmark.reference import nemotron_h as ref
from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import state_cache
from skypilot_tpu.models import falcon_h1
from skypilot_tpu.models import nemotron_h
from skypilot_tpu.ops import mamba2
from skypilot_tpu.ops import moe_dropless

CFG = dict(
    hidden_size=64, hybrid_override_pattern='MEM*EME', num_hidden_layers=7,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=16, expand=2, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, n_routed_experts=8,
    n_routed_experts_published=8, num_experts_per_tok=2,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    n_group=1, topk_group=1, vocab_size=512, layer_norm_epsilon=1e-5,
    engine={'max_seq_len': 256}, precision={'activations': 'float32'})
SEED = 2**31 + 27
PAGE = 16


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda v: v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v,
        tree)


@pytest.fixture(scope='module')
def model():
    config, params = fam.program(CFG, SEED)
    return config, _f32(params), fam.reference_weights(CFG, SEED)


@pytest.fixture(scope='module')
def steps(model):
    config = model[0]
    return (jax.jit(lambda p, c, s, row, t, o, n:
                    model_lib.hybrid_prefill_chunk(config, p, c, s, row, t,
                                                   o, n)),
            jax.jit(lambda p, c, tb, t, a:
                    model_lib.hybrid_decode_step(config, p, c, tb, t, a)))


def _cache(config, slots=2):
    return state_cache.init_hybrid_cache(config.cache_spec(), slots, 40,
                                         PAGE, jnp.float32)


def _tables(slots=2, pages=8):
    # slot s owns pages 1 + s*pages ..: page 0 is the sink
    return jnp.asarray(1 + np.arange(slots * pages).reshape(slots, pages),
                       jnp.int32)


def _prefill(steps, params, cache, slot, tables, tokens, chunk=32,
             upto=None):
    """Chunks of ``chunk`` (the tail padded to a 16 bucket), as the
    engine would dispatch them; ``upto`` stops after that many tokens
    (a slot left mid-prefill). Returns (cache, last logits)."""
    off, logits = 0, None
    n = len(tokens) if upto is None else upto
    while off < n:
        tl = min(chunk, n - off)
        bucket = -(-tl // PAGE) * PAGE
        pad = np.zeros(bucket, np.int32)
        pad[:tl] = tokens[off:off + tl]
        cache, logits = steps[0](params, cache, jnp.int32(slot),
                                 tables[slot], jnp.asarray(pad),
                                 jnp.int32(off), jnp.int32(tl))
        off += tl
    return cache, logits


def _close(got, want, what):
    err = float(jnp.abs(got - want).max())
    assert err < 2e-5 * float(jnp.abs(want).max()) + 1e-5, (what, err)


def test_prefill_in_chunks_then_decode_equals_the_reference(model, steps):
    """(a) a prompt whose length is no multiple of the chunk bucket:
    the padded tail must advance neither the SSM state nor the
    convolution's window, or every later logit is off."""
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
    # one live slot, top-2 of 8 experts, all 8 held, summed over three
    # E blocks (the fullest expert of each holds the one row)
    assert [int(v) for v in stats] == [6, 6, 3, 1]


def test_a_slot_mid_prefill_is_not_advanced_by_decode_steps(model, steps):
    """(b) slot 1 is half-way through its prompt while slot 0 decodes:
    the decode steps run over every slot, and a recurrent state, unlike
    a K/V row, is never overwritten."""
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
    # the rest of slot 1's prompt, from offset 32 on
    off = 32
    while off < 70:
        tl = min(32, 70 - off)
        pad = np.zeros(-(-tl // PAGE) * PAGE, np.int32)
        pad[:tl] = second[off:off + tl]
        cache, logits = steps[0](params, cache, jnp.int32(1), tables[1],
                                 jnp.asarray(pad), jnp.int32(off),
                                 jnp.int32(tl))
        off += tl
    _close(logits, want1[69], 'slot 1 after the interleaved decodes')


def test_a_prefill_from_offset_0_resets_the_slots_state(model, steps):
    """(c) preempt-and-resume at the level of the programs: the slot's
    old state is whatever the evicted request left; a prefill that
    starts at offset 0 must not see it."""
    config, params, W = model
    rng = np.random.default_rng(2)
    old, new = rng.integers(0, 512, (40,)), rng.integers(0, 512, (33,))
    want = ref.forward(CFG, W, jnp.asarray(new))
    tables = _tables()
    cache, _ = _prefill(steps, params, _cache(config), 0, tables, old)
    cache = state_cache.free_slot(cache, jnp.int32(0))
    cache, logits = _prefill(steps, params, cache, 0, tables, new)
    _close(logits, want[32], 'after reuse of the slot')


def _engine(config, params, **kw):
    base = dict(n_slots=4, max_seq_len=128, paged=True, page_size=16,
                prefill_chunk=32, prefill_buckets=(16, 32), n_pages=40)
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
    assert m['ssm_slot_steps'] >= 24 and m['moe_local_assignments'] > 0
    assert m['moe_expert_load_max'] <= m['moe_local_assignments']
    assert set(tight.compiled_counts()) == {'prefill', 'decode', 'free'}


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of the two shares (experts
    0-3 and 4-7 of 8) plus the shared expert ONCE equal the uncut
    layer, in the program and against the reference."""
    key = jax.random.PRNGKey(5)
    whole = nemotron_h.NemotronHConfig.tiny(dtype='float32')
    layer = nemotron_h.init_layer(whole, 'E', key)
    x = jax.random.normal(jax.random.PRNGKey(6), (24, whole.dim))
    valid = jnp.ones((24,), bool)
    full, stats = nemotron_h.moe_mixer(whole, layer, x, valid)
    assert int(stats[0]) == 24 * whole.experts_per_token
    parts = []
    for lo in (0, 4):
        share = dataclasses.replace(whole, experts_held=4, expert_offset=lo)
        cut = dict(layer, w_up=layer['w_up'][lo:lo + 4],
                   w_down=layer['w_down'][lo:lo + 4])
        out, st = nemotron_h.moe_mixer(share, cut, x, valid)
        parts.append((out, int(st[0])))
    h = ref.rms_norm(x, layer['norm'], 1e-5)
    shared = ref.shared_part(layer, h)
    total = parts[0][0] + parts[1][0] - shared
    assert parts[0][1] + parts[1][1] == int(stats[0])
    np.testing.assert_allclose(total, full, atol=2e-5)
    rcfg = dict(num_experts_per_tok=2, routed_scaling_factor=2.5)
    np.testing.assert_allclose(full, ref.moe_mixer(rcfg, layer, h),
                               atol=2e-5)


def test_rows_that_are_padding_reach_no_expert():
    cfg = nemotron_h.NemotronHConfig.tiny(dtype='float32')
    layer = nemotron_h.init_layer(cfg, 'E', jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (16, cfg.dim))
    valid = jnp.arange(16) < 5
    out, stats = nemotron_h.moe_mixer(cfg, layer, x, valid)
    alone, _ = nemotron_h.moe_mixer(cfg, layer, x[:5], jnp.ones((5,), bool))
    assert int(stats[0]) == 10
    np.testing.assert_allclose(out[:5], alone, atol=1e-5)


def test_grouped_matmul_kernel_agrees_with_ragged_dot():
    """The TPU path's Pallas kernel, interpreted, on groups that are
    empty, straddle a tile and leave rows past the last group."""
    rng = np.random.default_rng(0)
    sizes = jnp.asarray([0, 70, 3, 0, 100, 27], jnp.int32)   # 200 of 256
    lhs = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    for transpose in (True, False):
        rhs = jnp.asarray(rng.normal(size=(6, 128, 128)), jnp.float32)
        want = moe_dropless.grouped_matmul(
            lhs, rhs, sizes, transpose_rhs=transpose, impl='ragged_dot')
        got = moe_dropless.grouped_matmul(
            lhs, rhs, sizes, transpose_rhs=transpose, impl='pallas',
            interpret=True)
        np.testing.assert_allclose(got[:200], want[:200], rtol=1e-4,
                                   atol=1e-3)


def test_chunked_scan_equals_the_recurrence_from_a_carried_state():
    rng = np.random.default_rng(0)
    T, H, P, G, N = 48, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (T, H)), jnp.float32)
    dt = dt.at[40:].set(0.0)            # a padded tail: must hold S
    a = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(T, G, N)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(T, G, N)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(H, P, N)), jnp.float32)
    y, s = mamba2.ssd_chunk_scan(x, dt, a, b, c, d, s0, chunk=16)
    st, at40 = s0, None
    for t in range(T):
        yt, st2 = mamba2.ssd_decode_step(x[t][None], dt[t][None], a,
                                         b[t][None], c[t][None], d,
                                         st[None])
        st = st2[0]
        np.testing.assert_allclose(y[t], yt[0], atol=2e-5)
        if t == 39:
            at40 = st
    np.testing.assert_allclose(s, st, atol=2e-6)
    assert np.array_equal(np.asarray(st), np.asarray(at40))


# Both families whose slots hold recurrent state (Falcon-H1 keeps it in
# EVERY block, beside that block's K/V pages) refuse the same switches.
FAMILIES = [nemotron_h.NemotronHConfig, falcon_h1.FalconH1Config]


@pytest.mark.parametrize('family', FAMILIES, ids=lambda f: f.__name__)
@pytest.mark.parametrize('switch, kw', [
    ('prefix_cache=True', dict(prefix_cache=True)),
    ('spec_k=2', dict(spec_k=2)),
    ('fused_prefill=True', dict(fused_prefill=True)),
    ("kv_dtype='int8'", dict(kv_dtype='int8')),
    ('tp=2', dict(tp=2)),
    ('paged=False', dict(paged=False)),
    ('quantize=True', dict(quantize=True)),
])
def test_the_engine_refuses_what_recurrent_state_breaks(switch, kw, family):
    cfg = family.tiny()
    params = cfg.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError,
                       match=f'{family.__name__} cannot be served') as err:
        _engine(cfg, params, **kw)
    assert switch in str(err.value)


@pytest.mark.parametrize('family', FAMILIES, ids=lambda f: f.__name__)
def test_the_engine_refuses_kv_export_and_import(family):
    cfg = family.tiny()
    eng = _engine(cfg, cfg.init_params(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match='kv_wire'):
        eng.request_kv_export([1, 2, 3])
    with pytest.raises(ValueError, match='kv_wire'):
        eng.request_kv_import(b'')
    assert not eng.kv_index_armed()
