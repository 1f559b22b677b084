"""The dots3-note family through the serving path, tiny and on the CPU:
the step programs against the plain float32 reference on seeded weights
(logits, not tokens) at a size where the top-k and the window both lie
under the context, the selection against ``lax.top_k``, the window
allocator, slot reuse, the expert layer's share and its gated form, and
the engine's refusals."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import dots3 as fam
from benchmark.reference import dots3 as ref
from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import latent_cache
from skypilot_tpu.infer import latent_steps
from skypilot_tpu.infer import paged_cache
from skypilot_tpu.models import dots3
from skypilot_tpu.ops import latent_attention as lat
from skypilot_tpu.ops import moe_dropless

LT = ['full_attention', 'full_attention', 'sliding_attention',
      'sliding_attention', 'sliding_attention']
CFG = dict(
    hidden_size=64, num_hidden_layers=5, layer_types=LT,
    first_k_dense_replace=1, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7,
    index_n_heads=4, index_head_dim=16, index_topk=8,
    swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=32,
    swa_kv_lora_rank=32, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
    swa_v_head_dim=16, swa_rope_theta=5e4, sliding_window_size=5,
    apply_mla_qkv_lora_rescale=True, attention_gate_type='headwise',
    swa_attention_gate_type='headwise', n_routed_experts=8,
    n_routed_experts_published=8, num_experts_per_tok=2,
    moe_intermediate_size=32, n_shared_experts=1, routed_scaling_factor=1,
    norm_topk_prob=True, scoring_func='sigmoid', topk_method='noaux_tc',
    moe_layer_freq=1, rope_scaling=None, attention_bias=False,
    hidden_act='silu', tie_word_embeddings=False, vocab_size=512,
    rms_norm_eps=1e-5, engine={'max_seq_len': 256},
    precision={'activations': 'float32'})
SEED = 2**31 + 31
PAGE, SLOTS, MAXP, N_PAGES, CHUNK = 16, 2, 16, 40, 32


@pytest.fixture(scope='module')
def model():
    config, params = fam.program(CFG, SEED)
    return config, params, fam.reference_weights(CFG, SEED)


@pytest.fixture(scope='module')
def steps(model):
    config = model[0]
    # Two key blocks and more in every context of these tests, so that
    # the scoring and attention loops take more than one trip.
    old, latent_steps._KEY_BLOCK = latent_steps._KEY_BLOCK, 128
    yield (jax.jit(lambda p, c, s, row, t, o, n:
                   latent_steps.prefill_chunk(config, p, c, s, row, t, o, n)),
           jax.jit(lambda p, c, tb, t, a:
                   latent_steps.decode_step(config, p, c, tb, t, a)))
    latent_steps._KEY_BLOCK = old


class _Host:
    """The two allocators and the cache, as the engine holds them."""

    def __init__(self, config):
        self.pages = paged_cache.PageAllocator(N_PAGES, PAGE, SLOTS, MAXP)
        self.window = paged_cache.WindowAllocator(PAGE, SLOTS, MAXP,
                                                  config.window, CHUNK)
        self.cache = latent_cache.init_latent_cache(
            config.cache_spec(), SLOTS, N_PAGES, PAGE, jnp.float32,
            window_pages=self.window.n_pages)

    def row(self, slot):
        return (jnp.asarray(self.pages.table()[slot]),
                jnp.asarray(self.window.table()[slot]))

    def tables(self):
        return (jnp.asarray(self.pages.table()),
                jnp.asarray(self.window.table()))

    def prefill(self, steps, params, slot, tokens):
        off = 0
        while off < len(tokens):
            n = min(CHUNK, len(tokens) - off)
            bucket = PAGE if n <= PAGE else CHUNK
            assert self.pages.extend(slot, off + bucket)
            self.window.cover(slot, off, off + bucket)
            padded = np.zeros((bucket,), np.int32)
            padded[:n] = tokens[off:off + n]
            self.cache, logits = steps[0](
                params, self.cache, jnp.int32(slot), self.row(slot),
                jnp.asarray(padded), jnp.int32(off), jnp.int32(n))
            off += n
        return logits

    def decode(self, steps, params, slot, at, token):
        assert self.pages.extend(slot, at + 1)
        self.window.cover(slot, at, at + 1)
        tokens = np.zeros((SLOTS,), np.int32)
        tokens[slot] = token
        active = np.zeros((SLOTS,), bool)
        active[slot] = True
        logits, self.cache, stats = steps[1](
            params, self.cache, self.tables(), jnp.asarray(tokens),
            jnp.asarray(active))
        return logits[slot], stats

    def free(self, slot):
        self.pages.free(slot)
        self.window.free(slot)
        self.cache = latent_cache.free_slot(self.cache, jnp.int32(slot))


def _close(got, want, what):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=0, err_msg=what)


def test_prefill_in_chunks_then_decode_equals_the_reference(model, steps):
    """70 prompt tokens in chunks of 32 (boundaries at 32 and 64, each
    inside a window of 5; every query from t = 8 on selects) and 20
    decode steps, against the reference's full forward of all 90."""
    config, params, weights = model
    tokens = np.random.default_rng(0).integers(0, 512, (90,)).astype(np.int32)
    want = ref.forward(CFG, weights, jnp.asarray(tokens))
    host = _Host(config)
    _close(host.prefill(steps, params, 1, tokens[:70]), want[69], 'prefill')
    for at in range(70, 90):
        logits, stats = host.decode(steps, params, 1, at, tokens[at])
        _close(logits, want[at], f'decode at {at}')
    counts = dict(zip(latent_cache.STEP_STATS, np.asarray(stats)))
    # one live token in two full blocks: all 90 keys scored, 8 kept
    assert counts['index_scored_keys'] == 2 * 90
    assert counts['index_selected_keys'] == 2 * 8
    assert counts['cache_slots_live'] == 1
    assert counts['latent_pages_live'] == host.pages.pages_of(1) == 6
    # a window layer holds the pages rows 85..89 lie in, not six
    assert counts['window_rows_live'] == host.window.pages_of(1) * PAGE
    assert host.window.pages_of(1) <= 2


@pytest.mark.parametrize('drop', ref.MECHANISMS)
def test_each_mechanism_left_out_of_the_reference_changes_the_logits(
        model, drop):
    """What the controls compute is not what the program computes."""
    _, _, weights = model
    tokens = np.random.default_rng(1).integers(0, 512, (48,)).astype(np.int32)
    want = ref.forward(CFG, weights, jnp.asarray(tokens))
    got = ref.forward(CFG, weights, jnp.asarray(tokens), act=drop)
    assert float(jnp.max(jnp.abs(got[-8:] - want[-8:]))) > 1e-3


def test_a_freed_slot_shows_none_of_its_former_rows(model, steps):
    """A long request, then the slot freed and given a shorter one: its
    logits are those of the same request on a cache nothing ever wrote
    to. The stale rows are still in the pools (nothing zeroes a page);
    no window layer and no indexer reads them."""
    config, params, _ = model
    rng = np.random.default_rng(2)
    first = rng.integers(0, 512, (75,)).astype(np.int32)
    second = rng.integers(0, 512, (41,)).astype(np.int32)
    used = _Host(config)
    used.prefill(steps, params, 0, first)
    for at in range(75, 80):
        used.decode(steps, params, 0, at, 7)
    assert float(jnp.abs(used.cache.window).max()) > 0
    used.free(0)
    assert used.window.free_pages == used.window.n_pages - 1
    fresh = _Host(config)
    np.testing.assert_array_equal(
        np.asarray(used.prefill(steps, params, 0, second)),
        np.asarray(fresh.prefill(steps, params, 0, second)))
    for at in range(41, 46):
        a, _ = used.decode(steps, params, 0, at, 9)
        b, _ = fresh.decode(steps, params, 0, at, 9)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_slot_that_is_not_active_moves_nothing(model, steps):
    config, params, _ = model
    tokens = np.random.default_rng(3).integers(0, 512, (40,)).astype(np.int32)
    host = _Host(config)
    host.prefill(steps, params, 0, tokens)
    before = host.cache
    _, stats = host.decode(steps, params, 1, 0, 5)      # slot 1, empty
    assert int(host.cache.lengths[0]) == 40
    # slot 0's pages are as they were (slot 1 wrote its own first row)
    own = np.asarray(host.pages.owned_pages(0))
    rows = (own[:, None] * PAGE + np.arange(PAGE)).reshape(-1)
    np.testing.assert_array_equal(np.asarray(host.cache.full[rows]),
                                  np.asarray(before.full[rows]))


# ---- the selection ---------------------------------------------------------

@pytest.mark.parametrize('ties', [False, True])
def test_the_selection_keeps_what_top_k_keeps(ties):
    """Exact top-k, ``lax.top_k``'s tie rule (the lower position wins),
    rows with fewer than k keys, and ``-inf`` never kept."""
    rng = np.random.default_rng(4)
    scores = rng.normal(size=(12, 64)).astype(np.float32)
    if ties:
        scores = np.round(scores * 2) / 2         # many equal scores
    scores[3, 5:] = -np.inf                        # a row of 5 keys
    scores[4, :] = -np.inf                         # a row of none
    scores[5, 40:] = -np.inf
    k = 8
    top, idx = jax.lax.top_k(jnp.asarray(scores), k)
    want = np.full(scores.shape, -np.inf, np.float32)
    for r in range(scores.shape[0]):
        want[r, np.asarray(idx[r])[np.asarray(top[r]) > -np.inf]] = 0.0
    got = lat.selection_bias(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_the_attention_kernel_is_softmax_over_the_kept_rows():
    """``biased_attention`` (interpreted) against one plain pass over
    the gathered rows: batch rows of different lengths, a dead key
    block, a batch row with no live block, a query with no kept key,
    more than one query tile."""
    rng = np.random.default_rng(5)
    page, B, T, H, W, rank = 16, 3, 16, 64, 24, 16
    keys = lat.block_keys(page)                    # 128 a grid step
    pool = jnp.asarray(rng.normal(size=(50 * page, 128)), jnp.float32)
    pool = pool.at[:, W:].set(0.0)
    tables = jnp.asarray(rng.permutation(np.arange(1, 50))[:B * 16]
                         .reshape(B, 16).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(B, T, H, W)), jnp.float32)
    reach = np.asarray([200, 100, 0])              # keys each row has
    at = np.arange(2 * keys)
    bias = np.where(rng.random((B, T, 2 * keys)) < 0.3, 0.0, -np.inf)
    bias = np.where(at[None, None, :] < reach[:, None, None], bias, -np.inf)
    bias[0, 3] = -np.inf                           # a query that keeps none
    got = lat.biased_attention(
        q, pool, tables, jnp.asarray(bias, jnp.float32),
        jnp.asarray(-(-reach // keys)), page=page, scale=0.5, rank=rank,
        interpret=True)
    rows = pool.reshape(50, page, 128)[tables].reshape(B, 16 * page, 128)
    want = lat.attend(q, rows[..., :W], jnp.asarray(bias > -np.inf), 0.5,
                      rank)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got[0, 3]).max()) == 0.0
    assert float(jnp.abs(got[2]).max()) == 0.0
    s = jnp.einsum('bthw,bkw->bthk', q, rows[..., :W]) * 0.5
    p = jnp.nan_to_num(jax.nn.softmax(s + bias[:, :, None, :], -1))
    np.testing.assert_allclose(
        want, jnp.einsum('bthk,bkr->bthr', p, rows[..., :rank]), atol=2e-5)


def test_the_index_kernel_is_the_plain_scores_under_the_causal_mask():
    """``paged_index_scores`` (interpreted) against ``index_scores`` on
    the gathered keys: two query tiles, a dead key block, an invalid
    query (position -1), keys past a query's position."""
    rng = np.random.default_rng(7)
    page, B, T, J, di = 16, 2, 512, 3, 24
    keys = lat.block_keys(page)
    pool = jnp.asarray(rng.normal(size=(40 * page, 128)), jnp.float32)
    pool = pool.at[:, di:].set(0.0)
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:B * 16]
                         .reshape(B, 16).astype(np.int32))
    qi = jnp.asarray(rng.normal(size=(B, T, J, di)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(B, T, J)), jnp.float32)
    positions = np.stack([np.arange(T) // 3, np.arange(T) // 8])
    positions[0, 5] = -1
    live = jnp.asarray([2, 1])
    got = lat.paged_index_scores(qi, wi, pool, tables, jnp.asarray(positions),
                                 live, page=page, interpret=True)
    ki = pool.reshape(40, page, 128)[tables].reshape(B, 16 * page, 128)
    want = lat.index_scores(qi, wi, ki[..., :di], key_block=keys)
    at = np.arange(2 * keys)
    seen = (at[None, None, :] <= positions[:, :, None]) & (
        at[None, None, :] < np.asarray(live)[:, None, None] * keys)
    assert got.shape == (B, T, 2 * keys)
    np.testing.assert_allclose(np.where(seen, got, 0.0),
                               np.where(seen, want, 0.0), atol=1e-4)
    assert np.isneginf(np.asarray(got)[~seen]).all()


def test_the_head_kernel_is_softmax_a_head_under_one_bias():
    """``head_attention`` (interpreted): every head under the same
    bias, key blocks past ``reach`` untouched, a query with no key."""
    rng = np.random.default_rng(8)
    G, T, S, D, V = 3, 64, 2048, 128, 128
    q = jnp.asarray(rng.normal(size=(G, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(G, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(G, S, V)), jnp.float32)
    bias = np.where(rng.random((T, S)) < 0.2, 0.0, -np.inf)
    bias[:, 300:] = -np.inf
    bias[7] = -np.inf
    got = lat.head_attention(q, k.at[:, 1024:].set(jnp.nan),
                             v.at[:, 1024:].set(jnp.nan),
                             jnp.asarray(bias, jnp.float32), jnp.int32(300),
                             scale=0.1, interpret=True)
    s_ = jnp.einsum('gtd,gsd->gts', q, k) * 0.1 + bias[None]
    want = jnp.einsum('gts,gsv->gtv', jnp.nan_to_num(jax.nn.softmax(s_, -1)),
                      v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got[:, 7]).max()) == 0.0


# ---- the window allocator --------------------------------------------------

def test_the_window_allocator_holds_the_window_and_frees_behind_it():
    alloc = paged_cache.WindowAllocator(page_size=16, n_slots=2,
                                        max_pages_per_slot=128, window=33,
                                        max_write=64)
    assert alloc.behind == 2 and alloc.per_slot == 2 + 4 + 1
    assert alloc.n_pages == 2 * 7 + 1
    held = []
    for off in range(0, 1024, 64):                 # a prefill in chunks
        alloc.cover(0, off, off + 64)
        held.append(alloc.pages_of(0))
        table = alloc.table()[0]
        live = np.nonzero(table)[0]
        # the pages the chunk writes and those its first row looks
        # back into, and nothing before them
        assert live.min() == max(off - 32, 0) // 16
        assert live.max() == (off + 64) // 16 - 1
        assert 0 not in table[live] and len(set(table[live])) == len(live)
    assert max(held) <= alloc.per_slot
    for at in range(1024, 1100):                   # decode
        alloc.cover(0, at, at + 1)
        assert alloc.pages_of(0) <= alloc.behind + 1
    alloc.cover(1, 0, 64)                          # the other slot fits
    version = alloc.version
    alloc.cover(0, 1099, 1100)                     # nothing new: no bump
    assert alloc.version == version
    alloc.free(0)
    alloc.free(1)
    assert alloc.free_pages == alloc.n_pages - 1
    assert not alloc.table().any() and alloc.rows_held() == 0


# ---- the expert layer ------------------------------------------------------

def _loop(h, idx, w, w_up, w_down, w_gate, offset):
    out = np.zeros(h.shape, np.float32)
    for t in range(h.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - offset
            if not 0 <= e < w_up.shape[0]:
                continue
            up = w_up[e] @ h[t]
            act = (np.square(np.maximum(up, 0)) if w_gate is None else
                   up * (lambda g: g / (1 + np.exp(-g)))(w_gate[e] @ h[t]))
            out[t] += w[t, j] * (act @ w_down[e])
    return out


@pytest.mark.parametrize('impl', ['ragged_dot', 'pallas'])
@pytest.mark.parametrize('gated', [False, True])
@pytest.mark.parametrize('offset, held', [(0, 8), (4, 4)])
def test_local_experts_is_the_plain_loop(gated, impl, offset, held):
    """Gated and not, every expert held or a share of them, both
    implementations of the grouped product."""
    rng = np.random.default_rng(6)
    T, d, f, k = 20, 128, 128, 2
    h = rng.normal(size=(T, d)).astype(np.float32)
    router = rng.normal(size=(d, 8)).astype(np.float32) * d ** -0.5
    bias = rng.normal(size=(8,)).astype(np.float32) * 0.1
    idx, w = moe_dropless.route(jnp.asarray(h), router, bias, k, 1.0)
    stacks = [rng.normal(size=(held, f, d)).astype(np.float32) * d ** -0.5
              for _ in range(3)]
    valid = jnp.arange(T) < 17
    out, stats = moe_dropless.local_experts(
        jnp.asarray(h), idx, w, stacks[0], stacks[1], valid, offset,
        w_gate=stacks[2] if gated else None, impl=impl, interpret=True)
    want = _loop(h[:17], np.asarray(idx), np.asarray(w), stacks[0], stacks[1],
                 stacks[2] if gated else None, offset)
    np.testing.assert_allclose(out[:17], want, atol=2e-4)
    assert float(jnp.abs(out[17:]).max()) == 0.0
    local = (np.asarray(idx[:17]) >= offset) & (np.asarray(idx[:17])
                                                < offset + held)
    assert int(stats[0]) == local.sum()


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of the eight shares (two
    experts each of 16) plus the shared expert ONCE equal the uncut
    layer, in the program and against the reference; attention is every
    chip's alike and is not in this sum at all."""
    whole = dots3.Dots3Config.tiny(dtype='float32', n_routed_experts=16)
    layer = dots3.init_ffn(whole, 1, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (24, whole.dim))
    valid = jnp.ones((24,), bool)
    full, stats = dots3.ffn(whole, 1, layer, x, valid)
    assert int(stats[0]) == 24 * whole.experts_per_token
    h = ref.rms_norm(x, layer['norm'], 1e-5)
    total, assigned = -7 * ref.shared_part(layer, h), 0
    for lo in range(0, 16, 2):
        share = dataclasses.replace(whole, experts_held=2, expert_offset=lo)
        cut = {**layer, **{k: layer[k][lo:lo + 2]
                           for k in ('w_gate', 'w_up', 'w_down')}}
        out, st = dots3.ffn(share, 1, cut, x, valid)
        total = total + out
        assigned += int(st[0])
    assert assigned == int(stats[0])
    np.testing.assert_allclose(total, full, atol=2e-5)
    rcfg = dict(num_experts_per_tok=2, routed_scaling_factor=1.0)
    np.testing.assert_allclose(full, ref.ffn(rcfg, layer, h), atol=2e-5)


# ---- the engine ------------------------------------------------------------

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
    assert m['index_scored_keys'] > m['index_selected_keys'] > 0
    assert m['moe_local_assignments'] > 0 and m['latent_pages_live'] > 0
    # every page of both pools is back, and a window layer never held
    # more than its window and a chunk a slot
    assert m['pages_free'] == 8 and m['window_rows_held'] == 0
    assert tight.window_alloc.free_pages == tight.window_alloc.n_pages - 1
    assert (m['window_rows_live'] / m['cache_slots_live']
            <= tight.window_alloc.per_slot * 16)
    assert set(tight.compiled_counts()) == {'prefill', 'decode', 'free'}


@pytest.mark.parametrize('switch, kw', [
    ('prefix_cache', dict(prefix_cache=True)),
    ('spec_k', dict(spec_k=2)),
    ('fused_prefill', dict(fused_prefill=True)),
    ('kv_int8', dict(kv_dtype='int8')),
    ('quantize', dict(quantize=True)),
    ('dense', dict(paged=False)),
])
def test_the_engine_refuses_what_latent_pools_break(switch, kw):
    config = dots3.Dots3Config.tiny(dtype='float32')
    assert switch in config.serving_refusals()
    with pytest.raises(ValueError, match='Dots3Config cannot be served'):
        _engine(config, None, **kw)


def test_the_published_preset_is_the_cells_configuration():
    config = dots3.Dots3Config.note_prev_ep8()
    spec = config.cache_spec().latent
    assert (spec.full_layers, spec.full_row, spec.index_row) == (2, 576, 128)
    assert (spec.window_layers, spec.window_row, spec.window) == (3, 1088,
                                                                  513)
    assert config.layer_types == ('full', 'full', 'sliding', 'sliding',
                                  'sliding')
    assert dots3.Dots3Config().count('full') == 13
    assert dots3.Dots3Config().count('sliding') == 33
    full = config.attn_sizes('full')
    assert dots3.latent_rescale(config, full) == (5 ** 0.5, 10 ** 0.5)
    assert dots3.latent_rescale(config, config.attn_sizes('sliding')) == (
        5 ** 0.5, 5 ** 0.5)
