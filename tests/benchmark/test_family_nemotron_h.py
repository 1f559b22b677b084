"""Family ``nemotron_h`` of the benchmark: its plain reference against
the equations written out a second time, its seeded weights, its work
counts against counts worked by hand, its configuration file against
the published widths, and its controls under the cell's limits, tiny
and on the CPU. (The program against this reference:
``tests/unit_tests/test_nemotron_h.py``.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, manifest, traffic
from benchmark import weights_nemotron_h as weights
from benchmark import work_nemotron_h as work
from benchmark.families import nemotron_h as fam
from benchmark.reference import nemotron_h as ref

BENCH = manifest.load()
NAME = 'nemotron3-nano-serve.chat-bursty'
CELL = manifest.cell(BENCH, NAME)
FULL = CELL['config']
SEED = 2**31 + 27   # past 32 signed bits, as the driver's seeds are


def _rehearsal():
    over = CELL['cell']['rehearse']
    cfg = manifest.deep_update(FULL, over['config'])
    return cfg, manifest.deep_update(CELL['cell'], over['cell'])['check']


CFG, SPEC = _rehearsal()


# ---- the configuration file ----------------------------------------------

def test_config_keeps_every_published_width():
    published = dict(
        hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_shared_experts=1, num_experts_per_tok=6, routed_scaling_factor=2.5,
        norm_topk_prob=True, n_group=1, topk_group=1,
        layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=1e-4, max_position_embeddings=262144,
        hybrid_override_pattern=('MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*'
                                 'EMEMEMEM*EMEMEMEME'))
    assert {k: FULL[k] for k in published} == published
    # the cut: exactly these three, the published values beside them
    assert FULL['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                               'vocab_size']
    assert (FULL['num_hidden_layers'], FULL['n_routed_experts'],
            FULL['vocab_size']) == (16, 64, 65536)
    assert FULL['published'] == {
        'num_hidden_layers': 52, 'n_routed_experts': 128,
        'vocab_size': 131072, 'layers_by_kind': {'M': 23, 'E': 23, '*': 6}}
    # the router keeps its published width whatever is held here
    assert FULL['n_routed_experts_published'] == 128
    assert weights.pattern(FULL) == 'MEMEM*EMEMEM*EME' == FULL['run_pattern']
    assert work.counts(FULL) == {'M': 7, 'E': 7, '*': 2}
    assert '2 TPU v5e chips' in FULL['deployment']
    assert 'No positional embedding' in \
        FULL['assumed']['no_positional_embedding']
    eng = FULL['engine']
    assert (eng['n_slots'], eng['n_pages'], eng['page_size'],
            eng['quantize']) == (64, 2048, 64, False)
    assert not (eng['prefix_cache'] or eng['fused_prefill'] or eng['spec_k']
                or eng['tp'] > 1) and eng['kv_dtype'] == 'bfloat16'
    assert manifest.problems() == []


def test_the_cells_files_are_found_by_name():
    cell, entry = CELL['cell'], CELL['entry']
    assert cell['kind'] == 'serve_open_family' and entry['chips'] == 1
    assert hasattr(manifest.kind(cell['kind']), 'run')
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        entry['config'], entry['traffic'], entry['chips'])
    reported = {m['name'] for m in manifest.metrics_of(BENCH, 'end_to_end',
                                                       NAME)}
    assert reported == set(cell['reports'])
    # the gap metrics are left out: their spread over seeds (reports_note)
    assert reported == {'ttft_p90_s', 'ttft_mean_s', 'setup_s'}
    assert 'itl_p50_ms' in cell['reports_note']
    assert 'rehearse' in cell and cell['check']['control'] in fam.CONTROLS
    mix = CELL['traffic']
    assert (mix['loop'], mix['burst'], mix['prompt']['dist']) == (
        'open', 4, 'pareto')
    assert 'order_seed' in mix


# ---- the work counts, against ISSUE 27's arithmetic -------------------------

def test_parameter_counts():
    expert = 2 * 2688 * 1856
    assert work.expert_params(FULL) == expert == 9_977_856
    # the router's 128 outputs and the shared expert of width 3712
    assert work.moe_dense_params(FULL) == 2688 * 128 + 2 * 2688 * 3712
    e_layer = 64 * expert + work.moe_dense_params(FULL)
    assert round(e_layer / 1e6, 1) == 658.9
    # W_in 2688 -> 4096 + 6144 + 64, W_out 4096 -> 2688
    assert work.mamba_matmul_params(FULL) == 2688 * 10304 + 4096 * 2688
    assert round(work.mamba_matmul_params(FULL) / 1e6, 1) == 38.7
    assert work.attn_matmul_params(FULL) == (2 * 2688 * 4096
                                             + 2 * 2688 * 256)
    small = (7 * (128 + 2688)                       # correction bias, norm
             + 7 * (5 * 6144 + 3 * 64 + 4096 + 2688)  # conv, dt/A/D, norms
             + 2 * 2688 + 2688)
    assert work.total_params(FULL) == (
        7 * e_layer + 7 * work.mamba_matmul_params(FULL)
        + 2 * work.attn_matmul_params(FULL) + 2 * 65536 * 2688 + small)
    assert round(work.total_params(FULL) / 1e9, 2) == 5.28


def test_state_and_cache_bytes():
    # 7 M blocks: 64 x 64 x 128 float32 and the conv's last 3 inputs
    assert work.state_elements(FULL) == 524_288
    assert work.state_bytes_per_slot(FULL) == 7 * (4 * 524_288
                                                   + 3 * 6144 * 2)
    assert round(work.state_bytes_per_slot(FULL) / 1e6, 1) == 14.9
    # K and V, 2 attention blocks, 2 heads of 128, bf16
    assert work.kv_bytes_per_token(FULL) == 2 * 2 * 2 * 128 * 2 == 2048


def test_decode_work_counts_live_slots_and_touched_experts_only():
    flops, bytes_ = work.ssm_decode_work(FULL, slot_steps=40, steps=1)
    per_slot = 2 * (4 * 524_288 + 3 * 6144 * 2)    # read and written
    assert bytes_ == 7 * (40 * per_slot + 38_707_200 * 2)
    assert flops == 7 * 40 * (2 * 38_707_200 + 5 * 524_288)
    flops, bytes_ = work.moe_experts_work(FULL, assignments=120, touched=50)
    assert (flops, bytes_) == (2 * 9_977_856 * 120, 50 * 9_977_856 * 2)
    # one live token, 10 keys in each of 2 attention blocks, 3 expert
    # passes, the head over the slice
    per_token = (7 * (2 * 38_707_200 + 5 * 524_288)
                 + 2 * 2 * work.attn_matmul_params(FULL)
                 + 7 * 2 * work.moe_dense_params(FULL) + 2 * 2688 * 65536)
    assert work.decode_flops(FULL, 1, 3, 10) == (
        per_token + 4 * 32 * 128 * 2 * 10 + 2 * 9_977_856 * 3)
    # every held expert touched: ISSUE 27's 10.2 GB a step
    assert round(work.decode_weight_bytes(FULL, 64) / 1e9, 1) == 10.2


# ---- the seeded weights ------------------------------------------------------

@pytest.fixture(scope='module')
def tree():
    return weights.init_all(CFG, SEED)


def test_a_block_made_alone_equals_the_programs_block(tree):
    key = weights.root_key(SEED)
    seen = {k: 0 for k in 'ME*'}
    for index, kind in enumerate(weights.pattern(CFG)):
        # jitted, as the family makes a block for the reference
        alone = jax.jit(lambda k: weights.layer(CFG, k, index))(key)
        mine = tree['layers'][kind][seen[kind]]
        seen[kind] += 1
        assert set(alone) == set(mine)
        for name in alone:
            assert alone[name].dtype == mine[name].dtype
            assert bool((alone[name] == mine[name]).all()), (index, name)
    other = weights.init_all(CFG, SEED + 1)
    assert not bool((other['lm_head'] == tree['lm_head']).all())


def test_an_expert_is_keyed_by_its_published_id():
    """The experts a share holds are the same matrices in every share
    and in the uncut layer; the router is the whole one everywhere."""
    key = weights.root_key(SEED)
    uncut = dict(CFG, n_routed_experts=8, expert_offset=0)
    upper = dict(CFG, n_routed_experts=4, expert_offset=4)
    index = weights.pattern(CFG).index('E')
    whole = weights.layer(uncut, key, index)
    lower, high = weights.layer(CFG, key, index), weights.layer(upper, key,
                                                                index)
    for name in ('w_up', 'w_down'):
        assert whole[name].shape == (8, 32, 64)       # [held, f, d]
        assert bool((whole[name][:4] == lower[name]).all())
        assert bool((whole[name][4:] == high[name]).all())
    for name in ('router', 'router_bias', 'shared_up', 'shared_down'):
        assert bool((whole[name] == lower[name]).all())
    assert whole['router'].shape == (64, 8)


def test_the_router_is_drawn_balanced():
    """Equal spread of every expert's logit under the block's norm
    weight (hot channels and all), and the two halves' biases alike."""
    key = weights.root_key(SEED)
    e = weights.layer(CFG, key, weights.pattern(CFG).index('E'))
    seen = e['norm'].astype(jnp.float32)[:, None] * e['router']
    np.testing.assert_allclose(jnp.sum(seen * seen, 0), 1.0, rtol=1e-5)
    half = e['router_bias'].shape[0] // 2
    assert bool((e['router_bias'][:half] == e['router_bias'][half:]).all())
    assert 0 < float(jnp.abs(e['router_bias']).max()) < 0.1


def test_what_the_precision_block_keeps_in_float32_is_float32(tree):
    m, e = tree['layers']['M'][0], tree['layers']['E'][0]
    for name in ('conv_w', 'conv_b', 'dt_bias', 'a_log', 'd_skip'):
        assert m[name].dtype == jnp.float32, name
    for name in ('router', 'router_bias'):
        assert e[name].dtype == jnp.float32, name
    for name in ('w_in', 'w_out', 'norm', 'gate_norm'):
        assert m[name].dtype == jnp.bfloat16, name
    for name in ('w_up', 'w_down', 'shared_up', 'shared_down'):
        assert e[name].dtype == jnp.bfloat16, name


def test_dt_a_and_d_lie_in_the_ranges_the_config_implies(tree):
    for m in tree['layers']['M']:
        dt = np.asarray(jax.nn.softplus(m['dt_bias']))
        assert (dt >= CFG['time_step_floor']).all()
        assert (dt >= CFG['time_step_min'] * 0.999).all()
        assert (dt <= CFG['time_step_max'] * 1.001).all()
        rate = np.exp(np.asarray(m['a_log']))
        assert (rate >= 1.0).all() and (rate <= 16.0).all()
        assert (np.abs(np.asarray(m['d_skip']) - 1.0) < 0.6).all()


def test_every_norm_has_the_hot_channels_of_weights_py(tree):
    from benchmark import weights as base
    hot = np.asarray(base.hot_channels(CFG, weights.root_key(SEED)))
    norms = [tree['final_norm']] + [
        layer['norm'] for kind in 'ME*' for layer in tree['layers'][kind]]
    for w in norms:
        w = np.asarray(w.astype(jnp.float32))
        assert (w[hot] > 0.5 * base.HOT_GAIN).all()
        assert np.delete(w, hot).max() < 1.5


# ---- the reference against the equations written a second time -------------

def _loops_mamba(cfg, w, h):
    """ISSUE 27's ``M`` equations in numpy, one step, head and channel
    at a time (float64)."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    h = np.asarray(h, np.float64)
    T = h.shape[0]
    H, P = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    G, N, K = cfg['n_groups'], cfg['ssm_state_size'], cfg['conv_kernel']
    di, gn = H * P, G * N
    proj = h @ w['w_in']
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * gn], \
        proj[:, 2 * di + 2 * gn:]
    conv = np.zeros_like(xbc)
    for t in range(T):
        for j in range(K):          # tap K-1 weighs the current step
            if t - (K - 1 - j) >= 0:
                conv[t] += xbc[t - (K - 1 - j)] * w['conv_w'][j]
    xbc = conv + w['conv_b']
    xbc = xbc / (1 + np.exp(-xbc))
    xs, B, C = xbc[:, :di], xbc[:, di:di + gn], xbc[:, di + gn:]
    dt = np.log1p(np.exp(dt + w['dt_bias']))
    A = -np.exp(w['a_log'])
    y = np.zeros((T, di))
    for head in range(H):
        g = head // (H // G)
        S = np.zeros((P, N))
        for t in range(T):
            x_t = xs[t, head * P:(head + 1) * P]
            S = (np.exp(dt[t, head] * A[head]) * S
                 + dt[t, head] * np.outer(x_t, B[t, g * N:(g + 1) * N]))
            y[t, head * P:(head + 1) * P] = (
                S @ C[t, g * N:(g + 1) * N] + w['d_skip'][head] * x_t)
    y = y * (z / (1 + np.exp(-z)))
    y = y.reshape(T, G, di // G)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True)
                    + cfg['layer_norm_epsilon'])
    return (y.reshape(T, di) * w['gate_norm']) @ w['w_out']


def _block(kind):
    index = weights.pattern(CFG).index(kind)
    return fam._f32_block(CFG, kind, weights.root_key(SEED),
                          jnp.int32(index))


def test_the_mamba_mixer_is_the_recurrence_step_by_step():
    w = _block('M')
    h = jax.random.normal(jax.random.PRNGKey(1), (9, CFG['hidden_size']))
    got = ref.mamba_mixer(CFG, w, h)
    want = _loops_mamba(CFG, w, h)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * np.abs(want).max())


def test_the_router_picks_by_biased_score_and_weighs_by_the_score():
    w = _block('E')
    h = jax.random.normal(jax.random.PRNGKey(2), (64, CFG['hidden_size']))
    idx, wts = ref.route(CFG, w, h)
    s = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                        @ np.asarray(w['router'], np.float64)))
    biased = s + np.asarray(w['router_bias'], np.float64)
    k = CFG['num_experts_per_tok']
    for t in range(64):
        assert set(np.asarray(idx[t]).tolist()) == set(
            np.argsort(-biased[t])[:k].tolist())
        chosen = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(wts[t], chosen / chosen.sum() * 2.5,
                                   rtol=1e-5)
    # the bias decides: without it some token chooses otherwise
    plain, _ = ref.route(CFG, dict(w, router_bias=0 * w['router_bias']), h)
    assert bool((jnp.sort(plain, -1) != jnp.sort(idx, -1)).any())


def test_the_expert_layer_sums_the_held_experts_and_the_shared_once():
    w = _block('E')
    h = jax.random.normal(jax.random.PRNGKey(3), (12, CFG['hidden_size']))
    idx, wts = ref.route(CFG, w, h)
    want = np.zeros((12, CFG['hidden_size']))
    for t in range(12):
        for e, g in zip(np.asarray(idx[t]), np.asarray(wts[t])):
            if e < CFG['n_routed_experts']:        # held here: ids 0-3 of 8
                up = np.maximum(np.asarray(h[t]) @ np.asarray(w['w_up'][e]).T,
                                0) ** 2
                want[t] += g * (up @ np.asarray(w['w_down'][e]))
        up = np.maximum(np.asarray(h[t]) @ np.asarray(w['shared_up']), 0) ** 2
        want[t] += up @ np.asarray(w['shared_down'])
    held = np.asarray(idx) < CFG['n_routed_experts']
    assert held.any() and not held.all()     # the share really cuts
    np.testing.assert_allclose(ref.moe_mixer(CFG, w, h), want, rtol=2e-4,
                               atol=1e-5)


def test_attention_is_causal_and_carries_no_position():
    w = _block('*')
    h = jax.random.normal(jax.random.PRNGKey(4), (6, CFG['hidden_size']))
    out = ref.attn_mixer(CFG, w, h)
    # causal: a later row does not move an earlier one
    np.testing.assert_allclose(ref.attn_mixer(CFG, w, h[:4]), out[:4],
                               rtol=1e-5, atol=1e-6)
    # no positional embedding: equal rows attend alike wherever they
    # stand, so every output row of a constant sequence is the same
    same = ref.attn_mixer(CFG, w, jnp.tile(h[:1], (6, 1)))
    np.testing.assert_allclose(same, jnp.tile(same[:1], (6, 1)), rtol=1e-5,
                               atol=1e-6)


def test_the_stated_precision_rounds_and_the_controls_go_below_it():
    W = fam.reference_weights(CFG, SEED)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG['vocab_size'], 40))
    exact = ref.forward(CFG, W, toks)
    # the mean error: the largest is set by which router choice flips
    err = {a: float(jnp.abs(ref.forward(CFG, W, toks, act=a) - exact).mean())
           for a in ref.ACTS[1:]}
    assert 0 < err['bf16'] < min(err['bf16-w8'], err['bf16-w8a8'])
    assert err['bf16-state'] > err['bf16']
    assert min(err[a] for a in ref.MECHANISMS) > err['bf16-w8a8']
    with pytest.raises(ValueError, match='unknown control'):
        ref.hooks('fp4')


# ---- the comparison under the cell's limits ---------------------------------

def _greedy(W, n_prompts=3, prompt_len=24, n_new=40):
    # the tree's block kinds are strings: closed over, not traced
    fwd = jax.jit(lambda tokens: ref.forward(CFG, W, tokens))
    samples = []
    for i in range(n_prompts):
        prompt = traffic.request_tokens(SEED, i, prompt_len,
                                        CFG['vocab_size'])
        seq, served = list(prompt) + [0] * n_new, []
        for j in range(n_new):
            tok = int(fwd(jnp.asarray(seq))[prompt_len + j - 1].argmax())
            served.append(tok)
            seq[prompt_len + j] = tok
        samples.append({'prompt': prompt, 'served': served})
    return samples


def _ok(found):
    return all(c['ok'] for c in check.verdict(found, SPEC, {}).values())


def test_the_control_comes_out_as_not_correct():
    W = fam.reference_weights(CFG, SEED)
    control = SPEC['control']
    own = fam.serve_gaps(CFG, SEED, _greedy(W), controls=(control,),
                         pad_to=(32,), rows_pad=8)
    assert _ok(own) and own['served']['mismatch_share'] == 0
    assert 0 <= own['router_flip_share'] < 0.2
    assert not _ok(dict(own, served=own['controls'][control]))
    # ids that have nothing to do with the model lie far below both
    far = fam.serve_gaps(CFG, SEED, _greedy(W, n_prompts=1),
                         controls=(fam.UNRELATED,), pad_to=(32,), rows_pad=8)
    assert far['controls'][fam.UNRELATED]['logit_gap_mean'] > 100 * \
        SPEC['limits']['logit_gap_mean']
    assert any(own['controls'][control][n] > 3 * limit
               for n, limit in SPEC['limits'].items())


@pytest.mark.parametrize('leaf', ['conv_b', 'router_bias'])
def test_leaving_out_a_bias_fails_the_check(leaf):
    """ISSUE 27, point 3: both are drawn large enough that a program
    without them does not pass."""
    W = fam.reference_weights(CFG, SEED)
    broken = dict(W, layers=[
        (kind, dict(w, **{leaf: 0 * w[leaf]}) if leaf in w else w)
        for kind, w in W['layers']])
    found = fam.serve_gaps(CFG, SEED, _greedy(broken), pad_to=(32,),
                           rows_pad=8)
    assert not _ok(found), found['served']


# ---- blind to the router's near-ties ----------------------------------------

def _an_e_block(n_tokens=20000):
    W = fam.reference_weights(CFG, SEED)
    w = next(leaves for kind, leaves in W['layers'] if kind == 'E')
    x = jax.random.normal(jax.random.PRNGKey(3), (n_tokens,
                                                  CFG['hidden_size']))
    return w, x


def test_the_margin_is_the_distance_to_a_tie_in_roundings_of_the_input():
    w, x = _an_e_block()
    k = CFG['num_experts_per_tok']
    margin = np.asarray(ref.router_margin(CFG, w, x))
    assert margin.shape == (x.shape[0],) and (margin >= 0).all()
    # worked a second way for one token: the k-th and (k+1)-th biased
    # scores, and the spread of their distance under roundings of h
    h = np.asarray(ref.rms_norm(x[:1], w['norm'], CFG['layer_norm_epsilon']),
                   np.float64)[0]
    r = np.asarray(w['router'], np.float64)
    s = 1 / (1 + np.exp(-(h @ r)))
    c = s + np.asarray(w['router_bias'], np.float64)
    order = np.argsort(-c)
    a, b = order[k - 1], order[k]
    pull = s[a] * (1 - s[a]) * r[:, a] - s[b] * (1 - s[b]) * r[:, b]
    sigma = np.sqrt(((h * pull) ** 2).sum()) * 2.0 ** -9 / np.sqrt(3)
    assert margin[0] == pytest.approx((c[a] - c[b]) / sigma, rel=1e-3)
    # what it is for: every token that one rounding of the input flips
    # has a margin of a few roundings (the error is bounded: sqrt(3)
    # sigma an element), and a good share of those under one flip
    flipped = np.asarray(ref.router_flips(CFG, w, x))
    assert flipped.any() and margin[flipped].max() < 4
    assert flipped[margin < 1].mean() > 0.1 > flipped.mean()


def test_the_mean_is_over_the_settled_tokens_and_the_max_over_all():
    W = fam.reference_weights(CFG, SEED)
    samples = _greedy(W)
    plain = fam.serve_gaps(CFG, SEED, samples, pad_to=(32,), rows_pad=8)
    n = sum(len(s['served']) for s in samples)
    assert plain['served_tokens'] == plain['served']['settled_tokens'] == n
    margins = plain['margins']
    assert margins.shape == (n,) and np.isfinite(margins).all()
    # A token the reference ranks far down, planted as a request's LAST
    # token (which is fed to no later position), the one of them at
    # which the router is nearest a tie.
    ends = np.cumsum([len(s['served']) for s in samples]) - 1
    which = int(np.argmin(margins[ends]))
    at = int(ends[which])
    planted = [dict(s) for s in samples]
    last = samples[which]['served'][-1]
    planted[which]['served'] = samples[which]['served'][:-1] + [
        (last + 1) % CFG['vocab_size']]

    def read(tie_margin):
        return fam.serve_gaps(CFG, SEED, planted, pad_to=(32,), rows_pad=8,
                              tie_margin=tie_margin)
    # with the token left unsettled: the widest gap sees it, the mean
    # over the settled tokens does not
    found = read(float(margins[at]) * 1.001)
    settled = found['margins'] >= float(margins[at]) * 1.001
    assert not settled[at] and 0 < int(settled.sum()) < n
    assert found['served_tokens'] == int(settled.sum())
    gap = float(found['gaps'][None][at])
    assert gap > SPEC['limits']['logit_gap_max']
    assert found['served']['logit_gap_max'] == pytest.approx(gap)
    assert found['served']['logit_gap_mean'] == 0.0
    assert found['served']['logit_gap_mean_all'] == pytest.approx(gap / n)
    # with the token settled, the mean sees it too
    found = read(float(margins[at]) * 0.999)
    assert found['served']['logit_gap_mean'] == pytest.approx(
        gap / found['served_tokens'])
    assert found['served']['logit_gap_mean'] > SPEC['limits']['logit_gap_mean']
