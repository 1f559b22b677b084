"""The falcon_h1 family of the benchmark
(``benchmark/families/falcon_h1.py``): the configuration file against
the catalog's row, ISSUE 33's parameter and byte arithmetic, the seeded
weights (drawn for the published multipliers), the reference's block
against the equations written a second time, the check's comparison at
the rehearsal's size (each mechanism dropped and the precision below
the stated one fail it), a whole rehearsal run with the control in the
program's place, and the cell's per-layer readers on a hand-built
traced run."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark import weights_falcon_h1 as weights
from benchmark import work_falcon_h1 as work
from benchmark.families import falcon_h1 as fam
from benchmark.reference import falcon_h1 as ref

BENCH = manifest.load()
NAME = 'falcon-h1-serve.reasoning-steady'
CELL = manifest.cell(BENCH, NAME)
FULL = CELL['config']
PEAK = work.peaks('TPU v5 lite')
SEED = 2**31 + 33   # past 32 signed bits, as the driver's seeds are


def _rehearsal():
    over = CELL['cell']['rehearse']
    cfg = manifest.deep_update(FULL, over['config'])
    return cfg, manifest.deep_update(CELL['cell'], over['cell'])['check']


CFG, SPEC = _rehearsal()


# ---- the configuration and the cell, against ISSUE 33 ----------------------

def test_the_config_file_holds_the_catalogs_row():
    """Every number of the catalog's ``config`` under the same key, but
    for the two reduced ones (the guide's rule, checked here where the
    catalog is beside the guide)."""
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    try:
        rows = [json.loads(line) for line in open(path, encoding='utf-8')]
    except OSError:
        pytest.skip('no catalog here')
    row = next(r for r in rows if r['name'] == 'Falcon-H1-34B-Instruct')
    assert FULL['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if FULL.get(k) != v}
    assert differ == set(FULL['reduced']) == {'num_hidden_layers',
                                             'vocab_size'}


def test_config_keeps_every_published_width_and_states_its_cut():
    assert (FULL['hidden_size'], FULL['intermediate_size'],
            FULL['num_attention_heads'], FULL['num_key_value_heads'],
            FULL['head_dim']) == (5120, 21504, 20, 4, 128)
    assert (FULL['mamba_d_ssm'], FULL['mamba_d_state'], FULL['mamba_d_head'],
            FULL['mamba_n_heads'], FULL['mamba_n_groups'],
            FULL['mamba_d_conv'], FULL['mamba_chunk_size']) == (
        4096, 256, 128, 32, 2, 4, 128)
    assert FULL['rope_theta'] == 1e11 and FULL['model_type'] == 'falcon_h1'
    assert (FULL['num_hidden_layers'], FULL['vocab_size']) == (9, 32640)
    assert FULL['published'] == {'num_hidden_layers': 72,
                                 'vocab_size': 261120}
    assert FULL['num_hidden_layers'] * 8 == 72     # whole blocks a stage
    assert FULL['vocab_size'] * 8 == 261120        # vocabulary-parallel
    assert '8 stages of a pipeline' in FULL['deployment']
    for reading in ('block', 'multipliers', 'rope', 'd_ssm', 'gated_norm',
                    'in_proj_layout', 'seeded_weights'):
        assert reading in FULL['assumed']
    assert 'float32' in FULL['precision']['ssm_state']
    eng = FULL['engine']
    assert (eng['n_slots'], eng['max_seq_len'], eng['page_size'],
            eng['scheduler'], eng['pipeline_depth'],
            eng['prefill_chunk']) == (64, 2304, 64, 'fcfs', 1, 256)
    assert eng['n_pages'] == 64 * 2048 // 64 + 256    # and headroom
    assert not (eng['prefix_cache'] or eng['fused_prefill'] or eng['spec_k']
                or eng['quantize'] or eng['tp'] > 1)
    assert fam.config_of(FULL).in_proj == 9248
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
    assert {'ttft_p90_s', 'ttft_mean_s', 'setup_s'} <= reported
    assert reported - {'ttft_p90_s', 'ttft_mean_s', 'setup_s'} <= {
        'itl_p50_ms', 'itl_mean_ms', 'itl_p90_ms'}
    assert len(entry['why']) <= 200
    assert cell['check']['control'] == 'bf16-w8a8'
    assert cell['check']['control'] in fam.CONTROLS
    assert 'tie_margin' not in cell['check']      # nothing is routed
    mix = CELL['traffic']
    assert (mix['loop'], mix['burst'], mix['drain_s']) == ('open', 1, 40)
    assert mix['prompt'] == {'dist': 'pareto', 'shape': 2.0, 'scale': 128,
                             'min': 128, 'max': 1024}
    assert mix['output'] == {'dist': 'uniform', 'min': 256, 'max': 1024}
    assert 'order_seed' in mix and 'knee' in mix['rate_note']
    assert mix['rate_rps'] * 2 == round(mix['rate_rps'] * 2)
    assert (mix['prompt']['max'] + mix['output']['max']
            <= FULL['engine']['max_seq_len'])
    per_layer = {m['name']: m for m in manifest.metrics_of(
        BENCH, 'per_layer', NAME)}
    assert set(per_layer) == {
        'mfu.parallel_decode', 'mfu.parallel_prefill',
        'kernel.parallel_ssm_decode_roofline',
        'kernel.gqa5_paged_decode_roofline',
        'ssm.parallel_live_slots_per_step'}
    assert all(m['workloads'] == [NAME] for m in per_layer.values())
    # prefill moves the time to first token; the four decode-side ones
    # the gap metric the cell reports (the mean's spread did not admit
    # it: the cell file's ``reports_note``), the bare decode step
    assert per_layer['mfu.parallel_prefill']['moves'] == 'ttft_mean_s'
    assert 'itl_p50_ms' in reported and 'reports_note' in cell
    assert {m['moves'] for n, m in per_layer.items()
            if n != 'mfu.parallel_prefill'} == {'itl_p50_ms'}
    # the rehearsal keeps what the model forces: a group of 5, 2 SSM
    # groups, a state wider than the mixer's head
    over = cell['rehearse']['config']
    assert over['num_attention_heads'] // over['num_key_value_heads'] == 5
    assert over['mamba_n_groups'] == 2
    assert over['mamba_d_state'] > over['mamba_d_head']


def test_parameter_counts():
    """ISSUE 33, Tentpole 1 and 2, reckoned again."""
    m = 1e6
    assert round(work.attn_matmul_params(FULL) / m, 2) == 31.46
    assert round(work.mixer_matmul_params(FULL) / m, 2) == 68.32
    assert weights.sizes(FULL)['in_proj'] == 9248
    assert round(5120 * 9248 / m, 2) == 47.35
    assert round(4096 * 5120 / m, 2) == 20.97
    assert work.mlp_matmul_params(FULL) == 3 * 5120 * 21504
    assert round(work.mlp_matmul_params(FULL) / m, 2) == 330.30
    assert round(work.block_matmul_params(FULL) / m, 1) == 430.1
    assert round(work.block_matmul_params(FULL) * 2 / m) == 860
    assert round(work.head_params(FULL) / 1e9, 3) == 0.167
    whole = dict(FULL, vocab_size=261120, num_hidden_layers=72)
    assert round(work.head_params(whole) / 1e9, 3) == 1.337
    assert round(work.total_params(FULL) * 2 / 1e9, 2) == 8.41
    assert round(work.total_params(whole) * 2 / 1e9) == 67
    # the head's share of what a decode step streams: here as deployed
    here = work.head_params(FULL) * 2 / work.decode_weight_bytes(FULL)
    there = work.head_params(whole) * 2 / work.decode_weight_bytes(whole)
    assert round(100 * here, 1) == round(100 * there, 1) == 4.1
    six = dict(FULL, vocab_size=261120, num_hidden_layers=6)
    assert round(100 * work.head_params(six) * 2
                 / work.decode_weight_bytes(six)) == 34
    # the program's tree has that many leaves' elements
    from skypilot_tpu.models import falcon_h1
    tree = jax.eval_shape(lambda: falcon_h1.FalconH1Config.h1_34b_pp8()
                          .init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(v.shape)) for v in
               jax.tree_util.tree_leaves(tree)) == work.total_params(FULL)


def test_state_and_cache_bytes():
    assert work.state_elements(FULL) * 4 == 32 * 128 * 256 * 4 == 4_194_304
    per_slot = work.state_bytes_per_slot(FULL)
    assert per_slot == 9 * (4_194_304 + 3 * 5120 * 2)
    assert round(64 * per_slot / 1e9, 2) == 2.43
    assert work.kv_bytes_per_token(FULL) == 9 * 4 * 128 * 2 * 2 == 18432
    eng = FULL['engine']
    pool = eng['n_pages'] * eng['page_size'] * work.kv_bytes_per_token(FULL)
    assert round(pool / 1e9, 2) == 2.72
    total = work.total_params(FULL) * 2 + 64 * per_slot + pool
    assert round(total / 1e9, 1) == 13.6 and total > 0.75 * 16e9
    # the program's cache has those bytes
    spec = fam.config_of(FULL).cache_spec()
    assert spec.kv_layers == spec.state.layers == 9
    assert (int(np.prod(spec.state.ssm_shape)) * 4
            + int(np.prod(spec.state.conv_shape)) * 2) * 9 == per_slot


def test_decode_work_counts_live_slots_and_live_contexts_only():
    # 10 steps that advanced 450 slot states: 45 live slots of 64
    flops, bytes_ = work.ssm_decode_work(FULL, 450, 10)
    state = 2 * (4_194_304 + 3 * 5120 * 2)
    assert bytes_ == 9 * (450 * state + 10 * 68_321_280 * 2)
    assert flops == 9 * 450 * (2 * 68_321_280 + 5 * 1_048_576)
    # a full batch a step: 64 x 9 x 4.19 MB of state both ways is
    # 4.83 GB (ISSUE 33's 4.86), 4.87 with the convolution's windows
    moved = work.ssm_decode_work(FULL, 64, 1)[1] - 9 * 68_321_280 * 2
    assert round(64 * 9 * 2 * 4_194_304 / 1e9, 2) == 4.83
    assert round(moved / 1e9, 2) == 4.87
    # attention at 20 query heads over 4 KV heads: K and V of the live
    # pages once, whatever the group
    contexts = [100, 1000]
    f, b = work.paged_decode_work(FULL, contexts, 64)
    assert f == 4.0 * 20 * 128 * 9 * 1100
    assert b == (128 + 1024) * 18432 + 2 * 20 * 128 * 2 * 9 * 2
    # the step: every block's matrices and the recurrence a live token
    per_token = 9 * (2 * 430_080_000 + 5 * 1_048_576) + 2 * 5120 * 32640
    assert work.decode_flops(FULL, 450, 1100.0) == 450 * per_token + f
    assert work.prefill_flops(FULL, [(256, 0)]) == (
        256 * 9 * (2 * 430_080_000 + 5 * 1_048_576)
        + 4.0 * 20 * 128 * 9 * 256 * 257 / 2 + 2 * 5120 * 32640)
    assert round(work.decode_weight_bytes(FULL) / 1e9, 2) == 8.08


# ---- the seeded weights -----------------------------------------------------

@pytest.fixture(scope='module')
def tree():
    return weights.init_all(CFG, SEED)


def test_a_block_made_alone_equals_the_programs_block(tree):
    key = weights.root_key(SEED)
    make = jax.jit(lambda k, i: weights.layer(CFG, k, i))
    for index, block in enumerate(tree['layers']['P']):
        alone = make(key, jnp.int32(index))
        assert set(alone) == set(block)
        for leaf in block:
            np.testing.assert_array_equal(
                np.asarray(alone[leaf], np.float32),
                np.asarray(block[leaf], np.float32))
    first, second = tree['layers']['P'][:2]
    assert not np.array_equal(np.asarray(first['wq'], np.float32),
                              np.asarray(second['wq'], np.float32))


def test_what_the_precision_block_keeps_in_float32_is_float32(tree):
    block = tree['layers']['P'][0]
    for leaf in ('conv_w', 'conv_b', 'dt_bias', 'a_log', 'd_skip'):
        assert block[leaf].dtype == jnp.float32, leaf
    for leaf in ('wq', 'wk', 'wv', 'wo', 'w_in', 'w_out', 'w_gate', 'w_up',
                 'w_down', 'norm', 'ff_norm', 'gate_norm'):
        assert block[leaf].dtype == jnp.bfloat16, leaf
    assert tree['embed'].dtype == tree['lm_head'].dtype == jnp.bfloat16
    dt = np.log1p(np.exp(np.asarray(block['dt_bias'])))
    assert (dt >= 1e-4).all() and (dt <= 0.1 + 1e-6).all()
    a = np.exp(np.asarray(block['a_log']))
    assert (a >= 1).all() and (a <= 16).all()


def test_every_norm_has_the_hot_channels_of_weights_py(tree):
    from benchmark import weights as base
    hot = np.asarray(base.hot_channels(CFG, weights.root_key(SEED)))
    norms = [tree['final_norm']] + [b[n] for b in tree['layers']['P']
                                    for n in ('norm', 'ff_norm')]
    for w in norms:
        w = np.asarray(w.astype(jnp.float32))
        assert (w[hot] > 0.5 * base.HOT_GAIN).all()
        assert np.delete(w, hot).max() < 1.5


def test_the_weights_are_drawn_for_the_published_multipliers(tree):
    """Each matrix's spread times its multiplier is the usual
    ``fan_in ** -0.5`` times its gain: W_in segment by segment."""
    block = {k: np.asarray(v, np.float32)
             for k, v in tree['layers']['P'][0].items()}
    s, d = weights.sizes(CFG), CFG['hidden_size']
    std = d ** -0.5
    assert block['wk'].std() * CFG['key_multiplier'] == pytest.approx(
        weights.GAIN['wk'] * std, rel=0.1)
    edges = np.cumsum([0, s['d_inner'], s['d_inner'], s['gn'], s['gn'],
                       CFG['mamba_n_heads']])
    for lo, hi, m in zip(edges[:-1], edges[1:], CFG['ssm_multipliers']):
        got = block['w_in'][:, lo:hi].std() * m * CFG['ssm_in_multiplier']
        assert got == pytest.approx(std, rel=0.25), (lo, hi)
    assert block['w_gate'].std() * CFG['mlp_multipliers'][0] == \
        pytest.approx(std, rel=0.1)
    assert np.asarray(tree['embed'], np.float32).std() \
        * CFG['embedding_multiplier'] == pytest.approx(1.0, rel=0.05)


def test_the_three_branches_add_terms_of_comparable_size():
    """What the draw is for (``weights_falcon_h1.py``): a dropped
    branch must not hide under the others. The gains are set at the
    published widths (the module's docstring: 0.27 / 0.25 / 0.25). At
    the rehearsal's width the ONE hot channel of 64 carries four
    fifths of a norm's output, which the attention branch and the MLP
    pass on and the mixer's gated norm takes out again, so the terms
    lie further apart here: within a factor of 10, none under a tenth
    of the stream."""
    W = fam.reference_weights(CFG, SEED)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG['vocab_size'], (96,)))
    x = ref.embed(CFG, W['embed'], toks)
    got = {k: float(v) for k, v in
           ref.branch_rms(CFG, W['layers'][0], x).items()}
    assert got['stream'] == pytest.approx(1.0, rel=0.15)
    terms = [got['attn'], got['ssm'], got['mlp']]
    assert max(terms) < 10 * min(terms) and min(terms) > 0.1, got


# ---- the reference against the equations written a second time -------------

def _loops_block(cfg, w, x):
    """ISSUE 33's block in numpy float64, one step, head and channel at
    a time."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    x = np.asarray(x, np.float64)
    T, eps = x.shape[0], cfg['rms_norm_eps']
    silu = (lambda v: v / (1 + np.exp(-v)))
    norm = (lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + eps)
            * g)
    h = norm(x, w['norm'])
    # attention
    hq, hkv, hd = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                   cfg['head_dim'])
    ha = h * cfg['attention_in_multiplier']
    q = (ha @ w['wq']).reshape(T, hq, hd)
    k = ((ha @ w['wk']) * cfg['key_multiplier']).reshape(T, hkv, hd)
    v = (ha @ w['wv']).reshape(T, hkv, hd)

    def rot(t):
        out = np.zeros_like(t)
        for p in range(T):
            for i in range(hd // 2):
                ang = p * cfg['rope_theta'] ** (-2.0 * i / hd)
                c, s = np.cos(ang), np.sin(ang)
                a, b = t[p, :, i], t[p, :, i + hd // 2]
                out[p, :, i], out[p, :, i + hd // 2] = (a * c - b * s,
                                                         b * c + a * s)
        return out
    q, k = rot(q), rot(k)
    att = np.zeros((T, hq, hd))
    for head in range(hq):
        kv = head // (hq // hkv)
        for t in range(T):
            sc = q[t, head] @ k[:t + 1, kv].T / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            att[t, head] = (p / p.sum()) @ v[:t + 1, kv]
    a = att.reshape(T, hq * hd) @ w['wo'] * cfg['attention_out_multiplier']
    # the mixer
    H, P = cfg['mamba_n_heads'], cfg['mamba_d_head']
    G, N, K = cfg['mamba_n_groups'], cfg['mamba_d_state'], cfg['mamba_d_conv']
    di, gn = cfg['mamba_d_ssm'], G * N
    u = (h * cfg['ssm_in_multiplier']) @ w['w_in']
    m = cfg['ssm_multipliers']
    z = u[:, :di] * m[0]
    xbc = np.concatenate([u[:, di:2 * di] * m[1],
                          u[:, 2 * di:2 * di + gn] * m[2],
                          u[:, 2 * di + gn:2 * di + 2 * gn] * m[3]], -1)
    dt = u[:, 2 * di + 2 * gn:] * m[4]
    conv = np.zeros_like(xbc)
    for t in range(T):
        for j in range(K):          # tap K-1 weighs the current step
            if t - (K - 1 - j) >= 0:
                conv[t] += xbc[t - (K - 1 - j)] * w['conv_w'][j]
    xbc = silu(conv + w['conv_b'])
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
    y = (y * silu(z)).reshape(T, G, di // G)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + eps)
    s = (y.reshape(T, di) * w['gate_norm']) @ w['w_out'] \
        * cfg['ssm_out_multiplier']
    x = x + a + s
    g = norm(x, w['ff_norm'])
    mlp = (silu(g @ w['w_gate'] * cfg['mlp_multipliers'][0])
           * (g @ w['w_up'])) @ w['w_down'] * cfg['mlp_multipliers'][1]
    return x + mlp


def test_the_block_is_the_equations_step_by_step():
    W = fam.reference_weights(CFG, SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (11, CFG['hidden_size']))
    got = ref.layer_forward(CFG, W['layers'][1], x)
    want = _loops_block(CFG, W['layers'][1], x)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * np.abs(want).max())


def test_attention_is_causal_and_carries_position():
    W = fam.reference_weights(CFG, SEED)['layers'][0]
    h = jax.random.normal(jax.random.PRNGKey(2), (12, CFG['hidden_size']))
    full = ref.attn_branch(CFG, W, h)
    np.testing.assert_allclose(ref.attn_branch(CFG, W, h[:7]), full[:7],
                               atol=1e-5)
    # rope: the same two rows in another order give another answer for
    # the last one (Nemotron-H's attention, which has none, would not)
    swapped = h.at[0].set(h[1]).at[1].set(h[0])
    assert float(jnp.abs(ref.attn_branch(CFG, W, swapped)[-1]
                         - full[-1]).max()) > 1e-4


# ---- the check's comparison at the rehearsal's size -------------------------

def _greedy(W, n_prompts=3, prompt_len=40, n_new=24):
    rng = np.random.default_rng(11)
    fwd = jax.jit(lambda t: ref.forward(CFG, W, t))
    out = []
    for _ in range(n_prompts):
        seq = list(map(int, rng.integers(0, CFG['vocab_size'], prompt_len)))
        served = []
        for _ in range(n_new):
            padded = seq + served + [0] * (64 - len(seq) - len(served))
            logits = fwd(jnp.asarray(padded, jnp.int32))
            served.append(int(jnp.argmax(logits[len(seq) + len(served) - 1])))
        out.append({'prompt': seq, 'served': served})
    return out


@pytest.fixture(scope='module')
def served():
    return _greedy(fam.reference_weights(CFG, 43))


def test_the_references_own_tokens_have_no_gap(served):
    found = fam.serve_gaps(CFG, 43, served, pad_to=(64,), rows_pad=12)
    assert found['served']['logit_gap_max'] == 0.0
    assert found['served_tokens'] == found['gaps'][None].size == 3 * 24


@pytest.mark.parametrize('control', [*ref.MECHANISMS, 'bf16-w8a8'])
def test_a_control_reads_above_the_rehearsals_limits(served, control):
    """Each of the six things the configuration adds, dropped, and the
    precision below the stated one, puts other tokens first than the
    reference does: the comparison that decides ``correct`` fails by
    one of the cell's limits."""
    found = fam.serve_gaps(CFG, 43, served, controls=(control,),
                           pad_to=(64,), rows_pad=12)
    got = found['controls'][control]
    assert (got['logit_gap_max'] > SPEC['limits']['logit_gap_max']
            or got['logit_gap_mean'] > SPEC['limits']['logit_gap_mean'])
    assert got['mismatch_share'] > 0


def test_the_stated_precision_rounds_and_the_control_goes_below_it(served):
    found = fam.serve_gaps(CFG, 43, served, controls=('bf16', 'bf16-w8a8'),
                           pad_to=(64,), rows_pad=12)
    low, lower = found['controls']['bf16'], found['controls']['bf16-w8a8']
    assert lower['logit_gap_mean'] > low['logit_gap_mean']
    x = jax.random.normal(jax.random.PRNGKey(3), (8, CFG['hidden_size']))
    W = fam.reference_weights(CFG, 43)['layers'][0]
    exact, rounded = (ref.layer_forward(CFG, W, x),
                      ref.layer_forward(CFG, W, x, 'bf16'))
    assert np.array_equal(np.asarray(rounded), np.asarray(ref.bf16(rounded)))
    assert 0 < float(jnp.abs(exact - rounded).max()) < 0.05 * float(
        jnp.abs(exact).max())
    with pytest.raises(ValueError, match='unknown control'):
        ref.hooks('fp8')


def test_the_control_in_the_programs_place_makes_a_whole_run_incorrect():
    """``test_family_whole_run.py``'s case for this cell: a whole
    rehearsal run with the cell's own control's tokens standing in the
    program's place (``calibrate_family.py --as-control``) prints
    ``correct: false``."""
    control = CELL['cell']['check']['control']
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), 'calibrate_family.py'),
         '--family', 'falcon_h1', '--as-control', control, '--',
         '--workload', NAME, '--seed', '43', '--seconds', '4', '--trace',
         '0', '--rehearse-cpu'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stderr.strip().splitlines()[-1] == 'correct=False'
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line['correct'] is False and line['failed'] == 0
    assert any(line['checks'][n]['value'] > line['checks'][n]['limit']
               for n in ('logit_gap_max', 'logit_gap_mean'))
    assert line['notes']['controls'][control]['mismatch_share'] > 0


# ---- the per-layer readers -------------------------------------------------

def _counters(steps, slot_steps):
    return {'decode_steps': steps, 'ssm_slot_steps': slot_steps}


def _run():
    """Window of 10 s; the traced stretch is seconds 4..6. Request 0
    (prompt 600 = chunks of 256, 256, 88) is sent at 3.9, waits 0.1 and
    gets its first token at 7.0: its chunks are spread over 4.0..7.0,
    at 4.5, 5.5 and 6.5, so two fall inside the stretch (offsets 0 and
    256). Request 1 decodes two tokens inside the stretch."""
    return {
        'config': FULL, 'seconds': 10.0, 'client': {'t0': 1000.0},
        'records': [
            {'idx': 0, 'prompt_len': 600, 'due_s': 3.9, 'sent_s': 3.9,
             'queue_wait_s': 0.1, 'arrivals': [[7.0, 1]]},
            {'idx': 1, 'prompt_len': 300, 'due_s': 0.1, 'sent_s': 0.1,
             'queue_wait_s': 0.0,
             'arrivals': [[2.0, 1], [4.5, 1], [5.5, 1], [7.0, 1]]}],
        'metrics_before': _counters(10, 100),
        'metrics_after': _counters(410, 18100),
        'stepline': {'steps': []},
        'trace': {
            'wall_s': [4.0, 6.0], 'window_s': 2.0, 'peak': PEAK,
            'metrics_start': _counters(150, 6000),
            'metrics_stop': _counters(230, 9600),
            'reduced': {'modules': {
                'jit__decode_paged': {'count': 80, 'seconds': 1.6},
                'jit__prefill_chunk_paged': {'count': 2, 'seconds': 0.03}},
                'ops': {'paged_attention_decode.3': {'count': 720,
                                                     'seconds': 0.05},
                        'fusion.1': {'count': 9, 'seconds': 9.0}}},
            'scopes': {
                '_decode_paged': {'ssm': {'seconds': 0.7, 'count': 7200},
                                  'mlp': {'seconds': 0.5, 'count': 2000},
                                  'attn': {'seconds': 0.1, 'count': 20}},
                '_prefill_chunk_paged': {
                    'ssm': {'seconds': 0.004, 'count': 16}}}},
    }


def _read(name, run):
    return manifest.metric_reader(name).read(run)


def test_the_counter_reader_takes_the_whole_window():
    assert _read('ssm.parallel_live_slots_per_step', _run()) == 45.0


def test_the_rooflines_read_their_scope_their_kernel_and_the_stretch():
    run = _run()
    flops, bytes_ = work.ssm_decode_work(FULL, 3600, 80)
    least = max(flops / PEAK['bf16_flops_per_s'],
                bytes_ / PEAK['hbm_bytes_per_s'])
    assert bytes_ / PEAK['hbm_bytes_per_s'] > flops / PEAK[
        'bf16_flops_per_s']                       # the state bounds it
    assert _read('kernel.parallel_ssm_decode_roofline', run) == \
        pytest.approx(100 * least / 0.7)
    # decode token j of request 1 attends to 300 + j + 1 keys
    flops, bytes_ = work.paged_decode_work(FULL, [302, 303], 64)
    least = max(flops / PEAK['bf16_flops_per_s'],
                bytes_ / PEAK['hbm_bytes_per_s'])
    assert _read('kernel.gqa5_paged_decode_roofline', run) == \
        pytest.approx(100 * least / 0.05)


def test_mfu_counts_the_algorithms_operations():
    run = _run()
    assert _read('mfu.parallel_decode', run) == pytest.approx(
        100 * work.decode_flops(FULL, 3600, 302.0 + 303.0)
        / (1.6 * PEAK['bf16_flops_per_s']))
    assert _read('mfu.parallel_prefill', run) == pytest.approx(
        100 * work.prefill_flops(FULL, [(256, 0), (256, 256)])
        / (0.03 * PEAK['bf16_flops_per_s']))


@pytest.mark.parametrize('name', [
    'mfu.parallel_decode', 'mfu.parallel_prefill',
    'kernel.parallel_ssm_decode_roofline',
    'kernel.gqa5_paged_decode_roofline',
    'ssm.parallel_live_slots_per_step'])
def test_a_program_without_the_counters_or_scopes_reads_nothing(name):
    """What the parent commit's program gives a reader: no such
    counter in ``/metrics``, no such scope, module or kernel in the
    trace."""
    run = _run()
    for key in ('metrics_before', 'metrics_after'):
        run[key] = {'decode_steps': run[key]['decode_steps']}
    for key in ('metrics_start', 'metrics_stop'):
        run['trace'][key] = {'decode_steps': 50}
    run['trace']['scopes'] = {}
    run['trace']['reduced'] = {'modules': {}, 'ops': {}}
    run['records'] = []
    assert _read(name, run) is None
    run['trace'] = None
    assert _read(name, run) is None
