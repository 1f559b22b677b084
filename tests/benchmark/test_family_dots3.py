"""The dots3 family of the benchmark (``benchmark/families/dots3.py``):
the configuration file against the catalog's row, ISSUE 31's size
arithmetic, the seeded weights, the reference's mechanisms, the check's
comparison at the rehearsal's size, and the cell's per-layer readers on
a hand-built traced run."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark import weights_dots3 as weights
from benchmark import work_dots3 as work
from benchmark.families import dots3 as fam
from benchmark.reference import dots3 as ref

BENCH = manifest.load()
NAME = 'dots3-serve.longctx-mixed'
CELL = manifest.cell(BENCH, NAME)
FULL = CELL['config']
PEAK = work.peaks('TPU v5 lite')


def _rehearsal():
    over = CELL['cell']['rehearse']
    cfg = manifest.deep_update(FULL, over['config'])
    return cfg, manifest.deep_update(CELL['cell'], over['cell'])['check']


CFG, SPEC = _rehearsal()
PUBLISHED = dict(
    apply_mla_qkv_lora_rescale=True, attention_bias=False,
    attention_gate_type='headwise', first_k_dense_replace=1,
    hidden_act='silu', hidden_size=5120, index_head_dim=128,
    index_n_heads=64, index_topk=2048, intermediate_size=13824,
    kv_lora_rank=512, max_position_embeddings=524288,
    model_type='dots3_note', moe_intermediate_size=1536, moe_layer_freq=1,
    n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128,
    num_experts_per_tok=8, num_key_value_heads=128, q_lora_rank=1024,
    qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-5,
    rope_scaling=None, rope_theta=80000000, routed_scaling_factor=1,
    scoring_func='sigmoid', sliding_window_size=513,
    swa_attention_gate_type='headwise', swa_kv_lora_rank=1024,
    swa_num_attention_heads=64, swa_num_key_value_heads=64,
    swa_q_lora_rank=1024, swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64,
    swa_rope_theta=50000, swa_v_head_dim=128, tie_word_embeddings=False,
    topk_method='noaux_tc', v_head_dim=128)


# ---- the configuration file ----------------------------------------------

def test_config_keeps_every_published_key():
    assert {k: FULL[k] for k in PUBLISHED} == PUBLISHED
    types = FULL['layer_types']
    assert len(types) == 46 and types.count('full_attention') == 13
    assert types[:5] == ['full_attention', 'full_attention'] + [
        'sliding_attention'] * 3 == FULL['run_layer_types']
    assert all(types[1 + 4 * i:5 + 4 * i] == types[1:5] for i in range(11))
    # the cut: exactly these three, the published values beside them
    assert FULL['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                               'vocab_size']
    assert (FULL['num_hidden_layers'], FULL['n_routed_experts'],
            FULL['vocab_size']) == (5, 32, 19008)
    assert FULL['published'] == {
        'num_hidden_layers': 46, 'n_routed_experts': 256,
        'vocab_size': 152064,
        'layers_by_kind': {'full_attention': 13, 'sliding_attention': 33}}
    assert FULL['n_routed_experts_published'] == 256
    assert FULL['vocab_size'] * 8 == 152064 and FULL['ep_degree'] == 8
    assert weights.layer_types(FULL) == ('full', 'full', 'sliding',
                                         'sliding', 'sliding')
    assert work.counts(FULL) == {'full': 2, 'sliding': 3, 'dense': 1,
                                 'moe': 4}
    assert '8 TPU v5e chips' in FULL['deployment']
    for reading in ('lora_rescale', 'headwise_gate', 'indexer', 'n_group',
                    'window', 'rope_pairing'):
        assert reading in FULL['assumed']
    assert 'float32' in FULL['precision']['index_scores']
    eng = FULL['engine']
    assert (eng['n_slots'], eng['max_seq_len'], eng['page_size'],
            eng['scheduler'], eng['pipeline_depth']) == (16, 33792, 64,
                                                         'fcfs', 1)
    assert eng['n_pages'] == 16 * 33792 // 64 + 1     # 16 full contexts
    assert eng['prefill_chunk'] in (512, 1024)
    assert not (eng['prefix_cache'] or eng['fused_prefill'] or eng['spec_k']
                or eng['quantize'] or eng['tp'] > 1)
    assert manifest.problems() == []


def test_the_config_file_holds_the_catalogs_row():
    """Every number of the catalog's ``config`` under the same key, but
    for the three reduced ones (the guide's rule, checked here where
    the catalog is beside the guide)."""
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    try:
        rows = [json.loads(line) for line in open(path, encoding='utf-8')]
    except OSError:
        pytest.skip('no catalog here')
    row = next(r for r in rows if r['name'] == 'dots3-note-prev')
    assert FULL['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if FULL.get(k) != v}
    assert differ == set(FULL['reduced'])


def test_the_cells_files_are_found_by_name():
    cell, entry = CELL['cell'], CELL['entry']
    assert cell['kind'] == 'serve_open_family' and entry['chips'] == 1
    assert hasattr(manifest.kind(cell['kind']), 'run')
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        entry['config'], entry['traffic'], entry['chips'])
    reported = {m['name'] for m in manifest.metrics_of(BENCH, 'end_to_end',
                                                       NAME)}
    assert reported == set(cell['reports']) == {'ttft_p90_s', 'ttft_mean_s',
                                                'setup_s'}
    assert 'decode unguarded' in entry['why'] and len(entry['why']) <= 200
    assert cell['check']['control'] in fam.CONTROLS
    assert cell['check']['min_tokens'] == 200
    mix = CELL['traffic']
    assert (mix['loop'], mix['burst']) == ('open', 1)
    assert mix['prompt'] == {'dist': 'pareto', 'shape': 2.0, 'scale': 8192,
                             'min': 8192, 'max': 32768}
    assert mix['output'] == {'dist': 'uniform', 'min': 128, 'max': 512}
    assert 'order_seed' in mix and 'knee' in mix['rate_note']
    # every prompt is over the top-k, and fits the cache with its answer
    assert mix['prompt']['min'] > FULL['index_topk']
    assert (mix['prompt']['max'] + mix['output']['max']
            < FULL['engine']['max_seq_len'])
    per_layer = {m['name'] for m in manifest.metrics_of(BENCH, 'per_layer',
                                                        NAME)}
    assert per_layer == {
        'mfu.sparse_prefill', 'mfu.sparse_decode',
        'kernel.latent_attention_roofline', 'kernel.gated_experts_roofline',
        'attn.selected_share', 'cache.window_rows_per_slot',
        'kernel.selected_attention_roofline', 'kernel.index_scores_roofline'}
    # the rehearsal: top-k 8 and window 5, both under its contexts
    over = cell['rehearse']
    assert (over['config']['index_topk'],
            over['config']['sliding_window_size']) == (8, 5)
    assert over['traffic']['prompt']['min'] > 8


# ---- the work counts, against ISSUE 31's arithmetic ------------------------

def test_parameter_counts():
    m = 1e6
    assert round(work.attn_params(FULL, 'full') / m, 1) == 144.0
    assert round(work.attn_params(FULL, 'sliding') / m, 1) == 90.8
    assert round(work.index_params(FULL) / m, 1) == 9.4
    assert work.expert_params(FULL) == 3 * 5120 * 1536
    assert round(work.expert_params(FULL) / m, 2) == 23.59
    assert round(work.moe_dense_params(FULL) / m, 1) == 24.9
    assert round(work.dense_mlp_params(FULL) / m, 1) == 212.3
    blocks = [work.block_params(FULL, k, d) for k, d in
              weights.block_kinds(FULL)]
    assert [round(b / m, 1) for b in blocks] == [356.4, 923.9, 870.7, 870.7,
                                                 870.7]
    assert round(2 * 5120 * 19008 / m, 1) == 194.6
    assert round(work.total_params(FULL) / 1e9, 3) == 4.087
    assert round(work.total_params(FULL) * 2 / 1e9, 2) == 8.17
    # whole, one expert layer is 6.0 B parameters: no chip holds one
    whole = dict(FULL, n_routed_experts=256)
    assert round((256 * work.expert_params(whole)
                  + work.moe_dense_params(whole)) / 1e9, 1) == 6.1
    # the program's tree has that many leaves' elements
    from skypilot_tpu.models import dots3
    tree = jax.eval_shape(lambda: dots3.Dots3Config.note_prev_ep8()
                          .init_params(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(v.shape)) for v in
               jax.tree_util.tree_leaves(tree)) == work.total_params(FULL)


def test_cache_bytes():
    # a full layer: 576 + 128 values a token; two of them
    assert work.cache_bytes_per_token(FULL) == 2 * (576 + 128) * 2 == 2816
    assert round(16 * 33792 * 2816 / 1e9, 2) == 1.52
    assert work.window_row_bytes(FULL) == 2176
    # window + one chunk of 1024, whole pages: 1,600 rows a slot
    assert round(3 * 16 * 1600 * 2176 / 1e9, 2) == 0.17


def test_selection_and_attention_work():
    # a chunk of 1024 at offset 16384: every query scores its context
    assert work.scored_keys(1024, 16384) == 1024 * 16384 + 1024 * 1025 / 2
    assert work.selected_keys(FULL, 1024, 16384) == 1024 * 2048
    assert work.window_keys(FULL, 1024, 16384) == 1024 * 513
    # the first chunk: query t keeps all t + 1
    assert work.selected_keys(FULL, 1024, 0) == 1024 * 1025 / 2
    assert work.selected_keys(FULL, 1024, 1536) == (
        sum(range(1537, 2049)) + 512 * 2048)
    assert work.window_keys(FULL, 1024, 0) == sum(range(1, 514)) + 511 * 513
    # ISSUE 31's arithmetic a token at a context of 16k: the indexer
    # 0.27 GFLOP a full layer, attention over 2,048 rows 0.57
    assert round(work.index_flops(FULL, 16384) / 1e9, 2) == 0.27
    assert round(work.absorbed_attention_flops(FULL, 'full', 2048) / 1e9,
                 2) == 0.57
    flops, bytes_ = work.attn_scope_work(FULL, [(1, 16383)])
    assert flops > 2 * (0.27e9 + 0.57e9)
    # a decode token reads its chosen rows and every indexer key
    assert bytes_ > 2 * 2 * (2048 * 576 + 16384 * 128)
    flops, bytes_ = work.gated_experts_work(FULL, 120, 50)
    assert (flops, bytes_) == (2 * 23_592_960 * 120, 50 * 23_592_960 * 2)
    # per prompt token the share multiplies 0.97 B parameters
    assert round((work.token_matmul_params(FULL)
                  + 4 * work.expert_params(FULL)) / 1e9, 2) == 0.97


# ---- the seeded weights ------------------------------------------------------

@pytest.fixture(scope='module')
def tree():
    return weights.init_all(CFG, 2**31 + 5)


def test_a_block_made_alone_equals_the_programs_block(tree):
    key = weights.root_key(2**31 + 5)
    for index, (kind, dense) in enumerate(weights.block_kinds(CFG)):
        alone = jax.jit(lambda k, i, kind=kind, dense=dense: weights.block(
            CFG, kind, dense, k, i))(key, jnp.int32(index))
        for a, b in zip(jax.tree_util.tree_leaves(alone),
                        jax.tree_util.tree_leaves(tree['layers'][index])):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    assert tree['layers'][1]['ffn']['router'].dtype == jnp.float32
    assert tree['layers'][1]['ffn']['router_bias'].dtype == jnp.float32
    assert tree['layers'][1]['attn']['w_uq'].dtype == jnp.bfloat16


def test_an_expert_is_keyed_by_its_published_id_and_the_bias_mirrored():
    key = weights.root_key(7)
    whole = dict(CFG, n_routed_experts=8, expert_offset=0)
    upper = dict(CFG, n_routed_experts=4, expert_offset=4)
    a = weights.ffn(whole, key, 1, False)
    b = weights.ffn(upper, key, 1, False)
    for leaf in ('w_gate', 'w_up', 'w_down'):
        np.testing.assert_array_equal(np.asarray(a[leaf][4:], np.float32),
                                      np.asarray(b[leaf], np.float32))
    np.testing.assert_array_equal(a['router'], b['router'])
    bias = np.asarray(a['router_bias'])
    np.testing.assert_array_equal(bias[:4], bias[4:])      # ep_degree 2
    assert bias.std() > 0


def test_every_hidden_norm_has_the_hot_channels_of_weights_py(tree):
    from benchmark import weights as base
    hot = np.asarray(base.hot_channels(CFG, weights.root_key(2**31 + 5)))
    for block in tree['layers']:
        for half in ('attn', 'ffn'):
            w = np.asarray(block[half]['norm'], np.float32)
            assert (w[hot] > 8).all() and np.delete(w, hot).max() < 2


# ---- the reference's own shortcuts -------------------------------------------

def test_a_window_blocks_key_span_equals_the_dense_mask(monkeypatch):
    """A sliding block of the reference works a query block against
    the query blocks its windows reach only (``ref.key_spans``). At 96
    positions (query blocks of 32, window 5: a span of 64 under the
    length) that equals every key under the dense window mask."""
    T = 96
    key = weights.root_key(5)
    w = fam._makers(CFG)['sliding', False](key, jnp.int32(2))['attn']
    x = jax.random.normal(jax.random.PRNGKey(3), (T, CFG['hidden_size']))
    first, span, ok = ref.key_spans(CFG, 'sliding', T)
    assert span == 64 and list(map(int, first)) == [0, 0, 32]
    got = ref.attention(CFG, 'sliding', w, x)

    def dense(cfg, kind, T, act=None):
        at = jnp.arange(T)
        mask = (at[None, :] <= at[:, None]) & (
            at[None, :] > at[:, None] - cfg['sliding_window_size'])
        qb = ref._query_block(T)
        return (jnp.zeros((T // qb,), jnp.int32), T,
                mask.reshape(T // qb, qb, T))
    monkeypatch.setattr(ref, 'key_spans', dense)
    want = ref.attention(CFG, 'sliding', w, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and the control that drops the window sees every earlier key
    monkeypatch.undo()
    assert ref.key_spans(CFG, 'sliding', T, 'no-window')[1:] == (T, None)
    assert ref.key_spans(CFG, 'full', T)[1:] == (T, None)


@pytest.mark.parametrize('index', [0, 1, 2])
def test_layer_and_margin_is_the_forward_and_the_margin(index):
    """``serve_gaps`` takes the reference through a block once for
    both its stream and its router margin."""
    kind, dense = weights.block_kinds(CFG)[index]
    w = fam._makers(CFG)[kind, dense](weights.root_key(5), jnp.int32(index))
    x = jax.random.normal(jax.random.PRNGKey(4), (64, CFG['hidden_size']))
    out, margin = ref.layer_and_margin(CFG, kind, w, x)
    np.testing.assert_allclose(out, ref.layer_forward(CFG, kind, w, x),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(margin, ref.block_margin(CFG, kind, w, x),
                               rtol=1e-5)
    assert bool(jnp.isinf(margin).all()) == dense


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
    assert found['served_tokens'] == 3 * 24


@pytest.mark.parametrize('control', ['no-index', 'no-window', 'no-gate',
                                     'no-rescale', 'bf16-w8a8'])
def test_a_control_reads_above_the_rehearsals_limits(served, control):
    """Each mechanism dropped, and the precision below the stated one,
    puts other tokens first than the reference does: the comparison
    that decides ``correct`` fails by one of the cell's limits."""
    found = fam.serve_gaps(CFG, 43, served, controls=(control,),
                           pad_to=(64,), rows_pad=12)
    got = found['controls'][control]
    assert (got['logit_gap_max'] > SPEC['limits']['logit_gap_max']
            or got['logit_gap_mean'] > SPEC['limits']['logit_gap_mean'])
    assert got['mismatch_share'] > 0


def test_the_stated_precision_rounds_and_passes_near_the_reference(served):
    found = fam.serve_gaps(CFG, 43, served, controls=('bf16', 'bf16-w8a8'),
                           pad_to=(64,), rows_pad=12)
    low, lower = found['controls']['bf16'], found['controls']['bf16-w8a8']
    assert lower['logit_gap_mean_all'] > low['logit_gap_mean_all']


# ---- the per-layer readers ---------------------------------------------------

def _counters(steps, assign, touched, scored, selected, rows, pages, slots):
    return {'decode_steps': steps, 'moe_local_assignments': assign,
            'moe_experts_touched': touched, 'moe_expert_load_max': 0,
            'index_scored_keys': scored, 'index_selected_keys': selected,
            'window_rows_live': rows, 'latent_pages_live': pages,
            'cache_slots_live': slots}


def _run():
    """Window of 10 s; the traced stretch is seconds 4..6. Request 0
    (prompt 9216 = 9 chunks of 1024) is sent at 3.9, waits 0.1 and gets
    its first token at 8.5: its chunks are spread over 4.0..8.5, one
    every 0.5 s from 4.25, so four fall inside the stretch (at 4.25,
    4.75, 5.25, 5.75: offsets 0, 1024, 2048, 3072). Request 1 decodes
    two tokens inside the stretch."""
    return {
        'config': FULL, 'seconds': 10.0, 'client': {'t0': 1000.0},
        'records': [
            {'idx': 0, 'prompt_len': 9216, 'due_s': 3.9, 'sent_s': 3.9,
             'queue_wait_s': 0.1, 'arrivals': [[8.5, 1]]},
            {'idx': 1, 'prompt_len': 10000, 'due_s': 0.1, 'sent_s': 0.1,
             'queue_wait_s': 0.0,
             'arrivals': [[2.0, 1], [4.5, 1], [5.5, 1], [7.0, 1]]}],
        'metrics_before': _counters(10, 0, 0, 1000, 1000, 0, 0, 0),
        'metrics_after': _counters(110, 90000, 9000, 9_001_000, 1_001_000,
                                   80_000, 9000, 100),
        'stepline': {'steps': []},
        'trace': {
            'wall_s': [4.0, 6.0], 'window_s': 2.0, 'peak': PEAK,
            'metrics_start': _counters(50, 40000, 4000, 0, 0, 0, 0, 0),
            'metrics_stop': _counters(60, 56392, 4500, 0, 0, 0, 0, 0),
            'reduced': {'modules': {
                'jit__decode_paged': {'count': 10, 'seconds': 0.2},
                'jit__prefill_chunk_paged': {'count': 4, 'seconds': 0.4}},
                'ops': {'selected_head_attention.3': {'count': 8,
                                                      'seconds': 0.1},
                        'selected_latent_attention.7': {'count': 20,
                                                        'seconds': 0.02},
                        'latent_index_scores.5': {'count': 8,
                                                  'seconds': 0.01},
                        'fusion.1': {'count': 9, 'seconds': 9.0}}},
            'scopes': {
                '_decode_paged': {'moe.experts': {'seconds': 0.05,
                                                  'count': 40},
                                  'attn': {'seconds': 0.1, 'count': 20}},
                '_prefill_chunk_paged': {
                    'moe.experts': {'seconds': 0.15, 'count': 16},
                    'attn': {'seconds': 0.2, 'count': 80}}}},
    }


def _read(name, run):
    return manifest.metric_reader(name).read(run)


CHUNKS = [(1024, 0), (1024, 1024), (1024, 2048), (1024, 3072)]


def test_counter_readers_take_the_whole_window():
    run = _run()
    assert _read('attn.selected_share', run) == pytest.approx(
        100.0 * 1_000_000 / 9_000_000)
    assert _read('cache.window_rows_per_slot', run) == 800.0


def test_the_rooflines_read_their_scopes_and_the_stretch():
    run = _run()
    flops, bytes_ = work.attn_scope_work(FULL, CHUNKS)
    least = max(flops / PEAK['bf16_flops_per_s'],
                bytes_ / PEAK['hbm_bytes_per_s'])
    assert _read('kernel.latent_attention_roofline', run) == pytest.approx(
        100 * least / 0.2)
    flops, bytes_ = work.gated_experts_work(FULL, 16392, 500)
    least = max(flops / PEAK['bf16_flops_per_s'],
                bytes_ / PEAK['hbm_bytes_per_s'])
    assert _read('kernel.gated_experts_roofline', run) == pytest.approx(
        100 * least / (0.05 + 0.15))
    # the kernel by its operation name, in both programs; its work is
    # the chosen rows': 1 + .. + 1024 + 1024 x 1025 / 2 + 1024 x 1024
    # under the top-k, 2 x 1024 x 2048 and two decode tokens' 2 x 2048
    flops, bytes_ = work.selected_attention_work(FULL, CHUNKS,
                                                 [10002, 10003])
    chosen = (1024 * 1025 / 2 + (1024 * 1024 + 1024 * 1025 / 2)
              + 2 * 1024 * 2048 + 2 * 2048)
    assert flops == 2 * 2 * 128 * (2 * 512 + 64) * chosen
    least = max(flops / PEAK['bf16_flops_per_s'],
                bytes_ / PEAK['hbm_bytes_per_s'])
    assert _read('kernel.selected_attention_roofline', run) == pytest.approx(
        100 * least / 0.12)
    # the indexer's kernel: every (query, key) pair of the four chunks
    flops, bytes_ = work.index_scores_work(FULL, CHUNKS)
    pairs = sum(1024 * off + 1024 * 1025 / 2 for _, off in CHUNKS)
    assert flops == 2 * 2 * 64 * 128 * pairs
    assert bytes_ > 2 * 4 * pairs
    least = max(flops / PEAK['bf16_flops_per_s'],
                bytes_ / PEAK['hbm_bytes_per_s'])
    assert _read('kernel.index_scores_roofline', run) == pytest.approx(
        100 * least / 0.01)


def test_mfu_counts_the_algorithms_operations():
    run = _run()
    # 4096 prefill tokens and 2 decode tokens shared 16392 routed
    # passes over 4 expert blocks: one a token and block
    per_token = 16392 / (4098 * 4)
    assert per_token == 1.0
    assert _read('mfu.sparse_prefill', run) == pytest.approx(
        100 * work.prefill_flops(FULL, CHUNKS, 1.0)
        / (0.4 * PEAK['bf16_flops_per_s']))
    # decode token j of request 1 attends to 10000 + j + 1 keys
    assert _read('mfu.sparse_decode', run) == pytest.approx(
        100 * work.decode_flops(FULL, [10002, 10003], 1.0)
        / (0.2 * PEAK['bf16_flops_per_s']))
    # the selection is what is counted: attention over 2,048 rows at a
    # context of 10k, not over 10k
    dense = work.absorbed_attention_flops(FULL, 'full', 10002)
    kept = work.absorbed_attention_flops(FULL, 'full', 2048)
    assert work.decode_flops(FULL, [10002], 0.0) < (
        work.decode_flops(FULL, [2048], 0.0) + 2 * (dense - kept))


@pytest.mark.parametrize('name', [
    'mfu.sparse_prefill', 'mfu.sparse_decode',
    'kernel.latent_attention_roofline', 'kernel.gated_experts_roofline',
    'attn.selected_share', 'cache.window_rows_per_slot',
    'kernel.selected_attention_roofline', 'kernel.index_scores_roofline'])
def test_a_program_without_the_counters_or_scopes_reads_nothing(name):
    """What the parent commit's program gives a reader: no such
    counter in ``/metrics``, no such scope or module in the trace."""
    run = _run()
    for key in ('metrics_before', 'metrics_after'):
        run[key] = {'decode_steps': run[key]['decode_steps']}
    for key in ('metrics_start', 'metrics_stop'):
        run['trace'][key] = {'decode_steps': 50}
    run['trace']['scopes'] = {}
    run['trace']['reduced'] = {'modules': {}, 'ops': {}}
    assert _read(name, run) is None
    run['trace'] = None
    assert _read(name, run) is None
