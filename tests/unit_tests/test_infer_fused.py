"""Fused mixed prefill+decode steps + int8 KV pages: the tier-1 gates.

Tentpole contracts (ISSUE 11, gated the way PR 3/8 gated theirs):

- Greedy outputs are BIT-IDENTICAL fused-on vs fused-off — dense and
  paged, pipeline depth 0 and 1, speculation on and off — over the
  mixed-length + paged-preemption workload. Fusing one prefill chunk
  into the decode dispatch changes step timing only, never tokens.
- int8 KV pages are gated at a PINNED TOLERANCE vs bf16 (quantization
  is lossy by design, so the bar is a max decode-logit delta plus a
  greedy-divergence-step floor on the template workload), with the
  resident-page byte math asserted (~2x pages per HBM byte).
- The prefill-stall decomposition metrics move the right way:
  fused-on steps fuse (decode_stall_steps stays 0), fused-off steps
  stall.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.jax

import jax.numpy as jnp  # noqa: E402

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.infer import model as model_lib  # noqa: E402
from skypilot_tpu.infer import paged_cache as paged_cache_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()

# The PR 3 determinism workload: mixed short/multi-chunk prompts, more
# requests than slots, and a page pool small enough to force
# preemption + resume-by-recompute mid-run on the paged engines.
_PROMPTS = [[11] * 60, [23] * 60, [37] * 60,
            [5, 17, 101, 7], [9, 8, 7, 6, 5]]

# int8 tolerance pins (CPU/interpret path; ~2.5x headroom over the
# 0.0079 observed under conftest's weights — quantization noise above
# these is a regression in the quant/dequant path, not model weather).
_MAX_LOGIT_DELTA = 0.02
_DIVERGENCE_FLOOR = 12


@pytest.fixture(scope='module')
def params(tiny_params):
    return tiny_params


@pytest.fixture(scope='module')
def gold(greedy_oracle):
    """What every UNFUSED engine produces over this workload: the
    no-cache float32 forward's greedy tokens (conftest's oracle), which
    test_infer_sched / _pipeline / _spec hold the unfused engines to —
    dense and paged, depth 0 and 1, spec on and off. Comparing the
    FUSED engines against it gates fused-on vs fused-off without
    re-running the unfused baselines here (tier-1 wall-clock is a
    budget)."""
    return greedy_oracle(_PROMPTS, 6)


def _engine(params, fused, paged, kv_dtype='bfloat16', spec_k=3):
    kw = {}
    if paged:
        kw.update(paged=True, page_size=16, n_pages=13,
                  kv_dtype=kv_dtype)
    return engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, pipeline_depth=1,
                                fused_prefill=fused, spec_k=spec_k,
                                **kw))


def _matrix_runs(eng):
    """(depth, spec) -> outputs, on ONE engine via the runtime knobs
    (each build pays a full compile on this box; the knob path is also
    exactly what the multihost driver / ops tooling uses). Two passes
    cover both values of both axes — (depth 1, spec on) and (depth 0,
    spec off); the remaining cross combos ride the slow-marked
    composition test, and depth/spec invariance is itself gated by
    test_infer_pipeline/test_infer_spec."""
    out = {}
    for depth, spec in ((1, 3), (0, 0)):
        eng.set_pipeline_depth(depth)
        eng.set_spec_k(spec)
        out[(depth, spec)] = [
            r.output_tokens
            for r in eng.generate(_PROMPTS, max_new_tokens=6)]
    return out


@pytest.fixture(scope='module')
def dense_matrix(params):
    eng = _engine(params, fused=True, paged=False)
    return eng, _matrix_runs(eng)


@pytest.fixture(scope='module')
def paged_matrix(params):
    eng = _engine(params, fused=True, paged=True)
    return eng, _matrix_runs(eng)


def test_greedy_identical_fused_on_off_dense(dense_matrix, gold):
    _, fused = dense_matrix
    for key, out in fused.items():
        assert out == gold, (
            f'fused mixed steps changed greedy output (dense, '
            f'depth/spec {key})')


def test_greedy_identical_fused_on_off_paged_preempting(paged_matrix,
                                                        gold):
    eng, fused = paged_matrix
    # The workload must actually exercise the hard path: pool
    # pressure (the fused-chunk plan-drop / deferral ladder).
    assert eng.metrics()['preemptions'] >= 1, (
        'workload never preempted — the gate is not testing fusion '
        'under page pressure')
    for key, out in fused.items():
        assert out == gold, (
            f'fused mixed steps changed greedy output (paged, '
            f'depth/spec {key})')


def test_fused_metrics_decomposition(dense_matrix, paged_matrix):
    """fused_steps count real fused dispatches and the decode batch
    never waits on a standalone prefill dispatch with fusion on;
    prefill accounting covers every prompt token exactly once per
    (re-)prefill — never fewer (preemption recompute legitimately
    re-counts)."""
    for eng, _ in (dense_matrix, paged_matrix):
        m = eng.metrics()
        assert m['fused_steps'] > 0, 'no chunk ever rode a dispatch'
        assert m['decode_stall_steps'] == 0, (
            'fused engine still dispatched standalone prefill under '
            'an active decode batch')
        # _matrix_runs made 2 generate passes; each pass prefills
        # every prompt at least once (preemption recompute adds more).
        assert m['prefill_tokens'] >= 2 * sum(
            len(p) for p in _PROMPTS), m
        assert m['prefill_tokens_per_step'] > 0


@pytest.mark.slow
def test_fused_matrix_cross_combos(params, gold):
    """The remaining (depth, spec) cross combos — (1, 0) and (0, 3) —
    on both cache flavors, out of the tier-1 wall-clock budget (the
    tier-1 gates cover both values of both axes; this closes the
    cross product)."""
    for paged in (False, True):
        eng = _engine(params, fused=True, paged=paged)
        for depth, spec in ((1, 0), (0, 3)):
            eng.set_pipeline_depth(depth)
            eng.set_spec_k(spec)
            outs = [r.output_tokens
                    for r in eng.generate(_PROMPTS, max_new_tokens=6)]
            assert outs == gold, (paged, depth, spec)


def test_unfused_engine_stalls_decode(params):
    """The counterexample the fused mode exists for: with fusion OFF,
    a prompt admitted mid-decode dispatches standalone prefill chunks
    while slots decode — decode_stall_steps moves."""
    eng = _engine(params, fused=False, paged=False, spec_k=0)
    first = eng.submit([3, 4, 5], max_new_tokens=32)
    for _ in range(4):
        eng.step()
    assert first.output_tokens and not first.done
    eng.submit([9] * 60, max_new_tokens=4)       # mid-decode arrival
    for _ in range(4):
        eng.step()
    assert eng.metrics()['decode_stall_steps'] > 0, (
        'standalone prefill under an active decode batch never '
        'counted as a stall')
    eng.run_until_idle()


def test_fused_off_default_has_no_mixed_program(params):
    eng = _engine(params, fused=False, paged=False, spec_k=0)
    assert 'mixed' not in eng.compiled_counts()
    m = eng.metrics()
    assert m['fused_steps'] == 0


# ---------------------------------------------------------------------------
# int8 KV pages
# ---------------------------------------------------------------------------
def test_int8_requires_paged(params):
    with pytest.raises(ValueError, match='paged'):
        engine_lib.InferenceEngine(
            CFG, params,
            engine_lib.EngineConfig(n_slots=2, max_seq_len=64,
                                    prefill_buckets=(16,),
                                    kv_dtype='int8'))


def test_int8_kv_page_bytes_half_of_bf16(params):
    """The resident-page claim: one int8 page (values + fp32 row
    scales) costs ~half a bf16 page, so a fixed HBM budget holds ~2x
    the pages."""
    bf = _engine(params, fused=True, paged=True,
                 kv_dtype='bfloat16', spec_k=0)
    i8 = _engine(params, fused=True, paged=True, kv_dtype='int8',
                 spec_k=0)
    b_bf = bf.metrics()['kv_page_bytes']
    b_i8 = i8.metrics()['kv_page_bytes']
    ratio = b_bf / b_i8
    # Exact: 2*hd / (hd + 4) — int8 values plus one fp32 scale per
    # row vs 2-byte bf16 values. The tiny test model's hd=16 gives
    # 1.6x; a production head_dim (>=64) gives 1.88-1.94x, which is
    # the "~2x resident pages" claim.
    hd = CFG.head_dim
    assert ratio == pytest.approx(2 * hd / (hd + 4)), (b_bf, b_i8)
    assert 2 * 128 / (128 + 4) > 1.9, 'production-hd ratio regressed'
    assert i8.metrics()['kv_dtype'] == 'int8'


def test_int8_greedy_divergence_floor(params, paged_matrix):
    """Greedy generation under int8 KV tracks bf16 for at least the
    pinned number of steps on the template workload (full preemption
    machinery live). Not bit-identity — the pinned-tolerance bar
    quantization is gated at. The bf16 lane reuses the paged fused
    engine (identical config minus kv_dtype) rather than building a
    fifth engine — tier-1 wall-clock is a budget."""
    bf_eng = paged_matrix[0]
    bf_eng.set_spec_k(0)
    try:
        outs = {'bfloat16': [
            r.output_tokens
            for r in bf_eng.generate(_PROMPTS, max_new_tokens=14)]}
    finally:
        # Restore the fixture's knobs: later tests sharing the
        # module-scoped engine must not inherit this lane's config.
        bf_eng.set_spec_k(3)
        bf_eng.set_pipeline_depth(1)
    i8 = _engine(params, fused=True, paged=True, kv_dtype='int8',
                 spec_k=0)
    outs['int8'] = [r.output_tokens
                    for r in i8.generate(_PROMPTS, max_new_tokens=14)]
    for a, b in zip(outs['bfloat16'], outs['int8']):
        agree = next((i for i, (x, y) in enumerate(zip(a, b))
                      if x != y), min(len(a), len(b)))
        assert agree >= _DIVERGENCE_FLOOR, (
            f'int8 KV diverged from bf16 at step {agree} '
            f'(floor {_DIVERGENCE_FLOOR}): {a} vs {b}')


def test_int8_decode_logit_delta_pinned(params):
    """Model-level tolerance pin: prefill the same prompt into a bf16
    and an int8 paged cache, decode one step, and bound the max logit
    delta. Catches quant/dequant-path regressions (wrong scale axis,
    missing dequant in a kernel) that the divergence floor might
    absorb."""
    page, n_pages, slots, maxp = 16, 9, 2, 6
    prompt = np.asarray([7, 3, 11, 3] * 4, np.int32)      # C=16
    table = np.zeros((slots, maxp), np.int32)
    table[0, :2] = [1, 2]
    tables = jnp.asarray(table)
    logits = {}
    for dt in ('bfloat16', 'int8'):
        cache = paged_cache_lib.init_paged_cache(
            CFG.n_layers, slots, n_pages, page, CFG.n_kv_heads,
            CFG.head_dim,
            dtype=jnp.int8 if dt == 'int8' else jnp.bfloat16)
        params_ = params
        cache, _ = model_lib.paged_prefill_chunk(
            CFG, params_, cache, jnp.int32(0), tables[0],
            jnp.asarray(prompt), jnp.int32(0), jnp.int32(16))
        step_logits, _ = model_lib.paged_decode_step(
            CFG, params_, cache, tables,
            jnp.asarray([5, 0], jnp.int32),
            jnp.asarray([True, False]))
        logits[dt] = np.asarray(step_logits[0])
    delta = float(np.max(np.abs(logits['bfloat16'] - logits['int8'])))
    assert delta <= _MAX_LOGIT_DELTA, (
        f'int8 decode logits drifted {delta:.4f} from bf16 '
        f'(pin {_MAX_LOGIT_DELTA})')
    assert delta > 0.0, (
        'zero delta — the int8 path silently ran bf16, the pin is '
        'vacuous')


@pytest.mark.slow
def test_int8_with_spec_and_prefix_runs_clean(params):
    """The full composition: int8 pages + fused steps + speculation +
    prefix cache + preemption on one engine — every request completes
    with in-range tokens and the page pool balances. Marked slow: the
    tier-1 gates above (divergence floor, logit-delta pin, recompile
    pin with prefix+spec in test_infer_pipeline) cover the acceptance
    surface; this is the belt-and-braces composition smoke."""
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, paged=True,
                                page_size=16, n_pages=13,
                                prefix_cache=True, kv_dtype='int8',
                                fused_prefill=True, spec_k=3))
    reqs = eng.generate(_PROMPTS, max_new_tokens=8)
    assert all(r.done for r in reqs)
    assert all(0 <= t < CFG.vocab_size
               for r in reqs for t in r.output_tokens)
    # Prefix donations may retain pages; cached + free must cover the
    # whole pool (nothing leaked).
    m = eng.metrics()
    assert (m['pages_free'] + m['prefix_cached_pages']
            == m['pages_total'] - 1)
