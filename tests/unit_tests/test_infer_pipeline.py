"""Overlapped decode pipeline: determinism gate, recompile stability,
event-driven token delivery, incremental streaming detokenization.

The dispatch-ahead loop (engine.EngineConfig.pipeline_depth) makes host
state stale-by-one behind the in-flight decode. These tests pin the
contracts that staleness must never break:

- Greedy outputs are BIT-IDENTICAL at depth 0 and depth 1 across a
  mixed prompt-length + paged-preemption workload (the tier-1 gate for
  the overlap).
- The number of distinct compiled programs stays at the predicted
  count under a mixed/preemption workload — the dirty-flag device
  caching and dispatch-ahead must not introduce shape-driven
  recompiles.
- Token delivery is event-driven: waiters wake on append/finish, not
  on a poll cadence.
"""
import threading

import pytest

pytestmark = pytest.mark.jax

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.infer import server as server_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()


@pytest.fixture(scope='module')
def params(tiny_params):
    return tiny_params


# The determinism workload: mixed short/multi-chunk prompts, more
# requests than slots (refill), and — for the paged runs — a pool small
# enough (12 usable pages x 16 = 192 tokens for ~3x66) to force
# preemption + resume-by-recompute mid-run.
_PROMPTS = [[11] * 60, [23] * 60, [37] * 60,
            [5, 17, 101, 7], [9, 8, 7, 6, 5]]


def _generate(params, depth, paged, temperature=0.0):
    kw = {}
    if paged:
        kw.update(paged=True, page_size=16, n_pages=13)
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32,
                                pipeline_depth=depth, **kw))
    reqs = eng.generate(_PROMPTS, max_new_tokens=6,
                        temperature=temperature)
    return eng, [r.output_tokens for r in reqs]


# Each engine build pays a full compile on this 1-core box, so each
# variant is built ONCE (module fixture) and run at depth 1 first, then
# at depth 0 via set_pipeline_depth on the same engine — which is also
# exactly the runtime-reconfiguration path the multihost driver uses.
@pytest.fixture(scope='module')
def dense_runs(params):
    eng, out1 = _generate(params, depth=1, paged=False)
    eng.set_pipeline_depth(0)
    out0 = [r.output_tokens
            for r in eng.generate(_PROMPTS, max_new_tokens=6)]
    return eng, out0, out1


@pytest.fixture(scope='module')
def paged_runs(params):
    eng, out1 = _generate(params, depth=1, paged=True)
    preempt_d1 = eng.metrics()['preemptions']
    pages_after_d1 = eng.allocator.free_pages
    eng.set_pipeline_depth(0)
    out0 = [r.output_tokens
            for r in eng.generate(_PROMPTS, max_new_tokens=6)]
    return eng, out0, out1, preempt_d1, pages_after_d1


def test_greedy_identical_depth0_vs_depth1_dense(dense_runs,
                                                 greedy_oracle):
    _, out0, out1 = dense_runs
    assert out0 == greedy_oracle(_PROMPTS, 6), (
        'the dense depth-0 engine left the no-cache float32 forward')
    assert out0 == out1, (
        'dispatch-ahead changed greedy output (dense)')


def test_greedy_identical_depth0_vs_depth1_paged_preempting(
        paged_runs, dense_runs):
    eng, out0, out1, preempt_d1, pages_after_d1 = paged_runs
    # The workload must actually exercise the hard path: pool pressure.
    assert preempt_d1 >= 1, (
        'workload never preempted — the gate is not testing overlap '
        'under page pressure')
    assert out0 == out1, ('dispatch-ahead changed greedy output under '
                          'paged preemption')
    # And the depths agree with the dense engine too (same math).
    assert out1 == dense_runs[2]
    # All pages returned after the overlapped run drained.
    assert pages_after_d1 == eng.allocator.n_pages - 1


def test_overlap_metrics_coherent(dense_runs):
    eng, _, _ = dense_runs
    m = eng.metrics()
    assert m['pipeline_depth'] == 0      # after the fixture's d0 pass
    assert m['tokens_in_flight'] == 0    # drained at idle
    assert m['decode_tokens'] == 2 * 6 * len(_PROMPTS), (
        'dropped/garbage in-flight tokens must not count as decoded')
    assert m['decode_tokens_per_sec'] > 0


def test_sampled_run_completes_at_depth1(paged_runs):
    """Temperature > 0 at depth 1: no determinism claim, but every
    request completes with in-range tokens (the stale-by-one mask and
    dropped post-finish tokens must not corrupt sampled runs)."""
    eng = paged_runs[0]
    eng.set_pipeline_depth(1)
    outs = [r.output_tokens
            for r in eng.generate(_PROMPTS, max_new_tokens=6,
                                  temperature=1.0)]
    assert all(len(o) == 6 for o in outs)
    assert all(0 <= t < CFG.vocab_size for o in outs for t in o)


def test_recompile_stability_mixed_preempting_workload(paged_runs):
    """Compiled-program count stays at the predicted figure through a
    mixed short/long + paged-preemption workload, and a SECOND pass of
    the same shapes compiles nothing new — guards the dirty-flag
    caching and dispatch-ahead against silent shape-driven recompiles.

    (Runs after the shared engine's depth-1/depth-0/sampled passes —
    by then every shape the workload can produce has been seen.)"""
    eng = paged_runs[0]
    counts = eng.compiled_counts()
    if -1 in counts.values():
        pytest.skip('jit._cache_size unavailable in this jax')
    # Buckets used by the workload: 60 = 32-chunk + 28-tail(→32),
    # 4/5-token prompts → 16. Decode and free are single programs.
    assert counts == {'prefill': 2, 'decode': 1, 'free': 1}, counts
    eng.generate(_PROMPTS, max_new_tokens=6)
    assert eng.compiled_counts() == counts, (
        'steady-state workload triggered a recompile')


def test_recompile_stability_dense(dense_runs):
    eng = dense_runs[0]
    counts = eng.compiled_counts()
    if -1 in counts.values():
        pytest.skip('jit._cache_size unavailable in this jax')
    assert counts == {'prefill': 2, 'decode': 1, 'free': 1}, counts


def test_recompile_stability_speculative(params):
    """With speculation on, the program budget grows by EXACTLY the
    verify program (static draft pad + draft_len mask — no
    per-draft-length shapes): verify=1, still prefill=buckets,
    decode=1, free=1; a second pass compiles nothing new. Spec-off
    engines (above) must not even carry the key."""
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=2, max_seq_len=64,
                                prefill_buckets=(8,), prefill_chunk=8,
                                spec_k=3))
    reqs = eng.generate([[11] * 40, [9, 9, 3, 9, 9]],
                        max_new_tokens=16)
    assert all(r.done for r in reqs)
    counts = eng.compiled_counts()
    if -1 in counts.values():
        pytest.skip('jit._cache_size unavailable in this jax')
    assert counts == {'prefill': 1, 'decode': 1, 'free': 1,
                      'verify': 1}, counts
    eng.generate([[7] * 12], max_new_tokens=10)
    assert eng.compiled_counts() == counts, (
        'steady-state speculation triggered a recompile')
    assert eng.metrics()['spec_steps'] >= 1, (
        'workload never dispatched a verify step — pin is vacuous')


@pytest.mark.parametrize('kv_dtype', ['bfloat16', 'int8'])
def test_recompile_stability_fused(params, kv_dtype):
    """Fused mixed steps extend the program budget by EXACTLY the
    mixed programs (one per chunk bucket actually fused — the chunk
    shape is the only varying operand): mixed=chunk-buckets,
    decode=1, verify=1, free=1, cow<=1, and a further pass of warm
    shapes compiles nothing — for BOTH kv dtypes (int8's scale
    threading must not introduce shapes of its own). The int8 variant
    carries the prefix cache (pinning cow and the prefix-offset
    shapes); the bf16 variant runs prefix-off, whose program set is
    complete after ONE pass — tier-1 wall-clock is a budget."""
    prefix = kv_dtype == 'int8'
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32,
                                paged=True, page_size=16, n_pages=25,
                                prefix_cache=prefix,
                                kv_dtype=kv_dtype,
                                fused_prefill=True, spec_k=3))

    def one_pass():
        # Two multi-chunk prompts admitted at idle (standalone 32s),
        # then a short and a long prompt arriving MID-DECODE so both
        # chunk buckets (16-pad and 32) deterministically ride fused
        # dispatches. Repetition makes speculation verify.
        rs = [eng.submit([11] * 60, max_new_tokens=8),
              eng.submit([9] * 60, max_new_tokens=8)]
        while not any(r.output_tokens for r in rs):
            eng.step()
        rs.append(eng.submit([5, 17, 101, 7], max_new_tokens=8))
        rs.append(eng.submit([13] * 60, max_new_tokens=8))
        eng.run_until_idle()
        return rs

    reqs = one_pass()
    assert all(r.done for r in reqs)
    if prefix:
        # Pass 2 warms the shapes pass 1 couldn't reach: prefix-cache
        # hits shift chunk offsets, so a bucket that only ever rode
        # FUSED in the cold pass goes out standalone in the warm one
        # (both ladders stay bucket-bounded — that is the pin).
        one_pass()
    counts = eng.compiled_counts()
    if -1 in counts.values():
        pytest.skip('jit._cache_size unavailable in this jax')
    assert counts['decode'] == 1 and counts['free'] == 1, counts
    assert counts['verify'] == 1, counts
    # The chunk-bucket ladders: 16-token short prompts + 32-token
    # chunks of the long ones — the mixed AND standalone prefill
    # program sets are each capped by the bucket count, nothing more.
    assert counts['mixed'] == 2, counts
    assert counts['prefill'] == (2 if prefix else 1), counts
    if prefix:
        assert counts['cow'] <= 1, counts
    assert eng.metrics()['fused_steps'] > 0, (
        'workload never fused a chunk — the pin is vacuous')
    one_pass()
    assert eng.compiled_counts() == counts, (
        'steady-state fused workload triggered a recompile')


def test_token_events_wake_waiters(params):
    """wait_progress/wait_done return on engine progress without the
    waiter polling; listeners fire for every appended token."""
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=1, max_seq_len=64,
                                prefill_buckets=(8,)))
    req = eng.submit([5, 4], max_new_tokens=4)
    fired = []
    req.add_listener(lambda: fired.append(len(req.output_tokens)))
    t = threading.Thread(target=eng.run_until_idle, daemon=True)

    seen = []
    waiter_done = threading.Event()

    def consume():
        n = 0
        while True:
            assert req.wait_progress(n, timeout=30.0), \
                'waiter starved: no token event within 30s'
            n = len(req.output_tokens)
            seen.append(n)
            if req.done:
                waiter_done.set()
                return

    c = threading.Thread(target=consume, daemon=True)
    c.start()
    t.start()
    assert waiter_done.wait(60.0)
    t.join(timeout=30)
    assert req.wait_done(timeout=1.0)
    assert len(req.output_tokens) == 4
    assert fired, 'listener never fired'
    assert seen[-1] == 4


def test_set_pipeline_depth_drains(params):
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=2, max_seq_len=64,
                                prefill_buckets=(8,),
                                pipeline_depth=1))
    req = eng.submit([1, 2, 3], max_new_tokens=8)
    for _ in range(4):
        eng.step()
    assert len(eng._queue) <= 1
    eng.set_pipeline_depth(0)
    assert not eng._queue, 'set_pipeline_depth(0) must drain in-flight'
    eng.run_until_idle()
    assert req.done and len(req.output_tokens) == 8


class _CountingTokenizer(server_lib.Tokenizer):
    """Byte tokenizer that counts token positions decoded — the O(n)
    evidence for the incremental streaming detokenizer."""

    def __init__(self):
        super().__init__()
        self.positions_decoded = 0

    def decode(self, tokens):
        self.positions_decoded += len(tokens)
        return super().decode(tokens)


def test_incremental_decoder_linear_cost():
    tok = _CountingTokenizer()
    dec = server_lib.IncrementalDecoder(tok)
    text = 'héllo wörld! ' * 50    # multibyte chars throughout
    tokens = list(text.encode('utf-8'))
    out = []
    for n in range(1, len(tokens) + 1):    # one flush per token
        out.append(dec.feed(tokens[:n]))
    out.append(dec.flush(tokens))
    assert ''.join(out) == text
    n = len(tokens)
    # Cumulative re-decode would cost ~n^2/2 positions (~211k here);
    # the incremental window costs a small constant per flush.
    assert tok.positions_decoded < 12 * n, (
        f'{tok.positions_decoded} positions decoded for a {n}-token '
        f'stream — the O(n²) cumulative decode is back')


def test_incremental_decoder_split_multibyte_held_back():
    tok = server_lib.Tokenizer()
    dec = server_lib.IncrementalDecoder(tok)
    tokens = list('é'.encode('utf-8'))     # 2 bytes
    assert dec.feed(tokens[:1]) == ''      # half a char: held
    assert dec.feed(tokens) == 'é'         # completed: released whole
    assert dec.flush(tokens) == ''


def test_incremental_decoder_genuine_garbage_not_held_forever():
    tok = server_lib.Tokenizer()
    dec = server_lib.IncrementalDecoder(tok)
    tokens = [0xFF] * 6                    # never form a valid char
    emitted = ''
    for n in range(1, len(tokens) + 1):
        emitted += dec.feed(tokens[:n])
    emitted += dec.flush(tokens)
    assert emitted == tok.decode(tokens), (
        'incremental stream diverged from the cumulative decode')
    assert '�' in emitted


def test_incremental_decoder_preserves_spacing_real_tokenizers():
    """HF/sentencepiece decode is NOT concatenative across a cut — a
    bare-suffix window loses the joining space between words. The
    context-overlap restart must keep streamed text equal to the
    one-shot decode for the repo's real tokenizers."""
    import os
    pytest.importorskip('tokenizers')
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        '..', '..'))
    bpe = server_lib.Tokenizer(
        os.path.join(repo, 'examples', 'tokenizer_8k.json'))
    ids = bpe.encode('Launch a v5p-64 slice and gang-schedule the '
                     'job. Schöne Grüße!')
    dec = server_lib.IncrementalDecoder(bpe)
    emitted = ''.join(dec.feed(ids[:n]) for n in range(1, len(ids) + 1))
    emitted += dec.flush(ids)
    assert emitted == bpe.decode(ids)


def test_incremental_decoder_matches_cumulative_on_byte_soup():
    """Arbitrary byte streams (random-weight models emit these): the
    concatenated incremental stream equals the one-shot decode."""
    import random
    rng = random.Random(7)
    tok = server_lib.Tokenizer()
    tokens = [rng.randrange(0, 256) for _ in range(400)]
    dec = server_lib.IncrementalDecoder(tok)
    emitted = ''
    n = 0
    while n < len(tokens):
        n += rng.randrange(1, 4)           # uneven flush batches
        emitted += dec.feed(tokens[:min(n, len(tokens))])
    emitted += dec.flush(tokens)
    assert emitted == tok.decode(tokens)
