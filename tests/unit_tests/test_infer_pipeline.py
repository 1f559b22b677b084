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
- A prompt's FIRST token is read by its chunk's own in-flight record
  (``infer/inflight.py``), ahead of the decode pair dispatched behind
  that chunk: one token on the first notify, the same tokens at every
  depth, a stale record dropped, the sentinel's flag beside the token,
  and both kinds of record drained before the engine reads idle. Over
  the dense cache, the paged one and one state family (Falcon-H1).
"""
import dataclasses
import hashlib
import threading
import time

import pytest

pytestmark = pytest.mark.jax

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.infer import inflight  # noqa: E402
from skypilot_tpu.infer import server as server_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402
from skypilot_tpu.utils import failpoints  # noqa: E402

from tests.unit_tests import conftest  # noqa: E402

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


# ---- the first token's early read (infer/inflight.py) ---------------------

FAMILIES = ('dense', 'paged', 'state')
_BUSY = [11] * 40          # two chunks; decoding while the next arrives
_LATE = [5, 17, 101, 7]    # one chunk: its first dispatch ends its prompt
# What the PARENT commit (82a9fff) generated for ``_BUSY`` / ``_LATE``
# (8 / 5 tokens) through the same engines; dense and paged are also the
# no-cache float32 oracle's (``greedy_oracle``).
_AT_PARENT = {
    'dense': None, 'paged': None,   # the oracle's: checked against it
    'state': [[186, 418, 69, 505, 264, 504, 209, 438],
              [405, 423, 459, 171, 481]],
}
# sha256 of ``eng._decode.lower(...).as_text()`` at the parent commit:
# the decode programs did not change, only the chunk programs gained a
# result (``python tests/unit_tests/test_infer_pipeline.py`` prints
# the table; a PR that MEANS to change a decode program replaces it).
# Since replaced on purpose: the paged and state programs write their
# K/V rows through the row writer's kernel
# (``ops/paged_attention.paged_row_write``), with the same tokens out.
_DECODE_AT_PARENT = {
    'dense':
        '7d739f5b16a991492764a5b271a2d0bacece0faaf75533d9246504bc2e6eeb84',
    'paged':
        '54e8df5c5989742fa5278c45cf6df4abcf45c21ef94dc19ebc547c87a5357877',
    'state':
        '0bc225d9691970c1836d6a0f80216b1b57f4b3249ae43bd7c849faa871b0cb15',
}


def _family_engine(family, tiny_params):
    kw = dict(n_slots=3, max_seq_len=128, prefill_buckets=(16, 32),
              prefill_chunk=32, pipeline_depth=1)
    if family == 'dense':
        return engine_lib.InferenceEngine(
            CFG, tiny_params, engine_lib.EngineConfig(**kw))
    kw.update(paged=True, page_size=16, n_pages=40)
    if family == 'paged':
        return engine_lib.InferenceEngine(
            CFG, tiny_params, engine_lib.EngineConfig(**kw))
    return engine_lib.InferenceEngine(
        *conftest.tiny_state_model(),
        engine_lib.EngineConfig(cache_dtype='float32', **kw))


@pytest.fixture(scope='module')
def first_engines(params):
    """One depth-1 engine a family, built on first use and shared:
    every test leaves it idle, at depth 1 and clean of verdicts."""
    built = {}

    def get(family):
        if family not in built:
            built[family] = _family_engine(family, params)
        eng = built[family]
        assert eng.idle() and eng._depth == 1
        return eng
    return get


def _decode_digest(eng):
    args = [eng.cache, eng.params]
    if eng.allocator is not None:
        tables = jnp.asarray(eng.allocator.table())
        if eng.window_alloc is not None:
            tables = (tables, jnp.asarray(eng.window_alloc.table()))
        args.append(tables)
    args += [eng._last_dev, jax.random.PRNGKey(0),
             jnp.zeros((eng.ecfg.n_slots,), jnp.float32),
             jnp.zeros((eng.ecfg.n_slots,), jnp.bool_)]
    text = eng._decode.lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def _kinds(eng):
    return [type(rec).__name__ for rec in eng._queue]


def _spy(eng, monkeypatch):
    """Log every record as the consume ladder applies it."""
    log = []
    first, pair = eng._apply_first, eng._apply_pair

    def apply_first(host, rec):
        log.append(('first', rec, time.time()))
        first(host, rec)

    def apply_pair(host, rec):
        log.append(('pair', rec, time.time()))
        pair(host, rec)
    monkeypatch.setattr(eng, '_apply_first', apply_first)
    monkeypatch.setattr(eng, '_apply_pair', apply_pair)
    return log


def _event(eng, req, name):
    return [ev for ev in eng.stepline_snapshot()['events']
            if ev['request_id'] == req.request_id and ev['event'] == name]


def _decoding(eng):
    """A request far enough in to be decoding, so that whatever
    arrives next finds a decode in flight."""
    busy = eng.submit(_BUSY, max_new_tokens=12)
    while len(busy.output_tokens) < 2:
        eng.step()
    return busy


@pytest.mark.parametrize('family', FAMILIES)
def test_first_token_is_read_ahead_of_the_pair_behind_its_chunk(
        first_engines, family, monkeypatch):
    eng = first_engines(family)
    log = _spy(eng, monkeypatch)
    before = eng.metrics()
    busy = _decoding(eng)
    late = eng.submit(_LATE, max_new_tokens=5)
    seen = []
    late.add_listener(lambda: seen.append(len(late.output_tokens)))
    eng.step()      # the late prompt's one chunk, and the decode behind
    recs = list(eng._queue)
    (i,) = [k for k, rec in enumerate(recs)
            if isinstance(rec, inflight.FirstToken) and rec.req is late]
    behind = recs[i + 1]
    assert isinstance(behind, inflight.StepPair)
    assert sorted(req.request_id for _, req in behind.decoded) == [
        busy.request_id, late.request_id]
    assert not behind.prefilled and not late.output_tokens
    # depth 1 counts pairs: the first-token record is not one of them
    assert eng._queue.pairs() <= 1 < len(eng._queue)
    eng.run_until_idle()
    order = [(kind, rec) for kind, rec, _ in log]
    k_first = order.index(('first', recs[i]))
    k_pair = order.index(('pair', behind))
    assert k_first < k_pair
    # the first notify carries ONE token, and the pair behind the
    # chunk only the second
    assert seen[0] == 1 and seen[1] == 2
    (ev,) = _event(eng, late, 'first_token')
    assert ev['early'] == 1 and ev['t'] <= log[k_pair][2]
    assert late.first_token_at == ev['t']
    m = eng.metrics()
    assert m['first_token_total'] - before['first_token_total'] == 2
    assert (m['first_token_early_total']
            - before['first_token_early_total']) == 2
    assert len(late.output_tokens) == 5 and len(busy.output_tokens) == 12


@pytest.mark.parametrize('family', FAMILIES)
def test_greedy_tokens_are_the_parents_at_depth_0_and_1(
        first_engines, family, greedy_oracle):
    eng = first_engines(family)
    prompts, n = [_BUSY, _LATE], [8, 5]

    def run():
        reqs = [eng.submit(p, max_new_tokens=k)
                for p, k in zip(prompts, n)]
        eng.run_until_idle()
        return [r.output_tokens for r in reqs]
    out1 = run()
    eng.set_pipeline_depth(0)
    try:
        out0 = run()
    finally:
        eng.set_pipeline_depth(1)
    assert out0 == out1
    want = _AT_PARENT[family] or [
        greedy_oracle([p], k)[0] for p, k in zip(prompts, n)]
    assert out1 == want


@pytest.mark.parametrize('how', ['max_tokens', 'eos'])
@pytest.mark.parametrize('family', FAMILIES)
def test_a_request_its_first_token_ends_finishes_at_the_first_record(
        first_engines, family, how, monkeypatch):
    eng = first_engines(family)
    [probe] = eng.generate([_LATE], max_new_tokens=2)
    first = probe.output_tokens[0]
    if how == 'eos':
        monkeypatch.setattr(eng, 'ecfg', dataclasses.replace(
            eng.ecfg, eos_id=first))
    log = _spy(eng, monkeypatch)
    busy = _decoding(eng)
    req = eng.submit(_LATE, max_new_tokens=1 if how == 'max_tokens' else 9)
    seen = []

    def pairs_read():
        return sum(1 for kind, rec, _ in log if kind == 'pair'
                   and any(r is req for _, r in rec.decoded))
    req.add_listener(lambda: seen.append(
        (len(req.output_tokens), req.done, pairs_read())))
    while not req.done:
        eng.step()
    # ONE notify: done with one token, inside the first-token record's
    # apply, the pair behind the chunk still unread (its lane for this
    # slot is dropped when it is)
    assert seen == [(1, True, 0)]
    assert req.finish_reason == how
    assert req.output_tokens == [first]
    (ev,) = _event(eng, req, 'first_token')
    (done,) = _event(eng, req, 'done')
    assert ev['early'] == 1 and done['tokens'] == 1
    eng.run_until_idle()
    assert req.output_tokens == [first] and len(busy.output_tokens) == 12
    assert eng.metrics()['tokens_in_flight'] == 0


@pytest.mark.parametrize('family', FAMILIES)
def test_a_slot_preempted_before_the_read_emits_nothing_and_reprefills(
        first_engines, family):
    eng = first_engines(family)
    [want] = eng.generate([_LATE], max_new_tokens=5)
    preempted = eng.metrics().get('preemptions', 0)
    busy = _decoding(eng)
    req = eng.submit(_LATE, max_new_tokens=5)
    seen = []
    req.add_listener(lambda: seen.append(len(req.output_tokens)))
    eng.step()
    (slot,) = [rec.slot for rec in eng._queue
               if isinstance(rec, inflight.FirstToken) and rec.req is req]
    eng._preempt(slot)          # between the chunk's dispatch and the read
    eng._drain_inflight()
    assert req.output_tokens == [] and seen == []
    assert not _event(eng, req, 'first_token')
    eng.run_until_idle()
    assert req.output_tokens == want.output_tokens
    assert len(_event(eng, req, 'first_token')) == 1
    assert _event(eng, req, 'resume')
    assert len(busy.output_tokens) == 12
    if eng.allocator is not None:
        assert eng.metrics()['preemptions'] == preempted + 1
        assert eng.allocator.free_pages == eng.allocator.n_pages - 1


@pytest.mark.parametrize('family', FAMILIES)
def test_both_kinds_of_record_are_drained(first_engines, family):
    eng = first_engines(family)
    req = eng.submit(_LATE, max_new_tokens=6)
    eng.step()
    assert _kinds(eng) == ['FirstToken', 'StepPair']
    assert not eng.idle() and not req.output_tokens
    eng._consume_one()                       # the first-token record
    assert _kinds(eng) == ['StepPair'] and len(req.output_tokens) == 1
    assert not eng.idle()
    eng.run_until_idle()
    req = eng.submit(_LATE, max_new_tokens=6)
    eng.step()
    assert _kinds(eng) == ['FirstToken', 'StepPair']
    eng.set_pipeline_depth(0)
    try:
        assert not eng._queue, 'depth 0 leaves neither kind in flight'
        assert len(req.output_tokens) == 2
        assert eng.metrics()['tokens_in_flight'] == 0
    finally:
        eng.set_pipeline_depth(1)
    eng.step()
    assert eng._queue.pairs() == 1
    eng._drain_inflight()
    assert not eng._queue
    eng.run_until_idle()
    assert eng.idle() and len(req.output_tokens) == 6


@pytest.mark.parametrize('family', FAMILIES)
def test_sentinel_stops_a_first_token_its_chunk_logits_spoiled(
        first_engines, family, monkeypatch):
    """The failpoint stands for a device NaN in the ONE row of logits
    the chunk samples from: the flag rides beside the token, so the
    token is never appended, notified or counted."""
    eng = first_engines(family)
    busy = _decoding(eng)
    before = eng.metrics()
    req = eng.submit(_LATE, max_new_tokens=5)
    seen = []
    req.add_listener(lambda: seen.append(len(req.output_tokens)))
    eng.step()
    assert _kinds(eng)[-2:] == ['FirstToken', 'StepPair']
    while not isinstance(eng._queue[0], inflight.FirstToken):
        eng._consume_one()
    failpoints._reset_for_tests()
    monkeypatch.setenv('SKY_TPU_FAILPOINTS', 'infer.engine.sdc_nan=error@1')
    try:
        eng._consume_one()
        assert failpoints.fired('infer.engine.sdc_nan') == 1
        assert req.done and req.finish_reason == 'sdc'
        assert req.output_tokens == [] and seen == [0]   # the finish only
        assert not _event(eng, req, 'first_token')
        assert eng.integrity_suspect()
        m = eng.metrics()
        assert m['sdc_events_total'] == before['sdc_events_total'] + 1
        assert m['first_token_total'] == before['first_token_total']
        eng.run_until_idle()
        assert len(busy.output_tokens) == 12 and busy.finish_reason != 'sdc'
    finally:
        monkeypatch.delenv('SKY_TPU_FAILPOINTS')
        failpoints._reset_for_tests()
        eng._integrity_suspect = False      # the fixture is shared


@pytest.mark.parametrize('family', FAMILIES)
def test_decode_program_lowers_to_the_parents_stablehlo(first_engines,
                                                        family):
    assert _decode_digest(first_engines(family)) == _DECODE_AT_PARENT[family]


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


if __name__ == '__main__':
    import json

    weights = conftest.tiny_llama_params()
    table = {}
    for fam in FAMILIES:
        eng = _family_engine(fam, weights)
        reqs = [eng.submit(_BUSY, max_new_tokens=8),
                eng.submit(_LATE, max_new_tokens=5)]
        eng.run_until_idle()
        table[fam] = {'decode': _decode_digest(eng),
                      'tokens': [r.output_tokens for r in reqs]}
    print(json.dumps(table, indent=1))
