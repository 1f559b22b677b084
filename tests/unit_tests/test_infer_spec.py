"""Self-speculative multi-token decoding: drafter, exact-greedy
verification, bit-identity, rollback accounting, and the satellites.

The tier-1 gates for the speculative path (docs/serving.md
"Speculative decoding"):

- Greedy outputs are BIT-IDENTICAL spec-on vs spec-off, dense and
  paged (over the mixed-length + paged-preemption workload), at
  pipeline depth 0 and 1 — every emitted token is the model's own
  argmax; drafts only decide how many land per step.
- The verify program adds exactly ONE compiled program (static draft
  pad + draft_len mask), and steady-state speculation compiles
  nothing new.
- Page accounting survives speculation: rejected-draft pages roll
  back, and a chaos storm of cancels/preemptions landing mid-verify
  leaks and double-frees nothing.
- Multi-token flushes (1..k+1 tokens per event) stream through the
  IncrementalDecoder and the resume_from splice unchanged.
- The lockstep driver pins speculation OFF and re-enabling raises.
- Retry-After's queue-drain estimate divides by the accepted-aware
  effective tokens/sec, not 1 token/step.
"""
import random
import threading
import types

import numpy as np
import pytest

pytestmark = pytest.mark.jax

from skypilot_tpu.infer import drafter as drafter_lib  # noqa: E402
from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.infer import server as server_lib  # noqa: E402
from skypilot_tpu.infer.sched import base as sched_base  # noqa: E402
from skypilot_tpu.infer.sched import wfq as wfq_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()


@pytest.fixture(scope='module')
def params(spec_params):
    """Weights whose greedy continuation repeats (conftest's
    ``spec_params`` asserts it once): every "a draft was accepted"
    assertion below stands on that."""
    return spec_params


# The determinism workload of test_infer_pipeline: mixed short/
# multi-chunk prompts, more requests than slots, and (paged) a pool
# small enough to force preemption mid-run. Repetitive prompts make
# the drafter fire, so the gate actually exercises acceptance.
_PROMPTS = [[11] * 60, [23] * 60, [37] * 60,
            [5, 17, 101, 7], [9, 8, 7, 6, 5]]


def _engine(params, spec_k, paged=False, depth=1, n_pages=13,
            eos_id=None, max_queue_requests=None, n_slots=3,
            prefix=False, scheduler='fcfs'):
    kw = {}
    if paged:
        kw.update(paged=True, page_size=16, n_pages=n_pages)
    if prefix:
        kw.update(paged=True, page_size=16, n_pages=n_pages,
                  prefix_cache=True)
    return engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=n_slots, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, pipeline_depth=depth,
                                spec_k=spec_k, eos_id=eos_id,
                                max_queue_requests=max_queue_requests,
                                scheduler=scheduler, **kw))


# ---------- drafter (host-side, device-free) ------------------------------
def test_drafter_proposes_continuation_of_latest_match():
    d = drafter_lib.PromptLookupDrafter(max_ngram=3)
    ctx = [1, 2, 3, 9, 9, 1, 2, 3, 4, 5, 6, 1, 2, 3]
    # Trailing 3-gram (1,2,3) last occurred at 5..7 -> continue 4,5,6.
    assert d.propose(ctx, 3) == [4, 5, 6]
    assert d.propose(ctx, 2) == [4, 5]


def test_drafter_falls_back_to_shorter_ngrams():
    d = drafter_lib.PromptLookupDrafter(max_ngram=3, min_ngram=1)
    ctx = [7, 8, 9, 3, 9, 5]
    # No 3/2-gram repeat; unigram 9 occurred at 2 and 4 -> continues 5?
    # Latest prior occurrence of trailing token 5: none. Trailing is 5.
    assert d.propose(ctx, 4) == []
    ctx = [7, 8, 9, 3, 9]
    # Trailing unigram 9 occurred at index 2 -> copies [3, 9] and then
    # extends periodically into its own draft (the loop-drafting
    # rule): [3, 9, 3, 9].
    assert d.propose(ctx, 4) == [3, 9, 3, 9]


def test_drafter_memo_incremental_matches_fresh():
    d = drafter_lib.PromptLookupDrafter(max_ngram=3)
    rng = random.Random(5)
    ctx = [rng.randrange(6) for _ in range(40)]
    memo = {}
    for n in range(4, len(ctx) + 1):
        inc = d.propose(ctx[:n], 5, memo=memo)
        fresh = d.propose(ctx[:n], 5)
        assert inc == fresh, f'memoized drafting diverged at n={n}'


def test_cached_context_extends_incrementally():
    memo = {}
    prompt = [1, 2, 3]
    out = []
    ctx = drafter_lib.cached_context(prompt, out, memo)
    assert ctx == [1, 2, 3]
    out.extend([7, 8])
    ctx2 = drafter_lib.cached_context(prompt, out, memo)
    assert ctx2 is ctx and ctx2 == [1, 2, 3, 7, 8]
    out.append(9)
    assert drafter_lib.cached_context(prompt, out, memo) == prompt + out


def test_drafter_memo_reset_on_shrunk_context():
    d = drafter_lib.PromptLookupDrafter(max_ngram=2)
    memo = {}
    d.propose([1, 2, 1, 2, 1], 3, memo=memo)
    # A fresh (shorter) sequence reusing the memo must not see ghosts.
    assert d.propose([4, 5, 6], 3, memo=memo) == []


# ---------- bit-identity gates (the tier-1 contract) ----------------------
@pytest.fixture(scope='module')
def dense_runs(params):
    off = _engine(params, spec_k=0)
    out_off = [r.output_tokens
               for r in off.generate(_PROMPTS, max_new_tokens=12)]
    on = _engine(params, spec_k=4)
    out_on1 = [r.output_tokens
               for r in on.generate(_PROMPTS, max_new_tokens=12)]
    on.set_pipeline_depth(0)
    out_on0 = [r.output_tokens
               for r in on.generate(_PROMPTS, max_new_tokens=12)]
    return off, on, out_off, out_on1, out_on0


@pytest.fixture(scope='module')
def paged_runs(params):
    off = _engine(params, spec_k=0, paged=True)
    out_off = [r.output_tokens
               for r in off.generate(_PROMPTS, max_new_tokens=12)]
    on = _engine(params, spec_k=4, paged=True)
    out_on1 = [r.output_tokens
               for r in on.generate(_PROMPTS, max_new_tokens=12)]
    preempt = on.metrics()['preemptions']
    on.set_pipeline_depth(0)
    out_on0 = [r.output_tokens
               for r in on.generate(_PROMPTS, max_new_tokens=12)]
    return off, on, out_off, out_on1, out_on0, preempt


def test_greedy_identical_spec_on_vs_off_dense(dense_runs,
                                               greedy_oracle):
    _, on, out_off, out_on1, out_on0 = dense_runs
    assert out_off == greedy_oracle(_PROMPTS, 12), (
        'the spec-off engine left the no-cache float32 forward')
    assert out_on1 == out_off, 'speculation changed greedy output'
    assert out_on0 == out_off, (
        'speculation changed greedy output at pipeline depth 0')
    m = on.metrics()
    assert m['spec_accepted_tokens'] >= 1, (
        'workload never accepted a draft — the gate is vacuous')
    assert m['accepted_len_mean'] > 1.0


def test_greedy_identical_spec_on_vs_off_paged_preempting(
        paged_runs, dense_runs):
    _, on, out_off, out_on1, out_on0, preempt = paged_runs
    assert preempt >= 1, (
        'workload never preempted — page pressure untested')
    assert out_on1 == out_off
    assert out_on0 == out_off
    # Cross-cache agreement too (same math, both spec lanes).
    assert out_off == dense_runs[2]
    assert on.metrics()['spec_accepted_tokens'] >= 1


def test_spec_run_conserves_pages(paged_runs):
    _, on, *_ = paged_runs
    al = on.allocator
    assert al.free_pages == al.n_pages - 1, (
        'speculative run leaked pages (rejected-draft rollback?)')
    for pid in range(1, al.n_pages):
        assert al.refcount(pid) == 0


def test_spec_off_requests_ride_plain_decode(params):
    """Per-request opt-out: an all-opt-out workload on a spec-enabled
    engine never dispatches a verify step, and outputs still match."""
    eng = _engine(params, spec_k=4)
    reqs = [eng.submit(p, max_new_tokens=8, spec=False)
            for p in _PROMPTS]
    eng.run_until_idle()
    m = eng.metrics()
    assert m['spec_steps'] == 0
    assert m['tokens_per_step'] is not None
    off = _engine(params, spec_k=0)
    expect = [r.output_tokens
              for r in off.generate(_PROMPTS, max_new_tokens=8)]
    assert [r.output_tokens for r in reqs] == expect


def test_non_drafting_traffic_keeps_dispatch_ahead_overlap(params):
    """A spec-enabled engine serving only opted-out traffic must not
    pay the drain-before-draft sync each step — no slot can draft, so
    the step keeps the plain dispatch-ahead shape (the readback
    overlap is speculation-off's whole win on that workload)."""
    eng = _engine(params, spec_k=4)
    drains = []
    orig = eng._drain_inflight
    eng._drain_inflight = lambda: (drains.append(1), orig())[-1]
    for r in [eng.submit(p, max_new_tokens=6, spec=False)
              for p in _PROMPTS[:2]]:
        pass
    eng.run_until_idle()
    assert not drains, 'opted-out traffic paid the speculative drain'
    # And eligible traffic DOES drain before drafting.
    eng.submit(_PROMPTS[0], max_new_tokens=6)
    eng.run_until_idle()
    assert drains


def test_non_drafting_lane_does_not_dilute_acceptance_metrics(params):
    """An opted-out request co-batched with a drafting one rides the
    verify dispatch as a draft_len=0 lane — it must NOT count into
    accepted_len_mean (engine or per-request), or mixed traffic drags
    the draft-efficiency gauge toward 1.0."""
    eng = _engine(params, spec_k=4, n_slots=2)
    drafting = eng.submit([11] * 40, max_new_tokens=16)
    bystander = eng.submit([9, 8, 7, 6, 5], max_new_tokens=16,
                           spec=False)
    eng.run_until_idle()
    assert drafting.spec_steps >= 1
    assert bystander.spec_steps == 0 and bystander.spec_emitted == 0
    m = eng.metrics()
    # Engine alm reflects only the drafting lanes.
    assert m['spec_slot_steps'] == drafting.spec_steps
    assert m['accepted_len_mean'] == pytest.approx(
        drafting.spec_emitted / drafting.spec_steps, abs=1e-3)


def test_sampled_slots_never_draft_and_complete(params):
    eng = _engine(params, spec_k=4, paged=True)
    reqs = eng.generate(_PROMPTS, max_new_tokens=8, temperature=1.0)
    assert all(len(r.output_tokens) == 8 for r in reqs)
    assert all(0 <= t < CFG.vocab_size
               for r in reqs for t in r.output_tokens)
    assert eng.metrics()['spec_drafted_tokens'] == 0, (
        'a temperature>0 slot was drafted for')


# ---------- recompile stability + finish semantics ------------------------
def test_verify_recompile_stability(paged_runs):
    _, on, *_ = paged_runs
    counts = on.compiled_counts()
    if -1 in counts.values():
        pytest.skip('jit._cache_size unavailable in this jax')
    assert counts == {'prefill': 2, 'decode': 1, 'free': 1,
                      'verify': 1}, counts
    on.generate(_PROMPTS, max_new_tokens=6)
    assert on.compiled_counts() == counts, (
        'steady-state speculation triggered a recompile')


def test_max_tokens_truncates_accepted_run_exactly(params):
    """A run accepted past the request budget drops the surplus: the
    output length lands EXACTLY on max_new_tokens, matching spec-off
    token for token."""
    for budget in (1, 2, 5, 9):
        on = _engine(params, spec_k=4)
        off = _engine(params, spec_k=0)
        o_on = on.generate([[11] * 40], max_new_tokens=budget)[0]
        o_off = off.generate([[11] * 40], max_new_tokens=budget)[0]
        assert len(o_on.output_tokens) == budget
        assert o_on.output_tokens == o_off.output_tokens
        assert o_on.finish_reason == 'max_tokens'


def test_eos_mid_accepted_run_matches_spec_off(params):
    """Pick a token the greedy continuation actually emits mid-stream
    and declare it EOS: both lanes must stop at its first occurrence
    with identical output."""
    probe = _engine(params, spec_k=0)
    out = probe.generate([[11] * 40], max_new_tokens=12)[0].output_tokens
    eos = out[4]
    if eos in out[:4]:
        eos = next((t for i, t in enumerate(out) if t not in out[:i]),
                   out[4])
    on = _engine(params, spec_k=4, eos_id=eos)
    off = _engine(params, spec_k=0, eos_id=eos)
    o_on = on.generate([[11] * 40], max_new_tokens=12)[0]
    o_off = off.generate([[11] * 40], max_new_tokens=12)[0]
    assert o_on.output_tokens == o_off.output_tokens
    assert o_on.finish_reason == o_off.finish_reason


# ---------- scheduler budget hook -----------------------------------------
def _fake_req(tenant, cost=8):
    return types.SimpleNamespace(tenant=tenant,
                                 prompt_tokens=[1] * cost,
                                 output_tokens=[], cancelled=False,
                                 deadline=None)


def test_fcfs_spec_budget_is_global():
    s = sched_base.FCFSScheduler()
    assert s.spec_budget(_fake_req('a'), 6) == 6


def test_wfq_spec_budget_caps_under_contention():
    s = wfq_lib.WFQScheduler(sched_base.SchedulerConfig(
        tenant_weights={'victim': 2.0, 'aggressor': 1.0}))
    # Uncontended: full width.
    assert s.spec_budget(_fake_req('aggressor'), 6) == 6
    # Victim work queued: the aggressor's width is cut to its weight
    # share (1/3 of 6 = 2), the victim keeps 2/3 (4).
    s.enqueue(_fake_req('victim'))
    assert s.spec_budget(_fake_req('aggressor'), 6) == 2
    s.enqueue(_fake_req('aggressor'))
    assert s.spec_budget(_fake_req('victim'), 6) == 4
    # Queue drains -> budgets recover.
    while s.pop_next() is not None:
        pass
    assert s.spec_budget(_fake_req('aggressor'), 6) == 6


def test_wfq_spec_budget_floors_at_one_lane():
    """Many equal contenders: the truncated weight share would hit 0
    and silently turn speculation off for EVERYONE — each tenant keeps
    at least one draft lane instead."""
    s = wfq_lib.WFQScheduler(sched_base.SchedulerConfig())
    for i in range(7):
        s.enqueue(_fake_req(f't{i}'))
    assert s.spec_budget(_fake_req('t0'), 6) == 1


def test_wfq_spec_budget_applies_in_engine(params):
    """End to end, same two-request workload both times on a 1-slot
    wfq engine: submitted back-to-back (tenant b queued while a runs
    -> a's draft width halves) it drafts fewer tokens than submitted
    sequentially (never contended -> full width throughout)."""
    contended = _engine(params, spec_k=4, scheduler='wfq', n_slots=1)
    granted = []
    orig = contended._sched.spec_budget

    def spying_budget(req, k):
        got = orig(req, k)
        granted.append((req.tenant, contended._sched.pending(), got))
        return got

    contended._sched.spec_budget = spying_budget
    r1 = contended.submit([11] * 40, max_new_tokens=24, tenant='a')
    r2 = contended.submit([11] * 40, max_new_tokens=24, tenant='b')
    contended.run_until_idle()
    assert r1.done and r2.done
    contested = [g for t, pending, g in granted
                 if t == 'a' and pending > 0]
    free = [g for t, pending, g in granted if pending == 0]
    # Equal weights, two contenders: a's width halves (int(4/2) = 2)
    # exactly while b's work is queued; the uncontended tail recovers
    # full width. Outputs are the full greedy sequence regardless.
    assert contested and all(g == 2 for g in contested), granted
    assert free and max(free) == 4, granted
    assert r1.output_tokens == r2.output_tokens


# ---------- lockstep pin (satellite) --------------------------------------
def test_lockstep_driver_pins_spec_off_and_reenable_raises(params):
    from skypilot_tpu.infer import multihost
    eng = _engine(params, spec_k=4)
    multihost.MultihostEngineDriver(eng)
    assert eng._spec_k == 0, 'lockstep must pin speculation off'
    with pytest.raises(RuntimeError, match='lockstep'):
        eng.set_spec_k(2)
    # And pinned-off drafting really is off.
    eng.generate([_PROMPTS[0]], max_new_tokens=6)
    assert eng.metrics().get('spec_steps', 0) == 0


def test_set_spec_k_runtime_toggle(params):
    eng = _engine(params, spec_k=0)
    out_off = eng.generate([[11] * 40], max_new_tokens=10)[0]
    eng.set_spec_k(4)
    out_on = eng.generate([[11] * 40], max_new_tokens=10)[0]
    assert out_on.output_tokens == out_off.output_tokens
    assert eng.metrics()['spec_accepted_tokens'] >= 1
    eng.set_spec_k(0)
    assert eng._spec_k == 0


# ---------- Retry-After (satellite) ---------------------------------------
def test_retry_after_uses_effective_tokens_per_step(params):
    """The queue-drain estimate divides the backlog by the EMITTED-
    token rate (accepted-length-aware), not steps/sec — under
    speculation the two differ by the acceptance factor, and assuming
    1 token/step would overshoot the 429 backoff hint."""
    eng = _engine(params, spec_k=4, max_queue_requests=2, n_slots=1)
    eng.generate([[11] * 40], max_new_tokens=16)
    m = eng.metrics()
    assert m['tokens_per_step'] > 1.0, 'no multi-token steps happened'
    eff_tps = eng._decode_tokens / eng._decode_time
    eng.submit([5] * 30, max_new_tokens=4)
    eng.submit([5] * 30, max_new_tokens=4)
    with pytest.raises(engine_lib.AdmissionError) as ei:
        eng.submit([5] * 30, max_new_tokens=4)
    backlog = eng.metrics()['queued_tokens']
    expect = min(60.0, max(1.0, backlog / eff_tps))
    assert ei.value.retry_after_s == pytest.approx(expect, rel=1e-6)
    # The per-step rate alone would claim a backoff ~accepted_len_mean
    # times longer.
    steps_tps = eng._decode_steps / eng._decode_time
    assert backlog / eff_tps < backlog / steps_tps


# ---------- page chaos mid-verify (satellite) -----------------------------
def test_chaos_storm_cancel_mid_verify_conserves_pages(params):
    """PR 4-style conservation gate under speculation: waves of
    repetitive (draft-heavy) prompts over a tight pool + prefix cache,
    with cancels landing while verify steps are in flight and
    preemption firing under pressure — zero leaked and zero
    double-freed pages (the allocator asserts on double-free)."""
    rng = np.random.default_rng(7)
    eng = _engine(params, spec_k=4, prefix=True, n_pages=13)
    al = eng.allocator
    for wave in range(6):
        reqs = [eng.submit([11] * int(rng.integers(20, 60)),
                           max_new_tokens=10)
                for _ in range(3)]
        steps = 0
        while not all(r.done for r in reqs) and steps < 500:
            eng.step()
            steps += 1
            if steps == 2 + wave % 3:
                # Cancel one while its verify pair is (potentially)
                # still in flight: the stale-by-one rule must drop its
                # tokens and its pages must all come home.
                eng.cancel(reqs[wave % 3])
        eng.run_until_idle()
        assert all(r.done for r in reqs)
        assert al.free_pages + eng.prefix.cached_pages == al.n_pages - 1
        for pid in range(1, al.n_pages):
            assert al.refcount(pid) in (0, 1)
    eng.prefix.evict(al.n_pages)
    assert al.free_pages == al.n_pages - 1, 'storm leaked pages'
    assert eng.metrics()['spec_steps'] >= 1, 'storm never speculated'


# ---------- multi-token streaming (satellite) -----------------------------
def _feed_in_batches(decoder, tokens, rng, kmax):
    out, n = '', 0
    while n < len(tokens):
        n = min(len(tokens), n + rng.randrange(1, kmax + 1))
        out += decoder.feed(tokens[:n], n)
    out += decoder.flush(tokens)
    return out


def test_incremental_decoder_multi_token_flushes_byte_soup():
    rng = random.Random(11)
    tok = server_lib.Tokenizer()
    tokens = [rng.randrange(0, 256) for _ in range(600)]
    for kmax in (2, 5, 9):
        dec = server_lib.IncrementalDecoder(tok)
        assert _feed_in_batches(dec, tokens, random.Random(kmax),
                                kmax) == tok.decode(tokens)


def test_incremental_decoder_multi_token_flushes_wordlevel(tmp_path):
    path = server_lib.synthesize_wordlevel_tokenizer(
        512, str(tmp_path / 'wl.json'))
    pytest.importorskip('tokenizers')
    tok = server_lib.Tokenizer(path)
    text = ' '.join(f'w{i:07d}' for i in range(260, 380))
    ids = tok.encode(text)
    for kmax in (3, 7):
        dec = server_lib.IncrementalDecoder(tok)
        assert _feed_in_batches(dec, ids, random.Random(kmax),
                                kmax) == tok.decode(ids)


def test_incremental_decoder_multi_token_flushes_8k_bpe():
    import os
    pytest.importorskip('tokenizers')
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        '..', '..'))
    bpe = server_lib.Tokenizer(
        os.path.join(repo, 'examples', 'tokenizer_8k.json'))
    ids = bpe.encode('Gang-schedule the v5p-64 slice; drain, then '
                     'failover. Schöne Grüße! ' * 3)
    for kmax in (2, 6):
        dec = server_lib.IncrementalDecoder(bpe)
        assert _feed_in_batches(dec, ids, random.Random(kmax),
                                kmax) == bpe.decode(ids)


def test_resume_splice_lands_inside_accepted_run(params):
    """Mid-stream failover whose kill boundary falls INSIDE a
    multi-token accepted run: resuming from any delivered-token count
    splices a bit-identical continuation (resume recomputes
    prompt+delivered, then speculation continues past the boundary)."""
    oracle = _engine(params, spec_k=4, paged=True)
    full = oracle.generate([[11] * 40], max_new_tokens=16)[0]
    assert full.spec_steps >= 1
    assert len(full.output_tokens) == 16
    for cut in (3, 7, 10):   # arbitrary boundaries, incl. mid-run
        eng = _engine(params, spec_k=4, paged=True)
        r = eng.submit([11] * 40, max_new_tokens=16,
                       resume_tokens=full.output_tokens[:cut])
        eng.run_until_idle()
        assert r.output_tokens == full.output_tokens, (
            f'splice diverged at cut={cut}')


def test_multi_token_events_reach_waiters(params):
    """Event-driven delivery under speculation: waiters observe
    monotonically growing output with jumps up to k+1 and never miss
    the finish."""
    eng = _engine(params, spec_k=4)
    req = eng.submit([11] * 40, max_new_tokens=12)
    seen = []
    done = threading.Event()

    def consume():
        n = 0
        while True:
            assert req.wait_progress(n, timeout=30.0)
            n = len(req.output_tokens)
            seen.append(n)
            if req.done:
                done.set()
                return

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    eng.run_until_idle()
    assert done.wait(30.0)
    assert seen[-1] == 12
    assert all(b > a for a, b in zip(seen, seen[1:]))
    assert max(b - a for a, b in zip([0] + seen, seen)) <= 5


# ---------- metrics surfaces ----------------------------------------------
def test_spec_metrics_surfaced_and_pool_merges(params):
    eng = _engine(params, spec_k=4)
    eng.generate([[11] * 40], max_new_tokens=12)
    m = eng.metrics()
    for key in ('spec_k', 'spec_steps', 'spec_slot_steps',
                'spec_drafted_tokens', 'spec_accepted_tokens',
                'spec_emitted_tokens', 'spec_accept_rate',
                'accepted_len_mean', 'tokens_per_step'):
        assert key in m, key
    assert m['accepted_len_mean'] > 1.0
    pool = engine_lib.EnginePool([eng])
    pm = pool.metrics()
    assert pm['spec_accepted_tokens'] == m['spec_accepted_tokens']
    assert pm['accepted_len_mean'] == m['accepted_len_mean']
    assert pm['tokens_per_step'] == m['tokens_per_step']


def test_spec_metrics_absent_when_off(params):
    eng = _engine(params, spec_k=0)
    eng.generate([_PROMPTS[3]], max_new_tokens=4)
    m = eng.metrics()
    assert 'spec_steps' not in m
    assert m['tokens_per_step'] == 1.0
