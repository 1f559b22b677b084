"""Pluggable engine scheduler (infer/sched/): policy edge cases and
the fcfs bit-identity gate.

Pure-policy tests drive the schedulers directly with stub requests
(no device, no engine): DRR weighted service ratios, deficit
carryover bounds, empty-tenant GC, per-tenant quota shedding (the
offender sheds, the victim never), weight changes mid-flight, EDF
ordering with deterministic ties, and page-pressure victim selection
under each policy.

Engine-level tests pin the refactor's contract: ``fcfs`` greedy
outputs MATCH THE PRE-REFACTOR ENGINE — the ``GOLD`` tokens below
were captured from the inline step loop before the scheduler
extraction, over the same mixed-length + paged-preemption workload
test_infer_pipeline gates, at pipeline depth 0 and 1.
"""
import dataclasses
import time
from typing import List, Optional

import pytest

from skypilot_tpu.infer import sched as sched_lib
from skypilot_tpu.infer.sched import base as sched_base

pytestmark = pytest.mark.jax


@dataclasses.dataclass
class FakeReq:
    request_id: int
    prompt_tokens: List[int]
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    tenant: str = 'default'
    deadline: Optional[float] = None
    cancelled: bool = False
    submitted_at: float = 0.0


def _req(rid, cost=10, tenant='default', deadline=None, sub=None):
    return FakeReq(request_id=rid, prompt_tokens=[1] * cost,
                   tenant=tenant, deadline=deadline,
                   submitted_at=sub if sub is not None else rid)


# ---------- factory / config ----------------------------------------------
def test_make_unknown_policy_is_loud():
    with pytest.raises(ValueError, match='unknown scheduler'):
        sched_lib.make('priority')


def test_admission_error_stays_valueerror():
    # The multihost lockstep uniform-rejection rule depends on it.
    assert issubclass(sched_lib.AdmissionError, ValueError)


# ---------- fcfs ------------------------------------------------------------
def test_fcfs_fifo_and_requeue_front():
    s = sched_lib.make('fcfs')
    for i in range(3):
        s.enqueue(_req(i))
    first = s.pop_next()
    assert first.request_id == 0
    s.requeue(first)          # preemption: back to the FRONT
    assert [s.pop_next().request_id for _ in range(3)] == [0, 1, 2]
    assert s.pop_next() is None


def test_fcfs_round_robin_cursor_matches_legacy_arithmetic():
    # The historical inline rule: rr = (rr + 1) % len(candidates);
    # slot = candidates[rr] — with the cursor persisting across steps.
    s = sched_lib.make('fcfs')
    slots = [None] * 4
    candidates = [0, 2, 3]
    rr = 0
    for _ in range(7):
        rr = (rr + 1) % len(candidates)
        assert s.next_prefill_slot(candidates, slots) \
            == candidates[rr]


def test_fcfs_admission_bounds_and_drain_estimate():
    s = sched_lib.make('fcfs', sched_lib.SchedulerConfig(
        max_queue_requests=2, max_queue_tokens=100))
    s.enqueue(_req(0, cost=40))
    s.enqueue(_req(1, cost=40))
    with pytest.raises(sched_lib.AdmissionError) as ei:
        s.admit(_req(2, cost=10), drain_tps=40.0)
    # 80 queued tokens at 40 tok/s → ~2 s drain estimate, not 1.0.
    assert ei.value.retry_after_s == pytest.approx(2.0)
    s.pop_next()
    with pytest.raises(sched_lib.AdmissionError, match='queued tokens'):
        s.admit(_req(3, cost=70), drain_tps=0.0)
    s.admit(_req(4, cost=30), drain_tps=0.0)   # fits both bounds


def test_fcfs_sweep_classifies_and_counts():
    s = sched_lib.make('fcfs')
    dead = _req(0)
    dead.cancelled = True
    late = _req(1, deadline=time.time() - 5)
    live = _req(2)
    for r in (dead, late, live):
        s.enqueue(r)
    swept = [(r.request_id, reason) for r, reason in
             s.sweep(time.time())]
    assert swept == [(0, 'cancelled'), (1, 'deadline')]
    assert [r.request_id for r in s.queued_requests()] == [2]
    snap = s.snapshot()['default']
    assert snap['abandoned'] == 1 and snap['expired'] == 1


# ---------- deadline (EDF) --------------------------------------------------
def test_deadline_pops_edf_with_fifo_ties():
    s = sched_lib.make('deadline')
    s.enqueue(_req(0, deadline=None))       # best-effort: last
    s.enqueue(_req(1, deadline=100.0))
    s.enqueue(_req(2, deadline=50.0))
    s.enqueue(_req(3, deadline=100.0))      # tie with 1: FIFO
    order = [s.pop_next().request_id for _ in range(4)]
    assert order == [2, 1, 3, 0]


def test_deadline_requeue_resumes_first_among_ties():
    s = sched_lib.make('deadline')
    a, b = _req(0, deadline=60.0), _req(1, deadline=60.0)
    s.enqueue(a)
    s.enqueue(b)
    got = s.pop_next()
    assert got is a
    s.requeue(a)            # preempted: front position wins the tie
    assert s.pop_next() is a


def test_deadline_victim_is_most_slack():
    s = sched_lib.make('deadline')
    slots = [_req(0, deadline=10.0, sub=5.0),
             _req(1, deadline=None, sub=1.0),   # infinite slack
             _req(2, deadline=99.0, sub=2.0)]
    assert s.pick_victim([0, 1, 2], slots) == 1
    # Among finite deadlines, the latest one pays.
    assert s.pick_victim([0, 2], slots) == 2


def test_deadline_prefill_budget_goes_to_most_urgent():
    s = sched_lib.make('deadline')
    slots = [_req(0, deadline=90.0), _req(1, deadline=10.0), None]
    assert s.next_prefill_slot([0, 1], slots) == 1


# ---------- wfq -------------------------------------------------------------
def test_wfq_service_tokens_proportional_to_weight():
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        tenant_weights={'a': 2.0, 'b': 1.0}))
    for i in range(30):
        s.enqueue(_req(i, cost=10, tenant='a'))
        s.enqueue(_req(100 + i, cost=10, tenant='b'))
    served = {'a': 0, 'b': 0}
    for n in range(1, 41):
        r = s.pop_next()
        served[r.tenant] += 10
        if n >= 20:
            share = served['a'] / (served['a'] + served['b'])
            assert 0.5 < share < 0.85, (
                f'weight-2 tenant got {share:.0%} of service '
                f'after {n} pops (ideal 67%)')


def test_wfq_deficit_carryover_bounded_and_gc():
    quantum = 64
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        quantum_tokens=quantum))
    s.enqueue(_req(0, cost=500, tenant='big'))   # head >> quantum
    s.enqueue(_req(1, cost=5, tenant='small'))
    while s.pending():
        # Invariant at every point: carryover never exceeds one
        # quantum beyond the head's own cost.
        for t, d in s._deficit.items():
            q = s._queues.get(t)
            head = sched_base.request_cost(q[0]) if q else 0
            assert d <= quantum * s.weight(t) + head + 1e-9
        s.pop_next()
    # Empty-tenant GC: scheduling state reclaimed, stats survive.
    assert not s._queues and not s._order and not s._deficit
    assert s.snapshot()['big']['decode_tokens'] == 0   # stats object


def test_wfq_quota_sheds_offender_only():
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        max_queue_requests=10))
    # Aggressor alone: the whole bound is its share.
    for i in range(10):
        s.admit(_req(i, tenant='aggr'))
        s.enqueue(_req(i, tenant='aggr'))
    # Victim arrives: its quota is ceil(10 * 1/2) = 5, queue empty.
    s.admit(_req(100, tenant='victim'))
    s.enqueue(_req(100, tenant='victim'))
    # The aggressor — now over its halved share — is the one shed.
    with pytest.raises(sched_lib.AdmissionError, match="'aggr'"):
        s.admit(_req(11, tenant='aggr'))
    # The victim keeps admitting up to ITS quota.
    for i in range(4):
        s.admit(_req(101 + i, tenant='victim'))
        s.enqueue(_req(101 + i, tenant='victim'))
    with pytest.raises(sched_lib.AdmissionError, match="'victim'"):
        s.admit(_req(200, tenant='victim'))
    assert s.snapshot()['aggr']['shed'] == 1
    assert s.snapshot()['victim']['shed'] == 1


def test_wfq_tenant_minting_hits_hard_ceiling():
    """Per-tenant quotas guarantee every tenant at least one slot, so
    a client minting a fresh tenant id per request would otherwise
    queue unboundedly past the configured cap: the 2x hard ceiling
    stops it."""
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        max_queue_requests=8))
    admitted = 0
    with pytest.raises(sched_lib.AdmissionError,
                       match='hard ceiling'):
        for i in range(100):
            s.admit(_req(i, tenant=f'mint-{i}'))
            s.enqueue(_req(i, tenant=f'mint-{i}'))
            admitted += 1
    assert admitted == 16, admitted   # exactly 2 x max_queue_requests
    # Token-denominated ceiling too.
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        max_queue_tokens=100))
    with pytest.raises(sched_lib.AdmissionError,
                       match='hard ceiling'):
        for i in range(100):
            s.admit(_req(i, cost=30, tenant=f'mint-{i}'))
            s.enqueue(_req(i, cost=30, tenant=f'mint-{i}'))
    assert s.queued_tokens() <= 200


def test_tenant_stats_map_is_bounded():
    """Cumulative per-tenant stats evict oldest idle entries at the
    cap — tenant ids are client-controlled and must not grow the map
    (or /metrics) without bound."""
    s = sched_lib.make('fcfs')
    s.max_tenant_stats = 8
    for i in range(50):
        s.note_tokens(_req(i, tenant=f't{i}'))
    assert len(s._stats) <= 8
    assert 't49' in s._stats          # newest survives
    # Tenants with QUEUED work are never evicted.
    s.enqueue(_req(1000, tenant='t49'))
    for i in range(50, 80):
        s.note_tokens(_req(i, tenant=f't{i}'))
    assert 't49' in s._stats


def test_wfq_oversized_request_sheds_loud():
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        max_queue_tokens=50))
    with pytest.raises(sched_lib.AdmissionError,
                       match='exceeds max_queue_tokens'):
        s.admit(_req(0, cost=60))


def test_wfq_retry_after_is_tenant_scoped():
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        tenant_weights={'a': 1.0, 'b': 1.0}))
    for i in range(10):
        s.enqueue(_req(i, cost=20, tenant='a'))
    s.enqueue(_req(100, cost=20, tenant='b'))
    # a: 200 queued tokens at half of 40 tok/s → ~10 s.
    assert s.retry_after('a', drain_tps=40.0) == pytest.approx(10.0)
    # b's backlog is one request — far sooner than a's.
    assert s.retry_after('b', 40.0) < s.retry_after('a', 40.0)


def test_wfq_weight_change_mid_flight():
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        tenant_weights={'a': 1.0, 'b': 1.0}))
    for i in range(40):
        s.enqueue(_req(i, cost=10, tenant='a'))
        s.enqueue(_req(100 + i, cost=10, tenant='b'))
    for _ in range(10):
        s.pop_next()
    s.set_tenant_weights({'a': 6.0, 'b': 1.0})   # the runtime knob
    served = {'a': 0, 'b': 0}
    for _ in range(28):
        served[s.pop_next().tenant] += 1
    assert served['a'] > 2 * served['b'], (
        f'weight bump never took effect: {served}')


def test_wfq_victim_is_over_share_tenants_youngest():
    s = sched_lib.make('wfq', sched_lib.SchedulerConfig(
        tenant_weights={'a': 1.0, 'b': 1.0}))
    slots = [_req(0, cost=40, tenant='a', sub=1.0),
             _req(1, cost=40, tenant='a', sub=3.0),
             _req(2, cost=10, tenant='b', sub=2.0)]
    # a holds 80 service tokens vs b's 10: a's youngest pays.
    assert s.pick_victim([0, 1, 2], slots) == 1
    # Weight can flip it: a at weight 10 is under-share.
    s.set_tenant_weights({'a': 10.0, 'b': 1.0})
    assert s.pick_victim([0, 1, 2], slots) == 2


def test_wfq_prefill_budget_rotates_tenants():
    s = sched_lib.make('wfq')
    slots = [_req(0, tenant='a'), _req(1, tenant='a'),
             _req(2, tenant='b'), None]
    picks = [s.next_prefill_slot([0, 1, 2], slots) for _ in range(4)]
    assert picks == [0, 2, 0, 2], (
        'chunk budget must alternate tenants, FIFO within')


# ---------- stats aggregation ----------------------------------------------
def test_aggregate_stats_merges_tiers_exactly():
    a = {'t': {'queue_depth': 1, 'queued_tokens': 10, 'weight': 1.0,
               'queue_waits': [0.010], 'ttfts': [0.5],
               'decode_tokens': 100, 'shed': 1, 'cancelled': 0,
               'expired': 0, 'abandoned': 0}}
    b = {'t': {'queue_depth': 2, 'queued_tokens': 30, 'weight': 1.0,
               'queue_waits': [0.030], 'ttfts': [1.5],
               'decode_tokens': 300, 'shed': 0, 'cancelled': 2,
               'expired': 0, 'abandoned': 0}}
    out = sched_lib.aggregate_stats([a, b], decode_time_s=2.0)['t']
    assert out['queue_depth'] == 3
    assert out['queued_tokens'] == 40
    assert out['decode_tokens'] == 400
    assert out['tokens_per_sec'] == pytest.approx(200.0)
    assert out['requests_shed'] == 1
    assert out['requests_cancelled'] == 2
    assert out['queue_wait_p50_ms'] == pytest.approx(30.0)
    assert out['ttft_p50_s'] == pytest.approx(1.5)


# ---------- engine level ----------------------------------------------------
from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()

# The test_infer_pipeline workload: mixed multi-chunk/short prompts, 3
# slots, paged pool small enough to force preemption.
_PROMPTS = [[11] * 60, [23] * 60, [37] * 60,
            [5, 17, 101, 7], [9, 8, 7, 6, 5]]


@pytest.fixture(scope='module')
def params(tiny_params):
    return tiny_params


def test_fcfs_bit_identical_to_the_oracle_at_depth_1_and_0(params,
        greedy_oracle):
    """The step loop under fcfs reproduces the greedy tokens of the
    no-cache float32 forward (conftest's oracle), at depth 1 and (same
    engine, the multihost reconfiguration path) depth 0, with paged
    preemption in play: scheduling decides when a token is computed,
    never which."""
    gold = greedy_oracle(_PROMPTS, 6)
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, pipeline_depth=1,
                                paged=True, page_size=16, n_pages=13))
    out1 = [r.output_tokens
            for r in eng.generate(_PROMPTS, max_new_tokens=6)]
    assert out1 == gold, 'depth 1 diverged from the oracle'
    assert eng.metrics()['preemptions'] >= 1, (
        'workload no longer exercises page pressure')
    eng.set_pipeline_depth(0)
    out0 = [r.output_tokens
            for r in eng.generate(_PROMPTS, max_new_tokens=6)]
    assert out0 == gold, 'depth 0 diverged from the oracle'


def test_deadline_engine_serves_edf(params):
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=1, max_seq_len=64,
                                prefill_buckets=(8,),
                                scheduler='deadline'))
    filler = eng.submit([9, 9], max_new_tokens=12)
    while eng.metrics()['num_waiting'] or not filler.output_tokens:
        eng.step()   # filler owns the only slot
    now = time.time()
    best_effort = eng.submit([1, 2], max_new_tokens=2)
    relaxed = eng.submit([3, 4], max_new_tokens=2,
                         deadline=now + 300)
    urgent = eng.submit([5, 6], max_new_tokens=2,
                        deadline=now + 120)
    eng.run_until_idle()
    assert (urgent.finished_at < relaxed.finished_at
            < best_effort.finished_at), (
        'deadline engine must serve EDF, best-effort last')


def test_set_scheduler_migrates_queued_work(params):
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=1, max_seq_len=64,
                                prefill_buckets=(8,)))
    reqs = [eng.submit([7, 7], max_new_tokens=2, tenant=f't{i}')
            for i in range(4)]
    eng.set_scheduler('wfq', tenant_weights={'t0': 2.0})
    assert eng.metrics()['scheduler'] == 'wfq'
    assert eng.metrics()['num_waiting'] == 4
    eng.run_until_idle()
    assert all(r.finish_reason == 'max_tokens' for r in reqs), (
        'queued requests lost in the scheduler swap')


def test_tenant_metrics_and_queue_wait_surfaced(params):
    eng = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=2, max_seq_len=64,
                                prefill_buckets=(8,)))
    eng.generate([[2, 3]], max_new_tokens=2)   # warm compile
    for tenant in ('acme', 'globex', 'acme'):
        eng.submit([4, 5, 6], max_new_tokens=3, tenant=tenant)
    eng.run_until_idle()
    m = eng.metrics()
    assert m['scheduler'] == 'fcfs'
    assert m['queued_tokens'] == 0
    assert m['queue_wait_p50_ms'] is not None
    assert m['queue_wait_p99_ms'] >= m['queue_wait_p50_ms']
    tenants = m['tenants']
    assert tenants['acme']['decode_tokens'] == 6
    assert tenants['globex']['decode_tokens'] == 3
    for row in tenants.values():
        assert row['ttft_p50_s'] is not None
        assert row['queue_wait_p50_ms'] is not None
        assert row['requests_shed'] == 0


def test_engine_pool_merges_tenants_across_tiers(params):
    short = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=1, max_seq_len=32,
                                prefill_buckets=(8,)))
    long = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=1, max_seq_len=64,
                                prefill_buckets=(8,)),
        seed=1)
    pool = engine_lib.EnginePool([short, long])
    pool.submit([1] * 4, max_new_tokens=2, tenant='acme')   # short
    pool.submit([1] * 40, max_new_tokens=2, tenant='acme')  # long tier
    pool.run_until_idle()
    m = pool.metrics()
    assert m['scheduler'] == 'fcfs'
    assert m['tenants']['acme']['decode_tokens'] == 4, (
        'pool must merge per-tenant stats across tiers')
    assert m['queue_wait_p50_ms'] is not None
