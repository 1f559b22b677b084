"""Engine flight recorder (observability/stepline.py): tier-1 gates.

- **Bit identity + overhead canary**: greedy outputs are identical
  recorder on vs off (dense and paged-preempting), and the overhead
  canary asserts in scheduler-VIRTUAL steps — identical
  ``decode_steps`` on vs off — plus an absolute per-append cost bound
  with ~50x headroom; never a wall-clock A/B ratio (the PR 11
  de-flake pattern: concurrent pytest load cannot flip it). The
  fused/spec/depth cross combos are covered in tier-1 by the existing
  golden gates (test_infer_fused/spec/pipeline run recorder-ON —
  the default — against goldens captured pre-recorder); the explicit
  on/off fused+spec matrix here is slow-marked belt-and-suspenders.
- **Ring wraparound**, **anomaly-dump triggering** for every trigger
  kind (ttft_slo / preemption / cache_full / admission_shed /
  breaker_open), **Perfetto JSON schema validation** of exported
  traces, a **concurrent-poll stress** (HTTP metrics/stepline readers
  racing the step loop — the PR 6 ``_ttfts`` bug class), and the span
  store's **TTL x size-cap GC composition**.

Engines are module-fixture-shared where the assertions allow (each
build pays a full compile on this box); the dump tests use one-bucket
minimal configs for the same reason.
"""
import asyncio
import collections
import threading
import time

import pytest

pytestmark = pytest.mark.jax

import jax  # noqa: E402
import numpy as np  # noqa: E402

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402
from skypilot_tpu.observability import render as render_lib  # noqa: E402
from skypilot_tpu.observability import stepline  # noqa: E402
from skypilot_tpu.observability import store as store_lib  # noqa: E402

CFG = llama.LlamaConfig.tiny()

# The PR 3 determinism workload shape: mixed short/multi-chunk
# prompts, more requests than slots; the paged variant's pool is small
# enough to force preemption mid-run.
_PROMPTS = [[11] * 60, [23] * 60, [37] * 60,
            [5, 17, 101, 7], [9, 8, 7, 6, 5]]


@pytest.fixture(scope='module')
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _ecfg(paged=False, **kw):
    base = dict(n_slots=3, max_seq_len=128, prefill_buckets=(16, 32),
                prefill_chunk=32, pipeline_depth=1)
    if paged:
        base.update(paged=True, page_size=16, n_pages=13)
    base.update(kw)
    return engine_lib.EngineConfig(**base)


def _tiny_ecfg(**kw):
    """One prefill bucket, two slots: the cheapest compile footprint
    that still decodes (for the per-trigger dump tests)."""
    base = dict(n_slots=2, max_seq_len=64, prefill_buckets=(16,),
                prefill_chunk=16, pipeline_depth=1)
    base.update(kw)
    return engine_lib.EngineConfig(**base)


def _run_paged(params):
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg(paged=True))
    reqs = eng.generate(_PROMPTS, max_new_tokens=6)
    return ([r.output_tokens for r in reqs],
            eng.metrics()['decode_steps'], eng)


@pytest.fixture(scope='module')
def recorded_paged(params):
    """A PAGED engine (pool small enough to preempt; asserted
    non-vacuous where used) run over the workload once: (outputs,
    decode_steps, engine) — shared by the determinism gate, the shape
    checks, the stress test and the Perfetto export."""
    return _run_paged(params)


@pytest.fixture
def dump_store(tmp_path):
    """Anomaly dumps land in a test-local store (never the user's
    traces.db); the session-wide tmp store (tests/conftest.py) is
    restored afterwards, not cleared — later tests' background dumps
    must keep a deterministic target."""
    prev = stepline._store  # noqa: SLF001 — save/restore, not reach-in
    st = store_lib.SpanStore(db_path=str(tmp_path / 'dumps.db'))
    stepline.set_dump_store(st)
    yield st
    stepline.flush_dumps(5.0)
    stepline.set_dump_store(prev)


def _dumps_by_trigger(store):
    out = {}
    for t in store.list_traces(limit=200):
        spans = store.get_trace(t['trace_id'])
        for s in spans:
            if s['name'] in ('stepline.trigger', 'stepline.fleet_dump'):
                out.setdefault(s['attrs'].get('trigger'),
                               []).append(spans)
    return out


# ---- ring mechanics ------------------------------------------------------

def test_ring_wraparound():
    ring = stepline.Ring(8)
    for i in range(20):
        ring.append(i)
    assert ring.total == 20
    assert len(ring) == 8
    assert ring.snapshot() == list(range(12, 20))
    small = stepline.Ring(1)
    small.append('a')
    small.append('b')
    assert small.snapshot() == ['b'] and small.total == 2


def test_step_ring_wraparound_keeps_idx_contiguous(params):
    """A capacity far below the workload's step count must retain the
    LAST cap records with contiguous monotonic idx."""
    eng = engine_lib.InferenceEngine(CFG, params,
                                     _tiny_ecfg(stepline_cap=8))
    eng.generate([[3] * 20, [5] * 20], max_new_tokens=12)
    snap = eng.stepline_snapshot()
    assert snap['steps_total'] > 8, 'workload too small to wrap'
    idxs = [r['idx'] for r in snap['steps']]
    assert len(idxs) == 8
    assert idxs == list(range(idxs[0], idxs[0] + 8))
    assert idxs[-1] == snap['steps_total'] - 1


# ---- bit identity + the overhead canary ----------------------------------

def test_recorded_run_repeats_in_tokens_and_virtual_steps(
        recorded_paged, params):
    """The recorder reads clocks and counters and never scheduling
    state: a second engine over the same paged-preempting workload
    produces identical greedy tokens and an IDENTICAL number of
    dispatched engine steps, whatever the wall clock did to either
    run's records. Preemption asserted non-vacuous. Asserted in
    scheduler-virtual steps — wall-clock comparisons of two runs flake
    under concurrent CPU load (the PR 11 fairness-gate lesson)."""
    first = recorded_paged
    assert first[2].metrics()['preemptions'] > 0, (
        'workload never preempted — the gate is not exercising page '
        'pressure')
    again = _run_paged(params)
    assert first[0] == again[0], 'a rerun changed greedy tokens'
    assert first[1] == again[1], (
        f'a rerun changed the step count: {first[1]} vs {again[1]}')


def test_overhead_canary_absolute_append_bound():
    """The wall-clock half of the overhead canary, de-flaked: a tight
    absolute bound on the recorder's OWN per-record cost (a ring slot
    write + index bump), with ~50x headroom over the observed ~2 µs —
    generous enough that a loaded CI box cannot flip it, tight enough
    that an accidental O(ring) append or per-record allocation storm
    fails."""
    rec = stepline.StepRecorder(cap=256, min_dump_interval_s=0)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        rec.note_step(stepline.StepRecord(
            idx=i, t=0.0, dur_s=1e-3, kind='decode',
            dispatch_s=5e-4, drain_s=1e-4, readback_s=1e-4,
            batch=3, chunk_tokens=0, prefilling=0, spec_drafted=0,
            spec_accepted=0, pages_free=4, prefix_evictions=0,
            preemptions=0, queue_depth=2,
            tenant_depths={'default': 2}))
    per_append = (time.perf_counter() - t0) / n
    assert per_append < 100e-6, (
        f'recorder append costs {per_append * 1e6:.1f}µs/step — the '
        f'"low-overhead" contract is broken')
    assert rec.steps.total == n and len(rec.steps) == 256


def test_step_records_shape(recorded_paged):
    eng = recorded_paged[2]
    snap = eng.stepline_snapshot()
    assert snap['enabled'] and snap['steps']
    kinds = {r['kind'] for r in snap['steps']}
    assert kinds <= {'prefill', 'decode', 'mixed', 'verify', 'free'}
    for r in snap['steps']:
        assert r['dur_s'] >= 0
        # Stage shares are measured independently; each is bounded by
        # the step and host is the clamped remainder.
        assert r['dispatch_s'] >= 0 and r['readback_s'] >= 0
        assert r['host_s'] >= 0
        assert r['pages_free'] >= 0      # paged engine reports pool
        assert isinstance(r['queue_depth'], int)
    events = {e['event'] for e in snap['events']}
    assert {'submit', 'first_dispatch', 'first_token',
            'done'} <= events
    # The paged workload preempted (asserted in the identity gate):
    # the timeline shows it and the post-preemption re-slot.
    assert 'preemption' in events and 'resume' in events
    summ = eng.stepline_summary()
    assert summ['steps'] == len(snap['steps'])
    shares = [summ[f'{s}_share'] for s in stepline.STAGES]
    assert all(sh is not None and 0 <= sh <= 1 for sh in shares)
    assert 0.99 <= sum(shares) <= 1.01
    # The thread's CPU time lies inside the step's wall time; a bare
    # engine (no server loop) never waits for work.
    for r in snap['steps']:
        assert 0.0 <= r['cpu_s'] <= r['dur_s']
        assert r['wait_s'] == 0.0 and r['dev_empty'] in (0, 1)
    assert 0 < summ['cpu_share'] <= 1 and summ['wait_s'] == 0.0
    assert summ['wait_share'] == 0.0
    m = eng.metrics()
    # every step that dispatched launched at least one program, and a
    # launch that found the device empty marked its step's record
    dispatched = sum(1 for r in snap['steps'] if r['kind'] != 'free')
    assert m['launches'] >= dispatched > 0
    assert (summ['dev_empty_steps'] <= m['launches_device_empty']
            <= m['launches'])
    assert m['launches_after_wait'] == 0
    assert m['engine_wait_s'] == 0.0


def test_recorder_surfaces_agree_on_what_was_recorded(recorded_paged):
    eng = recorded_paged[2]
    snap = eng.stepline_snapshot()
    assert snap['enabled'] is True
    assert eng.stepline_summary()['enabled'] is True
    m = eng.metrics()
    assert m['stepline_steps'] == snap['steps_total'] > 0
    assert m['stepline_dumps'] == snap['dumps']


# ---- the thread's CPU time, and what a launch finds on the device ---------

class _SlowPair:
    """An in-flight pair whose device→host copy takes ``delay_s``: the
    readback blocks, and the thread stands meanwhile."""

    def __init__(self, pair, delay_s):
        self.pair, self.delay_s = pair, delay_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.asarray(self.pair)


def test_a_blocked_readback_is_wall_time_but_not_cpu_time(params):
    eng = engine_lib.InferenceEngine(CFG, params, _tiny_ecfg())
    eng.generate([[3] * 5], max_new_tokens=3)          # compile
    eng.submit([5] * 5, max_new_tokens=6)
    while not eng._queue:
        eng.step()
    rec = eng._queue[0]
    rec.out = _SlowPair(rec.out, 0.3)
    before = eng.stepline_snapshot()['steps_total']
    eng.step()
    rec = next(r for r in eng.stepline_snapshot()['steps']
               if r['idx'] == before)
    eng.run_until_idle()
    assert rec['readback_s'] >= 0.3 and rec['dur_s'] >= 0.3
    # the thread slept through the copy: off the CPU, and the record
    # says so where dur_s and readback_s alone cannot
    assert rec['cpu_s'] < rec['dur_s'] - 0.25
    assert rec['cpu_s'] < 0.1 * rec['dur_s']


class _Result:
    """What a launch left behind, as ready as the test says."""

    def __init__(self, ready):
        self.ready = ready

    def is_deleted(self):
        return False

    def is_ready(self):
        if self.ready is None:
            raise RuntimeError('no such buffer')
        return self.ready


def _launch_counts(eng):
    m = eng.metrics()
    return (m['launches'], m['launches_device_empty'],
            m['launches_after_wait'])


def test_launch_counters_ask_the_previous_result(params):
    eng = engine_lib.InferenceEngine(CFG, params, _tiny_ecfg())
    assert _launch_counts(eng) == (0, 0, 0)
    eng._last_dev = _Result(False)      # the device is still at it
    eng._note_launch()
    assert _launch_counts(eng) == (1, 0, 0) and eng._sl_dev_empty == 0
    eng._last_dev = _Result(True)       # its queue ran empty
    eng._note_launch()
    assert _launch_counts(eng) == (2, 1, 0) and eng._sl_dev_empty == 1
    # the first launch after a wait for work finds it empty by
    # definition, and only the first
    with eng.wait_stage():
        pass
    eng._note_launch()
    eng._note_launch()
    assert _launch_counts(eng) == (4, 3, 1)
    # busy after a wait (another tier's program): not an empty launch
    with eng.wait_stage():
        pass
    eng._last_dev = _Result(False)
    eng._note_launch()
    assert _launch_counts(eng) == (5, 3, 1)
    # a result that cannot be asked counts as no launch, and the wait
    # before it marks no later launch
    with eng.wait_stage():
        pass
    eng._last_dev = _Result(None)
    eng._note_launch()
    deleted = jax.numpy.zeros((2,), jax.numpy.int32)
    deleted.delete()
    eng._last_dev = deleted
    eng._note_launch()
    eng._last_dev = _Result(True)
    eng._note_launch()
    assert _launch_counts(eng) == (6, 4, 1)


def test_engine_pool_sums_the_launch_counters_and_has_one_wait(params):
    small = engine_lib.InferenceEngine(
        CFG, params, _tiny_ecfg(max_seq_len=32))
    large = engine_lib.InferenceEngine(CFG, params, _tiny_ecfg())
    pool = engine_lib.EnginePool([small, large])
    # One thread steps every tier, so one clock: whichever tier
    # launches first after a wait is the launch after it...
    with pool.wait_stage():
        time.sleep(0.01)
    real = (small._last_dev, large._last_dev)
    for eng, ready in ((large, True), (small, True), (large, False)):
        eng._last_dev = _Result(ready)
        eng._note_launch()
    small._last_dev, large._last_dev = real
    m = pool.metrics()
    assert (m['launches'], m['launches_device_empty'],
            m['launches_after_wait']) == (3, 2, 1)
    assert [t['launches_after_wait'] for t in m['tiers']] == [0, 1]
    # ...and the wait is counted once, not once a tier
    assert 0.01 <= m['engine_wait_s'] < 0.02 * len(pool.engines)
    assert {t['engine_wait_s'] for t in m['tiers']} == {m['engine_wait_s']}
    # ...and goes to the record of the tier that works next, so no
    # later record of another tier spans steps that were not waits
    before = (small.stepline_snapshot()['steps_total'],
              large.stepline_snapshot()['steps_total'])
    with pool.wait_stage():
        time.sleep(0.01)
    for prompt in ([5] * 40, [5] * 5):      # the large tier's, the small's
        pool.submit(prompt, max_new_tokens=2)
        pool.run_until_idle()
    s_recs, l_recs = (
        [r for r in e.stepline_snapshot()['steps'] if r['idx'] >= n]
        for e, n in zip((small, large), before))
    assert l_recs[0]['wait_s'] >= 0.01
    assert s_recs and all(r['wait_s'] == 0.0 for r in s_recs + l_recs[1:])


# ---- anomaly-triggered dumps ---------------------------------------------

def test_dump_rate_limit_per_trigger():
    rec = stepline.StepRecorder(cap=8, min_dump_interval_s=1000.0)
    assert rec.should_dump('ttft_slo', now=100.0)
    assert not rec.should_dump('ttft_slo', now=100.5)
    assert rec.should_dump('preemption', now=100.5)   # separate kind
    unlimited = stepline.StepRecorder(cap=8, min_dump_interval_s=0)
    assert unlimited.should_dump('ttft_slo', now=1.0)
    assert unlimited.should_dump('ttft_slo', now=1.0)


def test_ttft_slo_dump_round_trips_to_profile(params, dump_store):
    """The acceptance-criteria round trip: induced TTFT-SLO breach →
    ring snapshot in the span store → a valid Perfetto trace
    containing the triggering step — findable by request id, exactly
    what `sky-tpu profile <request_id>` loads."""
    eng = engine_lib.InferenceEngine(CFG, params,
                                     _tiny_ecfg(ttft_slo_s=0.0))
    reqs = eng.generate([[7, 8, 9]], max_new_tokens=4)
    assert stepline.flush_dumps(10.0), 'dump writer did not drain'
    assert eng.metrics()['stepline_dumps'] >= 1
    by_trigger = _dumps_by_trigger(dump_store)
    assert 'ttft_slo' in by_trigger
    spans = by_trigger['ttft_slo'][0]
    names = {s['name'] for s in spans}
    assert 'stepline.dump' in names and 'stepline.trigger' in names
    assert any(n.startswith('step.') for n in names), (
        'dump carries no step records — the black box is empty')
    trigger = next(s for s in spans if s['name'] == 'stepline.trigger')
    assert trigger['status'] == 'anomaly:ttft_slo'
    assert trigger['attrs']['slo_s'] == 0.0
    rid = trigger['attrs']['request_id']
    assert rid in {r.request_id for r in reqs}
    # profile-by-request-id path: the store indexes the dump's spans
    # by the triggering request.
    assert dump_store.trace_for_request(str(rid)), (
        'dump not findable by request id')
    doc = render_lib.to_perfetto(spans)
    assert stepline.validate_perfetto(doc) == []


def test_preemption_dump_triggered(params, dump_store):
    # A pool of 4 usable pages against two 32-token prompts decoding
    # to 40: the second admission must evict the first (page_size 16).
    eng = engine_lib.InferenceEngine(
        CFG, params, _tiny_ecfg(paged=True, page_size=16, n_pages=5))
    eng.generate([[3] * 32, [5] * 32], max_new_tokens=8)
    assert eng.metrics()['preemptions'] > 0, 'no preemption induced'
    assert stepline.flush_dumps(10.0)
    by_trigger = _dumps_by_trigger(dump_store)
    assert 'preemption' in by_trigger
    trig = next(s for s in by_trigger['preemption'][0]
                if s['name'] == 'stepline.trigger')
    assert 'tokens_recomputed' in trig['attrs']


def test_cache_full_dump_triggered(params, dump_store):
    eng = engine_lib.InferenceEngine(CFG, params, _tiny_ecfg())
    r = eng.generate([[3] * 40], max_new_tokens=200)[0]
    assert r.finish_reason == 'cache_full'
    assert stepline.flush_dumps(10.0)
    assert 'cache_full' in _dumps_by_trigger(dump_store)


def test_admission_shed_dump_triggered(params, dump_store):
    # Submit-only (no step loop): no program ever compiles, the queue
    # bound alone drives the trigger.
    eng = engine_lib.InferenceEngine(
        CFG, params, _tiny_ecfg(max_queue_requests=1))
    eng.submit([1, 2, 3])          # fills the (unstepped) queue
    with pytest.raises(engine_lib.AdmissionError):
        eng.submit([4, 5, 6])
    assert stepline.flush_dumps(10.0)
    by_trigger = _dumps_by_trigger(dump_store)
    assert 'admission_shed' in by_trigger
    trig = next(s for s in by_trigger['admission_shed'][0]
                if s['name'] == 'stepline.trigger')
    assert trig['attrs']['tenant'] == 'default'


def test_breaker_open_dumps_fleet_history(dump_store):
    """The LB-tier trigger: a breaker tripping open (edge-detected
    per sync tick) snapshots the per-replica history rings into the
    span store."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'least_load')
    lb._replica_history['http://r1:1'] = collections.deque(
        [{'t': 10.0, 'queue_depth': 1, 'tokens_per_step': 2.5,
          'decode_tokens': 100}],
        maxlen=lb_lib.HISTORY_LEN)
    for _ in range(3):
        lb.breaker.record_failure('http://r1:1')
    assert lb.breaker.snapshot()['http://r1:1'] == 'open'
    asyncio.run(lb._dump_breaker_edges())
    by_trigger = _dumps_by_trigger(dump_store)
    assert 'breaker_open' in by_trigger
    spans = by_trigger['breaker_open'][0]
    root = next(s for s in spans
                if s['name'] == 'stepline.fleet_dump')
    assert root['attrs']['replicas_open'] == ['http://r1:1']
    samples = [s for s in spans if s['name'] == 'fleet.sample']
    assert samples and samples[0]['attrs']['queue_depth'] == 1
    # Edge semantics: a still-open breaker does not dump again.
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == 1


def test_breaker_edge_deferred_not_dropped_by_rate_limit(dump_store):
    """A SECOND replica tripping inside the dump interval is deferred
    to a later tick, never silently lost — a breaker edge is one-shot
    (the replica stays open, no re-fire), unlike the recurring engine
    triggers where dropping one occurrence is safe."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'least_load')
    for _ in range(3):
        lb.breaker.record_failure('http://r1:1')
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == 1
    # Replica B trips inside the 30 s interval: rate-limited now...
    for _ in range(3):
        lb.breaker.record_failure('http://r2:2')
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == 1
    # ...but the edge stays armed: once the interval passes
    # (simulated), the next tick writes B's fleet dump.
    lb._breaker_dump_at -= stepline.dump_interval_s() + 1
    asyncio.run(lb._dump_breaker_edges())
    dumps = _dumps_by_trigger(dump_store)['breaker_open']
    assert len(dumps) == 2
    roots = [next(s for s in d if s['name'] == 'stepline.fleet_dump')
             for d in dumps]
    assert any(r['attrs']['replicas_open'] == ['http://r2:2']
               for r in roots)


def test_breaker_hard_down_no_redump_via_half_open(dump_store):
    """A hard-down replica cycles open → half-open → failed probe →
    open every cooldown; none of that is a NEW edge — one incident,
    one fleet dump (re-armed only by a real recovery)."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'least_load')
    for _ in range(3):
        lb.breaker.record_failure('http://r1:1')
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == 1
    # Cooldown elapses (state reads half-open), rate-limit window
    # long past, then the probe fails and the breaker re-opens.
    lb.breaker._breakers['http://r1:1'].opened_at -= (
        lb.breaker.cooldown_s + 1)
    lb._breaker_dump_at -= stepline.dump_interval_s() + 1
    asyncio.run(lb._dump_breaker_edges())
    lb.breaker.record_failure('http://r1:1')
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == 1
    # Real recovery re-arms: closed, then a fresh trip dumps again.
    lb.breaker.record_success('http://r1:1')
    asyncio.run(lb._dump_breaker_edges())
    for _ in range(3):
        lb.breaker.record_failure('http://r1:1')
    lb._breaker_dump_at -= stepline.dump_interval_s() + 1
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == 2


def test_engine_pool_disjoint_request_ids(params):
    """Two-tier pools must not collide request ids: the merged
    snapshot (and the span-store dumps, and `sky-tpu profile
    <request_id>`) key per-request timelines by request_id — two
    tiers each counting 1, 2, 3, ... would fold different requests
    into one timeline."""
    short = engine_lib.InferenceEngine(
        CFG, params, engine_lib.EngineConfig(
            n_slots=2, max_seq_len=32, prefill_buckets=(8,)))
    long_e = engine_lib.InferenceEngine(
        CFG, params, engine_lib.EngineConfig(
            n_slots=1, max_seq_len=64, prefill_buckets=(8,)), seed=1)
    pool = engine_lib.EnginePool([long_e, short])
    reqs = pool.generate([[5, 6, 7], [7] * 40, [8, 9]],
                         max_new_tokens=3)
    assert len({r.request_id for r in reqs}) == 3
    snap = pool.stepline_snapshot()
    subs = [ev for ev in snap['events'] if ev['event'] == 'submit']
    assert len(subs) == 3
    assert len({ev['request_id'] for ev in subs}) == 3


def test_breaker_edge_pending_survives_breaker_closing(dump_store):
    """A rate-limited edge still dumps after the interval even when
    the breaker recovered meanwhile (half-open probe succeeded): the
    edge is the incident, not the state — losing it would leave the
    'why did B trip at 14:02' question unanswerable."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'least_load')
    for _ in range(3):
        lb.breaker.record_failure('http://r1:1')
    asyncio.run(lb._dump_breaker_edges())
    for _ in range(3):
        lb.breaker.record_failure('http://r2:2')
    asyncio.run(lb._dump_breaker_edges())    # rate-limited: pending
    lb.breaker.record_success('http://r2:2')   # B recovers
    lb._breaker_dump_at -= stepline.dump_interval_s() + 1
    asyncio.run(lb._dump_breaker_edges())
    dumps = _dumps_by_trigger(dump_store)['breaker_open']
    roots = [next(s for s in d if s['name'] == 'stepline.fleet_dump')
             for d in dumps]
    assert any(r['attrs']['replicas_open'] == ['http://r2:2']
               for r in roots)
    # The owed dump is one-shot: nothing further on the next tick.
    asyncio.run(lb._dump_breaker_edges())
    assert len(_dumps_by_trigger(dump_store)['breaker_open']) == len(dumps)


# ---- Perfetto export -----------------------------------------------------

def test_perfetto_export_schema_and_tracks(recorded_paged):
    snap = recorded_paged[2].stepline_snapshot()
    doc = stepline.to_perfetto(snap)
    assert stepline.validate_perfetto(doc) == []
    events = doc['traceEvents']
    meta_names = {e['args']['name'] for e in events
                  if e['ph'] == 'M' and e['name'] == 'process_name'}
    assert {'engine-step', 'requests'} <= meta_names
    stage_names = {e['args']['name'] for e in events
                   if e['ph'] == 'M' and e['name'] == 'thread_name'}
    assert stage_names == set(stepline.STAGES) | {'wait'}
    req_slices = {e['name'] for e in events
                  if e['ph'] == 'X' and e['pid'] == 1001}
    assert {'req.queue_wait', 'req.prefill', 'req.decode'} <= req_slices
    # Stitched with PR 1 propagated spans: hop pids never collide
    # with the stepline tracks.
    spans = [{'trace_id': 't1', 'span_id': 's1', 'parent_id': None,
              'name': 'lb.proxy', 'hop': 'serve-lb', 'start': 1.0,
              'dur_s': 0.5, 'status': 'ok',
              'attrs': {'request_id': 'r1'}}]
    merged = stepline.to_perfetto(snap, spans=spans)
    assert stepline.validate_perfetto(merged) == []
    names = {e['name'] for e in merged['traceEvents']}
    assert 'lb.proxy' in names and any(
        n.startswith('step.') for n in names)


def test_perfetto_repeated_request_events_all_rendered():
    """A request preempted/resumed twice shows TWO instants of each —
    the live export must not fold repeated events of one kind into
    the last occurrence (the span-store dump path keeps them all, and
    the two views have to agree)."""
    snap = {'enabled': True, 'steps': [], 'events': [
        {'request_id': 7, 'event': 'submit', 't': 1.0, 'tenant': 'a'},
        {'request_id': 7, 'event': 'preemption', 't': 2.0},
        {'request_id': 7, 'event': 'resume', 't': 2.5},
        {'request_id': 7, 'event': 'preemption', 't': 3.0},
        {'request_id': 7, 'event': 'resume', 't': 3.5},
        {'request_id': 7, 'event': 'done', 't': 4.0, 'tenant': 'a'},
    ]}
    doc = stepline.to_perfetto(snap)
    assert stepline.validate_perfetto(doc) == []
    names = [e['name'] for e in doc['traceEvents'] if e['ph'] == 'i']
    assert names.count('req.preemption') == 2
    assert names.count('req.resume') == 2


def test_perfetto_validator_rejects_malformed():
    assert stepline.validate_perfetto([]) != []
    assert stepline.validate_perfetto({}) != []
    assert stepline.validate_perfetto(
        {'traceEvents': [{'ph': 'X', 'name': 'x'}]}) != []
    assert stepline.validate_perfetto(
        {'traceEvents': [{'ph': '?', 'name': 'x', 'pid': 1,
                          'tid': 1}]}) != []


# ---- concurrent-poll stress ----------------------------------------------

def test_concurrent_pollers_race_step_loop(recorded_paged):
    """HTTP-thread readers (metrics / stepline snapshot / windows)
    hammer the engine while the step loop runs — the PR 6 bug class
    (iterating a live deque an appender is mutating raises in
    CPython). Any exception on either side fails. Reuses the warm
    module engine: only the racing itself is under test."""
    eng = recorded_paged[2]
    errors = []
    stop = threading.Event()

    def poller():
        while not stop.is_set():
            try:
                eng.metrics()
                eng.stepline_snapshot()
                eng.stepline_summary()
                eng.ttft_window()
            except Exception as e:  # noqa: BLE001 — the assertion
                errors.append(e)
                return

    threads = [threading.Thread(target=poller) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for p in _PROMPTS + _PROMPTS:
            eng.submit(p, max_new_tokens=6)
        eng.run_until_idle()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, f'poller raced the step loop: {errors[:1]}'
    assert not any(t.is_alive() for t in threads)


# ---- HTTP surfaces -------------------------------------------------------

def test_server_debug_stepline_endpoint(params):
    """GET /debug/stepline on the infer server returns the live ring
    (what `sky-tpu profile <replica-url>` fetches)."""
    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.infer import server as server_lib

    async def flow():
        eng = engine_lib.InferenceEngine(CFG, params, _tiny_ecfg())
        srv = server_lib.InferenceServer(eng)
        srv._thread.start()
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            r = await client.post(
                '/generate', json={'tokens': [7, 7],
                                   'max_new_tokens': 4})
            assert r.status == 200
            r = await client.get('/debug/stepline')
            assert r.status == 200
            snap = await r.json()
            assert snap['enabled'] is True
            assert snap['steps'] and snap['events']
            assert stepline.validate_perfetto(
                stepline.to_perfetto(snap)) == []
            m = await (await client.get('/metrics')).json()
            assert m['stepline_steps'] >= len(snap['steps'])
        finally:
            await client.close()
            srv._stop.set()

    asyncio.run(flow())


def test_lb_history_endpoint_and_windowed_gauges():
    """/-/metrics/history returns the raw per-replica rings;
    /-/metrics derives windowed rates from counter deltas."""
    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.serve import load_balancer as lb_lib

    async def flow():
        lb = lb_lib.LoadBalancer('svc', 'least_load')
        lb._replica_history['http://r1:1'] = collections.deque([
            {'t': 100.0, 'queue_depth': 2, 'tokens_per_step': 2.0,
             'decode_tokens': 100, 'prefix_hits': 10,
             'prefix_misses': 10},
            {'t': 110.0, 'queue_depth': 4, 'tokens_per_step': 3.0,
             'decode_tokens': 300, 'prefix_hits': 25,
             'prefix_misses': 15},
        ], maxlen=lb_lib.HISTORY_LEN)
        client = TestClient(TestServer(lb.make_app()))
        await client.start_server()
        try:
            r = await client.get('/-/metrics/history')
            assert r.status == 200
            hist = await r.json()
            assert hist['history_len'] == lb_lib.HISTORY_LEN
            rows = hist['replicas']['http://r1:1']
            assert [row['queue_depth'] for row in rows] == [2, 4]
            m = await (await client.get('/-/metrics')).json()
            assert m['history_window_s'] == 10.0
            # 200 tokens over 10 s of window.
            assert m['engine_tokens_per_sec_w'] == 20.0
            # Delta hits 15 over delta lookups 20 — the WINDOWED
            # rate, not the cumulative one (which would be 25/40).
            assert m['prefix_hit_rate_w'] == 0.75
        finally:
            await client.close()

    asyncio.run(flow())


def test_lb_history_gauges_null_without_two_samples():
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'least_load')
    m = lb.lb_metrics()
    assert m['history_window_s'] is None
    assert m['engine_tokens_per_sec_w'] is None
    assert m['prefix_hit_rate_w'] is None
    lb._replica_history['u'] = collections.deque(
        [{'t': 1.0, 'queue_depth': 0}], maxlen=4)
    assert lb.lb_metrics()['history_window_s'] is None


def test_lb_history_len_env_fail_open(monkeypatch):
    """Malformed/negative SKY_TPU_LB_HISTORY must never keep the LB
    from starting (same fail-open contract as the store TTL knob)."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    monkeypatch.setenv('SKY_TPU_LB_HISTORY', 'bogus')
    assert lb_lib._history_len() == 120
    monkeypatch.setenv('SKY_TPU_LB_HISTORY', '-3')
    assert lb_lib._history_len() == 1
    monkeypatch.setenv('SKY_TPU_LB_HISTORY', '7')
    assert lb_lib._history_len() == 7


def test_lb_history_gauges_go_stale_when_all_fetches_fail():
    """A fleet whose EVERY ring froze (e.g. the only replica hangs
    while staying in the ready set) must stop contributing rates: the
    frozen ring is its own freshest sample, so only the sync-tick
    counter — which advances even when all fetches fail — can see
    it."""
    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'least_load')
    lb._replica_history['u'] = collections.deque([
        {'t': 100.0, 'queue_depth': 1, 'decode_tokens': 100},
        {'t': 110.0, 'queue_depth': 1, 'decode_tokens': 300},
    ], maxlen=lb_lib.HISTORY_LEN)
    # Fresh (tick lag 0 by default): the window contributes.
    assert lb.lb_metrics()['engine_tokens_per_sec_w'] == 20.0
    # The sync loop kept ticking but 'u' stopped answering.
    lb._history_tick['u'] = 1
    lb._sync_tick = 5
    m = lb.lb_metrics()
    assert m['engine_tokens_per_sec_w'] is None
    assert m['history_window_s'] is None


# ---- span-store retention (TTL satellite) --------------------------------

def _span(trace_id, span_id, start):
    return {'trace_id': trace_id, 'span_id': span_id,
            'parent_id': None, 'name': 'op', 'hop': 'client',
            'start': start, 'dur_s': 0.1, 'status': 'ok',
            'attrs': {}}


def test_store_gc_ttl_drops_old_whole_traces(tmp_path):
    st = store_lib.SpanStore(db_path=str(tmp_path / 't.db'))
    now = time.time()
    st.add_spans([_span('old', f'o{i}', now - 5000) for i in range(3)])
    # A trace is aged by its NEWEST span: one fresh span keeps the
    # whole trace alive.
    st.add_spans([_span('mixed', 'm0', now - 5000),
                  _span('mixed', 'm1', now - 10)])
    st.add_spans([_span('fresh', 'f0', now - 10)])
    deleted = st.gc(ttl_s=3600)
    assert deleted == 3
    assert {t['trace_id'] for t in st.list_traces()} == {
        'mixed', 'fresh'}
    # TTL off (0/unset): nothing age-based happens.
    assert st.gc(ttl_s=0) == 0


def test_store_gc_ttl_env_knob(tmp_path, monkeypatch):
    st = store_lib.SpanStore(db_path=str(tmp_path / 't.db'))
    now = time.time()
    st.add_spans([_span('old', 'o0', now - 5000)])
    monkeypatch.setenv(store_lib.TTL_ENV, '3600')
    assert st.gc() == 1
    monkeypatch.setenv(store_lib.TTL_ENV, 'bogus')
    assert st.gc() == 0   # malformed env = TTL off, never a crash


def test_trace_ids_for_request_surfaces_dump_and_plain(tmp_path):
    """A request id living in BOTH its ordinary propagated-span trace
    and a recorder dump lists both, newest first — `sky-tpu profile`
    filters for the stepline-* one so it never silently renders the
    plain request trace (that's `sky-tpu trace`'s job)."""
    st = store_lib.SpanStore(db_path=str(tmp_path / 't.db'))
    now = time.time()
    plain = _span('req-trace', 'p0', now - 5)
    plain['attrs'] = {'request_id': '42'}
    dump = _span('stepline-abc', 'd0', now - 4)
    dump['attrs'] = {'request_id': '42'}
    st.add_spans([plain])
    st.add_spans([dump])
    tids = st.trace_ids_for_request('42')
    assert tids == ['stepline-abc', 'req-trace']
    assert st.trace_ids_for_request('nope') == []


def test_list_traces_prefix_filter_finds_buried_dumps(tmp_path):
    """The dump listing filters server-side: a dump whose OLDEST ring
    record (= its MIN(start_ts) sort key) predates a pile of newer
    ordinary traces must still appear, even when the page limit is
    smaller than the pile."""
    st = store_lib.SpanStore(db_path=str(tmp_path / 't.db'))
    now = time.time()
    st.add_spans([_span('stepline-old', 'd0', now - 300)])
    for i in range(6):
        st.add_spans([_span(f'req{i}', f'r{i}', now - i)])
    page = st.list_traces(limit=3)
    assert all(not t['trace_id'].startswith('stepline-')
               for t in page)   # the buried-dump scenario is real
    dumps = st.list_traces(limit=3, trace_id_prefix='stepline-')
    assert [t['trace_id'] for t in dumps] == ['stepline-old']


def test_store_gc_ttl_and_size_cap_compose(tmp_path):
    """Both caps in one gc(): age evicts expired traces FIRST, then
    the size cap prunes oldest survivors — so a store over both
    bounds ends under both, and fresh traces outlive stale ones that
    arrived later."""
    st = store_lib.SpanStore(db_path=str(tmp_path / 't.db'))
    now = time.time()
    st.add_spans([_span('expired', f'e{i}', now - 9000)
                  for i in range(4)])
    for k in range(3):
        st.add_spans([_span(f'live{k}', f'l{k}{i}',
                            now - 100 + k) for i in range(2)])
    # TTL kills 'expired' (4 rows); the cap of 4 then drops the
    # oldest live trace (2 rows) to fit 3*2=6 -> 4.
    deleted = st.gc(max_spans=4, ttl_s=3600)
    assert deleted == 6
    left = {t['trace_id'] for t in st.list_traces()}
    assert left == {'live1', 'live2'}
    assert st.count() == 4
