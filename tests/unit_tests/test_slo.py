"""Unit tests for the SLO engine's burn-rate math and surfaces
(docs/observability.md "SLOs and alerting").

The twin gates (tests/sim/test_slo_alerts.py) prove alert fidelity
end to end; these pin the math itself: window edge cases (series
ring wraparound, sparse samples), the stale-replica rule (a hung
replica counts BAD, never masks a burn), budget exhaustion and
reset, per-tenant vs fleet scoping, the spec schema, the autoscaler
slo_burn input, and the Prometheus exposition incl. hostile-label
sanitization.
"""
import asyncio
import json

import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.observability import prometheus as prom_lib
from skypilot_tpu.observability import slo as slo_lib


def _evaluator(objectives, **kw):
    return slo_lib.SloEvaluator(
        slo_lib.objectives_from_spec(objectives), **kw)


def _ttft(threshold=1.0, target=0.99):
    return [{'metric': 'ttft_p99', 'threshold_s': threshold,
             'target': target}]


# ---- objective schema ------------------------------------------------------

def test_objectives_parse_and_round_trip():
    objs = slo_lib.objectives_from_spec([
        {'metric': 'ttft_p99', 'threshold_s': 2.0},
        {'metric': 'itl_p99', 'threshold_s': 0.25, 'target': 0.95},
        {'metric': 'availability', 'target': 0.999},
        {'metric': 'shed_rate', 'tenant': 'web'},
        {'metric': 'replica_availability'},
    ])
    assert [o.key for o in objs] == [
        'ttft_p99', 'itl_p99', 'availability', 'shed_rate:web',
        'replica_availability']
    # to_config round-trips through the validator unchanged.
    again = slo_lib.objectives_from_spec(
        [o.to_config() for o in objs])
    assert again == objs


@pytest.mark.parametrize('bad', [
    {'metric': 'nope'},                                   # unknown metric
    {'metric': 'ttft_p99'},                               # missing threshold
    {'metric': 'ttft_p99', 'threshold_s': 0},             # non-positive
    {'metric': 'availability', 'threshold_s': 1.0},       # threshold misuse
    {'metric': 'availability', 'target': 1.0},            # target bound
    {'metric': 'availability', 'target': 'x'},            # target type
    {'metric': 'replica_availability', 'tenant': 'a'},    # fleet-only
    {'metric': 'ttft_p99', 'threshold_s': 1, 'extra': 1},  # unknown field
])
def test_objectives_reject_bad_entries(bad):
    with pytest.raises(exceptions.InvalidTaskError):
        slo_lib.objectives_from_spec([bad])


def test_objectives_reject_duplicate_keys():
    with pytest.raises(exceptions.InvalidTaskError):
        slo_lib.objectives_from_spec([
            {'metric': 'availability'}, {'metric': 'availability'}])
    # Distinct names disambiguate.
    objs = slo_lib.objectives_from_spec([
        {'metric': 'availability', 'name': 'a'},
        {'metric': 'availability', 'name': 'b', 'target': 0.9}])
    assert [o.key for o in objs] == ['a', 'b']


def test_service_spec_carries_slo():
    from skypilot_tpu.serve import spec as spec_lib
    cfg = {'replicas': 1,
           'slo': [{'metric': 'ttft_p99', 'threshold_s': 1.5}]}
    spec = spec_lib.ServiceSpec.from_config(cfg)
    assert spec.slo == [{'metric': 'ttft_p99', 'target': 0.99,
                         'threshold_s': 1.5}]
    assert spec_lib.ServiceSpec.from_config(
        spec.to_config()).slo == spec.slo
    with pytest.raises(exceptions.InvalidTaskError):
        spec_lib.ServiceSpec.from_config(
            {'replicas': 1, 'slo': [{'metric': 'bogus'}]})


# ---- burn math -------------------------------------------------------------

def test_burn_rate_zero_when_healthy_full_when_dead():
    ev = _evaluator(_ttft())
    for t in range(0, 600, 5):
        ev.note_latency('ttft', 0.1, None, float(t))
    obj = ev.objectives[0]
    assert ev.burn_rate(obj, 300.0, 600.0) == 0.0
    # All-bad traffic burns at 1/budget = 100x for a 0.99 target.
    for t in range(600, 1200, 5):
        ev.note_latency('ttft', 9.0, None, float(t))
    assert ev.burn_rate(obj, 300.0, 1200.0) == pytest.approx(100.0)


def test_multiwindow_blip_does_not_page_sustained_does():
    ev = _evaluator(_ttft())
    # 55 minutes of good traffic...
    for t in range(0, 3300, 5):
        ev.note_latency('ttft', 0.1, None, float(t))
        assert ev.evaluate(float(t)) == []
    # ...then a 1-minute total blip: the 5m window screams but the
    # 1h window holds — no page.
    for t in range(3300, 3360, 2):
        ev.note_latency('ttft', 9.0, None, float(t))
    trs = ev.evaluate(3360.0)
    assert not [t for t in trs if t['tier'] == 'page']
    obj = ev.objectives[0]
    assert ev.burn_rate(obj, slo_lib.PAGE.short_s,
                        3360.0) > slo_lib.PAGE.burn
    # Sustained badness crosses the long window too -> page fires,
    # and recovery clears it via the SHORT window.
    t = 3360.0
    fired = None
    while t < 5400.0 and fired is None:
        ev.note_latency('ttft', 9.0, None, t)
        for tr in ev.evaluate(t):
            if tr['tier'] == 'page' and tr['state'] == 'firing':
                fired = t
        t += 2.0
    assert fired is not None, 'sustained burn never paged'
    resolved = None
    while t < fired + 1200.0 and resolved is None:
        ev.note_latency('ttft', 0.1, None, t)
        for tr in ev.evaluate(t):
            if tr['tier'] == 'page' and tr['state'] == 'resolved':
                resolved = t
        t += 2.0
    assert resolved is not None, 'recovery never cleared the page'
    assert resolved - fired < slo_lib.PAGE.short_s + 120.0


def test_sparse_samples_never_fire():
    ev = _evaluator(_ttft(), min_samples=12)
    # 2 bad of 3 events: terrible ratio, but below min_samples.
    for t, v in ((10.0, 9.0), (20.0, 9.0), (30.0, 0.1)):
        ev.note_latency('ttft', v, None, t)
    assert ev.evaluate(40.0) == []
    assert ev.burn_rate(ev.objectives[0], 300.0, 40.0) == 0.0


def test_series_ring_wraparound():
    s = slo_lib._Series(width_s=10.0, keep_s=100.0)
    for t in range(0, 1000, 10):
        s.add(float(t), good=1, bad=0)
    # maxlen = keep/width + 2 = 12 buckets retained.
    assert len(s.buckets) == 12
    good, bad = s.window(1000.0, 1e9)
    assert good == 12   # oldest buckets really evicted
    # Window narrower than retention sums only its span.
    good, bad = s.window(1000.0, 30.0)
    assert good == 3


def test_same_bucket_and_stale_stamp_fold():
    s = slo_lib._Series(width_s=10.0, keep_s=100.0)
    s.add(15.0, good=1)
    s.add(17.0, bad=1)       # same bucket
    s.add(12.0, good=1)      # stale stamp: folds, never rewinds
    assert len(s.buckets) == 1
    assert s.window(20.0, 100.0) == (2, 1)


# ---- counter deltas, tenants, staleness ------------------------------------

def test_counter_deltas_first_ingest_is_baseline():
    ev = _evaluator([{'metric': 'availability', 'target': 0.99}])
    obj = ev.objectives[0]
    # A baseline snapshot of a long-running LB must not count as a
    # burst of events.
    ev.ingest_counters({'total': 10000, 'failed': 5000}, 100.0)
    assert ev.burn_rate(obj, 300.0, 100.0) == 0.0
    ev.ingest_counters({'total': 10100, 'failed': 5000}, 105.0)
    assert ev.burn_rate(obj, 300.0, 105.0) == 0.0
    ev.ingest_counters({'total': 10200, 'failed': 5100}, 110.0)
    assert ev.burn_rate(obj, 300.0, 110.0) == pytest.approx(50.0)


def test_tenant_vs_fleet_scoping():
    ev = _evaluator([
        {'metric': 'ttft_p99', 'threshold_s': 1.0},
        {'metric': 'ttft_p99', 'threshold_s': 1.0, 'tenant': 'web',
         'name': 'web-ttft'},
        {'metric': 'shed_rate', 'tenant': 'web', 'name': 'web-shed'},
    ])
    fleet, web, web_shed = ev.objectives
    # web is slow, batch is fine: only web's (and the fleet's,
    # diluted) series see the bad samples — itl routes identically
    # (the LB's _note_itl carries the stream's tenant).
    for t in range(0, 300, 2):
        ev.note_latency('ttft', 9.0, 'web', float(t))
        ev.note_latency('ttft', 0.1, 'batch', float(t))
    assert ev.burn_rate(web, 300.0, 300.0) == pytest.approx(100.0)
    assert ev.burn_rate(fleet, 300.0, 300.0) == pytest.approx(50.0)
    # Tenant shed deltas ride the tenants rows (total, shed, failed,
    # no_replica) — 3-field rows from an older writer pad cleanly.
    ev.ingest_counters(
        {'total': 0, 'tenants': {'web': (0, 0, 0)}}, 300.0)
    ev.ingest_counters(
        {'total': 100, 'tenants': {'web': (50, 25, 0)}}, 310.0)
    assert ev.burn_rate(web_shed, 300.0, 310.0) == pytest.approx(50.0)


def test_failures_lagging_arrivals_still_burn():
    """`total` counts arrivals, failures land at completion — often a
    later tick for long streams. An all-in-flight outage (failures
    with zero new arrivals that tick) must burn in full, never be
    clamped to the arrival delta."""
    ev = _evaluator([{'metric': 'availability', 'target': 0.99}])
    obj = ev.objectives[0]
    ev.ingest_counters({'total': 0, 'failed': 0}, 0.0)
    # 20 streams arrive (none failed yet)...
    ev.ingest_counters({'total': 20, 'failed': 0}, 10.0)
    # ...traffic pauses, then ALL 20 die mid-stream two ticks later.
    ev.ingest_counters({'total': 20, 'failed': 0}, 20.0)
    ev.ingest_counters({'total': 20, 'failed': 20}, 30.0)
    good, bad = ev._series[obj.key].window(30.0, 300.0)
    assert (good, bad) == (20, 20)
    assert ev.burn_rate(obj, 300.0, 30.0) == pytest.approx(50.0)


def test_lb_reloads_slo_config_on_serve_update():
    """`serve update` adding (or changing) the `slo:` section must
    arm the RUNNING LB: the spec is re-read every reload period, the
    evaluator rebuilds only on a real config change, and an unchanged
    spec keeps the burn history."""
    import asyncio
    import json as json_lib

    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import spec as spec_lib
    from skypilot_tpu.serve import state as serve_state

    def spec_json(slo=None):
        cfg = {'replicas': 1}
        if slo is not None:
            cfg['slo'] = slo
        return json_lib.dumps(
            spec_lib.ServiceSpec.from_config(cfg).to_config())

    serve_state.add_service('upd-svc', spec_json(), 'name: s',
                            lb_port=0, lb_policy='round_robin')
    lb = lb_lib.LoadBalancer('upd-svc', 'round_robin')
    asyncio.run(lb._slo_tick(0.0))
    assert lb.slo is None
    # Objectives added by a rolling update: armed after the reload
    # period elapses (never before — one narrow read per period).
    serve_state.update_service_spec(
        'upd-svc', spec_json([{'metric': 'ttft_p99',
                               'threshold_s': 1.0}]), 'name: s')
    lb._sync_tick = lb._SLO_RELOAD_TICKS - 1
    asyncio.run(lb._slo_tick(1.0))
    assert lb.slo is None
    lb._sync_tick = lb._SLO_RELOAD_TICKS
    asyncio.run(lb._slo_tick(2.0))
    assert lb.slo is not None
    first = lb.slo
    # Unchanged spec on the next reload: same evaluator object (burn
    # history preserved).
    lb._sync_tick += lb._SLO_RELOAD_TICKS
    asyncio.run(lb._slo_tick(3.0))
    assert lb.slo is first
    # Objectives removed: disarmed.
    serve_state.update_service_spec('upd-svc', spec_json(), 'name: s')
    lb._sync_tick += lb._SLO_RELOAD_TICKS
    asyncio.run(lb._slo_tick(4.0))
    assert lb.slo is None


def test_tenant_availability_counts_no_replica_as_bad():
    """An all-replicas-lost outage must burn the TENANT availability
    objective too: the no_replica field of the tenant row is bad,
    exactly like the fleet branch's failed + no_replica."""
    ev = _evaluator([
        {'metric': 'availability', 'tenant': 'web', 'name': 'web-av'},
    ])
    obj = ev.objectives[0]
    ev.ingest_counters(
        {'total': 0, 'tenants': {'web': (0, 0, 0, 0)}}, 0.0)
    ev.ingest_counters(
        {'total': 100, 'no_replica': 100,
         'tenants': {'web': (100, 0, 0, 100)}}, 10.0)
    assert ev.burn_rate(obj, 300.0, 10.0) == pytest.approx(100.0)


def test_stale_replica_ring_drives_burn_not_masking():
    """The PR 12 freshest-ring rule applied to alerting: a hung
    replica (frozen ring) is a BAD event per tick — a fleet where
    half the replicas hang pages, instead of the frozen rings
    silently dropping out of the signal."""
    ev = _evaluator([{'metric': 'replica_availability',
                      'target': 0.99}])
    obj = ev.objectives[0]
    for t in range(0, 600, 5):
        ev.note_replica_freshness(4, 0, float(t))
        assert ev.evaluate(float(t)) == []
    fired = False
    for t in range(600, 1500, 5):
        ev.note_replica_freshness(2, 2, float(t))
        fired = fired or any(
            tr['tier'] == 'page' and tr['state'] == 'firing'
            for tr in ev.evaluate(float(t)))
    assert fired, 'stale rings never paged replica_availability'
    assert ev.burn_rate(obj, 300.0, 1500.0) == pytest.approx(50.0)


def test_lb_stale_ring_detector():
    """The LB-side predicate the evaluator is fed from: a frozen ring
    lagging the freshest by >3 sync ticks is stale; so is one whose
    last successful fetch lags the sync-tick counter (the all-frozen
    fleet)."""
    import collections

    from skypilot_tpu.serve import load_balancer as lb_lib
    lb = lb_lib.LoadBalancer('svc', 'round_robin')
    lb.sync_interval_s = 1.0

    def ring(ts):
        return collections.deque(
            [{'t': float(t), 'decode_tokens': t} for t in ts])

    lb._sync_tick = 20
    lb._replica_history = {'a': ring(range(12, 21)),
                           'b': ring(range(5, 10))}   # frozen at t=9
    lb._history_tick = {'a': 20, 'b': 9}
    assert lb._stale_rings() == {'b'}
    # Lone replica, own freshest — the sync-tick counter catches it.
    lb._replica_history = {'b': ring(range(5, 10))}
    lb._history_tick = {'b': 9}
    assert lb._stale_rings() == {'b'}


# ---- budget ----------------------------------------------------------------

def test_budget_exhaustion_and_reset():
    ev = _evaluator(_ttft(), budget_window_s=600.0)
    obj = ev.objectives[0]
    assert ev.budget_remaining(obj, 0.0) == 1.0   # idle = unspent
    # Exactly the budget's error fraction: ~fully consumed.
    for t in range(0, 500, 1):
        ev.note_latency('ttft', 9.0 if t % 100 == 0 else 0.1,
                        None, float(t))
    assert 0.0 <= ev.budget_remaining(obj, 500.0) <= 0.1
    # Hard outage: pinned at 0, never negative.
    for t in range(500, 600, 1):
        ev.note_latency('ttft', 9.0, None, float(t))
    assert ev.budget_remaining(obj, 600.0) == 0.0
    # Reset: once the bad window ages past the accounting horizon
    # (and the ring), a clean stretch restores the budget.
    for t in range(600, 1400, 1):
        ev.note_latency('ttft', 0.1, None, float(t))
    assert ev.budget_remaining(obj, 1400.0) == 1.0


# ---- surfaces --------------------------------------------------------------

def test_transition_log_and_snapshot_shape():
    ev = _evaluator(_ttft())
    for t in range(0, 4000, 5):
        ev.note_latency('ttft', 9.0, None, float(t))
        ev.evaluate(float(t))
    log = ev.decision_log_jsonl()
    lines = [json.loads(line) for line in log.splitlines()]
    # All-bad from the first sample: both tiers fire (in tier order,
    # same evaluate pass) and neither ever resolves.
    assert {(x['tier'], x['state']) for x in lines} == {
        ('page', 'firing'), ('ticket', 'firing')}
    assert [x['seq'] for x in lines] == [0, 1]
    snap = ev.snapshot(4000.0)
    assert snap['enabled']
    assert {f['tier'] for f in snap['firing']} == {'page', 'ticket'}
    assert snap['objectives']['ttft_p99']['page_firing']
    assert ev.page_burn(4000.0) == pytest.approx(100.0)
    json.dumps(snap)   # JSON-able end to end


def test_autoscaler_reads_slo_burn():
    """The SLO-class scaling input: a page-level burn forces +1 even
    with an empty queue; a ticket-level burn vetoes downscale; the
    policy flag opts out."""
    import time

    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import spec as spec_lib
    from skypilot_tpu.serve import state as serve_state
    name = 'slo-scale'
    pol = spec_lib.ReplicaPolicy(
        min_replicas=1, max_replicas=6, queue_length_threshold=5.0,
        upscale_delay_seconds=1.0, downscale_delay_seconds=1.0)
    scaler = autoscalers.make(name, pol, has_slo=True)
    assert isinstance(scaler, autoscalers.QueueLengthAutoscaler)
    scaler.target_num_replicas = 3
    t0 = time.time()
    serve_state.set_inflight(name, 0)
    # Page burn + empty queue: scale UP (queue alone says min).
    serve_state.set_slo_burn(name, 20.0)
    scaler.evaluate(3, now=t0)
    d = scaler.evaluate(3, now=t0 + 2)
    assert d.target_num_replicas == 4
    assert 'slo_burn' in d.reason
    # Ticket-level burn: downscale vetoed, target holds.
    serve_state.set_slo_burn(name, 8.0)
    scaler.evaluate(3, now=t0 + 4)
    d = scaler.evaluate(3, now=t0 + 8)
    assert d.target_num_replicas == 4
    # Burn gone: the empty queue finally wins.
    serve_state.set_slo_burn(name, 0.0)
    scaler.evaluate(3, now=t0 + 10)
    d = scaler.evaluate(3, now=t0 + 12)
    assert d.target_num_replicas < 4
    # Staleness scales with the WRITER's declared flush cadence: a
    # 45s-cadence gauge written 60 virtual seconds ago is still
    # live (3 intervals = 135s), while an undeclared-cadence one
    # falls back to the 30s floor.
    from skypilot_tpu.utils import vclock
    clk = vclock.VirtualClock(start=1000.0)
    with vclock.installed(clk):
        serve_state.set_slo_burn(name, 20.0, interval_s=45.0)
        clk.advance_to(1060.0)
        assert serve_state.get_slo_burn(name) == 20.0
        clk.advance_to(1200.0)   # > 3 intervals: stale
        assert serve_state.get_slo_burn(name) == 0.0
        serve_state.set_slo_burn(name, 20.0)   # no declared cadence
        clk.advance_to(1240.0)   # > 30s floor
        assert serve_state.get_slo_burn(name) == 0.0
    # Opt-out flag: page burn ignored.
    pol2 = spec_lib.ReplicaPolicy(
        min_replicas=1, max_replicas=6, queue_length_threshold=5.0,
        upscale_delay_seconds=1.0, downscale_delay_seconds=1.0,
        slo_burn_upscale=False)
    scaler2 = autoscalers.make(name, pol2, has_slo=True)
    scaler2.target_num_replicas = 1
    serve_state.set_slo_burn(name, 50.0)
    scaler2.evaluate(1, now=t0)
    d = scaler2.evaluate(1, now=t0 + 2)
    assert d.target_num_replicas == 1
    # No objectives declared (make()'s default): the gauge is never
    # even read — SLO-less services skip the per-tick DB query.
    scaler3 = autoscalers.make(name, pol)
    scaler3.target_num_replicas = 1
    scaler3.evaluate(1, now=t0)
    d = scaler3.evaluate(1, now=t0 + 2)
    assert d.target_num_replicas == 1
    assert 'slo_burn' not in d.reason


# ---- Prometheus exposition -------------------------------------------------

def _full_lb_metrics():
    ev = _evaluator(_ttft())
    for t in range(0, 600, 5):
        ev.note_latency('ttft', 0.1, None, float(t))
    return {
        'requests_total': 10, 'requests_failed': 1,
        'requests_no_replica': 0, 'requests_retried': 2,
        'requests_resumed': 1, 'requests_shed': 3,
        'ready_replicas': 2, 'engine_queue_depth': 4,
        'ttft_p50_s': 0.1, 'ttft_p90_s': 0.2, 'ttft_p99_s': 0.3,
        'itl_p50_s': 0.01, 'itl_p99_s': 0.02,
        'engine_tokens_per_step': 1.5,
        'engine_tokens_per_sec_w': 100.0, 'prefix_hit_rate_w': 0.5,
        'history_window_s': 60.0, 'slo_alerts_firing': 0,
        'slo_burn': 0.0, 'slo': ev.gauges(600.0),
        'fleet_cost_per_hour': 12.4,
        'cost_per_1k_good_tokens': 0.0031, 'spot_fraction': 0.8,
        'cost_catalog_stale': 0, 'parked_requests': 0,
        'cold_starts_total': 2, 'cold_start_p50_s': 84.0,
        'replicas_quarantined': 1, 'probe_failures_total': 2,
        'probe_interval_s': 15.0,
        'kv_transfers_total': 4, 'kv_transfer_bytes': 65536,
        'kv_transfer_failures': 1, 'kv_transfer_p99_s': 0.4,
        'fleet_prefix_hit_rate': 0.75, 'fleet_prefix_pages': 96,
        'quarantined': ['http://r3:1'],
        'draining': ['http://r2:1'],
        'tenants': {'web': {'requests_total': 5, 'requests_shed': 1,
                            'requests_failed': 0,
                            'ttft_p99_s': 0.3}},
        'replica_queue_depth': {'http://r1:1': 4},
        'breaker': {'http://r1:1': 'closed'},
    }


def test_render_lb_covers_every_cataloged_family():
    text = prom_lib.render_lb(_full_lb_metrics())
    for fam, _ in prom_lib.lb_exposition().values():
        assert f'\n{fam}' in '\n' + text, f'{fam} missing'
    for name in ('sky_tpu_lb_tenant_requests_total{tenant="web"} 5',
                 'sky_tpu_lb_breaker_state{replica="http://r1:1",'
                 'state="closed"} 1',
                 'sky_tpu_lb_slo_error_budget_remaining'
                 '{objective="ttft_p99"} 1.0',
                 'sky_tpu_lb_slo_alert_firing{objective="ttft_p99",'
                 'tier="page"} 0',
                 'sky_tpu_lb_draining_replicas 1'):
        assert name in text, f'{name} missing from:\n{text}'
    # One # TYPE header per family, no duplicates.
    types = [line for line in text.splitlines()
             if line.startswith('# TYPE')]
    assert len(types) == len(set(types))


def test_exposition_families_are_contiguous_groups():
    """The text format requires ALL of a family's samples to form ONE
    group under its # TYPE header — entity-major rendering (two
    tenants, several objectives) must not interleave families."""
    m = _full_lb_metrics()
    m['tenants']['beta'] = {'requests_total': 2, 'requests_shed': 0,
                            'requests_failed': 1, 'ttft_p99_s': 0.1}
    text = prom_lib.render_lb(m)
    seen: list = []
    for line in text.splitlines():
        fam = (line.split(' ', 2)[2].split(' ')[0]
               if line.startswith('# TYPE')
               else line.split('{', 1)[0].split(' ', 1)[0])
        if not seen or seen[-1] != fam:
            seen.append(fam)
    assert len(seen) == len(set(seen)), (
        f'family re-appears after another family: {seen}')
    # Both tenants' samples sit under one header.
    idx = text.index('# TYPE sky_tpu_lb_tenant_requests_total')
    block = text[idx:].split('# TYPE', 2)[1]
    assert 'tenant="beta"' in block and 'tenant="web"' in block


def test_render_replica_and_none_skipping():
    m = {'decode_steps': 7, 'num_waiting': 0, 'tokens_per_step': None,
         'draining': True,
         'tenants': {'web': {'queue_depth': 2, 'decode_tokens': 50,
                             'requests_shed': 0,
                             'ttft_p99_s': None}}}
    text = prom_lib.render_replica(m)
    assert 'sky_tpu_engine_decode_steps 7' in text
    assert 'sky_tpu_server_draining 1' in text
    assert 'tokens_per_step' not in text          # None skipped
    assert ('sky_tpu_engine_tenant_queue_depth{tenant="web"} 2'
            in text)


def test_label_collision_never_emits_duplicate_series():
    """Two tenant ids sanitizing to the SAME label value must not
    produce duplicate samples (Prometheus rejects the whole scrape):
    counters fold by sum, gauges keep the first."""
    m = {'tenants': {
        'team a': {'requests_total': 3, 'requests_shed': 1,
                   'ttft_p99_s': 0.5},
        'team@a': {'requests_total': 4, 'requests_shed': 2,
                   'ttft_p99_s': 0.9},
    }}
    text = prom_lib.render_lb(m)
    totals = [line for line in text.splitlines()
              if line.startswith(
                  'sky_tpu_lb_tenant_requests_total{')]
    assert totals == [
        'sky_tpu_lb_tenant_requests_total{tenant="team_a"} 7']
    gauges = [line for line in text.splitlines()
              if line.startswith('sky_tpu_lb_tenant_ttft_p99')]
    assert len(gauges) == 1


def test_disarm_resolves_firing_alerts():
    """Replacing the evaluator on a config change must pair every
    dangling 'firing' edge with a synthetic 'resolved' so alert-log
    consumers never see an open edge."""
    ev = _evaluator(_ttft())
    for t in range(0, 4000, 5):
        ev.note_latency('ttft', 9.0, None, float(t))
        ev.evaluate(float(t))
    assert ev.firing()
    trs = ev.disarm(4100.0)
    assert {(tr['tier'], tr['state']) for tr in trs} == {
        ('page', 'resolved'), ('ticket', 'resolved')}
    assert not ev.firing()
    lines = [json.loads(line)
             for line in ev.decision_log_jsonl().splitlines()]
    opens = sum(1 if x['state'] == 'firing' else -1 for x in lines)
    assert opens == 0
    assert ev.disarm(4200.0) == []   # idempotent


def test_hostile_tenant_label_is_sanitized():
    evil = 'a"b\nc{},= d' + 'x' * 200
    m = {'tenants': {evil: {'requests_total': 1}}}
    text = prom_lib.render_lb(m)
    line = next(line for line in text.splitlines()
                if 'tenant_requests_total{' in line)
    # No raw quotes/newlines/braces survive inside the label value,
    # and the value is length-bounded (the store.py rule).
    label = line.split('tenant="', 1)[1].split('"', 1)[0]
    assert '"' not in label and '\n' not in label
    assert '{' not in label and len(label) <= 64
    from skypilot_tpu.observability import store as store_lib
    assert label == store_lib.sanitize_label(evil)


def test_lb_alerts_endpoint_and_prometheus_format():
    """/-/alerts answers disabled-shape without objectives and the
    full snapshot with them; /-/metrics?format=prometheus renders
    text exposition. Driven through the REAL handle()."""
    from skypilot_tpu.serve import load_balancer as lb_lib

    class _Req:
        method = 'GET'
        headers: dict = {}

        def __init__(self, path, query=None):
            self.path = path
            self.path_qs = path
            self.query = query or {}

        async def read(self):
            return b''

    lb = lb_lib.LoadBalancer('svc', 'round_robin')
    resp = asyncio.run(lb.handle(_Req('/-/alerts')))
    assert json.loads(resp.body)['enabled'] is False
    lb.slo = _evaluator(_ttft())
    resp = asyncio.run(lb.handle(_Req('/-/alerts')))
    doc = json.loads(resp.body)
    assert doc['enabled'] and 'ttft_p99' in doc['objectives']
    resp = asyncio.run(lb.handle(
        _Req('/-/metrics', {'format': 'prometheus'})))
    assert resp.content_type == 'text/plain'
    assert 'sky_tpu_lb_requests_total 0' in resp.text
    resp = asyncio.run(lb.handle(_Req('/-/metrics')))
    assert json.loads(resp.body)['slo_alerts_firing'] == 0


def test_replica_metrics_prometheus_format_end_to_end():
    """The infer server's /metrics?format=prometheus on a real
    handler: exposition families appear, JSON default unchanged."""
    from skypilot_tpu.infer import server as infer_server

    class _FakeEngine:
        def metrics(self):
            return {'decode_steps': 3, 'num_waiting': 1,
                    'tenants': {'web': {'queue_depth': 1}}}

        def kv_index_armed(self):
            return False

        def compiled_counts(self):
            return {'decode': 1}

    srv = infer_server.InferenceServer.__new__(
        infer_server.InferenceServer)
    srv.engine = _FakeEngine()
    srv.draining = False
    srv._active = 0
    srv._requests_shed = 0
    srv.drain_duration_s = None
    srv.role = 'mixed'
    srv.device = {'platform': 'cpu', 'device_kind': 'cpu', 'count': 1}
    srv.compile_cache_dir = ''

    class _Req:
        def __init__(self, query):
            self.query = query

    resp = asyncio.run(srv.h_metrics(_Req({'format': 'prometheus'})))
    assert 'sky_tpu_engine_decode_steps 3' in resp.text
    assert ('sky_tpu_engine_tenant_queue_depth{tenant="web"} 1'
            in resp.text)
    resp = asyncio.run(srv.h_metrics(_Req({})))
    assert json.loads(resp.body)['decode_steps'] == 3
