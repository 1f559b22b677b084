"""Incident converter unit tests (skypilot_tpu/observability/
incident.py, docs/simulation.md): fault-timeline inference from
synthetic flight-recorder dumps, the zero-request and truncated-ring
edge cases, and the double-export byte-identity contract."""

import json

import pytest

from skypilot_tpu.observability import incident
from skypilot_tpu.observability import stepline as stepline_lib
from skypilot_tpu.observability import store as store_lib
from skypilot_tpu.sim import tracefmt


def _req(t, tenant='prod', prompt=32, max_new=8, **kw):
    return {'t': t, 'tenant': tenant, 'prompt_tokens': prompt,
            'max_new_tokens': max_new, 'cohort': 'aabbccddeeff',
            'stream': True, 'deadline_s': None, 'outcome': 'completed',
            'output_tokens': max_new, 'resumes': 0, **kw}


def _dump(request_events=(), fleet_events=(), history=None,
          trigger='slo_page', detail=None, req_total=0,
          fleet_total=0):
    detail = {'lb_policy': 'round_robin', 'sync_interval_s': 5.0,
              'probe_interval_s': None, 'slo_cfg': [],
              **(detail or {})}
    return stepline_lib.fleet_history_spans(
        trigger, detail,
        history if history is not None
        else {'http://r1:8080': [{'t': 100.0, 'qlen': 1}]},
        request_events=list(request_events),
        request_events_total=req_total or len(request_events),
        fleet_events=list(fleet_events),
        fleet_events_total=fleet_total or len(fleet_events))


def test_zero_request_dump_converts_without_tenants():
    spans = _dump(fleet_events=[
        {'t': 100.0, 'kind': 'breaker_open',
         'replica': 'http://r1:8080', 'replica_id': 1}])
    trace = incident.trace_from_spans(spans)
    assert trace.kind == 'incident'
    assert trace.meta['tenants'] == {}
    assert not trace.truncated
    assert any(f['kind'] == 'wedge' for f in trace.faults)
    # The what-if layer still builds a runnable scenario (synthetic
    # probe load keeps the replay SLIs non-vacuous).
    from skypilot_tpu.sim import whatif
    sc = whatif.incident_scenario(trace)
    assert sc.tenants and sc.replicas >= 1


def test_replica_lost_cluster_infers_reclaim_storm():
    evs = (
        [{'t': 50.0, 'kind': 'replica_ready',
          'replica': f'http://r{i}:8080'} for i in range(4)]
        + [{'t': 200.0 + i, 'kind': 'replica_lost',
            'replica': f'http://r{i}:8080'} for i in range(3)])
    spans = _dump(request_events=[_req(190.0 + i) for i in range(20)],
                  fleet_events=evs)
    trace = incident.trace_from_spans(spans)
    storms = [f for f in trace.faults if f['kind'] == 'reclaim_storm']
    assert len(storms) == 1
    # 3 of a peak-4 fleet lost in one cluster.
    assert storms[0]['frac'] == pytest.approx(0.75)
    assert trace.meta['replicas'] == 4


def test_controller_crash_infers_kill():
    spans = _dump(
        request_events=[_req(100.0), _req(101.0)],
        fleet_events=[{'t': 140.0, 'kind': 'controller_recovered',
                       'recoveries': 1}])
    trace = incident.trace_from_spans(spans)
    assert trace.kills and trace.kills[0]['target'] == 'controller'
    assert trace.kills[0]['t'] < 140.0


def test_quarantine_dump_infers_sdc_fault():
    spans = _dump(
        request_events=[_req(100.0)],
        fleet_events=[{'t': 130.0, 'kind': 'quarantine',
                       'replica': 'http://r2:8080', 'replica_id': 2,
                       'reason': 'golden_probe'}],
        trigger='quarantine',
        detail={'probe_interval_s': 20.0,
                'replicas_quarantined': ['http://r2:8080']})
    trace = incident.trace_from_spans(spans)
    sdc = [f for f in trace.faults if f['kind'] == 'sdc']
    assert sdc and sdc[0]['flavor'] == 'token_flip'
    from skypilot_tpu.sim import whatif
    sc = whatif.incident_scenario(trace)
    assert sc.probe_interval_s == 20.0


def test_wrapped_rings_mark_trace_truncated():
    spans = _dump(request_events=[_req(100.0)], req_total=500,
                  fleet_events=[{'t': 90.0, 'kind': 'replica_ready',
                                 'replica': 'http://r1:8080'}],
                  fleet_total=300)
    trace = incident.trace_from_spans(spans)
    assert trace.truncated
    assert trace.meta['dropped_request_events'] == 499
    assert trace.meta['dropped_fleet_events'] == 299


def test_double_export_is_byte_identical(tmp_path):
    store = store_lib.SpanStore(db_path=str(tmp_path / 's.db'))
    spans = _dump(
        request_events=[_req(100.0 + 0.1 * i) for i in range(30)],
        fleet_events=[{'t': 101.0, 'kind': 'slo_alert',
                       'objective': 'ttft_p99', 'tier': 'page',
                       'state': 'firing'}])
    store.add_spans(spans)
    dump_id = spans[0]['trace_id']
    p1, p2 = str(tmp_path / 'a.jsonl'), str(tmp_path / 'b.jsonl')
    incident.export(store, dump_id, p1)
    incident.export(store, dump_id, p2)
    with open(p1, 'rb') as a, open(p2, 'rb') as b:
        b1, b2 = a.read(), b.read()
    assert b1 == b2
    # And the exported file round-trips through the versioned loader.
    trace = tracefmt.load(p1)
    assert trace.kind == 'incident'
    assert trace.meta['expected_page_firing'] == ['ttft_p99']
    assert len(trace.events) == 30


def test_list_dumps_puts_the_last_written_first(tmp_path):
    """Successive dumps of one incident hold the same oldest ring
    record, so the store's own order (oldest span, then the random
    trace id) cannot tell which is newest: the ids here are chosen so
    that neither id order is the order of writing."""
    store = store_lib.SpanStore(db_path=str(tmp_path / 's.db'))
    for written, tag in ((1000.0, 'b'), (2000.0, 'a'), (3000.0, 'c')):
        spans = _dump(request_events=[_req(100.0), _req(written / 10)])
        for sp in spans:
            sp['trace_id'] = f'stepline-fleet-{tag}'
        spans[0]['start'] = written    # the root: when it was taken
        store.add_spans(spans)
    assert [d['dump_id'] for d in incident.list_dumps(store)] == [
        'stepline-fleet-c', 'stepline-fleet-a', 'stepline-fleet-b']


def test_find_dump_rejects_unknown_and_ambiguous(tmp_path):
    store = store_lib.SpanStore(db_path=str(tmp_path / 's.db'))
    with pytest.raises(ValueError, match='no flight-recorder dump'):
        incident.find_dump(store, 'nope')
    store.add_spans(_dump(request_events=[_req(1.0)]))
    with pytest.raises(ValueError, match='no flight-recorder dump'):
        incident.find_dump(store, 'stepline-fleet-ffffffffff')


def test_scrubbed_export_carries_no_token_ids(tmp_path):
    spans = _dump(request_events=[_req(100.0), _req(100.5)])
    trace = incident.trace_from_spans(spans)
    p = str(tmp_path / 'i.jsonl')
    tracefmt.save(trace, p)
    with open(p) as f:
        lines = [json.loads(line) for line in f]
    reqs = [r for r in lines if r.get('type') == 'request']
    assert reqs and all('tokens' not in r for r in reqs)
    assert all(r['prompt_tokens'] == 32 for r in reqs)
