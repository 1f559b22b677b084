"""Incident converter: flight-recorder dump → twin scenario
(docs/simulation.md "Incident lifecycle").

This is the piece that closes the PR 12 ↔ PR 13 loop: every anomaly
dump the LB's flight recorder writes (``breaker_open``, ``slo_page``,
``quarantine`` fleet dumps; engine stepline dumps) already carries the
two evidence rings — scrubbed request arrivals and control-plane
fleet events. :func:`trace_from_spans` reconstructs a replayable
:class:`~skypilot_tpu.sim.tracefmt.Trace` from them:

- the **arrival process** (per-tenant rate, prompt/output shape,
  prefix-cohort mix, deadlines) is re-derived from the request ring —
  the recorded window itself is usually far too short to sustain a
  multi-minute burn-rate alert, so replay synthesizes full-duration
  traffic from the reconstructed tenant specs while the raw (scrubbed)
  window records ride along as evidence;
- the **fault timeline** is inferred from the fleet-event ring:
  ``replica_lost`` clusters become a reclaim storm, ``breaker_open``
  edges a wedge, ``quarantine`` verdicts an SDC injection,
  ``controller_recovered`` deltas a controller kill — each with
  inter-event spacing preserved;
- the **expected anomaly class** (the ordered page-tier alert
  transitions the LB recorded before dumping) lands in ``meta`` so
  :func:`verify_replay` can gate "the replay reproduces the incident".

No prompt content crosses this boundary: the LB ring records are
scrubbed at capture (lengths + one-way cohort hashes), so an exported
incident file is safe to commit as a permanent regression gate in
``tests/sim/incidents/``.

``python -m skypilot_tpu.observability.incident`` is the
``make incident-smoke`` entry: storm → page dump → export → replay →
assert the page reproduces.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from skypilot_tpu.sim import tracefmt

# Root span names that mark a flight-recorder dump in the span store.
ROOT_NAMES = ('stepline.fleet_dump', 'stepline.dump')
# replica_lost events within this window collapse into ONE reclaim
# storm (a storm's victims drop over a few sync ticks, not one).
_STORM_CLUSTER_S = 60.0
# Replay margin added past the recorded fault→dump span so the outage
# persists long enough for the burn windows to re-fire.
_HOLD_MARGIN_S = 600.0


def list_dumps(store) -> List[Dict[str, Any]]:
    """Flight-recorder dumps in the span store, the last WRITTEN
    first: ``{'dump_id', 'root', 'trigger', 'start', 'written',
    'n_spans'}``. A trace's ``start`` is its oldest span, and a dump's
    spans carry ring time: successive dumps of one incident share
    their oldest ring record, so ``start`` ties them and the store
    then orders them by their random ids. ``written`` is the root
    span's end, the wall-clock moment the dump was taken."""
    out = []
    for tr in store.list_traces(limit=200,
                                trace_id_prefix='stepline-'):
        if tr.get('root') not in ROOT_NAMES:
            continue
        spans = store.get_trace(tr['trace_id'])
        root = _root_span(spans) or {}
        out.append({
            'dump_id': tr['trace_id'], 'root': tr['root'],
            'trigger': root.get('attrs', {}).get('trigger'),
            'start': tr.get('start_ts'),
            'written': (float(root.get('start') or 0.0)
                        + float(root.get('dur_s') or 0.0)),
            'n_spans': tr['n_spans'],
        })
    out.sort(key=lambda d: (-d['written'], d['dump_id']))
    return out


def find_dump(store, dump_id: str) -> List[Dict[str, Any]]:
    """Spans for a dump by exact id or unique prefix; raises
    ``ValueError`` (never an empty trace) when the id is unknown or
    ambiguous — the loud-failure rule."""
    spans = store.get_trace(dump_id)
    if spans:
        return spans
    matches = [d for d in list_dumps(store)
               if d['dump_id'].startswith(dump_id)]
    if not matches:
        raise ValueError(f'no flight-recorder dump matches '
                         f'{dump_id!r} (see `sky-tpu incident list`)')
    if len(matches) > 1:
        raise ValueError(
            f'{dump_id!r} is ambiguous: matches '
            f'{[m["dump_id"] for m in matches]}')
    return store.get_trace(matches[0]['dump_id'])


def _root_span(spans: List[Dict[str, Any]]
               ) -> Optional[Dict[str, Any]]:
    for s in spans:
        if s.get('parent_id') is None and s.get('name') in ROOT_NAMES:
            return s
    return None


def _children(spans: List[Dict[str, Any]], name: str
              ) -> List[Dict[str, Any]]:
    """Deterministically ordered child spans: span ids are random, so
    order by (virtual time, canonical attrs) — two exports of the
    same dump must produce byte-identical traces."""
    rows = [s for s in spans if s.get('name') == name]
    rows.sort(key=lambda s: (s.get('start') or 0.0,
                             json.dumps(s.get('attrs') or {},
                                        sort_keys=True)))
    return rows


def _rel(t: Any, t0: float) -> float:
    return round(max(0.0, float(t or t0) - t0), 6)


def _mean(xs: List[float], default: float = 0.0) -> float:
    return (sum(xs) / len(xs)) if xs else default


def _tenant_specs(requests: List[Dict[str, Any]],
                  window_s: float) -> Dict[str, Dict[str, Any]]:
    """Reconstruct loadgen tenant specs from the recorded window: the
    arrival PROCESS (rate, shape, cohort mix), not the literal
    arrivals — replay synthesizes full-duration traffic from these."""
    by_tenant: Dict[str, List[Dict[str, Any]]] = {}
    for r in requests:
        by_tenant.setdefault(str(r.get('tenant') or 'default'),
                             []).append(r)
    specs: Dict[str, Dict[str, Any]] = {}
    for name in sorted(by_tenant):
        rows = by_tenant[name]
        prompts = [int(r.get('prompt_tokens') or 1) for r in rows]
        max_new = [int(r['max_new_tokens']) for r in rows
                   if r.get('max_new_tokens')]
        cohorts = [r.get('cohort') for r in rows if r.get('cohort')]
        shared = [c for c in cohorts if cohorts.count(c) >= 2]
        deadlines = [float(r['deadline_s']) for r in rows
                     if r.get('deadline_s')]
        disconnects = sum(1 for r in rows
                          if r.get('outcome') == 'disconnect')
        spec: Dict[str, Any] = {
            'rps': round(max(0.1, len(rows) / max(1.0, window_s)), 4),
            'prompt_mean': max(1, round(_mean(prompts, 1.0))),
            'prompt_max': max(prompts) if prompts else 1,
            'max_new': max(1, round(_mean(max_new, 16.0))),
        }
        if shared:
            spec['shared_prefix_frac'] = round(
                len(shared) / len(rows), 4)
            spec['prefix_tokens'] = tracefmt.COHORT_LEAD
        if deadlines and len(deadlines) >= len(rows) // 2:
            spec['deadline_s'] = round(_mean(deadlines), 3)
        if disconnects:
            spec['disconnect_frac'] = round(
                disconnects / len(rows), 4)
        specs[name] = spec
    return specs


def _infer_faults(fleet_events: List[Tuple[float, Dict[str, Any]]],
                  n_replicas: int, probe_interval_s: Optional[float]
                  ) -> Tuple[List[Dict[str, Any]],
                             List[Dict[str, Any]],
                             List[Dict[str, Any]]]:
    """Fault timeline from the fleet-event ring. Returns (faults,
    kills, alert transitions); times are relative to the ring's t0."""
    faults: List[Dict[str, Any]] = []
    kills: List[Dict[str, Any]] = []
    alerts: List[Dict[str, Any]] = []
    lost: List[float] = []
    breaker: List[Tuple[float, Dict[str, Any]]] = []
    quarantine: List[Tuple[float, Dict[str, Any]]] = []
    for t, ev in fleet_events:
        kind = ev.get('kind')
        if kind == 'replica_lost':
            lost.append(t)
        elif kind == 'breaker_open':
            breaker.append((t, ev))
        elif kind == 'quarantine':
            quarantine.append((t, ev))
        elif kind == 'controller_recovered':
            # The recovery is when the LB NOTICED; the crash preceded
            # it by at most a reload cadence — close enough for a
            # what-if replay.
            kills.append({'target': 'controller',
                          't': round(max(0.0, t - 30.0), 6)})
        elif kind == 'slo_alert':
            alerts.append({'t': t, 'objective': ev.get('objective'),
                           'tier': ev.get('tier'),
                           'state': ev.get('state')})
    # replica_lost clusters → reclaim storms (inter-cluster spacing
    # preserved; within a cluster the loss count sets the storm
    # fraction).
    lost.sort()
    i = 0
    while i < len(lost):
        j = i
        while (j + 1 < len(lost)
               and lost[j + 1] - lost[i] <= _STORM_CLUSTER_S):
            j += 1
        n = j - i + 1
        frac = min(0.9, max(0.1, n / max(1, n_replicas)))
        faults.append({'kind': 'reclaim_storm',
                       't': round(lost[i], 6),
                       'frac': round(frac, 4), 'notice_frac': 0.5})
        i = j + 1
    if breaker:
        urls = sorted({str(ev.get('replica')) for _, ev in breaker})
        faults.append({'kind': 'wedge',
                       't': round(max(0.0, breaker[0][0] - 15.0), 6),
                       'count': len(urls)})
    if quarantine:
        urls = sorted({str(ev.get('replica'))
                       for _, ev in quarantine})
        lead = probe_interval_s or 20.0
        faults.append({
            'kind': 'sdc', 'flavor': 'token_flip',
            't': round(max(0.0, quarantine[0][0] - lead), 6),
            'count': len(urls)})
    faults.sort(key=lambda f: (f['t'], f['kind']))
    return faults, kills, alerts


def trace_from_spans(spans: List[Dict[str, Any]]) -> tracefmt.Trace:
    """Pure conversion: dump spans → versioned incident trace.
    Deterministic — same spans in, byte-identical trace out (the
    double-export gate)."""
    root = _root_span(spans)
    if root is None:
        raise ValueError(
            'not a flight-recorder dump: no '
            f'{"/".join(ROOT_NAMES)} root span in the trace')
    attrs = root.get('attrs') or {}
    if root['name'] == 'stepline.dump':
        return _trace_from_engine_dump(root, spans)
    samples = _children(spans, 'fleet.sample')
    req_spans = _children(spans, 'fleet.request')
    ev_spans = _children(spans, 'fleet.event')
    # NOTE: the root span's `start` is WALL time (the one clock the
    # twin does not virtualize); every child carries ring time. The
    # timeline anchors on the EVIDENCE rings, never the root.
    ring_ts = ([s['start'] for s in req_spans]
               + [s['start'] for s in ev_spans])
    t0 = min(ring_ts) if ring_ts else 0.0
    dump_t = max(ring_ts) if ring_ts else t0
    requests = []
    for s in req_spans:
        requests.append({'t': _rel(s['start'], t0),
                         **(s.get('attrs') or {})})
    # The arrival RATE comes from the request ring's own span — the
    # ring holds the most recent N arrivals, a much shorter window
    # than the fleet-event timeline (dividing by the global window
    # would under-estimate rps by the ratio of the two).
    req_ts = [s['start'] for s in req_spans]
    window_s = (max(1.0, max(req_ts) - min(req_ts))
                if len(req_ts) >= 2 else 1.0)
    fleet_events = [(_rel(s['start'], t0), s.get('attrs') or {})
                    for s in ev_spans]
    # Initial fleet size: the dump's history only covers replicas
    # ALIVE at dump time (the sync tick prunes departed rings), so
    # reconstruct survivors + losses − replacements from the event
    # ring.
    at_dump = set(attrs.get('replicas') or ())
    # Walk the membership edges to the PEAK concurrent fleet: a
    # replica whose first edge is `lost` predates the window, one
    # whose first edge is `ready` joined inside it, and a replica
    # with no edges at all was simply there the whole time.  (A plain
    # union over-counts churned replacements; survivors-plus-losses
    # under-counts a fleet that ramped inside the window.)
    first_edge: Dict[str, str] = {}
    for _, ev in fleet_events:
        kind = ev.get('kind')
        if kind in ('replica_ready', 'replica_lost'):
            first_edge.setdefault(str(ev.get('replica')), kind)
    fleet = {u for u, k in first_edge.items() if k == 'replica_lost'}
    fleet |= at_dump - set(first_edge)
    peak = len(fleet)
    for _, ev in fleet_events:
        kind, u = ev.get('kind'), str(ev.get('replica'))
        if kind == 'replica_ready':
            fleet.add(u)
        elif kind == 'replica_lost':
            fleet.discard(u)
        peak = max(peak, len(fleet))
    n_replicas = max(1, peak)
    probe_interval = attrs.get('probe_interval_s')
    # Cold-start shape: when the ring shows replicas becoming READY
    # around the recorded arrivals (traffic racing provisioning), the
    # replay must recreate that ordering — record each ready edge as
    # an offset from the first recorded arrival.
    ready_offsets = sorted(
        round(s['start'] - min(req_ts), 6) for s in ev_spans
        if (s.get('attrs') or {}).get('kind') == 'replica_ready'
    ) if req_ts else []
    faults, kills, alerts = _infer_faults(
        fleet_events, n_replicas, probe_interval)
    # No-silent-caps: a ring that wrapped before the dump yields a
    # PARTIAL incident — say so in the header, and say how much fell
    # off.
    dropped_req = max(0, int(attrs.get('request_events_total') or 0)
                      - len(req_spans))
    dropped_fleet = max(0, int(attrs.get('fleet_events_total') or 0)
                        - len(ev_spans))
    page_firing = []
    for a in alerts:
        if (a['tier'] == 'page' and a['state'] == 'firing'
                and a['objective'] not in page_firing):
            page_firing.append(a['objective'])
    first_fault_t = min([f['t'] for f in faults]
                        + [k['t'] for k in kills] + [0.0])
    meta: Dict[str, Any] = {
        'trigger': attrs.get('trigger'),
        'dump_id': root.get('trace_id'),
        'replicas': n_replicas,
        'lb_policy': attrs.get('lb_policy'),
        'sync_interval_s': attrs.get('sync_interval_s'),
        'probe_interval_s': probe_interval,
        'slo': attrs.get('slo_cfg') or [],
        'window_s': round(window_s, 6),
        'tenants': _tenant_specs(requests, window_s),
        'expected_page_firing': page_firing,
        'expected_alert_transitions': [
            [a['objective'], a['tier'], a['state']] for a in alerts],
        # How long past the first fault the outage must persist in
        # replay for the recorded anomaly to re-fire.
        'hold_outage_s': round(
            max(0.0, dump_t - t0 - first_fault_t) + _HOLD_MARGIN_S, 6),
        'ready_offsets_s': ready_offsets[:32],
        'dropped_request_events': dropped_req,
        'dropped_fleet_events': dropped_fleet,
    }
    for key in ('objectives', 'replicas_open',
                'replicas_quarantined'):
        if attrs.get(key) is not None:
            meta[key] = attrs[key]
    return tracefmt.Trace(
        events=[], requests=requests, faults=faults, kills=kills,
        meta=meta, kind='incident',
        truncated=bool(dropped_req or dropped_fleet))


def _trace_from_engine_dump(root: Dict[str, Any],
                            spans: List[Dict[str, Any]]
                            ) -> tracefmt.Trace:
    """Engine stepline dump (``stepline.dump``): per-request
    ``req.<event>`` child spans instead of LB ring records — group by
    request_id into scrubbed arrival records. No fleet-event ring
    here, so the fault timeline is empty (the trigger detail rides in
    meta)."""
    attrs = root.get('attrs') or {}
    by_req: Dict[str, Dict[str, Any]] = {}
    t_min: Optional[float] = None
    for s in spans:
        name = s.get('name') or ''
        if not name.startswith('req.'):
            continue
        a = s.get('attrs') or {}
        rid = str(a.get('request_id') or s.get('request_id') or '')
        if not rid:
            continue
        rec = by_req.setdefault(rid, {'outcome': None})
        t = float(s.get('start') or 0.0)
        t_min = t if t_min is None else min(t_min, t)
        event = name[len('req.'):]
        if event == 'submit':
            rec['t_abs'] = t
            rec['tenant'] = a.get('tenant')
            rec['prompt_tokens'] = int(a.get('prompt_tokens') or 1)
        elif event == 'done':
            rec['output_tokens'] = a.get('tokens')
            rec['outcome'] = ('completed'
                              if a.get('finish_reason') != 'error'
                              else 'failed')
    t0 = t_min or 0.0
    requests = []
    for rid in sorted(by_req):
        rec = by_req[rid]
        if 't_abs' not in rec:
            continue   # ring wrapped between submit and done
        requests.append({
            't': _rel(rec.pop('t_abs'), t0),
            'tenant': rec.get('tenant') or 'default',
            'prompt_tokens': rec.get('prompt_tokens') or 1,
            'max_new_tokens': rec.get('output_tokens'),
            'cohort': None,
            'outcome': rec.get('outcome'),
            'output_tokens': rec.get('output_tokens'),
        })
    dropped = max(
        0, int(attrs.get('events_total') or 0)
        - sum(1 for s in spans
              if (s.get('name') or '').startswith('req.')))
    meta = {'trigger': attrs.get('trigger'),
            'dump_id': root.get('trace_id'),
            'window_s': 0.0, 'tenants': {},
            'expected_page_firing': [],
            'expected_alert_transitions': [],
            'hold_outage_s': 0.0,
            'dropped_request_events': dropped,
            'dropped_fleet_events': 0}
    return tracefmt.Trace(events=[], requests=requests, faults=[],
                          kills=[], meta=meta, kind='incident',
                          truncated=bool(dropped))


def export(store, dump_id: str, path: str) -> tracefmt.Trace:
    """dump → incident trace file. Returns the trace (callers report
    ``trace.truncated`` / dropped counts — the no-silent-caps
    surface)."""
    trace = trace_from_spans(find_dump(store, dump_id))
    tracefmt.save(trace, path)
    return trace


def replay(trace: tracefmt.Trace, seed: int = 0):
    """Run the incident through the twin; returns the SimReport."""
    from skypilot_tpu.sim import twin as twin_lib
    from skypilot_tpu.sim import whatif
    sc = whatif.incident_scenario(trace)
    return twin_lib.DigitalTwin(sc, seed=seed).run()


def verify_replay(trace: tracefmt.Trace, report) -> List[str]:
    """The reproduction gate: does the replay show the same anomaly
    CLASS the dump recorded? Returns human-readable problems (empty =
    reproduced)."""
    problems: List[str] = []
    replay_page: List[str] = []
    for d in report.slo_alerts:
        if (d.get('tier') == 'page' and d.get('state') == 'firing'
                and d['objective'] not in replay_page):
            replay_page.append(d['objective'])
    recorded = list(trace.meta.get('expected_page_firing') or [])
    for obj in recorded:
        if obj not in replay_page:
            problems.append(
                f'recorded page alert {obj!r} did not fire in '
                f'replay (replay fired {replay_page or "none"})')
    if recorded:
        prefix = [o for o in replay_page if o in recorded]
        if prefix != recorded:
            problems.append(
                f'page-alert ORDER diverged: recorded {recorded}, '
                f'replay {prefix}')
    trigger = trace.meta.get('trigger')
    if trigger == 'slo_page' and not replay_page:
        problems.append('slo_page incident: no page-tier alert '
                        'fired in replay')
    if trigger == 'breaker_open' and not any(
            d['kind'] == 'breaker_open' for d in report.decisions):
        problems.append('breaker_open incident: no breaker opened '
                        'in replay')
    if trigger == 'quarantine' and not any(
            d['kind'] == 'quarantine' for d in report.decisions):
        problems.append('quarantine incident: no replica was '
                        'quarantined in replay')
    shed_rec = sum(1 for r in trace.requests
                   if r.get('outcome') == 'shed')
    if (trace.requests
            and shed_rec / len(trace.requests) > 0.05
            and report.shed == 0):
        problems.append(
            f'recorded window shed {shed_rec}/{len(trace.requests)} '
            f'requests but the replay shed none')
    return problems


def _smoke() -> int:
    """``make incident-smoke``: grow an SLO-page incident in the
    twin, export it from the dump store, replay the export, and
    assert the page alert reproduces — the full lifecycle in one
    process, < 60s."""
    import tempfile

    from skypilot_tpu.observability import stepline as stepline_lib
    from skypilot_tpu.observability import store as store_lib
    from skypilot_tpu.sim import scenarios
    from skypilot_tpu.sim import twin as twin_lib

    sc = scenarios.incident_page_storm(replicas=4,
                                       duration_s=1500.0)
    with tempfile.TemporaryDirectory() as tmp:
        store = store_lib.SpanStore(f'{tmp}/spans.db')
        prev = stepline_lib._store  # noqa: SLF001 — smoke injection
        stepline_lib.set_dump_store(store)
        try:
            twin_lib.DigitalTwin(sc, seed=3).run()
        finally:
            stepline_lib.set_dump_store(prev)
        dumps = [d for d in list_dumps(store)
                 if d['trigger'] == 'slo_page']
        assert dumps, 'storm replay wrote no slo_page fleet dump'
        path = f'{tmp}/incident.jsonl'
        trace = export(store, dumps[0]['dump_id'], path)
        assert trace.meta['expected_page_firing'], (
            'exported incident recorded no page-tier firing')
        loaded = tracefmt.load(path)
        report = replay(loaded, seed=3)
        problems = verify_replay(loaded, report)
        assert not problems, f'replay did not reproduce: {problems}'
        print(json.dumps({
            'incident_smoke': 'ok',
            'dump_id': dumps[0]['dump_id'],
            'recorded_page_firing':
                trace.meta['expected_page_firing'],
            'replayed_requests': len(report.records),
            'truncated': trace.truncated,
        }, indent=2, sort_keys=True))
    return 0


if __name__ == '__main__':
    raise SystemExit(_smoke())
