"""Replayable trace-driven load generator (the fairness harness).

Synthesizes SEEDED, fully deterministic request traces with the shapes
production traffic actually has — bursty arrivals, heavy-tail prompt
lengths, shared-prefix cohorts, per-tenant mixes, mid-stream
disconnects — and replays them either directly against an
``InferenceEngine`` (the tier-1 starvation gates in
``test_scheduler_fairness.py``) or over HTTP through the serve LB.

Determinism contract: ``synthesize(seed=s, ...)`` returns an
identical event list for identical arguments (one ``random.Random(s)``
drives every draw), and a replay submits those events in a fixed
order (arrival time, then index). Wall-clock latencies naturally vary
run to run; the *workload* never does.

Trace-file format: the shared versioned schema in
``skypilot_tpu/sim/tracefmt.py`` (docs/simulation.md) — line 1 is a
``{"sky_tpu_trace": 2, "schema_version": 2, ...meta}`` header, each
further line a typed record. ``save_trace`` / ``load_trace``
round-trip byte-exactly; legacy version-less v1 files keep loading
through tracefmt's compat reader, and an unknown/newer version raises
instead of yielding an empty trace.
"""
from __future__ import annotations

import concurrent.futures
import json
import math
import random
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Tuple

from skypilot_tpu.sim.tracefmt import TraceEvent


def _block(rng: random.Random, n: int) -> List[int]:
    """n token ids in [2, 201] — inside every model's vocab."""
    return [2 + rng.randrange(200) for _ in range(n)]


def rate_envelope(spec: Any) -> Optional[Tuple[Callable[[float], float],
                                               float]]:
    """Compile a tenant's ``envelope`` spec into ``(multiplier(t),
    peak)`` — the rate SHAPE over (virtual) trace time that the
    digital twin replays.
    ``rps`` stays the rate at multiplier 1.0. Shapes:

    - ``{'kind': 'diurnal', 'period_s': 86400, 'low': 0.2}`` — a
      sinusoid from ``low`` (trough, at t=0) up to 1.0 (peak at
      period/2): the classic day curve.
    - ``{'kind': 'flash', 'at': t0, 'duration_s': d, 'mult': m}`` —
      baseline 1.0 with an ``m``x flash crowd during [t0, t0+d).
    - ``[[t, mult], ...]`` — piecewise-linear breakpoints (held flat
      before the first and after the last).

    Returns None for no envelope (the constant-rate legacy shape)."""
    if spec is None:
        return None
    if isinstance(spec, dict):
        kind = spec.get('kind')
        if kind == 'diurnal':
            period = float(spec.get('period_s', 86400.0))
            low = float(spec.get('low', 0.2))
            span = 1.0 - low

            def diurnal(t: float) -> float:
                return low + span * 0.5 * (
                    1.0 - math.cos(2.0 * math.pi * t / period))
            return diurnal, 1.0
        if kind == 'flash':
            t0 = float(spec['at'])
            t1 = t0 + float(spec.get('duration_s', 60.0))
            mult = float(spec.get('mult', 10.0))

            def flash(t: float) -> float:
                return mult if t0 <= t < t1 else 1.0
            return flash, max(1.0, mult)
        raise ValueError(f'unknown envelope kind {kind!r} '
                         f"(have: 'diurnal', 'flash', or breakpoints)")
    points = sorted((float(t), float(m)) for t, m in spec)
    if not points:
        return None

    def piecewise(t: float) -> float:
        if t <= points[0][0]:
            return points[0][1]
        for (ta, ma), (tb, mb) in zip(points, points[1:]):
            if t < tb:
                return ma + (mb - ma) * (t - ta) / (tb - ta)
        return points[-1][1]
    return piecewise, max(m for _, m in points)


def synthesize(seed: int, tenants: Dict[str, Dict[str, Any]],
               duration_s: float = 2.0) -> List[TraceEvent]:
    """Build a deterministic trace. Per-tenant spec keys (all
    optional but ``rps``):

    - ``rps``: mean request rate (arrivals are bursty, not uniform)
    - ``burst``: requests per arrival burst (default 1)
    - ``prompt_mean`` / ``prompt_max``: heavy-tail (bounded Pareto)
      prompt lengths (defaults 16 / 64)
    - ``max_new``: decode budget per request (default 8)
    - ``shared_prefix_frac``: fraction of requests opening with one of
      the tenant's two cohort prefix blocks (default 0.0)
    - ``prefix_tokens``: cohort block length (default 32)
    - ``disconnect_frac``: fraction that hang up mid-stream, after
      roughly half their decode budget (default 0.0)
    - ``deadline_s``: per-request budget stamped on every event
      (default None)
    - ``start`` / ``until``: active window inside the trace
      (defaults 0 / duration_s)
    - ``envelope``: a rate SHAPE over trace time (see
      :func:`rate_envelope`): diurnal day-curves and flash crowds for
      the digital twin's 24h replays. ``rps`` is the rate at
      multiplier 1.0; arrivals are thinned deterministically (same
      seed → same trace). Absent ⇒ the legacy constant-rate shape,
      byte-identical to before.
    """
    events: List[TraceEvent] = []
    for name in sorted(tenants):
        spec = tenants[name]
        # One PRNG per (seed, tenant): adding a tenant to the mix
        # never perturbs another tenant's arrivals.
        rng = random.Random(f'{seed}/{name}')
        rps = float(spec['rps'])
        burst = max(1, int(spec.get('burst', 1)))
        prompt_mean = int(spec.get('prompt_mean', 16))
        prompt_max = int(spec.get('prompt_max', 64))
        max_new = int(spec.get('max_new', 8))
        shared_frac = float(spec.get('shared_prefix_frac', 0.0))
        prefix_tokens = int(spec.get('prefix_tokens', 32))
        disconnect_frac = float(spec.get('disconnect_frac', 0.0))
        deadline_s = spec.get('deadline_s')
        start = float(spec.get('start', 0.0))
        until = float(spec.get('until', duration_s))
        envelope = rate_envelope(spec.get('envelope'))
        cohorts = [(f'{name}/c{i}',
                    _block(random.Random(f'{seed}/{name}/cohort{i}'),
                           prefix_tokens))
                   for i in range(2)]
        t = start
        while t < until:
            if envelope is not None:
                # Non-homogeneous arrivals by THINNING: candidate
                # bursts are drawn at the envelope's PEAK rate (the
                # expovariate below) and each is accepted with
                # probability multiplier(t)/peak — the standard
                # Lewis-Shedler construction, deterministic for a
                # fixed seed. The no-envelope path draws exactly the
                # sequence it always did (old traces stay
                # byte-identical).
                mult, peak = envelope
                if rng.random() >= mult(t) / peak:
                    t += rng.expovariate(rps * peak / burst)
                    continue
            for b in range(burst):
                n = max(1, min(prompt_max,
                               int(prompt_mean
                                   * rng.paretovariate(2.0) / 2)))
                cohort = None
                prefix: List[int] = []
                if shared_frac and rng.random() < shared_frac:
                    cohort, prefix = cohorts[rng.randrange(
                        len(cohorts))]
                tail = _block(rng, n)
                disconnect = None
                if disconnect_frac and rng.random() < disconnect_frac:
                    disconnect = max(1, max_new // 2)
                events.append(TraceEvent(
                    t=round(t + b * 1e-4, 6), tenant=name,
                    tokens=prefix + tail, max_new_tokens=max_new,
                    cohort=cohort, disconnect_after=disconnect,
                    deadline_s=deadline_s))
            # Bursty inter-arrival: exponential gaps between bursts at
            # the burst rate, so the mean request rate stays ~rps (the
            # thinning above scales it by the envelope's multiplier).
            t += rng.expovariate(
                rps * (envelope[1] if envelope else 1.0) / burst)
    events.sort(key=lambda e: e.t)
    return events


def save_trace(events: List[TraceEvent], path: str,
               meta: Optional[Dict[str, Any]] = None) -> str:
    from skypilot_tpu.sim import tracefmt
    return tracefmt.save_events(events, path, meta)


def load_trace(path: str
               ) -> Tuple[List[TraceEvent], Dict[str, Any]]:
    from skypilot_tpu.sim import tracefmt
    return tracefmt.load_events(path)


# ---- replay: directly against an engine ------------------------------------
def replay_on_engine(events: List[TraceEvent], engine,
                     speed: float = 1.0) -> List[Dict[str, Any]]:
    """Drive ``engine.step()`` while submitting the trace's arrivals
    at their (speed-scaled) offsets from the caller's thread — the
    single-threaded analogue of the production server loop. Returns
    one record per event: ``tenant``, ``shed`` (admission 429),
    ``ttft``/``queue_wait`` (seconds, None when shed/never-started),
    ``steps_waited`` (decode steps between submit and first token — a
    machine-speed-independent fairness measure), ``finish_reason`` and
    ``tokens``."""
    from skypilot_tpu.infer import engine as engine_lib

    records: List[Dict[str, Any]] = []
    live: List[Tuple[TraceEvent, Any, Dict[str, Any]]] = []
    t0 = time.perf_counter()
    i = 0
    while True:
        now = (time.perf_counter() - t0) * speed
        while i < len(events) and events[i].t <= now:
            ev = events[i]
            i += 1
            rec: Dict[str, Any] = {
                'tenant': ev.tenant, 'shed': False, 'ttft': None,
                'queue_wait': None, 'steps_waited': None,
                'finish_reason': None, 'tokens': 0}
            records.append(rec)
            deadline = (time.time() + ev.deadline_s
                        if ev.deadline_s is not None else None)
            try:
                req = engine.submit(ev.tokens,
                                    max_new_tokens=ev.max_new_tokens,
                                    deadline=deadline,
                                    tenant=ev.tenant)
            except engine_lib.AdmissionError:
                rec['shed'] = True
                rec['finish_reason'] = 'shed'
                continue
            rec['_steps_at_submit'] = engine.metrics()['decode_steps']
            live.append((ev, req, rec))
        done_now = []
        for ev, req, rec in live:
            if rec['steps_waited'] is None and req.output_tokens:
                rec['steps_waited'] = (
                    engine.metrics()['decode_steps']
                    - rec.pop('_steps_at_submit'))
            if (ev.disconnect_after is not None and not req.done
                    and len(req.output_tokens) >= ev.disconnect_after):
                engine.cancel(req)
            if req.done:
                rec['ttft'] = req.ttft
                rec['queue_wait'] = req.queue_wait
                rec['finish_reason'] = req.finish_reason
                rec['tokens'] = len(req.output_tokens)
                rec.pop('_steps_at_submit', None)
                done_now.append((ev, req, rec))
        for item in done_now:
            live.remove(item)
        if i >= len(events) and not live and engine.idle():
            break
        if engine.idle() and i < len(events):
            # Nothing to do until the next arrival: advance the clock
            # without spinning (the trace drives a real wall clock).
            time.sleep(min(0.002,
                           max(0.0, events[i].t - now) / speed))
        engine.step()
    return records


# ---- replay: over HTTP through the serve LB --------------------------------
def _http_one(gen_url: str, ev: TraceEvent, tenant_header: str,
              timeout: float) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        'tenant': ev.tenant, 'shed': False, 'ttft': None,
        'queue_wait': None, 'itls': [], 'finish_reason': None,
        'tokens': 0, 'completed': False}
    payload = {'tokens': ev.tokens,
               'max_new_tokens': ev.max_new_tokens, 'stream': True}
    req = urllib.request.Request(
        gen_url, data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json',
                 tenant_header: ev.tenant})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            t_prev = None
            for line in iter(r.readline, b''):
                now = time.perf_counter()
                if not line.strip():
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                toks = obj.get('tokens') or []
                if toks:
                    if rec['ttft'] is None:
                        rec['ttft'] = now - t0
                    elif t_prev is not None:
                        rec['itls'].extend(
                            [(now - t_prev) / len(toks)] * len(toks))
                    t_prev = now
                    rec['tokens'] += len(toks)
                if obj.get('done'):
                    rec['completed'] = True
                    rec['finish_reason'] = obj.get('finish_reason')
                    rec['queue_wait'] = obj.get('queue_wait_s')
                    break
                if (ev.disconnect_after is not None
                        and rec['tokens'] >= ev.disconnect_after):
                    rec['finish_reason'] = 'client_disconnect'
                    break   # closing the response = the hang-up
    except urllib.error.HTTPError as e:
        if e.code == 429:
            rec['shed'] = True
            rec['finish_reason'] = 'shed'
        else:
            rec['finish_reason'] = f'http_{e.code}'
    except Exception as e:  # noqa: BLE001 — a dead stream is data here
        rec['finish_reason'] = f'error_{type(e).__name__}'
    return rec


def replay_over_http(events: List[TraceEvent], gen_url: str,
                     tenant_header: str = 'X-SkyTpu-Tenant',
                     speed: float = 1.0, timeout: float = 300.0,
                     max_workers: int = 64) -> List[Dict[str, Any]]:
    """Replay a trace through a live /generate endpoint (the serve
    LB): each event fires at its speed-scaled offset on a worker
    thread, streams its response, and reports client-observed
    TTFT/ITL, the done-line ``queue_wait_s``, and shed/disconnect
    outcomes."""
    t0 = time.perf_counter()

    def run(ev: TraceEvent) -> Dict[str, Any]:
        delay = ev.t / speed - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        return _http_one(gen_url, ev, tenant_header, timeout)

    workers = min(max_workers, max(1, len(events)))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        return list(pool.map(run, events))


def tenant_summary(records: List[Dict[str, Any]]
                   ) -> Dict[str, Dict[str, Any]]:
    """Per-tenant rollup of replay records: issued/shed counts plus
    TTFT, ITL and queue-wait percentiles (seconds; ITL in ms)."""
    def pct(vals: List[float], p: float) -> Optional[float]:
        if not vals:
            return None
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(len(vals) * p))]

    out: Dict[str, Dict[str, Any]] = {}
    for tenant in sorted({r['tenant'] for r in records}):
        rs = [r for r in records if r['tenant'] == tenant]
        ttfts = [r['ttft'] for r in rs if r['ttft'] is not None]
        waits = [r['queue_wait'] for r in rs
                 if r.get('queue_wait') is not None]
        steps = [r['steps_waited'] for r in rs
                 if r.get('steps_waited') is not None]
        itls = [x for r in rs for x in r.get('itls', [])]
        shed = sum(1 for r in rs if r['shed'])
        out[tenant] = {
            'issued': len(rs),
            'shed': shed,
            'shed_rate': round(shed / len(rs), 4),
            'ttft_p50_s': pct(ttfts, 0.50),
            'ttft_p99_s': pct(ttfts, 0.99),
            # Scheduler-owned VIRTUAL time (engine replays only):
            # decode steps between submit and first token. Immune to
            # wall-clock noise from concurrent CPU load — the fairness
            # gates assert on these, not on wall percentiles.
            'steps_waited_p50': pct(steps, 0.50),
            'steps_waited_p99': pct(steps, 0.99),
            'queue_wait_p50_ms': (
                round(pct(waits, 0.50) * 1e3, 3) if waits else None),
            'queue_wait_p99_ms': (
                round(pct(waits, 0.99) * 1e3, 3) if waits else None),
            'itl_p50_ms': (round(pct(itls, 0.50) * 1e3, 3)
                           if itls else None),
            'itl_p99_ms': (round(pct(itls, 0.99) * 1e3, 3)
                           if itls else None),
        }
    return out
