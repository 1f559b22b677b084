"""Engine flight recorder: step-level timelines + anomaly dumps.

The serving dashboard answers "how slow is it"; nothing before this
module answered "*why was that step slow*". The flight recorder is an
always-on, low-overhead ring of per-engine-step records (one compact
:class:`StepRecord` per worked step — kind, dispatch/drain/readback
wall shares, batch/chunk sizes, speculation accept counts, page
pressure, queue depth per tenant) plus a per-request timeline ring
(submit → first_dispatch → prefill_dispatched → first_token →
first_flush → done, with resume / cancel / shed events; the
``submit`` event carries the server's ``recv_t`` and the LB's
``lb_recv_t`` where there were any), both appended under the
engine's ``_lock``.

The step loop times its stages through :class:`StageClock`, which
feeds the step record AND opens ``jax.profiler`` annotations
(``engine.step`` with its ``step_num``, ``engine.dispatch`` /
``engine.drain`` / ``engine.readback`` / ``engine.sched``, and between
steps ``engine.wait``, the loop's block for work): a profiler trace of
the replica shows the stages on the device's clock.

Three export paths:

- **Perfetto**: :func:`to_perfetto` renders a snapshot as
  Chrome-trace JSON — one track per step-loop stage (dispatch /
  drain / readback / host) and one per request — mergeable with the
  PR 1 propagated spans (``render.to_perfetto``'s event shape, pids
  offset so the hops never collide), stitched by ``request_id``.
- **Anomaly dumps**: a TTFT-SLO breach, preemption, ``cache_full``
  finish, admission shed, or LB breaker-open snapshots the ring into
  the PR 1 sqlite span store (one ``stepline.dump`` root span, one
  child span per step / request event, the triggering event tagged)
  — a black box you read *after* the incident with
  ``sky-tpu profile``. Writes happen on a background thread, never
  under the engine lock, rate-limited per trigger kind.
- **Fleet history**: the serve LB keeps a bounded per-replica history
  ring of the gauges its sync tick already fetches (queue depth,
  tokens_per_step, accept rate, prefix hit rate) — surfaced as
  ``/-/metrics/history`` and as windowed-rate gauges; the signal
  shape the ROADMAP autoscaler and digital twin consume.

Determinism contract: the recorder reads clocks and counters only —
it never influences scheduling, sampling, or page decisions, so
greedy outputs are bit-identical recorder on vs off (gated with the
fused/pipeline/spec golden tests).
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# Ring capacities (records, not bytes). A step record is ~15 scalars;
# 1024 of them cover minutes of steady-state decode — enough context
# around any anomaly without growing replica RSS measurably.
CAP_ENV = 'SKY_TPU_STEPLINE_CAP'
DEFAULT_CAP = 1024
# Minimum seconds between two dumps of the SAME trigger kind: a
# preemption storm must not turn the span store into a write
# amplifier (each dump is O(ring) rows). 0 disables the limit.
DUMP_INTERVAL_ENV = 'SKY_TPU_STEPLINE_DUMP_INTERVAL_S'
DEFAULT_DUMP_INTERVAL_S = 30.0

TRIGGERS = ('ttft_slo', 'preemption', 'cache_full', 'admission_shed',
            'breaker_open', 'slo_page')

# Step-loop stage keys, in the order they run inside one step. 'host'
# is the remainder (scheduling, page accounting, drafting).
STAGES = ('dispatch', 'drain', 'readback', 'host')


def default_cap() -> int:
    try:
        return max(8, int(os.environ.get(CAP_ENV, DEFAULT_CAP)))
    except ValueError:
        return DEFAULT_CAP


def dump_interval_s() -> float:
    try:
        return max(0.0, float(os.environ.get(
            DUMP_INTERVAL_ENV, DEFAULT_DUMP_INTERVAL_S)))
    except ValueError:
        return DEFAULT_DUMP_INTERVAL_S


@dataclasses.dataclass(slots=True)
class StepRecord:
    """One engine step, compactly. All times are wall seconds; the
    stage shares are DISJOINT: ``dispatch_s`` (device program
    launches), ``drain_s`` (consume bookkeeping while catching host
    state up), ``readback_s`` (blocked on the device→host pair copy),
    and host = ``dur_s`` minus the three. ``sched_s`` (the step's
    admission / sweep section under the engine lock) is a part OF
    host, not taken out of it."""
    idx: int                 # monotonic step index (survives wrap)
    t: float                 # wall-clock step start
    dur_s: float
    kind: str                # prefill | decode | mixed | verify | free
    dispatch_s: float
    drain_s: float
    readback_s: float
    batch: int               # decoding slots in the dispatch
    chunk_tokens: int        # prefill tokens dispatched this step
    prefilling: int          # slots mid-prefill after the step
    spec_drafted: int        # draft tokens consumed this step
    spec_accepted: int
    pages_free: int          # -1 on dense engines
    prefix_evictions: int    # cumulative (deltas = per-step evictions)
    preemptions: int         # cumulative
    queue_depth: int
    tenant_depths: Optional[Dict[str, int]]   # None when single-tenant
    sched_s: float = 0.0     # part of host_s()
    cpu_s: float = 0.0       # thread CPU time inside dur_s
    wait_s: float = 0.0      # waited for work before this step
    dev_empty: int = 0       # 1: a launch found the device's queue empty

    def host_s(self) -> float:
        return max(0.0, self.dur_s - self.dispatch_s - self.drain_s
                   - self.readback_s)

    def as_dict(self) -> Dict[str, Any]:
        d = {k: getattr(self, k) for k in self.__slots__}
        d['host_s'] = self.host_s()
        return d


class _Stage:
    """One open stage of a step: its wall time goes to the clock's
    accumulator of that name, and the interval is an ``engine.<name>``
    annotation in a profiler trace, should one be running."""
    __slots__ = ('_clock', '_name', '_ann', '_t0')

    def __init__(self, clock: 'StageClock', name: str) -> None:
        self._clock, self._name = clock, name

    def __enter__(self) -> None:
        # The annotation starts when it is constructed.
        self._ann = self._clock.annotation('engine.' + self._name)
        self._t0 = time.perf_counter()

    def __exit__(self, *exc: Any) -> None:
        self._clock.acc[self._name] += time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


class _Wait(_Stage):
    """Stage ``wait``: the loop's block for work, BETWEEN steps. Its
    seconds go to the clock's ``wait_state``, which a reader on another
    thread sees grow while the wait is still open."""
    __slots__ = ()

    def __enter__(self) -> None:
        super().__enter__()
        self._clock.wait_state = (self._clock.wait_state[0], self._t0)

    def __exit__(self, *exc: Any) -> None:
        clock = self._clock
        dur = time.perf_counter() - self._t0
        clock.wait_state = (clock.wait_state[0] + dur, 0.0)
        self._ann.__exit__(*exc)


class StageClock:
    """The step loop's one way of timing a stage, on both clocks: the
    seconds land in ``acc`` (what the step's :class:`StepRecord` is
    built from) and the interval in the ``jax.profiler`` trace (plane
    ``/host:CPU``, the engine thread's line), where it lies beside the
    device's operations. With no profiler session an annotation costs
    under a microsecond. Engine thread only (plain floats, never read
    cross-thread); ``dispatch`` / ``drain`` / ``readback`` are disjoint,
    ``sched`` is a part of the host remainder.

    Stage ``wait`` lies between steps (the loop's block for work), so
    no step's ``acc`` holds it: ``wait_state`` keeps the running total,
    and each of its readers takes what has closed since it last asked
    (:meth:`take_wait`): the step record its ``wait_s``, the launch
    counter whether a wait came before it. ``wait_state`` is the one
    field another thread reads (``/metrics``, :meth:`waited_s`): a
    tuple, replaced whole, so a reader never sees a half-closed wait
    and no lock joins the loop."""
    NAMES = ('dispatch', 'drain', 'readback', 'sched')

    def __init__(self) -> None:
        # jax stays out of this module's import: the serve LB and the
        # CLI import it too, and must not pay for (or reach for) jax.
        from jax import profiler
        self.annotation = profiler.TraceAnnotation
        self._step_annotation = profiler.StepTraceAnnotation
        self.acc: Dict[str, float] = dict.fromkeys(self.NAMES, 0.0)
        # (seconds of closed waits, perf_counter at which the open
        # wait began or 0.0)
        self.wait_state: Tuple[float, float] = (0.0, 0.0)
        # The closed seconds each reader on the engine thread has
        # taken so far.
        self._wait_taken = {'record': 0.0, 'launch': 0.0}

    def step(self, idx: int) -> Any:
        """Open one step: the accumulators start from zero, and the
        returned context is the ``engine.step`` annotation carrying
        the index of the record this step will write."""
        for name in self.NAMES:
            self.acc[name] = 0.0
        return self._step_annotation('engine.step', step_num=idx)

    def stage(self, name: str) -> _Stage:
        return (_Wait if name == 'wait' else _Stage)(self, name)

    def take_wait(self, reader: str) -> float:
        """The seconds of closed waits since ``reader`` last asked:
        ``'record'`` is the step record (its ``wait_s``: the wait that
        the step ended), ``'launch'`` the launch counter (above zero
        at the first launch after a wait). Engine thread."""
        closed = self.wait_state[0]
        waited = closed - self._wait_taken[reader]
        self._wait_taken[reader] = closed
        return waited

    def waited_s(self) -> float:
        """Seconds waited for work since the start, an open wait
        counted as far as it has got. Any thread."""
        closed, t0 = self.wait_state
        return closed + (time.perf_counter() - t0 if t0 else 0.0)


class Ring:
    """Fixed-capacity ring buffer: O(1) append, oldest-first
    ``snapshot``, and a monotonic ``total`` so wraparound is
    observable (record ``idx`` continuity is testable). NOT
    thread-safe by itself — the owner (the engine) serializes access
    under its own lock."""

    __slots__ = ('_buf', '_cap', 'total')

    def __init__(self, cap: int) -> None:
        self._cap = max(1, int(cap))
        self._buf: List[Any] = [None] * self._cap
        self.total = 0

    def __len__(self) -> int:
        return min(self.total, self._cap)

    @property
    def cap(self) -> int:
        return self._cap

    def append(self, item: Any) -> None:
        self._buf[self.total % self._cap] = item
        self.total += 1

    def snapshot(self) -> List[Any]:
        n = len(self)
        start = self.total - n
        return [self._buf[i % self._cap]
                for i in range(start, self.total)]


class StepRecorder:
    """The engine-side recorder: a step ring + a request-event ring +
    per-trigger dump rate limiting. Every method is called under the
    owning engine's ``_lock`` (the recorder owns no lock; same
    contract as the scheduler)."""

    def __init__(self, cap: Optional[int] = None,
                 min_dump_interval_s: Optional[float] = None) -> None:
        cap = cap if cap is not None else default_cap()
        self.steps = Ring(cap)
        # Requests produce ~4 events each; give them a wider window so
        # the request timeline spans the same wall interval as steps.
        self.events = Ring(cap * 4)
        self.dumps = 0
        self._min_dump_s = (min_dump_interval_s
                            if min_dump_interval_s is not None
                            else dump_interval_s())
        self._last_dump: Dict[str, float] = {}

    # -- recording (holds: engine _lock) -----------------------------------
    def note_step(self, rec: StepRecord) -> None:
        self.steps.append(rec)

    def note_event(self, request_id: int, tenant: str, event: str,
                   t: float, **detail: Any) -> None:
        ev = {'request_id': request_id, 'tenant': tenant,
              'event': event, 't': t}
        if detail:
            ev.update(detail)
        self.events.append(ev)

    def should_dump(self, trigger: str, now: float) -> bool:
        """Per-trigger rate limit: at most one dump per kind per
        ``min_dump_interval_s`` (the span store is sqlite; a
        preemption storm must not DoS it). ``dumps`` counts rate-
        limit passes, i.e. dumps TRIGGERED — the handoff queue is
        bounded and the store write fail-open, so completion is not
        guaranteed (metric semantics documented accordingly)."""
        last = self._last_dump.get(trigger)
        if last is not None and self._min_dump_s > 0 \
                and now - last < self._min_dump_s:
            return False
        self._last_dump[trigger] = now
        self.dumps += 1
        return True

    # -- export ------------------------------------------------------------
    def raw(self) -> Dict[str, Any]:
        """O(n) POINTER copy of both rings (oldest first) — the only
        part that needs the owner's lock. Records and event dicts are
        write-once after append, so sharing the references is safe;
        render with :func:`render_snapshot` OUTSIDE the lock."""
        return {
            'cap': self.steps.cap,
            'steps_total': self.steps.total,
            'events_total': self.events.total,
            'dumps': self.dumps,
            'steps_raw': self.steps.snapshot(),
            'events': self.events.snapshot(),
        }


def render_snapshot(raw: Dict[str, Any]) -> Dict[str, Any]:
    """Expand a ``StepRecorder.raw()`` copy into the JSON-able
    snapshot shape (per-record dict building — thousands of dicts for
    a full ring — deliberately OUTSIDE any lock: a 1 Hz
    /debug/stepline poll must not stall the step loop for the
    build)."""
    out = dict(raw)
    out['steps'] = [r.as_dict() for r in out.pop('steps_raw')]
    return out


def summarize(recs: List[StepRecord]) -> Dict[str, Any]:
    """Aggregate step-time breakdown over a snapshot of step records:
    total and fractional share per stage (``/debug/stepline``'s
    ``summary``). Runs on a COPY, so callers can (and do) compute it
    outside any lock."""
    tot = {s: 0.0 for s in STAGES}
    kinds: Dict[str, int] = {}
    dur = cpu = wait = 0.0
    dev_empty = 0
    for r in recs:
        tot['dispatch'] += r.dispatch_s
        tot['drain'] += r.drain_s
        tot['readback'] += r.readback_s
        tot['host'] += r.host_s()
        dur += r.dur_s
        cpu += r.cpu_s
        wait += r.wait_s
        dev_empty += r.dev_empty
        kinds[r.kind] = kinds.get(r.kind, 0) + 1
    out: Dict[str, Any] = {
        'steps': len(recs),
        'step_kinds': kinds,
        'step_time_s': round(dur, 6),
        'step_mean_ms': (round(dur / len(recs) * 1e3, 4)
                         if recs else None),
    }
    for s in STAGES:
        out[f'{s}_s'] = round(tot[s], 6)
        out[f'{s}_share'] = (round(tot[s] / dur, 4) if dur
                             else None)
    # The thread's CPU time as a share of the steps' wall time (the
    # rest it was off the CPU), and beside the steps the waits for
    # work between them, as a share of both together.
    out['cpu_s'] = round(cpu, 6)
    out['cpu_share'] = round(cpu / dur, 4) if dur else None
    out['wait_s'] = round(wait, 6)
    out['wait_share'] = (round(wait / (dur + wait), 4) if dur + wait
                         else None)
    out['dev_empty_steps'] = dev_empty
    return out


# ---- Perfetto export -----------------------------------------------------
# Stepline tracks use pids far above render.to_perfetto's hop pids
# (which start at 1), so a merged document never collides.
_PID_STEPS = 1000
_PID_REQUESTS = 1001
_STAGE_TIDS = {s: i + 1 for i, s in enumerate(STAGES + ('wait',))}

# A request's phases, each between two of its stamps. A stamp is an
# event's own time, or (``submit.recv_t``) a detail of its ``submit``
# event: the server's receipt (``recv_t``) and the LB's
# (``lb_recv_t``) precede the engine's submit, which is the first
# moment the ring can hold anything of the request.
REQUEST_PHASES = (
    ('lb_inbound', 'submit.lb_recv_t', 'submit.recv_t'),
    ('admit', 'submit.recv_t', 'submit'),
    ('queue_wait', 'submit', 'first_dispatch'),
    ('prefill', 'first_dispatch', 'first_token'),
    ('first_token_lag', 'prefill_dispatched', 'first_token'),
    ('first_flush', 'first_token', 'first_flush'),
    ('decode', 'first_token', 'done'),
)
_PHASE_EVENTS = frozenset(
    key.partition('.')[0] for _, a, b in REQUEST_PHASES for key in (a, b))


def stamp(evs: Dict[str, Dict[str, Any]], key: str) -> Optional[float]:
    """The time of stamp ``key`` among one request's events by name,
    or None where the request has no such stamp."""
    name, _, detail = key.partition('.')
    ev = evs.get(name)
    if ev is None:
        return None
    return ev.get(detail) if detail else ev['t']


def stepline_events(snapshot: Dict[str, Any]
                    ) -> List[Dict[str, Any]]:
    """The snapshot as raw Chrome-trace events (including the track
    metadata), suitable for ``render.to_perfetto``'s
    ``extra_events`` — the stitch path that merges the recorder with
    a request's PR 1 propagated spans."""
    events: List[Dict[str, Any]] = [
        {'name': 'process_name', 'ph': 'M', 'pid': _PID_STEPS,
         'tid': 1, 'args': {'name': 'engine-step'}},
        {'name': 'process_name', 'ph': 'M', 'pid': _PID_REQUESTS,
         'tid': 1, 'args': {'name': 'requests'}},
    ]
    for s, tid in _STAGE_TIDS.items():
        events.append({'name': 'thread_name', 'ph': 'M',
                       'pid': _PID_STEPS, 'tid': tid,
                       'args': {'name': s}})
    for rec in snapshot.get('steps', ()):
        # Stages laid out sequentially inside the step's wall
        # interval: dispatch, drain, readback, then host remainder —
        # an approximation of interleaving, exact in total.
        t = rec['t']
        wait = rec.get('wait_s', 0.0)
        if wait > 0.0:
            # The wait for work that this step ended, drawn up to the
            # step's start (idle ticks inside it are not told apart).
            events.append({
                'name': 'engine.wait', 'ph': 'X',
                'ts': (t - wait) * 1e6, 'dur': wait * 1e6,
                'pid': _PID_STEPS, 'tid': _STAGE_TIDS['wait'],
                'args': {'step': rec['idx'], 'stage': 'wait'}})
        spans = (('dispatch', rec['dispatch_s']),
                 ('drain', rec['drain_s']),
                 ('readback', rec['readback_s']),
                 ('host', rec.get('host_s', 0.0)))
        for stage, dur in spans:
            if dur <= 0.0:
                continue
            events.append({
                'name': f"step.{rec['kind']}",
                'ph': 'X', 'ts': t * 1e6, 'dur': dur * 1e6,
                'pid': _PID_STEPS, 'tid': _STAGE_TIDS[stage],
                'args': {'step': rec['idx'], 'stage': stage,
                         'batch': rec['batch'],
                         'chunk_tokens': rec['chunk_tokens'],
                         'queue_depth': rec['queue_depth']},
            })
            t += dur
    # Request tracks: one tid per request_id; lifecycle phases become
    # 'X' slices bounded by the recorded stamps, everything else an
    # instant.
    # Phase boundaries keyed by FIRST occurrence (a request preempted
    # before its first token re-dispatches its last chunk; the first
    # stamp is the one its TTFT phases start from); repeatable events
    # (preemption, resume, shed, ...) are NOT folded into this map —
    # every occurrence in the ring gets its own instant below, so a
    # request preempted twice shows two instants, same as the
    # span-store dump path.
    by_req: Dict[int, Dict[str, Any]] = {}
    for ev in snapshot.get('events', ()):
        by_req.setdefault(ev['request_id'], {}).setdefault(
            ev['event'], ev)
    for rid, evs in by_req.items():
        tid = (rid % 100000) + 1
        for name, a, b in REQUEST_PHASES:
            t_a, t_b = stamp(evs, a), stamp(evs, b)
            if t_a is not None and t_b is not None and t_b >= t_a:
                events.append({
                    'name': f'req.{name}', 'ph': 'X',
                    'ts': t_a * 1e6, 'dur': (t_b - t_a) * 1e6,
                    'pid': _PID_REQUESTS, 'tid': tid,
                    'args': {'request_id': rid,
                             'tenant': evs[a.partition('.')[0]]
                             .get('tenant')}})
    for ev in snapshot.get('events', ()):
        if ev['event'] in _PHASE_EVENTS:
            continue
        events.append({
            'name': f"req.{ev['event']}", 'ph': 'i',
            'ts': ev['t'] * 1e6, 's': 't',
            'pid': _PID_REQUESTS,
            'tid': (ev['request_id'] % 100000) + 1,
            'args': {k: v for k, v in ev.items() if k != 't'}})
    return events


def to_perfetto(snapshot: Dict[str, Any],
                spans: Optional[List[Dict[str, Any]]] = None
                ) -> Dict[str, Any]:
    """Chrome-trace JSON of a recorder snapshot; with ``spans`` (PR 1
    propagated spans of the same request/replica) the two merge into
    one document, stitched on the wall clock + request_id."""
    events = stepline_events(snapshot)
    if spans:
        from skypilot_tpu.observability import render
        return render.to_perfetto(spans, extra_events=events)
    return {'traceEvents': events, 'displayTimeUnit': 'ms'}


def validate_perfetto(doc: Any) -> List[str]:
    """Schema check for an exported trace (``[]`` = valid): the
    contract ui.perfetto.dev / chrome://tracing require. Shared by
    the tests and ``make profile-smoke``."""
    errs: List[str] = []
    if not isinstance(doc, dict):
        return ['document is not an object']
    events = doc.get('traceEvents')
    if not isinstance(events, list):
        return ["missing 'traceEvents' list"]
    if not events:
        errs.append('traceEvents is empty')
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errs.append(f'event {i} is not an object')
            continue
        for key in ('name', 'ph', 'pid', 'tid'):
            if key not in ev:
                errs.append(f'event {i} missing {key!r}')
        ph = ev.get('ph')
        if ph not in ('X', 'M', 'i', 'B', 'E'):
            errs.append(f'event {i} has unknown phase {ph!r}')
        if ph == 'X':
            if not isinstance(ev.get('ts'), (int, float)):
                errs.append(f'event {i} missing numeric ts')
            if not isinstance(ev.get('dur'), (int, float)) \
                    or ev.get('dur', -1) < 0:
                errs.append(f'event {i} missing non-negative dur')
    return errs


# ---- anomaly dumps into the span store -----------------------------------

def dump_spans(trigger: str, detail: Dict[str, Any],
               snapshot: Dict[str, Any],
               trace_id: Optional[str] = None
               ) -> List[Dict[str, Any]]:
    """Encode one ring snapshot as PR 1 span-store rows: a
    ``stepline.dump`` root carrying the trigger tag, a child span per
    step record, a child per request event (carrying its
    ``request_id`` so ``sky-tpu profile <request_id>`` finds the
    dump), and one ``stepline.trigger`` span for the anomaly
    itself."""
    if trace_id is None:
        trace_id = 'stepline-' + os.urandom(12).hex()
    now = time.time()
    root_id = os.urandom(8).hex()
    steps = snapshot.get('steps', [])
    events = snapshot.get('events', [])
    start = min([r['t'] for r in steps]
                + [e['t'] for e in events] + [now])
    spans: List[Dict[str, Any]] = [{
        'trace_id': trace_id, 'span_id': root_id, 'parent_id': None,
        'name': 'stepline.dump', 'hop': 'stepline',
        'start': start, 'dur_s': max(0.0, now - start),
        'status': 'ok',
        'attrs': {'trigger': trigger, 'steps': len(steps),
                  'events': len(events),
                  # Monotonic ring totals ride along so an exporter
                  # can tell how much history wrapped off the rings
                  # before the dump (no-silent-caps: a truncated
                  # incident must say so; docs/simulation.md).
                  'steps_total': int(snapshot.get('steps_total')
                                     or len(steps)),
                  'events_total': int(snapshot.get('events_total')
                                      or len(events)),
                  'request_id': detail.get('request_id'), **detail},
    }, {
        'trace_id': trace_id, 'span_id': os.urandom(8).hex(),
        'parent_id': root_id,
        'name': 'stepline.trigger', 'hop': 'stepline',
        'start': detail.get('t', now), 'dur_s': 0.0,
        'status': f'anomaly:{trigger}',
        'attrs': {'trigger': trigger, **detail},
    }]
    for rec in steps:
        spans.append({
            'trace_id': trace_id, 'span_id': os.urandom(8).hex(),
            'parent_id': root_id,
            'name': f"step.{rec['kind']}", 'hop': 'stepline',
            'start': rec['t'], 'dur_s': rec['dur_s'], 'status': 'ok',
            'attrs': {k: v for k, v in rec.items()
                      if k not in ('t', 'dur_s', 'kind')
                      and v is not None},
        })
    for ev in events:
        spans.append({
            'trace_id': trace_id, 'span_id': os.urandom(8).hex(),
            'parent_id': root_id,
            'name': f"req.{ev['event']}", 'hop': 'stepline',
            'start': ev['t'], 'dur_s': 0.0, 'status': 'ok',
            'attrs': {k: v for k, v in ev.items() if k != 't'},
        })
    return spans


def fleet_history_spans(trigger: str, detail: Dict[str, Any],
                        history: Dict[str, List[Dict[str, Any]]],
                        *,
                        request_events: List[Dict[str, Any]] = (),
                        request_events_total: int = 0,
                        fleet_events: List[Dict[str, Any]] = (),
                        fleet_events_total: int = 0
                        ) -> List[Dict[str, Any]]:
    """The LB-tier analog of :func:`dump_spans`: one span per
    retained per-replica history sample (``breaker_open`` is the
    trigger that snapshots the fleet), plus the LB's incident-replay
    evidence rings (docs/simulation.md) — one ``fleet.request`` span
    per retained scrubbed request record and one ``fleet.event`` span
    per retained fleet event (replica joins/losses, breaker edges,
    quarantines, SLO transitions). The root carries the monotonic
    ring totals so an exporter can report how many records wrapped
    off before the dump (no-silent-caps)."""
    trace_id = 'stepline-fleet-' + os.urandom(10).hex()
    now = time.time()
    root_id = os.urandom(8).hex()
    spans: List[Dict[str, Any]] = [{
        'trace_id': trace_id, 'span_id': root_id, 'parent_id': None,
        'name': 'stepline.fleet_dump', 'hop': 'serve-lb',
        'start': now, 'dur_s': 0.0, 'status': f'anomaly:{trigger}',
        'attrs': {'trigger': trigger,
                  'replicas': sorted(history),
                  'request_events': len(request_events),
                  'request_events_total': int(request_events_total
                                              or len(request_events)),
                  'fleet_events': len(fleet_events),
                  'fleet_events_total': int(fleet_events_total
                                            or len(fleet_events)),
                  **detail},
    }]
    for url, rows in history.items():
        for row in rows:
            spans.append({
                'trace_id': trace_id, 'span_id': os.urandom(8).hex(),
                'parent_id': root_id,
                'name': 'fleet.sample', 'hop': 'serve-lb',
                'start': row.get('t', now), 'dur_s': 0.0,
                'status': 'ok',
                'attrs': {'replica': url,
                          **{k: v for k, v in row.items()
                             if k != 't'}},
            })
    for name, rows in (('fleet.request', request_events),
                       ('fleet.event', fleet_events)):
        for row in rows:
            spans.append({
                'trace_id': trace_id, 'span_id': os.urandom(8).hex(),
                'parent_id': root_id,
                'name': name, 'hop': 'serve-lb',
                'start': row.get('t', now), 'dur_s': 0.0,
                'status': 'ok',
                'attrs': {k: v for k, v in row.items() if k != 't'},
            })
    return spans


# Background dump writer: the trigger fires on the engine thread (or
# an HTTP submit thread) — sqlite writes must happen elsewhere, and
# never while any engine lock is held. Bounded queue, fail-open.
_dump_q: collections.deque = collections.deque(maxlen=64)
_dump_cv = threading.Condition()
_writer_started = False
_inflight_writes = 0
_store = None            # test/ops injection (SpanStore-compatible)


def set_dump_store(store: Any) -> None:
    """Inject the span store dumps land in (tests point this at a
    tmp-path store; None restores the default resolution)."""
    global _store
    _store = store


def _resolve_store():
    if _store is not None:
        return _store
    from skypilot_tpu.observability import store as store_lib
    return store_lib.SpanStore()


def write_dump_sync(spans: List[Dict[str, Any]]) -> Optional[str]:
    """Synchronous dump write (the LB's ``asyncio.to_thread`` path
    and ``profile-smoke``). Returns the dump's trace_id, or None on
    failure — fail-open like every observability write."""
    try:
        store = _resolve_store()
        store.add_spans(spans)
        store.gc()
        return spans[0]['trace_id'] if spans else None
    except Exception:  # noqa: BLE001 — telemetry must never throw
        return None


def enqueue_dump(spans: Any) -> None:
    """Queue a dump for the background writer: a span list, or a
    zero-arg callable producing one — the engine hands a thunk so the
    O(ring) span rendering runs on the writer thread, not the step
    loop. Drops oldest beyond the bound (an anomaly storm degrades to
    fewer dumps, never to a blocked engine)."""
    with _dump_cv:
        _dump_q.append(spans)
        _ensure_writer()
        _dump_cv.notify_all()


def _ensure_writer() -> None:
    global _writer_started
    if _writer_started:
        return
    _writer_started = True

    def loop() -> None:
        global _inflight_writes
        while True:
            with _dump_cv:
                while not _dump_q:
                    # Bounded wait (not an idle poll: the enqueue
                    # notifies; the timeout only re-arms the wait).
                    _dump_cv.wait(timeout=60.0)
                spans = _dump_q.popleft()
                _inflight_writes += 1
            try:
                if callable(spans):
                    try:
                        spans = spans()
                    except Exception:  # noqa: BLE001 — fail-open
                        spans = []
                write_dump_sync(spans)
            finally:
                with _dump_cv:
                    _inflight_writes -= 1
                    _dump_cv.notify_all()

    threading.Thread(target=loop, daemon=True,
                     name='stepline-dump-writer').start()


def flush_dumps(timeout_s: float = 5.0) -> bool:
    """Block until every queued dump has been written (tests and the
    smoke target; the serving path never calls this)."""
    deadline = time.monotonic() + timeout_s
    with _dump_cv:
        while _dump_q or _inflight_writes:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            _dump_cv.wait(remaining)
    return True


# ---- profile-smoke -------------------------------------------------------

def _smoke() -> int:
    """``make profile-smoke``: run a tiny in-process workload with
    the recorder on, force an anomaly dump, and validate both the
    live Perfetto export and the dump round-trip through the span
    store. Exit code 0 = the flight recorder works end to end."""
    import json
    import tempfile

    import jax

    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.models import llama
    from skypilot_tpu.observability import store as store_lib

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = engine_lib.InferenceEngine(
        cfg, params,
        engine_lib.EngineConfig(
            n_slots=2, max_seq_len=128, prefill_buckets=(16, 32),
            prefill_chunk=32,
            # Any TTFT breaches a zero SLO: guarantees one dump.
            ttft_slo_s=0.0))
    with tempfile.TemporaryDirectory() as tmp:
        store = store_lib.SpanStore(
            db_path=os.path.join(tmp, 'smoke-traces.db'))
        set_dump_store(store)
        try:
            eng.generate([[7, 8, 9], [11] * 40], max_new_tokens=8)
            snap = eng.stepline_snapshot()
            doc = to_perfetto(snap)
            errs = validate_perfetto(doc)
            if errs:
                print('profile-smoke: live export INVALID:', errs)
                return 1
            if not snap['steps']:
                print('profile-smoke: recorder captured no steps')
                return 1
            if not flush_dumps(10.0):
                print('profile-smoke: dump writer did not drain')
                return 1
            traces = store.list_traces()
            dump = next((t for t in traces
                         if str(t.get('trace_id', ''))
                         .startswith('stepline-')), None)
            if dump is None:
                print('profile-smoke: no anomaly dump in the store')
                return 1
            spans = store.get_trace(dump['trace_id'])
            from skypilot_tpu.observability import render
            errs = validate_perfetto(render.to_perfetto(spans))
            if errs:
                print('profile-smoke: dump export INVALID:', errs)
                return 1
            if not any(s['name'] == 'stepline.trigger'
                       for s in spans):
                print('profile-smoke: dump lacks the trigger span')
                return 1
            summ = eng.stepline_summary()
            print('profile-smoke OK:',
                  json.dumps({'steps': summ['steps'],
                              'step_mean_ms': summ['step_mean_ms'],
                              'dump_spans': len(spans),
                              'dump_trace': dump['trace_id']}))
            return 0
        finally:
            set_dump_store(None)


if __name__ == '__main__':
    import sys

    # `python -m` runs this file as `__main__` — a SECOND module
    # object. Delegate to the canonical package import so the smoke's
    # set_dump_store hits the same globals the engine's dump path
    # uses.
    from skypilot_tpu.observability import stepline as _canonical
    sys.exit(_canonical._smoke())
