"""The engine thread's whole time, from the program's own records.

The step loop's block for work is the engine's stage ``wait``: its
seconds are the ``/metrics`` counter ``engine_wait_s`` and the next
worked step's ``wait_s``; a step record carries the thread's CPU time
``cpu_s`` beside its wall time ``dur_s``; ``launches`` counts the
step-program launches, ``launches_device_empty`` those that found the
device's queue empty and ``launches_after_wait`` those of them that
followed a wait (docs/observability.md). A program that keeps none of
these (an older one) leaves every reader here nothing to read.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import trace_reduce
from benchmark.metrics import _common


def wait_share(run: Dict[str, Any]) -> Optional[float]:
    """The share of the window that lies inside a wait for work. A
    record's wait ends where its step starts; the part of it inside
    the window counts, whichever side of the window the step lies on.
    (The counter's growth between the scrapes around the window would
    also hold the client child's start and the drain.)"""
    steps = run['stepline'].get('steps', [])
    lo = run['client']['t0']
    hi = lo + run['seconds']
    if not steps or hi <= lo or any('wait_s' not in s for s in steps):
        return None
    waited = sum(max(0.0, min(s['t'], hi) - max(s['t'] - s['wait_s'], lo))
                 for s in steps)
    return 100.0 * waited / (hi - lo)


def idle_with_work_share(run: Dict[str, Any]) -> Optional[float]:
    """The traced stretch: the device's idle time less the engine's
    wait for work, over the stretch. Not clipped at 0: the two scrapes
    lie a few milliseconds inside the trace's two ends."""
    waited = _common.counter_delta(run, 'engine_wait_s', traced=True)
    if waited is None:
        return None
    trace = run['trace']
    if not trace['reduced'].get('busy_s') or not trace['window_s']:
        return None
    idle = trace['window_s'] - trace_reduce.busy_mean_s(trace['reduced'])
    return 100.0 * (idle - waited) / trace['window_s']


def starved_launch_share(run: Dict[str, Any]) -> Optional[float]:
    launches = _common.counter_delta(run, 'launches')
    empty = _common.counter_delta(run, 'launches_device_empty')
    after_wait = _common.counter_delta(run, 'launches_after_wait')
    if not launches or empty is None or after_wait is None:
        return None
    return 100.0 * (empty - after_wait) / launches


def cpu_ms_per_step(run: Dict[str, Any]) -> Optional[float]:
    cpu = [s.get('cpu_s') for s in _common.window_steps(run)]
    if not cpu or None in cpu:
        return None
    return 1e3 * sum(cpu) / len(cpu)


def unaccounted_share(run: Dict[str, Any]) -> Optional[float]:
    """From the start of the window's first step record to the end of
    its last: the share of that span which lies neither inside a step
    nor inside a wait for work. The first record's wait precedes the
    span and stays out."""
    steps = sorted(_common.window_steps(run), key=lambda s: s['t'])
    if len(steps) < 2 or any('wait_s' not in s for s in steps):
        return None
    span = steps[-1]['t'] + steps[-1]['dur_s'] - steps[0]['t']
    if span <= 0:
        return None
    named = (sum(s['dur_s'] for s in steps)
             + sum(s['wait_s'] for s in steps[1:]))
    return 100.0 * (1.0 - named / span)
