"""Helpers shared by the per-layer metric readers.

A reader gets ``run``, what one traced run collected: the client's
``records``, ``metrics_before`` / ``metrics_after`` (the engine's
``/metrics`` around the window), ``stepline`` (``/debug/stepline``
after it), and ``trace`` (``None`` in an untraced run): the reduced
profiler trace, the wall times of the traced stretch relative to the
window's start, ``/metrics`` at its two ends and the device's peaks.
A reader that finds nothing to read returns ``None``.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmark.stats import percentile  # noqa: F401 — readers use it


def own_file(reader_path: str) -> Dict[str, Any]:
    with open(os.path.splitext(reader_path)[0] + '.json',
              encoding='utf-8') as f:
        return json.load(f)


def window_steps(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The stepline's records whose start lies inside the window."""
    t0 = run['client']['t0']
    return [s for s in run['stepline'].get('steps', [])
            if t0 <= s['t'] <= t0 + run['seconds']]


def host_ms_per_step(run: Dict[str, Any]) -> Optional[float]:
    steps = window_steps(run)
    if not steps:
        return None
    busy = sum(s['dispatch_s'] + s['drain_s'] + s['host_s'] for s in steps)
    return 1e3 * busy / len(steps)


def counter_delta(run: Dict[str, Any], key: str,
                  traced: bool = False) -> Optional[float]:
    if traced:
        if not run['trace']:
            return None
        a, b = run['trace']['metrics_start'], run['trace']['metrics_stop']
    else:
        a, b = run['metrics_before'], run['metrics_after']
    if a.get(key) is None or b.get(key) is None:
        return None
    return b[key] - a[key]


def traced_decode_contexts(run: Dict[str, Any]) -> List[int]:
    """The context (keys attended) of every decode token that reached
    the client inside the traced stretch. A request's first token comes
    from prefill; token j > 0 attends to the prompt and the j tokens
    before it, and itself."""
    lo, hi = run['trace']['wall_s']
    out: List[int] = []
    for r in run['records']:
        j = 0
        for t, k in r['arrivals']:
            for _ in range(k):
                if j > 0 and lo <= t <= hi:
                    out.append(r['prompt_len'] + j + 1)
                j += 1
    return out


def traced_prefill_chunks(run: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(tokens, offset) of the prefill chunks dispatched inside the
    traced stretch: a request's chunks are spread evenly between its
    first dispatch and its first token."""
    lo, hi = run['trace']['wall_s']
    cap = run['config']['engine']['prefill_chunk']
    out: List[Tuple[int, int]] = []
    for r in run['records']:
        if not r['arrivals'] or r['sent_s'] is None:
            continue
        start = r['sent_s'] + (r['queue_wait_s'] or 0.0)
        end = r['arrivals'][0][0]
        n = r['prompt_len']
        n_chunks = -(-n // cap)
        for c in range(n_chunks):
            t = start + (c + 0.5) / n_chunks * (end - start)
            if lo <= t <= hi:
                out.append((min(cap, n - c * cap), c * cap))
    return out
