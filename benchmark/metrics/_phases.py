"""A request's phases, from the stamps of the engine's flight recorder.

``run['stepline']['events']`` (``/debug/stepline`` after the window)
holds, for each request, the events ``submit`` (with the details
``recv_t``, the server's handler entry, and ``lb_recv_t``, the load
balancer's), ``first_dispatch``, ``prefill_dispatched``,
``first_token``, ``first_flush`` and ``done``, each with its wall time
``t``. A stamp is named by its event, or as ``submit.recv_t`` by a
detail of one. The readers give means, because means add: lateness of
the client, the six phases and a remainder are the mean time to first
token of the same run. A program that takes no such stamp (an older
one) leaves the reader nothing to read.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def _stamp(events: Dict[str, Dict[str, Any]], key: str) -> Optional[float]:
    name, _, detail = key.partition('.')
    ev = events.get(name)
    if ev is None:
        return None
    return ev.get(detail) if detail else ev.get('t')


def mean_ms(run: Dict[str, Any], start: str, end: str) -> Optional[float]:
    """Mean milliseconds from stamp ``start`` to stamp ``end``, over
    the requests whose ``submit`` lies inside the window and that carry
    both (the first of each, where a preempted request repeats one)."""
    t0 = run['client']['t0']
    by_request: Dict[Any, Dict[str, Dict[str, Any]]] = {}
    for ev in run['stepline'].get('events', []):
        by_request.setdefault(ev['request_id'], {}).setdefault(
            ev['event'], ev)
    spans = []
    for events in by_request.values():
        submit = events.get('submit')
        if submit is None or not t0 <= submit['t'] <= t0 + run['seconds']:
            continue
        a, b = _stamp(events, start), _stamp(events, end)
        if a is not None and b is not None:
            spans.append(b - a)
    return 1e3 * sum(spans) / len(spans) if spans else None
