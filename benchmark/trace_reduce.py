"""From a profiler trace to device metrics.

``jax.profiler`` writes an ``.xplane.pb``; ``load`` turns it into plain
rows ``[plane, line, name, start_ns, duration_ns]`` (the form the
recorded sample under ``tests/benchmark/data`` is kept in), and
``reduce`` turns rows into what the metrics read:

- per device: ``busy_s``, the union of the intervals in which an
  operation ran (the ``XLA Ops`` line of a ``/device:TPU:n`` plane);
- ``modules`` and ``ops``: count and summed device seconds of every
  jitted program and of every operation, by the name the trace gives;
- ``gaps``: the longest idle gaps on the first device, each named by
  the program that ended it (what the device was waiting for), since
  the host's spans are not on this clock yet.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Tuple

Row = Tuple[str, str, str, int, int]

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {log_dir}')
    return found[-1]


def load(path: str, device_only: bool = True) -> List[Row]:
    from jax.profiler import ProfileData
    rows: List[Row] = []
    for plane in ProfileData.from_file(path).planes:
        if device_only and not plane.name.startswith('/device:'):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)))
    return rows


def _union_s(intervals: Iterable[Tuple[int, int]]) -> Tuple[float, List[Tuple[int, int]]]:
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return (sum(e - s for s, e in merged) / 1e9,
            [(s, e) for s, e in merged])


def short_name(name: str) -> str:
    """``jit__decode_paged(1234567)`` -> ``jit__decode_paged``; an op's
    ``%fusion.12 = ...`` text -> ``fusion.12``."""
    name = name.split(' = ')[0].lstrip('%')
    return name.split('(')[0]


def reduce(rows: Iterable[Row], top: int = 10) -> Dict[str, Any]:
    rows = list(rows)
    devices = sorted({r[0] for r in rows if r[0].startswith('/device:')
                      and any(c.isdigit() for c in r[0])})
    out: Dict[str, Any] = {'devices': devices, 'busy_s': {}, 'modules': {},
                           'ops': {}, 'gaps': [], 'span_s': 0.0}
    if not devices:
        return out
    lo = min(r[3] for r in rows if r[0] in devices)
    hi = max(r[3] + r[4] for r in rows if r[0] in devices)
    out['span_s'] = (hi - lo) / 1e9
    for dev in devices:
        mine = [r for r in rows if r[0] == dev]
        ops = [r for r in mine if r[1] == OPS_LINE]
        busy, merged = _union_s((r[3], r[3] + r[4]) for r in ops)
        if not ops:
            continue
        out['busy_s'][dev] = busy
        if dev != devices[0]:
            continue
        for r in ops:
            slot = out['ops'].setdefault(short_name(r[2]),
                                         {'count': 0, 'seconds': 0.0})
            slot['count'] += 1
            slot['seconds'] += r[4] / 1e9
        modules = sorted((r for r in mine if r[1] == MODULES_LINE),
                         key=lambda r: r[3])
        for r in modules:
            slot = out['modules'].setdefault(short_name(r[2]),
                                             {'count': 0, 'seconds': 0.0})
            slot['count'] += 1
            slot['seconds'] += r[4] / 1e9
        gaps = []
        for (_, end), (start, _) in zip(merged, merged[1:]):
            nxt = next((short_name(m[2]) for m in modules
                        if m[3] + m[4] > start), 'unattributed')
            gaps.append((f'before:{nxt}', (start - end) / 1e9))
        out['gaps'] = sorted(gaps, key=lambda g: -g[1])[:top]
    return out


def busy_mean_s(reduced: Dict[str, Any]) -> float:
    vals = list(reduced['busy_s'].values())
    return sum(vals) / len(vals) if vals else 0.0


CONTAINERS = ('while', 'conditional', 'call')


def top_ops(reduced: Dict[str, Any], top: int = 10) -> List[List[Any]]:
    """The operations that took most device time. Loops, branches and
    calls span the operations inside them and are left out."""
    leaves = {k: v for k, v in reduced['ops'].items()
              if k.split('.')[0] not in CONTAINERS}
    return [[name, v['seconds']] for name, v in sorted(
        leaves.items(), key=lambda kv: -kv[1]['seconds'])[:top]]


def seconds_matching(table: Dict[str, Dict[str, float]],
                     needles: Iterable[str]) -> Tuple[float, int]:
    """Summed seconds and count of the entries whose name holds any of
    ``needles``."""
    needles = list(needles)
    hit = [v for k, v in table.items() if any(n in k for n in needles)]
    return sum(v['seconds'] for v in hit), sum(v['count'] for v in hit)
