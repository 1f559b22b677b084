"""A traced run's device time by named scope.

The step programs name their parts with ``jax.named_scope``. A scope
does not show on a device event of the profiler's trace but on the
event's **metadata**: stat ``tf_op`` holds the HLO ``op_name``, e.g.
``jit(_decode_paged)/ssm/reduce_sum:`` (PERF.md section 7(2)).
``jax.profiler.ProfileData`` hands out no metadata stats, and the
generated protobuf module (``xplane_pb2``) comes only with tensorflow,
whose import takes most of a minute and may not run in a process that
holds the chip. So ``load`` reads the ``.xplane.pb`` wire format
itself: the few fields of ``XSpace`` / ``XPlane`` / ``XLine`` /
``XEvent`` / ``XEventMetadata`` / ``XStat`` that this needs, nothing
imported, safe in the process that holds the chip.

``load`` returns rows ``[plane, line, name, start_ns, duration_ns,
tf_op]`` of the device planes' ``XLA Ops`` lines (the form of the
recorded sample under ``tests/benchmark/data``); ``by_scope`` sums them
into ``{program: {scope: {'seconds', 'count'}}}``. An operation counts
under the INNERMOST of the known scopes on its path, under ``'(none)'``
if its path has none, and with the next operation if it has no path;
loops, branches and calls span the operations inside them and are left
out, as in ``trace_reduce``.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Iterable, Iterator, List, Tuple

from benchmark.trace_reduce import CONTAINERS, OPS_LINE, short_name

Row = Tuple[str, str, str, int, int, str]

SCOPES = ('embed', 'ssm', 'attn', 'kv_write', 'moe.route', 'moe.experts',
          'moe.shared', 'mlp', 'head', 'sample')
_PROGRAM = re.compile(r'^jit\(([^)]*)\)')


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: ints for varints, bytes
    for length-delimited fields; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, buf[pos:pos + size]
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f'wire type {wire} in an xplane file')


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b''
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def load(path: str) -> List[Row]:
    with open(path, 'rb') as f:
        space = f.read()
    rows: List[Row] = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, event_meta, stat_names = '', [], {}, {}
        for n, v in _fields(plane):
            if n == 2:
                name = v.decode('utf-8', 'replace')
            elif n == 3:
                lines.append(v)
            elif n == 4:
                k, body = _map_entry(v)
                event_meta[k] = body
            elif n == 5:
                k, body = _map_entry(v)
                stat_names[k] = next(
                    (x.decode('utf-8', 'replace')
                     for m, x in _fields(body) if m == 2), '')
        if not name.startswith('/device:'):
            continue
        tf_op_ids = {k for k, s in stat_names.items() if s == 'tf_op'}
        meta: Dict[int, Tuple[str, str]] = {}
        for k, body in event_meta.items():
            ev_name, tf_op = '', ''
            for n, v in _fields(body):
                if n == 2:
                    ev_name = v.decode('utf-8', 'replace')
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op_ids:
                        ref = stat.get(7)
                        tf_op = (stat_names.get(ref, '') if ref is not None
                                 else (stat.get(5) or b'').decode(
                                     'utf-8', 'replace'))
            meta[k] = (ev_name, tf_op)
        for line in lines:
            line_name, t0_ns, events = '', 0, []
            for n, v in _fields(line):
                if n == 2:
                    line_name = v.decode('utf-8', 'replace')
                elif n == 3:
                    t0_ns = v
                elif n == 4:
                    events.append(v)
            if line_name != OPS_LINE:
                continue
            for ev in events:
                e = dict(_fields(ev))
                ev_name, tf_op = meta.get(e.get(1, 0), ('', ''))
                rows.append((name, line_name, ev_name,
                             t0_ns + e.get(2, 0) // 1000,
                             e.get(3, 0) // 1000, tf_op))
    return rows


def scope_of(tf_op: str, scopes: Iterable[str] = SCOPES) -> Tuple[str, str]:
    """(program, innermost known scope) of one ``tf_op`` path."""
    m = _PROGRAM.match(tf_op)
    program = m.group(1) if m else '(unknown)'
    known = set(scopes)
    found = [p for p in tf_op.rstrip(':').split('/')[:-1] if p in known]
    return program, (found[-1] if found else '(none)')


def by_scope(rows: Iterable[Row], scopes: Iterable[str] = SCOPES
             ) -> Dict[str, Dict[str, Dict[str, float]]]:
    """Seconds and count of the first device's operations by (program,
    scope). An operation with no path at all is the end of an
    asynchronous copy (``copy-done``: the wait for a weight that the
    compiler prefetches into fast memory carries no ``op_name``): it
    counts under the next operation that has a path, which is what
    waited for it, so that a scope's time leaves out none of its
    work. What is left at the stretch's end stays ``'(unknown)'``."""
    rows = list(rows)
    devices = sorted({r[0] for r in rows})
    out: Dict[str, Dict[str, Dict[str, float]]] = {}

    def add(program, scope, ns, count):
        slot = out.setdefault(program, {}).setdefault(
            scope, {'seconds': 0.0, 'count': 0})
        slot['seconds'] += ns / 1e9
        slot['count'] += count
    waited_ns = waited = 0
    for r in sorted((r for r in rows if r[0] == devices[0]
                     and r[1] == OPS_LINE), key=lambda r: r[3]):
        if short_name(r[2]).split('.')[0] in CONTAINERS:
            continue
        if not r[5]:
            waited_ns, waited = waited_ns + r[4], waited + 1
            continue
        add(*scope_of(r[5], scopes), r[4] + waited_ns, 1 + waited)
        waited_ns = waited = 0
    if waited:
        add('(unknown)', '(none)', waited_ns, waited)
    return out


def seconds_of(scopes: Dict[str, Any], program_match: Iterable[str],
               scope: str) -> Tuple[float, int]:
    """Summed seconds and count of ``scope`` in the programs whose name
    holds any of ``program_match``."""
    needles = list(program_match)
    hit = [v[scope] for k, v in (scopes or {}).items()
           if any(n in k for n in needles) and scope in v]
    return sum(h['seconds'] for h in hit), sum(h['count'] for h in hit)


def table(scopes: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """``{program: {scope: seconds}}``, for the result line."""
    return {p: {s: v['seconds'] for s, v in sorted(
        by.items(), key=lambda kv: -kv[1]['seconds'])}
            for p, by in (scopes or {}).items()}
