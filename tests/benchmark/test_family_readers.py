"""The per-layer readers of the hybrid cell on one hand-built traced
run (the number each reads is the one worked by hand), and the scope
reducer: on rows built by hand, on an ``.xplane.pb`` encoded by hand,
and on a stretch of the v5e's recorded trace of the cell."""
import json
import os

import pytest

from benchmark import manifest, scope_reduce, trace_reduce
from benchmark import work_nemotron_h as work

BENCH = manifest.load()
NAME = 'nemotron3-nano-serve.chat-bursty'
CFG = manifest.cell(BENCH, NAME)['config']
PEAK = work.peaks('TPU v5 lite')
DEV = '/device:TPU:0'
OPS = trace_reduce.OPS_LINE
NEW = ['mfu.hybrid_decode', 'kernel.ssm_decode_roofline',
       'kernel.moe_experts_roofline', 'moe.load_imbalance',
       'ssm.live_slots_per_step']


def _counters(steps, slot_steps, assignments, touched, load_max):
    return {'decode_steps': steps, 'ssm_slot_steps': slot_steps,
            'moe_local_assignments': assignments,
            'moe_experts_touched': touched, 'moe_expert_load_max': load_max}


def _run():
    """Window of 10 s; the traced stretch is seconds 4..6 of it, in
    which 10 decode steps advanced 400 slot states. Request 0 (prompt
    300) gets its first token at 5.0 and decode tokens at 5.5 (inside
    the stretch) and 7.0 (outside)."""
    return {
        'config': CFG, 'seconds': 10.0, 'client': {'t0': 1000.0},
        'records': [{'idx': 0, 'prompt_len': 300, 'due_s': 3.9,
                     'sent_s': 3.9, 'queue_wait_s': 0.1,
                     'arrivals': [[5.0, 1], [5.5, 1], [7.0, 2]]}],
        'metrics_before': _counters(10, 100, 1000, 500, 90),
        'metrics_after': _counters(110, 4100, 17000, 4500, 1090),
        'stepline': {'steps': []},
        'trace': {
            'wall_s': [4.0, 6.0], 'window_s': 2.0, 'peak': PEAK,
            'metrics_start': _counters(50, 2000, 8000, 2000, 400),
            'metrics_stop': _counters(60, 2400, 10400, 2430, 520),
            'reduced': {'modules': {
                'jit__decode_paged': {'count': 10, 'seconds': 0.2},
                'jit__prefill_chunk_paged': {'count': 2, 'seconds': 0.05}},
                'ops': {}},
            'scopes': {
                '_decode_paged': {'ssm': {'seconds': 0.05, 'count': 70},
                                  'moe.experts': {'seconds': 0.1,
                                                  'count': 140},
                                  'attn': {'seconds': 0.01, 'count': 20}},
                '_prefill_chunk_paged': {'ssm': {'seconds': 9.0,
                                                 'count': 14}}}},
    }


def _read(name, run):
    return manifest.metric_reader(name).read(run)


def test_counter_readers_take_the_whole_window():
    run = _run()
    # 4000 slot states advanced in 100 decode steps
    assert _read('ssm.live_slots_per_step', run) == pytest.approx(40.0)
    # the fullest expert of a step and block held 1000 rows in all; the
    # mean load is 16000 assignments over the 64 held experts
    assert _read('moe.load_imbalance', run) == pytest.approx(
        1000 * 64 / 16000)


def test_the_rooflines_read_the_decode_programs_scope_and_the_stretch():
    run = _run()
    flops, bytes_ = work.ssm_decode_work(CFG, 400, 10)
    least = max(flops / 197e12, bytes_ / 819e9)
    assert least == bytes_ / 819e9            # memory-bound
    assert _read('kernel.ssm_decode_roofline', run) == pytest.approx(
        100 * least / 0.05)                   # not the prefill's 9 s
    flops, bytes_ = work.moe_experts_work(CFG, 2400, 430)
    assert _read('kernel.moe_experts_roofline', run) == pytest.approx(
        100 * max(flops / 197e12, bytes_ / 819e9) / 0.1)


def test_mfu_counts_the_live_tokens_the_assignments_and_the_contexts():
    run = _run()
    # the one decode token that arrived in 4..6 attended to 302 keys
    flops = work.decode_flops(CFG, 400, 2400, 302.0)
    assert _read('mfu.hybrid_decode', run) == pytest.approx(
        100 * flops / (0.2 * 197e12))


@pytest.mark.parametrize('name', NEW)
def test_a_program_without_the_counters_or_scopes_reads_nothing(name):
    """The parent commit has neither: the reader returns nothing and
    does not raise (the benchmark contract for a metric new in a PR)."""
    run = _run()
    for key in ('metrics_before', 'metrics_after'):
        run[key] = {'decode_steps': run[key]['decode_steps']}
    for key in ('metrics_start', 'metrics_stop'):
        run['trace'][key] = {'decode_steps': run['trace'][key]['decode_steps']}
    run['trace'].pop('scopes')
    assert _read(name, run) is None
    run['trace'] = None
    assert _read(name, run) is None


# ---- the scope reducer -------------------------------------------------------

def _rows():
    d = 'jit(_decode_paged)/jit(main)/'
    return [
        (DEV, OPS, '%fusion.1 = f32[64] fusion(...)', 0, 4_000_000,
         d + 'ssm/mul:'),
        (DEV, OPS, '%fusion.2', 5_000_000, 1_000_000, d + 'ssm/reduce_sum:'),
        (DEV, OPS, '%gmm.3 = custom-call(...)', 6_000_000, 2_000_000,
         d + 'moe.experts/pallas_call:'),
        # the innermost known scope wins; an unknown one is passed over
        (DEV, OPS, '%fusion.4', 8_000_000, 500_000,
         d + 'attn/kv_write/scatter:'),
        (DEV, OPS, '%fusion.5', 9_000_000, 250_000,
         d + 'moe.route/helper/top_k:'),
        (DEV, OPS, '%copy.6', 9_500_000, 125_000, d + 'copy:'),
        (DEV, OPS, '%fusion.7', 10_000_000, 3_000_000,
         'jit(_prefill_chunk_paged)/jit(main)/ssm/dot_general:'),
        # containers span the operations inside them
        (DEV, OPS, '%while.8 = while(...)', 0, 50_000_000, d + 'ssm/while:'),
        # no path at all: an async copy's end counts with what waited
        # for it (the next operation that has a path: fusion.2, 'ssm')
        (DEV, OPS, '%copy-done.9', 4_000_000, 1_000_000, ''),
        (DEV, OPS, '%copy-done.10', 13_000_000, 7_000_000, ''),
        ('/device:TPU:1', OPS, '%fusion.1', 0, 9_000_000, d + 'ssm/mul:'),
        (DEV, trace_reduce.MODULES_LINE, 'jit__decode_paged(1)', 0,
         9_000_000, ''),
    ]


def test_an_operation_counts_under_its_innermost_known_scope():
    assert scope_reduce.scope_of('jit(_decode_paged)/jit(main)/ssm/mul:') \
        == ('_decode_paged', 'ssm')
    assert scope_reduce.scope_of('jit(f)/attn/kv_write/scatter:') == (
        'f', 'kv_write')
    assert scope_reduce.scope_of('') == ('(unknown)', '(none)')
    # a scope is a whole path element: 'ssm' inside a name is none
    assert scope_reduce.scope_of('jit(f)/assmble/add:')[1] == '(none)'


def test_scope_seconds_of_the_first_device_without_containers():
    by = scope_reduce.by_scope(_rows())
    dec = by['_decode_paged']
    assert dec['ssm'] == {'seconds': pytest.approx(0.006), 'count': 3}
    assert dec['moe.experts']['seconds'] == pytest.approx(0.002)
    assert dec['kv_write']['seconds'] == pytest.approx(0.0005)
    assert dec['moe.route']['seconds'] == pytest.approx(0.00025)
    assert dec['(none)']['seconds'] == pytest.approx(0.000125)
    assert 'attn' not in dec
    assert by['_prefill_chunk_paged']['ssm']['count'] == 1
    # nothing follows the last one: it stays unknown
    assert by['(unknown)']['(none)'] == {'seconds': pytest.approx(0.007),
                                         'count': 1}
    assert scope_reduce.seconds_of(by, ['_decode_paged'], 'ssm') == (
        pytest.approx(0.006), 3)
    assert scope_reduce.seconds_of(by, ['_decode'], 'head') == (0, 0)
    assert scope_reduce.seconds_of(None, ['_decode'], 'ssm') == (0, 0)
    table = scope_reduce.table(by)
    assert list(table['_decode_paged'])[0] == 'ssm'


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, body):
    return _field(1, key) + _field(2, body)


def test_load_reads_the_wire_format_itself(tmp_path):
    """XSpace.planes(1) / XPlane name(2) lines(3) event_metadata(4)
    stat_metadata(5) / XLine name(2) timestamp_ns(3) events(4) / XEvent
    metadata_id(1) offset_ps(2) duration_ps(3) / XEventMetadata name(2)
    stats(5) / XStat metadata_id(1) str_value(5) | ref_value(7)."""
    tf_op, path_ref = 7, 9
    op = 'jit(_decode_paged)/jit(main)/ssm/mul:'
    stat_meta = (_field(5, _entry(tf_op, _field(1, tf_op)
                                  + _field(2, 'tf_op')))
                 + _field(5, _entry(path_ref, _field(1, path_ref)
                                    + _field(2, op)))
                 + _field(5, _entry(3, _field(1, 3) + _field(2, 'flops'))))
    # event 1 names its path by reference, event 2 by a string; a
    # fixed-width stat (a double) beside them is passed over
    double = _varint(2 << 3 | 1) + bytes(8)
    ev_meta = (_field(4, _entry(1, _field(1, 1) + _field(2, '%fusion.1')
                                + _field(5, _field(1, 3) + double)
                                + _field(5, _field(1, tf_op)
                                         + _field(7, path_ref))))
               + _field(4, _entry(2, _field(1, 2) + _field(2, '%gmm.2')
                                  + _field(5, _field(1, tf_op) + _field(
                                      5, 'jit(f)/moe.experts/call:')))))
    events = (_field(4, _field(1, 1) + _field(2, 2_000_000)
                     + _field(3, 5_000_000))
              + _field(4, _field(1, 2) + _field(2, 9_000_000)
                       + _field(3, 1_000_000)))
    line = _field(3, _field(2, OPS) + _field(3, 1_000) + events)
    other = _field(3, _field(2, 'Steps') + _field(3, 0) + events)
    plane = _field(2, DEV) + line + other + ev_meta + stat_meta
    host = _field(2, '/host:CPU') + line + ev_meta + stat_meta
    path = tmp_path / 'x.xplane.pb'
    path.write_bytes(_field(1, plane) + _field(1, host))
    assert scope_reduce.load(str(path)) == [
        (DEV, OPS, '%fusion.1', 3_000, 5_000, op),   # ps -> ns
        (DEV, OPS, '%gmm.2', 10_000, 1_000, 'jit(f)/moe.experts/call:')]


def test_recorded_trace_of_the_hybrid_cell():
    """A stretch of the v5e's trace of the cell (PR 27, my chip run):
    rows as ``scope_reduce.load`` gave them. The scopes the readers
    name are there under the decode program, and the two Pallas
    products of an expert layer count under ``moe.experts``."""
    path = os.path.join(os.path.dirname(__file__), 'data',
                        'scope_sample_hybrid.json')
    with open(path, encoding='utf-8') as f:
        sample = json.load(f)
    rows = [tuple(r) for r in sample['rows']]
    by = scope_reduce.by_scope(rows)
    assert any('_decode_paged' in p for p in by)
    for metric in ('kernel.ssm_decode_roofline',
                   'kernel.moe_experts_roofline'):
        own = manifest.metric_file(metric)
        seconds, count = scope_reduce.seconds_of(by, own['programs_match'],
                                                 own['scope'])
        assert count > 0 and seconds > 0, metric
    dec = next(v for p, v in by.items() if '_decode_paged' in p)
    for scope in ('ssm', 'attn', 'kv_write', 'moe.route', 'moe.experts',
                  'moe.shared', 'head'):
        assert dec[scope]['count'] > 0, scope
    gmm = [r for r in rows if 'gmm' in r[2]
           and scope_reduce.scope_of(r[5])[0] == '_decode_paged']
    assert gmm and all(scope_reduce.scope_of(r[5])[1] == 'moe.experts'
                       for r in gmm)
    # what no program claims stays a small share of the stretch
    lost = by.get('(unknown)', {}).get('(none)', {'seconds': 0.0})
    total = sum(s['seconds'] for v in by.values() for s in v.values())
    assert lost['seconds'] < 0.02 * total
