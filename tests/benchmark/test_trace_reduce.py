"""trace_reduce on rows built by hand and on a small recorded trace."""
import json
import os

import pytest

from benchmark import trace_reduce

DEV = '/device:TPU:0'
MS = 1_000_000


def _rows():
    ops, mod = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    return [
        (DEV, mod, 'jit__decode_paged(123)', 0, 10 * MS),
        (DEV, ops, '%fusion.1 = bf16[8] fusion(...)', 0, 4 * MS),
        (DEV, ops, '%paged_attention.2 = custom-call(...)', 3 * MS, 5 * MS),
        (DEV, mod, 'jit__prefill_chunk_paged(9)', 20 * MS, 10 * MS),
        (DEV, ops, '%fusion.1 = bf16[8] fusion(...)', 20 * MS, 10 * MS),
        ('/device:TPU:1', ops, '%fusion.1', 0, 30 * MS),
        ('/host:CPU', 'python', 'ignored', 0, 99 * MS),
    ]


def test_busy_is_the_union_of_op_intervals_on_each_device():
    r = trace_reduce.reduce(_rows())
    assert r['devices'] == [DEV, '/device:TPU:1']
    assert r['busy_s'][DEV] == pytest.approx(0.018)     # 0-8 and 20-30 ms
    assert r['busy_s']['/device:TPU:1'] == pytest.approx(0.030)
    assert trace_reduce.busy_mean_s(r) == pytest.approx(0.024)
    assert r['span_s'] == pytest.approx(0.030)


def test_ops_and_modules_are_summed_by_short_name():
    r = trace_reduce.reduce(_rows())
    assert r['ops']['fusion.1'] == {'count': 2,
                                    'seconds': pytest.approx(0.014)}
    assert r['modules']['jit__decode_paged']['count'] == 1
    assert trace_reduce.seconds_matching(r['ops'], ['paged_attention']) == (
        pytest.approx(0.005), 1)
    assert trace_reduce.top_ops(r, 1) == [['fusion.1', pytest.approx(0.014)]]


def test_the_longest_gap_is_named_by_the_program_that_ended_it():
    r = trace_reduce.reduce(_rows())
    assert r['gaps'] == [('before:jit__prefill_chunk_paged',
                          pytest.approx(0.012))]


def test_no_device_plane_reduces_to_nothing():
    r = trace_reduce.reduce([('/host:CPU', 'python', 'x', 0, 5)])
    assert r['busy_s'] == {} and trace_reduce.busy_mean_s(r) == 0.0


def test_recorded_trace_of_the_chat_cell():
    """120 ms of the v5e's trace of the chat cell (PR 24, my chip run):
    the lines the reduction reads are there under the names it expects,
    and the decode program and the paged kernel are found."""
    path = os.path.join(os.path.dirname(__file__), 'data',
                        'trace_sample_chat.json')
    with open(path, encoding='utf-8') as f:
        sample = json.load(f)
    rows = [tuple(r) for r in sample['rows']]
    r = trace_reduce.reduce(rows)
    assert r['devices'] == [DEV]
    assert 0 < r['busy_s'][DEV] <= r['span_s']
    assert any('decode' in m for m in r['modules'])
    for metric in ('kernel.paged_decode_roofline', 'mfu.decode'):
        from benchmark import manifest
        own = manifest.metric_file(metric)
        table = r['ops'] if 'ops_match' in own else r['modules']
        seconds, count = trace_reduce.seconds_matching(
            table, own.get('ops_match') or own['modules_match'])
        assert count > 0 and seconds > 0, metric
