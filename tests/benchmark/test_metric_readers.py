"""Every per-layer reader on one hand-built traced run: the number it
reads is the one worked by hand, and with nothing to read it returns
nothing, never 0."""
import copy

import pytest

from benchmark import manifest, work

BENCH = manifest.load()
CFG = manifest.cell(BENCH, 'mistral7b-serve.chat')['config']
PEAK = work.peaks('TPU v5 lite')
PER_LAYER = [m['name'] for m in BENCH['per_layer']]


def _run():
    """Window of 10 s starting at wall time 1000; the traced stretch is
    seconds 4..6 of it. Request 0 (prompt 300) is sent at 3.9, waits
    0.1 s in the queue and gets its first token at 5.0, then one token
    at 5.5 and two in one line at 7.0 (outside the stretch). Request 1
    never got a token."""
    return {
        'config': CFG, 'seconds': 10.0, 'client': {'t0': 1000.0},
        'setup': {'after_devices_s': 11.5},
        'records': [
            {'idx': 0, 'prompt_len': 300, 'due_s': 3.898, 'sent_s': 3.9,
             'queue_wait_s': 0.1, 'arrivals': [[5.0, 1], [5.5, 1], [7.0, 2]]},
            {'idx': 1, 'prompt_len': 128, 'due_s': 9.0, 'sent_s': None,
             'queue_wait_s': None, 'arrivals': []}],
        'metrics_before': {'prefill_tokens': 100, 'stepline_steps': 10},
        'metrics_after': {'prefill_tokens': 700, 'stepline_steps': 40},
        'stepline': {'steps': [
            {'t': 999.0, 'dispatch_s': 9.0, 'drain_s': 9.0, 'host_s': 9.0},
            {'t': 1001.0, 'dispatch_s': 0.002, 'drain_s': 0.001,
             'host_s': 0.003},
            {'t': 1002.0, 'dispatch_s': 0.004, 'drain_s': 0.001,
             'host_s': 0.001}]},
        'trace': {
            'wall_s': [4.0, 6.0], 'window_s': 2.0, 'peak': PEAK,
            'metrics_start': {'prefill_tokens': 400, 'decode_tokens': 50},
            'metrics_stop': {'prefill_tokens': 700, 'decode_tokens': 52},
            'reduced': {
                'modules': {'jit__decode_paged': {'count': 2, 'seconds': 0.1},
                            'jit__prefill_chunk_paged': {'count': 2,
                                                         'seconds': 0.2}},
                'ops': {'paged_attention.13': {'count': 64, 'seconds': 0.01},
                        'closed_call.9': {'count': 64, 'seconds': 0.02},
                        'fusion.1': {'count': 9, 'seconds': 0.5}}}},
    }


def _read(name, run):
    return manifest.metric_reader(name).read(run)


def test_client_and_scheduler_readers():
    run = _run()
    assert _read('client.late_p95_ms', run) == pytest.approx(2.0)
    assert _read('sched.queue_wait_p95_ms', run) == pytest.approx(100.0)
    assert _read('sched.prefill_tokens_per_step', run) == pytest.approx(20.0)
    # gaps of 0.5 s once and 0.75 s twice: all three over 100 ms
    assert _read('client.itl_slow_share', run) == pytest.approx(100.0)
    run['records'][0]['arrivals'] = [[5.0, 1], [5.05, 1], [7.0, 2]]
    assert _read('client.itl_slow_share', run) == pytest.approx(200 / 3)
    assert _read('setup.after_devices_s', run) == 11.5
    # the two steps that start inside the window: 6 ms and 6 ms of host
    for name in ('engine.host_ms_per_step.chat',
                 'engine.host_ms_per_step.batch'):
        assert _read(name, run) == pytest.approx(6.0)


def test_decode_readers_count_the_tokens_that_arrived_in_the_stretch():
    run = _run()
    # only the token at 5.5 is a decode token inside 4..6: it attends
    # to the prompt, the one token before it and itself
    flops = work.forward_flops(CFG, 1, 302.0, 1)
    assert _read('mfu.decode', run) == pytest.approx(
        100 * flops / (0.1 * 197e12))
    _, bytes_ = work.paged_decode_work(CFG, [302], 64)
    assert _read('kernel.paged_decode_roofline', run) == pytest.approx(
        100 * (bytes_ / 819e9) / 0.01)


def test_prefill_readers_spread_a_requests_chunks_over_its_prefill():
    run = _run()
    # prefill runs from 4.0 (sent + queue wait) to 5.0: chunks of 256
    # and 44 tokens at 4.25 and 4.75, both inside the stretch; the
    # engine counted 300 prefill tokens there, so the scale is 1
    chunks = [(256, 0), (44, 256)]
    flops, bytes_ = work.paged_prefill_work(CFG, chunks)
    least = max(flops / 197e12, bytes_ / 819e9)
    assert _read('kernel.paged_prefill_roofline', run) == pytest.approx(
        100 * least / 0.02)
    ctx = sum(c * off + c * (c + 1) / 2 for c, off in chunks) + 2 * 302.0
    whole = work.forward_flops(CFG, 302, ctx, 2 + 1)
    assert _read('mfu.serve', run) == pytest.approx(
        100 * whole / (2.0 * 197e12))


@pytest.mark.parametrize('name', PER_LAYER)
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    run = _run()
    run.update(records=[], metrics_before={}, metrics_after={},
               stepline={'steps': []}, setup=None)
    run['trace']['metrics_stop'] = run['trace']['metrics_start']
    assert _read(name, run) is None
    if BENCH['per_layer'][PER_LAYER.index(name)]['source'] == 'device_trace':
        untraced = copy.deepcopy(_run())
        untraced['trace'] = None
        assert _read(name, untraced) is None
        unnamed = copy.deepcopy(_run())
        unnamed['trace']['reduced'] = {'modules': {}, 'ops': {}}
        unnamed['trace']['metrics_stop'] = unnamed['trace']['metrics_start']
        assert _read(name, unnamed) is None
