"""The six readers of the engine thread's time on hand-built runs: each
number is the one worked by hand, traced and untraced, and a program
that keeps no such counter or field leaves them nothing to read."""
import copy

import pytest

from benchmark import manifest

BENCH = manifest.load()
NAMES = ['engine.wait_share', 'device.idle_with_work_share',
         'engine.starved_launch_share', 'engine.cpu_ms_per_step',
         'engine.cpu_ms_per_step.batch', 'engine.unaccounted_share']


def _step(t, dur_s, cpu_s, wait_s):
    return {'t': t, 'dur_s': dur_s, 'cpu_s': cpu_s, 'wait_s': wait_s,
            'dispatch_s': 0.0, 'drain_s': 0.0, 'host_s': 0.0}


def _run():
    """A window of 10 s from wall time 1000; the traced stretch is its
    seconds 4..6, of which the device was busy 1.5 s. The engine waited
    for work 0.4 s of the stretch; its records hold waits of 0.7 s and
    0.45 s inside the window, one that began 8 s before it and one
    that ends 1 s after it. Of 200 launches 30 found the device empty,
    10 of them after a wait."""
    return {
        'seconds': 10.0, 'client': {'t0': 1000.0},
        'metrics_before': {'engine_wait_s': 1.0, 'launches': 100,
                           'launches_device_empty': 20,
                           'launches_after_wait': 15},
        'metrics_after': {'engine_wait_s': 3.5, 'launches': 300,
                          'launches_device_empty': 50,
                          'launches_after_wait': 25},
        'stepline': {'steps': [
            _step(999.0, 0.5, 0.5, 9.0),                # before the window
            _step(1001.0, 0.010, 0.002, 0.7),           # its wait: outside
            _step(1001.010, 0.020, 0.004, 0.0),
            _step(1001.530, 0.010, 0.003, 0.45),        # 0.05 s unnamed
            _step(1011.0, 0.5, 0.5, 9.0)]},             # after the window
        'trace': {
            'wall_s': [4.0, 6.0], 'window_s': 2.0,
            'metrics_start': {'engine_wait_s': 2.0},
            'metrics_stop': {'engine_wait_s': 2.4},
            'reduced': {'busy_s': {'/device:TPU:0': 1.5}}},
    }


def _read(name, run):
    return manifest.metric_reader(name).read(run)


def test_the_entries_list_at_least_the_cells_measured_so_far():
    # a later PR appends cells to these lists by data alone
    entries = {m['name']: m for m in BENCH['per_layer']}
    assert set(NAMES) <= set(entries)
    for name in NAMES:
        m = entries[name]
        if name.endswith('.batch'):
            assert m['workloads'] == ['mistral7b-serve.longprompt-batch']
            assert m['moves'] == 'serve_tokens_per_s'
        else:
            assert set(m['workloads']) >= {
                'mistral7b-serve.chat', 'nemotron3-nano-serve.chat-bursty'}
            assert m['moves'] == 'ttft_mean_s'
    assert entries['device.idle_with_work_share']['layer'] == 'device'


def test_the_counter_readers():
    run = _run()
    # (30 - 10) of 200 launches had work in hand and an empty device
    assert _read('engine.starved_launch_share', run) == pytest.approx(10.0)
    # a window in which every empty launch followed a wait: 0, not None
    run['metrics_after']['launches_after_wait'] = 45
    assert _read('engine.starved_launch_share', run) == 0.0
    # untraced, it reads the same
    run = _run()
    run['trace'] = None
    assert _read('engine.starved_launch_share', run) == pytest.approx(10.0)


def test_wait_share_is_the_part_of_each_wait_inside_the_window():
    run = _run()
    # 0.7 + 0.45 s inside; of the 9 s wait that ended 1 s after the
    # window closed, its last second lies outside and 8 s inside, the
    # one that ended 1 s before it opened lies outside altogether
    assert _read('engine.wait_share', run) == pytest.approx(
        100 * (0.7 + 0.45 + 8.0) / 10.0)
    run['stepline']['steps'].pop()
    assert _read('engine.wait_share', run) == pytest.approx(11.5)
    # a wait that began before the window opened counts from its start
    run['stepline']['steps'][1]['wait_s'] = 3.0
    assert _read('engine.wait_share', run) == pytest.approx(14.5)
    run['trace'] = None
    assert _read('engine.wait_share', run) == pytest.approx(14.5)
    # an engine that never waited reads 0, not nothing
    for st in run['stepline']['steps']:
        st['wait_s'] = 0.0
    assert _read('engine.wait_share', run) == 0.0


def test_idle_with_work_is_the_idle_that_no_wait_explains():
    run = _run()
    # idle 0.5 s of 2.0, of which 0.4 s the engine had no request
    assert _read('device.idle_with_work_share', run) == pytest.approx(5.0)
    # two devices: the mean of their busy times
    run['trace']['reduced']['busy_s']['/device:TPU:1'] = 1.7
    assert _read('device.idle_with_work_share', run) == pytest.approx(0.0)
    # a wait longer than the idle (the scrapes lie inside the trace's
    # ends) is reported as it is, not clipped
    run['trace']['metrics_stop']['engine_wait_s'] = 2.6
    assert _read('device.idle_with_work_share', run) == pytest.approx(-10.0)
    untraced = _run()
    untraced['trace'] = None
    assert _read('device.idle_with_work_share', untraced) is None


def test_the_step_record_readers():
    run = _run()
    for name in ('engine.cpu_ms_per_step', 'engine.cpu_ms_per_step.batch'):
        assert _read(name, run) == pytest.approx(3.0)
    # first record's start to last record's end: 0.54 s, of which
    # 0.04 s are steps and 0.45 s the wait the third step ended
    assert _read('engine.unaccounted_share', run) == pytest.approx(
        100 * 0.05 / 0.54)
    run['stepline']['steps'] = run['stepline']['steps'][:2]
    assert _read('engine.unaccounted_share', run) is None   # one record


@pytest.mark.parametrize('name', NAMES)
def test_an_older_program_leaves_nothing_to_read(name):
    """No counter, no ``cpu_s`` / ``wait_s``: the parent's runs."""
    run = _run()
    for scrape in (run['metrics_before'], run['metrics_after'],
                   run['trace']['metrics_start'],
                   run['trace']['metrics_stop']):
        scrape.clear()
        scrape['decode_steps'] = 7
    for st in run['stepline']['steps']:
        del st['cpu_s'], st['wait_s']
    assert _read(name, run) is None
    # a counter missing at one end only
    run = _run()
    del run['metrics_before']['engine_wait_s']
    del run['metrics_before']['launches']
    del run['trace']['metrics_stop']['engine_wait_s']
    run['stepline']['steps'][2].pop('cpu_s')
    run['stepline']['steps'][2].pop('wait_s')
    assert _read(name, run) is None
    # and a trace that holds no device
    if name == 'device.idle_with_work_share':
        run = copy.deepcopy(_run())
        run['trace']['reduced'] = {'busy_s': {}}
        assert _read(name, run) is None
