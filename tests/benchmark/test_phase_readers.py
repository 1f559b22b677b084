"""The six readers of a request's phases on one hand-built run: each
gives the mean worked by hand, over the requests submitted inside the
window that carry both of its stamps, and skips the others."""
import pytest

from benchmark import manifest

BENCH = manifest.load()
T0 = 1000.0     # the window's start on the wall clock; it lasts 10 s

# metric -> (the phase's two stamps, its mean in ms over requests 1 and 2)
PHASES = {
    'lb.inbound_mean_ms': (('submit.lb_recv_t', 'submit.recv_t'), 2.0),
    'server.admit_mean_ms': (('submit.recv_t', 'submit'), 1.5),
    'sched.queue_wait_mean_ms': (('submit', 'first_dispatch'), 50.0),
    'engine.prefill_dispatch_mean_ms': (
        ('first_dispatch', 'prefill_dispatched'), 30.0),
    'engine.first_token_lag_mean_ms': (
        ('prefill_dispatched', 'first_token'), 100.0),
    'server.first_flush_mean_ms': (('first_token', 'first_flush'), 0.5),
}


def _request(rid, recv_t, phases_ms, lb_ms=None):
    """The events of one request whose server received it at ``recv_t``
    and whose phases took ``phases_ms`` (admit, queue wait, prefill
    dispatch, first-token lag, first flush), ``lb_ms`` after the LB."""
    t = recv_t
    submit = {'request_id': rid, 'tenant': 'default', 'event': 'submit',
              'recv_t': recv_t}
    if lb_ms is not None:
        submit['lb_recv_t'] = recv_t - lb_ms / 1e3
    events = []
    for name, ms in zip(('submit', 'first_dispatch', 'prefill_dispatched',
                         'first_token', 'first_flush'), phases_ms):
        t += ms / 1e3
        ev = submit if name == 'submit' else {
            'request_id': rid, 'tenant': 'default', 'event': name}
        ev['t'] = t
        events.append(ev)
    events.append({'request_id': rid, 'tenant': 'default', 'event': 'done',
                   't': t + 1.0})
    return events


def _run():
    """Requests 1 and 2 are the window's: LB legs of 1 and 3 ms, admits
    of 1 and 2 ms, queue waits of 20 and 80, prefill dispatches of 0
    (one chunk) and 60, first-token lags of 90 and 110, flushes of 0.4
    and 0.6. Request 3 was a warm-up request before the window, request
    4 was submitted after its end, and -1 is the replica's own
    lifecycle line: none of them counts."""
    events = (
        [{'request_id': -1, 'tenant': '_lifecycle',
          'event': 'coldstart.compiled', 't': T0 + 1.0}]
        + _request(3, T0 - 5.0, (9, 9, 9, 9, 9), lb_ms=9)
        + _request(1, T0 + 1.0, (1, 20, 0, 90, 0.4), lb_ms=1)
        + _request(2, T0 + 2.0, (2, 80, 60, 110, 0.6), lb_ms=3)
        + _request(4, T0 + 10.5, (7, 7, 7, 7, 7), lb_ms=7))
    return {'seconds': 10.0, 'client': {'t0': T0},
            'stepline': {'steps': [], 'events': events}}


def _read(name, run):
    return manifest.metric_reader(name).read(run)


def _without(run, rid, stamp):
    """``run`` with request ``rid`` lacking ``stamp``."""
    name, _, detail = stamp.partition('.')
    events = []
    for ev in run['stepline']['events']:
        if ev['request_id'] == rid and ev['event'] == name:
            if not detail:
                continue
            ev = {k: v for k, v in ev.items() if k != detail}
        events.append(ev)
    return dict(run, stepline={'steps': [], 'events': events})


def test_the_six_are_in_the_manifest_for_the_chat_cell():
    entries = {m['name']: m for m in BENCH['per_layer']}
    for name in PHASES:
        m = entries[name]
        assert m['workloads'] == ['mistral7b-serve.chat']
        assert (m['unit'], m['better'], m['source'], m['moves']) == (
            'ms', 'lower', 'program_span', 'ttft_mean_s')
    assert [entries[n]['layer'] for n in PHASES] == [
        'serve LB', 'server front end', 'scheduler', 'engine step',
        'engine step', 'server front end']


@pytest.mark.parametrize('name', list(PHASES))
def test_reader_gives_the_mean_worked_by_hand(name):
    assert _read(name, _run()) == pytest.approx(PHASES[name][1], abs=1e-6)


@pytest.mark.parametrize('name', list(PHASES))
def test_reader_skips_a_request_that_lacks_a_stamp(name):
    """Without either of a phase's stamps on request 2, the mean is
    request 1's alone; without them on both there is nothing to read,
    and nothing is not 0."""
    (start, end), _ = PHASES[name]
    one = {'lb.inbound_mean_ms': 1.0, 'server.admit_mean_ms': 1.0,
           'sched.queue_wait_mean_ms': 20.0,
           'engine.prefill_dispatch_mean_ms': 0.0,
           'engine.first_token_lag_mean_ms': 90.0,
           'server.first_flush_mean_ms': 0.4}[name]
    for stamp in (start, end):
        if stamp == 'submit':
            continue    # a request with no submit is not in the window
        run = _without(_run(), 2, stamp)
        assert _read(name, run) == pytest.approx(one, abs=1e-6)
        assert _read(name, _without(run, 1, stamp)) is None


def test_an_older_programs_events_leave_the_new_phases_unread():
    """The parent commit stamps submit, first_dispatch, first_token and
    done, and no recv_t: only the queue wait can be read there."""
    run = _run()
    for rid in (1, 2, 3, 4):
        for stamp in ('submit.recv_t', 'submit.lb_recv_t',
                      'prefill_dispatched', 'first_flush'):
            run = _without(run, rid, stamp)
    assert _read('sched.queue_wait_mean_ms', run) == pytest.approx(50.0)
    for name in PHASES:
        if name != 'sched.queue_wait_mean_ms':
            assert _read(name, run) is None
    # and a stepline without an events key at all reads as nothing
    bare = dict(run, stepline={'steps': []})
    assert all(_read(name, bare) is None for name in PHASES)


def test_the_phases_add_up_to_the_time_from_the_lb_to_the_flush():
    run = _run()
    total = sum(_read(name, run) for name in PHASES)
    by = {}
    for ev in run['stepline']['events']:
        by.setdefault(ev['request_id'], {})[ev['event']] = ev
    whole = [1e3 * (by[r]['first_flush']['t'] - by[r]['submit']['lb_recv_t'])
             for r in (1, 2)]
    assert total == pytest.approx(sum(whole) / 2, abs=1e-6)
