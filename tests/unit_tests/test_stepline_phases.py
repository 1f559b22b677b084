"""The request's whole timeline in the flight recorder, and the step's
stages in a profiler trace.

A tiny paged engine behind the real ``InferenceServer`` (and, where the
LB's stamp matters, the real ``LoadBalancer`` in front of it) on the
CPU: every finished request carries ``submit`` (with ``recv_t``, and
``lb_recv_t`` where an LB forwarded it) → ``first_dispatch`` →
``prefill_dispatched`` → ``first_token`` → ``first_flush`` in order,
the Perfetto export draws them, and none of it changes a token. Then
three steps under ``jax.profiler``: the ``engine.*`` annotations are in
the trace, each stage inside its step, and they add up to what the
step records hold. And a pause between two requests of a served
engine: the loop's wait for work is stage ``engine.wait``, between the
steps in the trace, on the ``/metrics`` counter while still open, and
on the record of the step that ends it. A CPU run shows order and
counts, never a speed.
"""
import asyncio
import json
import time

import pytest

pytestmark = pytest.mark.jax

import jax  # noqa: E402

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402
from skypilot_tpu.observability import stepline  # noqa: E402
from skypilot_tpu.utils import common as common_lib  # noqa: E402

CFG = llama.LlamaConfig.tiny()
CHUNK = 32
# short, multi-chunk (three chunks: offsets 0, 32, 64), short
PROMPTS = [[5, 17, 101, 7], [11] * 70, [9, 8, 7, 6, 5]]
STAMPS = ('submit', 'first_dispatch', 'prefill_dispatched', 'first_token',
          'first_flush')


@pytest.fixture(scope='module')
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _ecfg(**kw):
    base = dict(n_slots=3, max_seq_len=128, prefill_buckets=(16, 32),
                prefill_chunk=CHUNK, pipeline_depth=1, paged=True,
                page_size=16, n_pages=40)
    base.update(kw)
    return engine_lib.EngineConfig(**base)


async def _stream(client, tokens, max_new, headers=None):
    r = await client.post('/generate', headers=headers or {}, json={
        'tokens': tokens, 'max_new_tokens': max_new, 'stream': True})
    assert r.status == 200, await r.text()
    lines = [json.loads(ln) for ln in (await r.text()).splitlines() if ln]
    done = lines[-1]
    assert done.get('done'), lines
    return done['request_id'], [t for ln in lines
                                for t in ln.get('tokens', [])]


@pytest.fixture(scope='module')
def served(params):
    """The prompts streamed through the real server, twice: straight,
    and through the real load balancer. One more request asks for a
    single token, one carries a header that is no number."""
    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.serve import load_balancer as lb_lib

    async def flow():
        eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
        srv = server_lib.InferenceServer(eng)
        srv._thread.start()
        replica = TestServer(srv.make_app())
        direct = TestClient(replica)
        await direct.start_server()
        lb = lb_lib.LoadBalancer('svc-phases', 'round_robin')
        lb._session = aiohttp.ClientSession()
        lb.policy.set_ready_replicas([str(replica.make_url('')).rstrip('/')])
        via_lb = TestClient(TestServer(lb.make_app()))
        await via_lb.start_server()
        out = {'direct': {}, 'lb': {}, 'tokens': {}}
        try:
            for p in PROMPTS:
                rid, toks = await _stream(direct, p, 6)
                out['direct'][rid] = p
                out['tokens'][rid] = toks
            for p in PROMPTS:
                rid, toks = await _stream(via_lb, p, 6)
                out['lb'][rid] = p
                out['tokens'][rid] = toks
            out['one_token'], _ = await _stream(direct, PROMPTS[0], 1)
            out['bad_header'], _ = await _stream(
                direct, PROMPTS[0], 2,
                headers={common_lib.LB_RECV_HEADER: 'yesterday'})
            # a client may not plant the LB's stamp: the LB overwrites it
            out['planted'], _ = await _stream(
                via_lb, PROMPTS[0], 2,
                headers={common_lib.LB_RECV_HEADER: '12.5'})
            snap = await (await direct.get('/debug/stepline')).json()
            out['metrics'] = await (await direct.get('/metrics')).json()
        finally:
            await via_lb.close()
            await lb._session.close()
            await direct.close()
            srv._stop.set()
        out['snapshot'] = snap
        return out

    return asyncio.run(flow())


def _by_request(snapshot):
    by = {}
    for ev in snapshot['events']:
        by.setdefault(ev['request_id'], []).append(ev)
    return by


def _first(events, name):
    return next((ev for ev in events if ev['event'] == name), None)


def test_every_finished_request_carries_its_stamps_in_order(served):
    by = _by_request(served['snapshot'])
    rids = list(served['direct']) + list(served['lb'])
    assert len(rids) == 2 * len(PROMPTS)
    for rid in rids:
        evs = by[rid]
        got = [_first(evs, name) for name in STAMPS]
        assert all(got), (rid, [ev['event'] for ev in evs])
        times = [got[0]['recv_t']] + [ev['t'] for ev in got]
        assert times == sorted(times), (rid, times)
        done = _first(evs, 'done')
        assert done and got[3]['t'] <= done['t']
        # each stamp is taken once a request
        for name in STAMPS:
            assert sum(ev['event'] == name for ev in evs) == 1, (rid, name)


def test_a_one_token_answer_is_done_at_its_first_token(served):
    evs = _by_request(served['snapshot'])[served['one_token']]
    first, done = _first(evs, 'first_token'), _first(evs, 'done')
    assert first and done and first['t'] <= done['t']
    flush = _first(evs, 'first_flush')
    assert flush and flush['t'] >= first['t']


def test_every_first_token_was_read_early_and_counted(served):
    """No request of the fixture rode a fused step, so each first token
    came from its chunk's own record: the event says so, and the two
    ``/metrics`` counters agree with the count of requests."""
    by = _by_request(served['snapshot'])
    rids = (list(served['direct']) + list(served['lb'])
            + [served[k] for k in ('one_token', 'bad_header', 'planted')])
    for rid in rids:
        assert _first(by[rid], 'first_token')['early'] == 1, rid
    m = served['metrics']
    # request 1 was the server's own warm-up
    assert min(rids) == 2 and m['first_token_total'] == len(rids) + 1
    assert m['first_token_early_total'] == m['first_token_total']


FAMILIES = ('dense', 'paged', 'state')


def _family_engine(family, params):
    if family == 'state':
        from tests.unit_tests import conftest
        return engine_lib.InferenceEngine(
            *conftest.tiny_state_model(), _ecfg(cache_dtype='float32'))
    if family == 'dense':
        return engine_lib.InferenceEngine(
            CFG, params, engine_lib.EngineConfig(
                n_slots=3, max_seq_len=128, prefill_buckets=(16, 32),
                prefill_chunk=CHUNK, pipeline_depth=1))
    return engine_lib.InferenceEngine(CFG, params, _ecfg())


@pytest.mark.parametrize('family', FAMILIES)
def test_the_first_streamed_line_holds_one_token(params, family):
    """A request that arrives while another decodes: its first token
    leaves the server in a line of its own, stamped before the engine
    consumed the decode pair dispatched behind its last chunk (the
    pair's consume is slowed here, so the handler is never raced)."""
    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.infer import inflight
    from skypilot_tpu.infer import server as server_lib

    eng = _family_engine(family, params)
    eng.generate([PROMPTS[0]], max_new_tokens=2)       # compile outside
    pairs = []          # (wall time, lanes) of each pair's consume
    apply_pair = eng._apply_pair

    def slow_pair(host, rec):
        time.sleep(0.03)
        pairs.append((time.time(), [req.request_id
                                    for _, req, *_ in rec.decoded]))
        apply_pair(host, rec)
    eng._apply_pair = slow_pair

    async def lines_of(client, tokens, max_new):
        r = await client.post('/generate', json={
            'tokens': tokens, 'max_new_tokens': max_new, 'stream': True})
        assert r.status == 200, await r.text()
        return [json.loads(ln) for ln in (await r.text()).splitlines()
                if ln]

    async def flow():
        srv = server_lib.InferenceServer(eng)
        srv._thread.start()
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            busy = asyncio.ensure_future(lines_of(client, PROMPTS[1], 24))
            while eng.metrics()['decode_tokens'] < 4:   # it is decoding
                await asyncio.sleep(0.005)
            late = await lines_of(client, PROMPTS[0], 4)
            await busy
            snap = await (await client.get('/debug/stepline')).json()
        finally:
            await client.close()
            srv._stop.set()
        return late, snap

    late, snap = asyncio.run(flow())
    rid = late[-1]['request_id']
    assert late[-1]['done'] and len(late[0]['tokens']) == 1
    assert sum(len(ln.get('tokens', [])) for ln in late) == 4
    evs = _by_request(snap)[rid]
    first = _first(evs, 'first_token')
    assert first['early'] == 1
    # the first pair that carried this request was consumed after its
    # first token was stamped: that pair brought the SECOND token
    behind = next(t for t, lanes in pairs if rid in lanes)
    assert first['t'] < behind
    assert isinstance(eng._queue, inflight.Queue) and eng.idle()


def test_the_lb_header_starts_the_timeline(served):
    by = _by_request(served['snapshot'])
    for rid in served['lb']:
        sub = _first(by[rid], 'submit')
        assert sub['lb_recv_t'] <= sub['recv_t'] <= sub['t']
    for rid in served['direct']:
        assert 'lb_recv_t' not in _first(by[rid], 'submit')
    # a header that is no number is no stamp, and no error either
    assert 'lb_recv_t' not in _first(by[served['bad_header']], 'submit')
    planted = _first(by[served['planted']], 'submit')
    assert planted['lb_recv_t'] > 1e9
    assert planted['lb_recv_t'] <= planted['recv_t']


def test_a_multi_chunk_prompt_is_stamped_at_its_last_chunk(served):
    by = _by_request(served['snapshot'])
    for group in ('direct', 'lb'):
        for rid, prompt in served[group].items():
            ev = _first(by[rid], 'prefill_dispatched')
            last_off = (len(prompt) - 1) // CHUNK * CHUNK
            assert ev['off'] == last_off, (rid, ev)
    long_rid = next(r for r, p in served['direct'].items() if len(p) > CHUNK)
    evs = by[long_rid]
    # three chunks took three dispatches: the first and the last differ
    assert _first(evs, 'prefill_dispatched')['off'] == 64
    assert (_first(evs, 'first_dispatch')['t']
            < _first(evs, 'prefill_dispatched')['t'])


def test_perfetto_export_draws_the_new_phases(served):
    doc = stepline.to_perfetto(served['snapshot'])
    assert stepline.validate_perfetto(doc) == []
    rid = next(iter(served['lb']))
    names = {ev['name'] for ev in doc['traceEvents']
             if ev.get('ph') == 'X'
             and ev.get('args', {}).get('request_id') == rid}
    assert names == {'req.lb_inbound', 'req.admit', 'req.queue_wait',
                     'req.prefill', 'req.first_token_lag',
                     'req.first_flush', 'req.decode'}
    direct = next(iter(served['direct']))
    names = {ev['name'] for ev in doc['traceEvents']
             if ev.get('ph') == 'X'
             and ev.get('args', {}).get('request_id') == direct}
    assert 'req.lb_inbound' not in names and 'req.admit' in names
    # the stamps that bound a slice are not drawn a second time
    instants = {ev['name'] for ev in doc['traceEvents']
                if ev.get('ph') == 'i'}
    assert not instants & {'req.prefill_dispatched', 'req.first_flush'}


def test_step_records_carry_the_scheduling_share(served):
    steps = served['snapshot']['steps']
    assert steps and all('sched_s' in st for st in steps)
    for st in steps:
        # a part of the host remainder, not taken out of it
        assert 0.0 <= st['sched_s'] <= st['host_s'] + 1e-6, st
        assert st['host_s'] == pytest.approx(max(0.0, st['dur_s']
                                                 - st['dispatch_s']
                                                 - st['drain_s']
                                                 - st['readback_s']))
    assert any(st['sched_s'] > 0 for st in steps)


def test_server_streams_what_a_bare_engine_generates(served, params):
    """What the server streamed, with its front-end stamps, is what a
    bare engine generates (both recording)."""
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
    plain = [r.output_tokens for r in eng.generate(PROMPTS,
                                                   max_new_tokens=6)]
    for group in ('direct', 'lb'):
        got = [served['tokens'][rid] for rid in served[group]]
        assert got == plain


def test_engine_pool_routes_the_stamps_to_the_requests_tier(params):
    small = engine_lib.InferenceEngine(
        CFG, params, _ecfg(max_seq_len=64, n_pages=16))
    large = engine_lib.InferenceEngine(CFG, params, _ecfg())
    pool = engine_lib.EnginePool([small, large])
    reqs = [pool.submit([3, 4, 5], max_new_tokens=2, recv_t=10.0),
            pool.submit([7] * 70, max_new_tokens=2, recv_t=11.0,
                        lb_recv_t=10.5)]
    for req in reqs:
        pool.note_request_event(req, 'first_flush')
    for eng, req in zip((small, large), reqs):
        evs = [ev for ev in eng.stepline_snapshot()['events']
               if ev['request_id'] == req.request_id]
        assert [ev['event'] for ev in evs] == ['submit', 'first_flush']
        assert evs[0]['recv_t'] in (10.0, 11.0)
    sub = _first(large.stepline_snapshot()['events'], 'submit')
    assert sub['lb_recv_t'] == 10.5 and sub['recv_t'] == 11.0


PAUSE_S = 0.5


@pytest.fixture(scope='module')
def paused(params, tmp_path_factory):
    """One request through the real server, a pause with the engine
    empty (``/metrics`` read in the middle of it), a second request;
    all of it under ``jax.profiler`` as the benchmark takes it."""
    from aiohttp.test_utils import TestClient, TestServer

    from benchmark import trace_reduce
    from skypilot_tpu.infer import server as server_lib

    log_dir = str(tmp_path_factory.mktemp('wait-trace'))

    async def flow():
        eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
        srv = server_lib.InferenceServer(eng)
        srv._thread.start()
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        out = {}
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        try:
            await _stream(client, PROMPTS[0], 3)    # compiles outside
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                out['first'], _ = await _stream(client, PROMPTS[0], 3)
                out['before'] = await (await client.get('/metrics')).json()
                await asyncio.sleep(PAUSE_S / 2)
                out['mid'] = await (await client.get('/metrics')).json()
                await asyncio.sleep(PAUSE_S / 2)
                out['second'], _ = await _stream(client, PROMPTS[2], 3)
                out['after'] = await (await client.get('/metrics')).json()
            finally:
                jax.profiler.stop_trace()
            out['snapshot'] = await (
                await client.get('/debug/stepline')).json()
        finally:
            await client.close()
            srv._stop.set()
        return out

    out = asyncio.run(flow())
    out['rows'] = trace_reduce.load(trace_reduce.find_xplane(log_dir),
                                    device_only=False)
    return out


def _record_that_ended_the_pause(paused):
    """The first worked step after the second request's submit."""
    sub = _first(_by_request(paused['snapshot'])[paused['second']], 'submit')
    return next(st for st in paused['snapshot']['steps']
                if st['t'] + st['dur_s'] >= sub['t'])


def test_a_pause_between_requests_is_the_next_records_wait(paused):
    # from the first traced request on: the steps before it compiled
    t_first = _first(_by_request(paused['snapshot'])[paused['first']],
                     'submit')['t']
    steps = [st for st in paused['snapshot']['steps'] if st['t'] >= t_first]
    rec = _record_that_ended_the_pause(paused)
    prev = steps[steps.index(rec) - 1]
    gap = rec['t'] - (prev['t'] + prev['dur_s'])
    assert gap >= PAUSE_S
    # the gap between the two records is the wait, to a few ms (the
    # idle ticks every 0.1 s and the loop itself are the rest)
    assert rec['wait_s'] == pytest.approx(gap, abs=0.02)
    # no step's duration covers the pause, and only this one carries it
    assert all(st['dur_s'] < PAUSE_S / 2 for st in steps)
    later = [st for st in steps if st['idx'] > rec['idx']]
    assert later and all(st['wait_s'] < 0.05 for st in later)
    # the accounting closes over the stretch: steps + waits + a
    # remainder that is small here
    first = next(st for st in steps if st['idx'] == prev['idx'])
    stretch = [st for st in steps if st['idx'] >= first['idx']]
    span = stretch[-1]['t'] + stretch[-1]['dur_s'] - stretch[0]['t']
    named = (sum(st['dur_s'] for st in stretch)
             + sum(st['wait_s'] for st in stretch[1:]))
    assert 0.0 <= span - named < 0.05


def test_the_wait_counter_shows_an_open_wait_and_grows_by_the_pause(paused):
    before, mid, after = (paused[k] for k in ('before', 'mid', 'after'))
    rec = _record_that_ended_the_pause(paused)
    # read in the middle of the wait: part of it is there already
    assert 0.1 < mid['engine_wait_s'] - before['engine_wait_s'] < PAUSE_S
    grown = after['engine_wait_s'] - before['engine_wait_s']
    # the second scrape follows the first request's last step by a
    # moment the counter had already counted
    assert grown == pytest.approx(rec['wait_s'], abs=0.05)
    # the first launch after the wait found the device empty
    assert (after['launches_after_wait']
            - before['launches_after_wait']) == 1
    assert rec['dev_empty'] == 1
    assert after['launches'] > before['launches']
    assert (after['launches_after_wait'] <= after['launches_device_empty']
            <= after['launches'])


def test_engine_wait_is_on_the_engine_threads_line_outside_every_step(
        paused):
    mine = [r for r in paused['rows'] if r[2].startswith('engine.')]
    assert {r[0] for r in mine} == {'/host:CPU'}
    assert len({r[1] for r in mine}) == 1       # the engine's thread
    waits = [r for r in mine if r[2] == 'engine.wait']
    steps = [r for r in mine if r[2] == 'engine.step']
    assert steps and len(waits) >= PAUSE_S / 0.1 - 1
    for w in waits:
        assert all(w[3] + w[4] <= s[3] or s[3] + s[4] <= w[3]
                   for s in steps), w
    # what the trace holds of waits is what the counter counted
    total = sum(w[4] for w in waits) / 1e9
    grown = paused['after']['engine_wait_s'] - paused['before'][
        'engine_wait_s']
    assert total >= grown - 0.05
    # and the Perfetto export draws the wait on its own stage track
    doc = stepline.to_perfetto(paused['snapshot'])
    assert stepline.validate_perfetto(doc) == []
    rec = _record_that_ended_the_pause(paused)
    drawn = [ev for ev in doc['traceEvents'] if ev['name'] == 'engine.wait']
    assert any(ev['args']['step'] == rec['idx']
               and ev['dur'] == pytest.approx(rec['wait_s'] * 1e6)
               for ev in drawn)


def test_stages_are_annotations_in_a_profiler_trace(params, tmp_path):
    """Three steps under ``jax.profiler``: the trace holds
    ``engine.step`` and the four stages on the host plane, each stage
    inside a step's interval, and per step they add up to what the
    step's record holds."""
    from benchmark import trace_reduce

    eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
    eng.generate([PROMPTS[0]], max_new_tokens=2)    # compile outside
    before = eng.stepline_snapshot()['steps_total']
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # what benchmark/kinds/_serve.py takes
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(3):
            eng.step()
            if i == 1:
                with eng.wait_stage():      # what the server loop does
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    records = [st for st in eng.stepline_snapshot()['steps']
               if st['idx'] >= before]
    assert len(records) == 3
    assert [st['wait_s'] >= 0.002 for st in records] == [False, False, True]
    eng.run_until_idle()

    rows = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)),
                             device_only=False)
    mine = [r for r in rows if r[2].startswith('engine.')]
    assert {r[0] for r in mine} == {'/host:CPU'}
    assert len({r[1] for r in mine}) == 1       # the engine's thread
    names = {r[2] for r in mine}
    assert names >= {'engine.step', 'engine.dispatch', 'engine.readback',
                     'engine.drain', 'engine.sched', 'engine.wait'}
    steps = sorted((r for r in mine if r[2] == 'engine.step'),
                   key=lambda r: r[3])
    assert len(steps) == 3
    for step, rec in zip(steps, records):
        lo, hi = step[3], step[3] + step[4]
        inside = [r for r in mine if r[2] != 'engine.step'
                  and lo <= r[3] and r[3] + r[4] <= hi]
        for stage in ('dispatch', 'drain', 'readback', 'sched'):
            total = sum(r[4] for r in inside
                        if r[2] == f'engine.{stage}') / 1e9
            assert total == pytest.approx(rec[f'{stage}_s'], abs=1e-3), (
                stage, total, rec)
        assert step[4] / 1e9 == pytest.approx(rec['dur_s'], abs=2e-3)
    # every stage lies inside one of the steps, but the wait for work,
    # which lies between the second and the third
    stages = [r for r in mine
              if r[2] not in ('engine.step', 'engine.wait')]
    assert all(any(s[3] <= r[3] and r[3] + r[4] <= s[3] + s[4]
                   for s in steps) for r in stages)
    (wait,) = [r for r in mine if r[2] == 'engine.wait']
    assert steps[1][3] + steps[1][4] <= wait[3]
    assert wait[3] + wait[4] <= steps[2][3]
    assert wait[4] / 1e9 == pytest.approx(records[2]['wait_s'], abs=1e-3)
    # and the step carries the index of the record it wrote
    from jax.profiler import ProfileData
    nums = []
    for plane in ProfileData.from_file(
            trace_reduce.find_xplane(str(tmp_path))).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == 'engine.step':
                    nums.append(dict(ev.stats).get('step_num'))
    assert sorted(nums) == [rec['idx'] for rec in records]


def test_stage_clock_adds_up_and_starts_each_step_from_zero():
    clock = stepline.StageClock()
    with clock.step(7):
        with clock.stage('dispatch'):
            pass
        with clock.stage('dispatch'):
            pass
        with clock.stage('sched'):
            pass
    assert clock.acc['dispatch'] > 0 and clock.acc['sched'] > 0
    assert clock.acc['drain'] == 0 and clock.acc['readback'] == 0
    with clock.step(8):
        assert set(clock.acc.values()) == {0.0}
    # the wait for work lies between steps: no step's share holds it,
    # a step's start does not lose it, and it is taken once
    with clock.stage('wait'):
        time.sleep(0.001)
        open_s = clock.waited_s()
        assert 0.0 < open_s and clock.wait_state[1] > 0
        # an open wait is nobody's yet
        assert clock.take_wait('record') == 0.0
    assert clock.wait_state[1] == 0.0
    with clock.step(9):
        assert set(clock.acc.values()) == {0.0}
    closed_s = clock.waited_s()
    assert closed_s >= max(open_s, 0.001)
    # each reader takes it once, whichever asks first
    assert clock.take_wait('record') == closed_s
    assert clock.take_wait('record') == 0.0
    assert clock.take_wait('launch') == closed_s
    assert clock.take_wait('launch') == 0.0
    assert clock.waited_s() == closed_s
    with pytest.raises(KeyError):
        with clock.stage('lunch'):
            pass
