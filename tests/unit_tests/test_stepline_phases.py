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
step records hold. A CPU run shows order and counts, never a speed.
"""
import asyncio
import json

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
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    records = [st for st in eng.stepline_snapshot()['steps']
               if st['idx'] >= before]
    assert len(records) == 3
    eng.run_until_idle()

    rows = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)),
                             device_only=False)
    mine = [r for r in rows if r[2].startswith('engine.')]
    assert {r[0] for r in mine} == {'/host:CPU'}
    assert len({r[1] for r in mine}) == 1       # the engine's thread
    names = {r[2] for r in mine}
    assert names >= {'engine.step', 'engine.dispatch', 'engine.readback',
                     'engine.drain', 'engine.sched'}
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
    # every stage lies inside one of the steps
    stages = [r for r in mine if r[2] != 'engine.step']
    assert all(any(s[3] <= r[3] and r[3] + r[4] <= s[3] + s[4]
                   for s in steps) for r in stages)
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
    with pytest.raises(KeyError):
        with clock.stage('lunch'):
            pass
