"""Zero-downtime serving, engine + server layer: resumable generation,
bounded admission, deadlines, client-disconnect cancellation, and the
graceful-drain endpoint (docs/robustness.md "Zero-downtime serving").

The determinism gate: a request resumed from its first k delivered
tokens must continue BIT-IDENTICALLY to the uninterrupted greedy run —
resume rides the same recompute path as paged preemption, so prompt +
delivered prefills and decoding picks up at the boundary. The hygiene
gates: cancelled/expired requests free their slot AND their pages
(page conservation at idle), and abandoned queued requests stop
occupying admission-control queue slots.
"""
import asyncio
import json
import time

import pytest

pytestmark = pytest.mark.jax

import jax  # noqa: E402

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()


@pytest.fixture(scope='module')
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _ecfg(**kw):
    base = dict(n_slots=2, max_seq_len=64, prefill_buckets=(8, 16, 32))
    base.update(kw)
    return engine_lib.EngineConfig(**base)


def _paged_ecfg(**kw):
    base = dict(n_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
                prefill_chunk=16, paged=True, page_size=8)
    base.update(kw)
    return engine_lib.EngineConfig(**base)


# ---------- resumable generation ------------------------------------------
def test_resume_tokens_bit_identical_to_unkilled_run(params):
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
    [oracle] = eng.generate([[5, 17, 101, 7]], max_new_tokens=12)
    full = oracle.output_tokens
    for cut in (1, 5, 11):
        eng2 = engine_lib.InferenceEngine(CFG, params, _ecfg())
        req = eng2.submit([5, 17, 101, 7], max_new_tokens=12,
                          resume_tokens=full[:cut])
        eng2.run_until_idle()
        assert req.resumed_from == cut
        assert req.output_tokens == full, (
            f'resume at {cut} diverged from the uninterrupted run')
        assert req.finish_reason == 'max_tokens'


def test_resume_bit_identical_paged_with_prefix_cache(params):
    eng = engine_lib.InferenceEngine(
        CFG, params, _paged_ecfg(prefix_cache=True))
    [oracle] = eng.generate([list(range(2, 20))], max_new_tokens=10)
    full = oracle.output_tokens
    # Resume on the SAME engine: the finished run donated its pages, so
    # the resume's prompt+delivered prefill re-matches the donated
    # prefix (the near-free re-prefill the LB failover relies on).
    req = eng.submit(list(range(2, 20)), max_new_tokens=10,
                     resume_tokens=full[:6])
    eng.run_until_idle()
    assert req.output_tokens == full
    assert req.cached_tokens > 0, 'resume should hit the prefix cache'


def test_resume_with_spent_budget_finishes_without_queueing(params):
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
    req = eng.submit([1, 2], max_new_tokens=3, resume_tokens=[7, 8, 9])
    assert req.done and req.finish_reason == 'max_tokens'
    assert eng.metrics()['num_waiting'] == 0


def test_resume_counts_against_capacity(params):
    eng = engine_lib.InferenceEngine(
        CFG, params, _ecfg(max_seq_len=16, prefill_buckets=(8, 16)))
    with pytest.raises(ValueError, match='prompt\\+resume'):
        eng.submit([1] * 10, resume_tokens=[2] * 10)


# ---------- admission control ---------------------------------------------
def test_admission_queue_request_bound(params):
    eng = engine_lib.InferenceEngine(
        CFG, params, _ecfg(n_slots=1, max_queue_requests=2))
    eng.submit([1, 2], max_new_tokens=30)
    eng.submit([1, 2], max_new_tokens=30)
    with pytest.raises(engine_lib.AdmissionError) as ei:
        eng.submit([1, 2], max_new_tokens=30)
    assert ei.value.retry_after_s > 0
    # AdmissionError must stay a ValueError: the multihost lockstep
    # tick's uniform-rejection rule depends on it.
    assert isinstance(ei.value, ValueError)
    eng.run_until_idle()


def test_admission_queue_token_bound(params):
    eng = engine_lib.InferenceEngine(
        CFG, params, _ecfg(n_slots=1, max_queue_tokens=8))
    eng.submit([1] * 6, max_new_tokens=5)
    with pytest.raises(engine_lib.AdmissionError):
        eng.submit([1] * 6, max_new_tokens=5)
    eng.run_until_idle()


def test_abandoned_queued_request_dropped_before_admission(params):
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg(n_slots=1))
    r1 = eng.submit([1, 2], max_new_tokens=40)
    while eng.metrics()['num_waiting'] > 0:
        eng.step()   # r1 reaches the slot
    r2 = eng.submit([3, 4], max_new_tokens=5)
    r3 = eng.submit([5, 6], max_new_tokens=5)
    assert eng.cancel(r2)
    eng.step()
    # r2 left the queue WITHOUT occupying the slot; r3 is unaffected.
    assert r2.done and r2.finish_reason == 'cancelled'
    assert not r2.output_tokens
    eng.run_until_idle()
    assert r1.done and r3.done and r3.finish_reason == 'max_tokens'
    m = eng.metrics()
    assert m['requests_abandoned'] == 1
    assert m['requests_cancelled'] == 0
    assert eng.cancel(r2) is False   # already finished


# ---------- deadlines ------------------------------------------------------
def test_deadline_expired_in_queue_cancelled(params):
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg(n_slots=1))
    eng.submit([1, 2], max_new_tokens=30)
    late = eng.submit([3, 4], max_new_tokens=30,
                      deadline=time.time() - 1)
    eng.step()
    assert late.done and late.finish_reason == 'deadline'
    assert not late.output_tokens
    eng.run_until_idle()
    assert eng.metrics()['requests_expired'] == 1


def test_deadline_cancels_mid_decode_and_frees_pages(params):
    eng = engine_lib.InferenceEngine(CFG, params, _paged_ecfg())
    al = eng.allocator
    # Compile off the clock — same prefill bucket as the real prompt.
    eng.generate([list(range(30, 40))], max_new_tokens=2)
    req = eng.submit(list(range(2, 12)), max_new_tokens=40,
                     deadline=time.time() + 2.0)
    for _ in range(4):
        eng.step()   # prefill + a few decode steps, well pre-deadline
    assert not req.done and req.output_tokens
    time.sleep(2.1)  # let the deadline lapse mid-decode
    deadline = time.time() + 30
    while not req.done and time.time() < deadline:
        eng.step()
    assert req.finish_reason == 'deadline'
    assert req.output_tokens, 'should have decoded until the cutoff'
    assert len(req.output_tokens) < 40
    eng.run_until_idle()
    # Page conservation: the expired request's pages all returned.
    assert al.free_pages == al.n_pages - 1
    assert eng.metrics()['requests_expired'] == 1


def test_cancel_active_frees_slot_and_pages(params):
    eng = engine_lib.InferenceEngine(CFG, params, _paged_ecfg())
    al = eng.allocator
    req = eng.submit(list(range(2, 12)), max_new_tokens=500)
    for _ in range(5):
        eng.step()
    assert not req.done
    assert eng.cancel(req)
    eng.step()
    assert req.done and req.finish_reason == 'cancelled'
    eng.run_until_idle()
    assert al.free_pages == al.n_pages - 1
    assert eng.metrics()['requests_cancelled'] == 1
    # The slot is genuinely reusable.
    [after] = eng.generate([[9, 9]], max_new_tokens=3)
    assert len(after.output_tokens) == 3


def test_cancel_donates_clean_pages_to_prefix_cache(params):
    eng = engine_lib.InferenceEngine(
        CFG, params, _paged_ecfg(prefix_cache=True))
    prompt = list(range(2, 20))   # > 2 full pages at page_size=8
    req = eng.submit(prompt, max_new_tokens=500)
    for _ in range(5):
        eng.step()
    eng.cancel(req)
    eng.run_until_idle()
    assert eng.prefix.cached_pages > 0, (
        'cancelled request must donate its clean pages')
    again = eng.submit(prompt, max_new_tokens=3)
    eng.run_until_idle()
    assert again.cached_tokens > 0


def test_wallclock_cancel_disabled_ignores_deadline_and_cancel(params):
    eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
    eng.set_wallclock_cancel(False)   # the lockstep driver's pin
    req = eng.submit([1, 2], max_new_tokens=4,
                     deadline=time.time() - 1)
    eng.cancel(req)
    eng.run_until_idle()
    assert req.finish_reason == 'max_tokens'
    assert len(req.output_tokens) == 4


# ---------- server layer: drain + resume + shed ----------------------------
def _server(engine):
    from skypilot_tpu.infer import server as server_lib
    srv = server_lib.InferenceServer(engine)
    srv._thread.start()
    return srv


def test_server_drain_endpoint_completes_inflight_then_reports(params):
    """/drain long-polls (event-driven — no poll loop) until the last
    in-flight stream finishes; meanwhile new work is refused with 503
    and /health reports draining so the serve layer pulls the replica."""
    from aiohttp.test_utils import TestClient, TestServer

    async def flow():
        eng = engine_lib.InferenceEngine(CFG, params,
                                         _ecfg(max_seq_len=128))
        srv = _server(eng)
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            async def stream():
                r = await client.post(
                    '/generate', json={'tokens': [7, 7], 'stream': True,
                                       'max_new_tokens': 100})
                toks, done = [], False
                async for chunk in r.content:
                    if chunk.strip():
                        ln = json.loads(chunk)
                        done = done or bool(ln.get('done'))
                        toks.extend(ln.get('tokens', []))
                return toks, done

            task = asyncio.create_task(stream())
            await asyncio.sleep(0.1)   # let the stream start
            drain = asyncio.create_task(
                client.post('/drain', json={'deadline_s': 30}))
            await asyncio.sleep(0.05)
            r = await client.post('/generate',
                                  json={'tokens': [1],
                                        'max_new_tokens': 2})
            assert r.status == 503
            assert r.headers.get('Retry-After')
            h = await client.get('/health')
            assert h.status == 503
            assert (await h.json())['status'] == 'draining'
            toks, done = await task
            assert done and len(toks) == 100, 'drain truncated a stream'
            report = await (await drain).json()
            assert report['status'] == 'drained'
            assert report['inflight'] == 0
            m = await (await client.get('/metrics')).json()
            assert m['draining'] is True
            assert m['drain_duration_s'] is not None
            # The replica names its own device and compiled programs.
            assert m['device'] == {'platform': 'cpu',
                                   'device_kind': 'cpu',
                                   'count': len(jax.devices())}
            assert m['compiled_programs']['decode'] == 1
            assert min(m['compiled_programs'].values()) >= 0
        finally:
            await client.close()
            srv._stop.set()

    asyncio.run(flow())


def test_server_resume_from_streams_only_new_tokens(params):
    """The resume wire protocol: a stream re-issued with resume_from
    emits exactly the tokens after the boundary — the LB splices them
    onto the delivered prefix with no dedupe gymnastics needed."""
    from aiohttp.test_utils import TestClient, TestServer

    async def flow():
        eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
        srv = _server(eng)
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            r = await client.post('/generate',
                                  json={'tokens': [1, 2, 3],
                                        'max_new_tokens': 10})
            full = (await r.json())['tokens']
            r = await client.post(
                '/generate', json={'tokens': [1, 2, 3],
                                   'max_new_tokens': 10, 'stream': True,
                                   'resume_from': full[:4]})
            lines = []
            async for chunk in r.content:
                if chunk.strip():
                    lines.append(json.loads(chunk))
            assert lines[-1]['done']
            streamed = [t for ln in lines[:-1]
                        for t in ln.get('tokens', [])]
            assert streamed == full[4:]
        finally:
            await client.close()
            srv._stop.set()

    asyncio.run(flow())


def test_server_deadline_header_rejects_spent_budget(params):
    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.utils import common as common_lib

    async def flow():
        eng = engine_lib.InferenceEngine(CFG, params, _ecfg())
        srv = _server(eng)
        client = TestClient(TestServer(srv.make_app()))
        await client.start_server()
        try:
            r = await client.post(
                '/generate', json={'tokens': [1], 'max_new_tokens': 2},
                headers={common_lib.DEADLINE_HEADER: '0'})
            assert r.status == 504
            r = await client.post(
                '/generate', json={'tokens': [1], 'max_new_tokens': 2},
                headers={common_lib.DEADLINE_HEADER: 'bogus'})
            assert r.status == 400
            # A sane budget sails through.
            r = await client.post(
                '/generate', json={'tokens': [1], 'max_new_tokens': 2},
                headers={common_lib.DEADLINE_HEADER: '30'})
            assert r.status == 200
        finally:
            await client.close()
            srv._stop.set()

    asyncio.run(flow())
