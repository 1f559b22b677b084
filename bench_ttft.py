"""Serve-path TTFT benchmark on the local chip (north-star #2).

Measures time-to-first-token as a client experiences it THROUGH the
serve stack: a real inference server (continuous-batching engine,
infer/engine.py, optionally tensor-parallel) on the local accelerator,
registered as a ready replica in the serve state DB, fronted by the real
serve load balancer (serve/load_balancer.py). TTFT is clocked
client-side per request: send → first streamed byte back through the LB
(BASELINE.md: "sky serve p50 TTFT").

Protocol: one cold request (captures the compile tail separately), a
warmup pass, then a CONCURRENCY SWEEP — the same request mix at 1, 4,
and 16 concurrent in-flight requests — reporting warm p50/p90/p99 and
achieved throughput per level (the throughput-vs-TTFT curve of a
continuous-batching engine). Cold compile never pollutes the warm
percentiles.

Usage:
  python bench_ttft.py [--model 1b] [--requests-per-level 80]
                       [--concurrency 1 4 16] [--tp 1]
                       [--output TTFT_r03.json]
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import multiprocessing
import os
import random
import re
import statistics
import subprocess
import sys
import time
import urllib.request


def _get(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _wait_http(url: str, deadline_s: float) -> None:
    deadline = time.time() + deadline_s
    last = None
    while time.time() < deadline:
        try:
            _get(url, timeout=2.0)
            return
        except Exception as e:  # noqa: BLE001 — booting
            last = e
            time.sleep(0.5)
    raise RuntimeError(f'{url} never became healthy: {last}')


def _replica_device(base_url: str) -> str:
    """``device_kind`` as the replica's own /metrics reports it: the
    process that ran the work names the device, never this parent
    (which must stay off jax — it would take the chip, or land on the
    CPU and label a chip run 'cpu')."""
    return _get(f'{base_url}/metrics')['device']['device_kind']


def _refuse_unless_devices(n_servers: int, sweep: str) -> None:
    """A chip belongs to one process: a sweep that boots ``n_servers``
    engine processes hangs or fails on an accelerator host with fewer
    devices. Asked of a child, so this parent never holds a chip."""
    out = subprocess.run(
        [sys.executable, '-c',
         'import json; from skypilot_tpu.utils import jax_env; '
         'print(json.dumps(jax_env.device_summary()))'],
        capture_output=True, text=True, check=True).stdout
    device = json.loads(out.strip().splitlines()[-1])
    platform, count = device['platform'], device['count']
    if platform != 'cpu' and count < n_servers:
        raise SystemExit(
            f'--sweep {sweep} boots {n_servers} engine processes but '
            f'this host has {count} {platform} device(s), and a chip '
            f'belongs to one process at a time. Run it with '
            f'JAX_PLATFORMS=cpu (counts only) or on a host with a chip '
            f'per server.')


def _run_lb(service: str, port: int, policy: str = 'least_load') -> None:
    from skypilot_tpu.serve import load_balancer
    load_balancer.run_load_balancer(service, policy, '127.0.0.1',
                                    port)


def _streamed_request(url: str, payload, max_new_tokens: int = 8,
                      timeout: float = 300.0) -> tuple:
    """One streamed /generate through the LB. ``payload`` is a prompt
    string or a full request dict (the shared-prefix sweep sends token
    ids directly). Returns ``(ttft_s, itl_samples_s, queue_wait_s)``:
    send→first-byte seconds (true client-observed TTFT), one
    inter-token latency sample per token after the first — the arrival
    gap of each flushed line, amortized over the tokens it carried
    (the engine may batch several tokens into one flush under load) —
    and the done-line's engine-stamped queue wait (submit → first
    chunk dispatch), which decomposes TTFT into scheduling vs prefill
    compute."""
    if not isinstance(payload, dict):
        payload = {'prompt': payload}
    payload = {'max_new_tokens': max_new_tokens, 'stream': True,
               **payload}
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json'})
    t0 = time.perf_counter()
    itls = []
    queue_wait = None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        first = r.read(1)          # first streamed byte = first token
        t_prev = time.perf_counter()
        ttft = t_prev - t0
        if not first:
            raise RuntimeError('empty stream')
        r.readline()               # rest of the first line
        for line in iter(r.readline, b''):
            now = time.perf_counter()
            if not line.strip():
                continue
            try:
                obj = json.loads(line)
            except ValueError:     # truncated tail line
                continue
            tokens = obj.get('tokens') or []
            if tokens:
                itls.extend([(now - t_prev) / len(tokens)] * len(tokens))
                t_prev = now
            if obj.get('done'):
                queue_wait = obj.get('queue_wait_s')
    return ttft, itls, queue_wait


def _pct(sorted_vals, p: float):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(len(sorted_vals) * p))
    return round(sorted_vals[i], 5)


def _sweep_level(gen_url: str, concurrency: int, n_requests: int,
                 long_prompt_tokens: int = 0,
                 payload_for=None) -> dict:
    """One concurrency level. With long_prompt_tokens, every 8th
    request carries a long prompt (the mixed-length workload a paged
    cache exists for); long/short TTFTs are reported separately so the
    long lane cannot hide in the p50. ``payload_for`` overrides the
    request mix entirely (the shared-prefix sweep's token payloads)."""
    def prompt_for(i: int) -> str:
        if long_prompt_tokens and i % 8 == 7:
            filler = f'ctx{i} ' * (long_prompt_tokens // 5)
            return filler + ' summarize.'
        return f'request {i} hello world'

    make = payload_for or prompt_for
    results = []   # (is_long, ttft)
    itl_samples = []
    queue_waits = []
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
        futs = {pool.submit(_streamed_request, gen_url, make(i),
                            timeout=900): i
                for i in range(n_requests)}
        for f in concurrent.futures.as_completed(futs):
            i = futs[f]
            ttft, itls, qwait = f.result()
            results.append((bool(long_prompt_tokens and i % 8 == 7),
                            ttft))
            itl_samples.extend(itls)
            if qwait is not None:
                queue_waits.append(qwait)
    wall = time.perf_counter() - t0
    ttfts = sorted(t for _, t in results)
    itl_samples.sort()
    queue_waits.sort()
    out = {
        'concurrency': concurrency,
        'samples': len(ttfts),
        'ttft_p50_s': _pct(ttfts, 0.50),
        'ttft_p90_s': _pct(ttfts, 0.90),
        'ttft_p99_s': _pct(ttfts, 0.99),
        'ttft_mean_s': round(statistics.fmean(ttfts), 5),
        # TTFT decomposition: the engine-stamped queue wait (submit →
        # first chunk dispatch). ttft - queue_wait ≈ prefill compute +
        # transport, so a scheduling win is attributable apart from
        # prefill speed.
        'queue_wait_p50_ms': (round(_pct(queue_waits, 0.50) * 1e3, 3)
                              if queue_waits else None),
        'queue_wait_p99_ms': (round(_pct(queue_waits, 0.99) * 1e3, 3)
                              if queue_waits else None),
        # Inter-token latency: the steady-state decode cadence a
        # streaming client sees — the number the overlapped decode
        # pipeline moves (TTFT is dominated by prefill+queueing).
        'itl_p50_ms': (round(_pct(itl_samples, 0.50) * 1e3, 3)
                       if itl_samples else None),
        'itl_p99_ms': (round(_pct(itl_samples, 0.99) * 1e3, 3)
                       if itl_samples else None),
        'itl_samples': len(itl_samples),
        'throughput_rps': round(n_requests / wall, 2),
    }
    longs = sorted(t for is_long, t in results if is_long)
    if longs:
        shorts = sorted(t for is_long, t in results if not is_long)
        out['short_ttft_p50_s'] = _pct(shorts, 0.50)
        out['long_ttft_p50_s'] = _pct(longs, 0.50)
        out['long_samples'] = len(longs)
    return out


def _block(seed: int, n: int) -> list:
    """Deterministic token block, ids in [2, 201] (inside every model's
    vocab). The seed is mixed through a PRNG so any two distinct seeds
    give distinct leading blocks — a linear formula would collide for
    seeds congruent mod the id range, silently serving the 'cold'
    all-miss baseline from the prefix cache."""
    rng = random.Random(seed)
    return [2 + rng.randrange(200) for _ in range(n)]


def _shared_prefix_level(gen_url: str, metrics_url: str,
                         concurrency: int, n_requests: int,
                         sys_tokens: int, uniq_base: int) -> dict:
    """One concurrency level of the shared-system-prompt sweep: a COLD
    pass (every request a unique same-length system block — all prefix
    misses, the no-reuse baseline) then a SHARED pass (one system
    block, unique tails — the production shape the prefix cache
    exists for), with the replica's prefix counters sampled around the
    shared pass so the hit rate and tokens saved are windowed to it.
    The first shared request is issued alone (it seeds the radix tree;
    its TTFT is a miss by construction and is excluded)."""
    tail = 16

    def cold_payload(i: int) -> dict:
        return {'tokens': _block(uniq_base + 7 + i, sys_tokens)
                + _block(uniq_base + 100003 + i, tail)}

    shared_sys = _block(uniq_base, sys_tokens)

    def shared_payload(i: int) -> dict:
        return {'tokens': shared_sys + _block(uniq_base + 200003 + i,
                                              tail)}

    cold = _sweep_level(gen_url, concurrency, n_requests,
                        payload_for=cold_payload)
    _streamed_request(gen_url, shared_payload(0))   # seed the tree
    m0 = _get(metrics_url)
    shared = _sweep_level(gen_url, concurrency, n_requests,
                          payload_for=lambda i: shared_payload(i + 1))
    m1 = _get(metrics_url)
    lookups = ((m1['prefix_hits'] + m1['prefix_misses'])
               - (m0['prefix_hits'] + m0['prefix_misses']))
    hit_rate = ((m1['prefix_hits'] - m0['prefix_hits']) / lookups
                if lookups else 0.0)
    out = {
        'concurrency': concurrency,
        'samples': cold['samples'] + shared['samples'],
        'system_prompt_tokens': sys_tokens,
        'cold': cold,
        'shared': shared,
        'prefix_hit_rate': round(hit_rate, 4),
        'tokens_prefill_saved': (m1['prefix_tokens_saved']
                                 - m0['prefix_tokens_saved']),
    }
    if shared['ttft_p50_s'] and cold['ttft_p50_s']:
        out['ttft_improvement_x'] = round(
            cold['ttft_p50_s'] / shared['ttft_p50_s'], 2)
    if shared['itl_p50_ms'] and cold['itl_p50_ms']:
        # >1 means the shared pass DECODES slower — the regression
        # guard (prefix reuse must not tax steady-state decode).
        out['itl_ratio_shared_over_cold'] = round(
            shared['itl_p50_ms'] / cold['itl_p50_ms'], 3)
    return out


def _tenant_level(gen_url: str, lb_metrics_url: str, level: int,
                  seed: int, duration_s: float,
                  trace_path: str = None) -> dict:
    """One level of the multi-tenant fairness sweep: replay a seeded
    10:1 aggressor/victim trace (or ``trace_path``) through the LB
    with the X-SkyTpu-Tenant header, and report per-tenant
    TTFT/ITL/shed-rate plus the LB's own per-tenant view. ``level``
    scales the offered rate (victim ≈ level rps, aggressor 10x)."""
    from tests.load_tests import loadgen
    if trace_path:
        events, _ = loadgen.load_trace(trace_path)
    else:
        events = loadgen.synthesize(seed, {
            'victim': {'rps': float(level), 'burst': 2,
                       'prompt_mean': 16, 'prompt_max': 48,
                       'max_new': 8},
            'aggressor': {'rps': 10.0 * level, 'burst': 10,
                          'prompt_mean': 24, 'prompt_max': 96,
                          'max_new': 8},
        }, duration_s=duration_s)
    m0 = _get(lb_metrics_url)
    records = loadgen.replay_over_http(events, gen_url)
    m1 = _get(lb_metrics_url)
    tenants = loadgen.tenant_summary(records)
    shed_delta = (m1.get('requests_shed', 0)
                  - m0.get('requests_shed', 0))

    def lb_tenant_delta(key: str) -> dict:
        # The LB's per-tenant counters are cumulative: delta them so
        # each level reports ITS traffic, not every prior level's.
        return {t: (row.get(key, 0)
                    - ((m0.get('tenants') or {}).get(t) or {})
                    .get(key, 0))
                for t, row in (m1.get('tenants') or {}).items()}
    return {
        'concurrency': level,
        'samples': len(records),
        'trace_events': len(events),
        'tenants': tenants,
        'lb_requests_shed': shed_delta,
        'lb_tenants_requests': lb_tenant_delta('requests_total'),
        'lb_tenants_shed': lb_tenant_delta('requests_shed'),
        'engine_queue_depth_after': m1.get('engine_queue_depth'),
    }


def _collect_tokens(gen_url: str, payload: dict,
                    timeout: float = 300.0) -> list:
    """One streamed request, returning the full token id list — the
    bench-side bit-identity probe for the speculative sweep."""
    payload = {'stream': True, **payload}
    req = urllib.request.Request(
        gen_url, data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json'})
    tokens = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in iter(r.readline, b''):
            if not line.strip():
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            tokens.extend(obj.get('tokens') or [])
    return tokens


def _speculative_level(gen_url: str, metrics_url: str,
                       concurrency: int, n_requests: int,
                       spec_k: int, max_new: int = 32,
                       uniq_base: int = 0) -> dict:
    """One concurrency level of the speculative sweep: the SAME
    template-heavy workload with per-request speculation off (plain
    decode steps — the honest baseline: the engine dispatches the
    decode program when nobody drafts) vs on, with the replica's spec
    counters sampled around the on pass so accepted_len_mean /
    spec_accept_rate / tokens_per_step are windowed to it. Prompts are
    a shared template block plus a short unique tail — the
    template/repetition shape prompt-lookup drafting exists for."""
    template = _block(9973, 12) * 4

    def payload(i: int, spec: bool) -> dict:
        return {'tokens': template + _block(uniq_base + 31 + i, 6),
                'max_new_tokens': max_new, 'spec': spec}

    off = _sweep_level(gen_url, concurrency, n_requests,
                       payload_for=lambda i: payload(i, False))
    m0 = _get(metrics_url)
    on = _sweep_level(
        gen_url, concurrency, n_requests,
        payload_for=lambda i: payload(i + n_requests, True))
    m1 = _get(metrics_url)

    def delta(key: str) -> float:
        return (m1.get(key) or 0) - (m0.get(key) or 0)

    lanes = delta('spec_slot_steps')
    drafted = delta('spec_drafted_tokens')
    steps = delta('decode_steps')
    # Greedy outputs must not drift: same payload through both lanes.
    probe = payload(10**9, False)
    identical = (_collect_tokens(gen_url, probe)
                 == _collect_tokens(gen_url, {**probe, 'spec': True}))
    out = {
        'concurrency': concurrency,
        'samples': off['samples'] + on['samples'],
        'spec_k': spec_k,
        'spec_off': off,
        'spec_on': on,
        'accepted_len_mean': (round(
            delta('spec_emitted_tokens') / lanes, 4) if lanes
            else None),
        'spec_accept_rate': (round(
            delta('spec_accepted_tokens') / drafted, 4) if drafted
            else None),
        'tokens_per_step': (round(delta('decode_tokens') / steps, 4)
                            if steps else None),
        'bit_identical': identical,
    }
    if on['itl_p50_ms'] and off['itl_p50_ms']:
        # >1 = speculation CUT inter-token latency by that factor.
        out['itl_improvement_x'] = round(
            off['itl_p50_ms'] / on['itl_p50_ms'], 3)
    if on['ttft_p50_s'] and off['ttft_p50_s']:
        out['ttft_ratio_on_over_off'] = round(
            on['ttft_p50_s'] / off['ttft_p50_s'], 3)
    return out


def _chaos_request(gen_url: str, payload, max_new_tokens: int = 32,
                   timeout: float = 300.0) -> dict:
    """One streamed request under chaos: wall duration, the done-line's
    LB-stamped resume count, and whether a complete stream arrived."""
    if not isinstance(payload, dict):
        payload = {'prompt': payload}
    payload = {'max_new_tokens': max_new_tokens, 'stream': True,
               **payload}
    req = urllib.request.Request(
        gen_url, data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json'})
    t0 = time.perf_counter()
    done = None
    clean = True
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            for line in iter(r.readline, b''):
                if not line.strip():
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if 'error' in obj:
                    clean = False
                if obj.get('done'):
                    done = obj
    except Exception:  # noqa: BLE001 — a truncated stream = incomplete
        clean = False
    return {'duration_s': time.perf_counter() - t0,
            'resumed': int((done or {}).get('resumed', 0)),
            'completed': bool(done) and clean}


def _chaos_resume_level(gen_url: str, concurrency: int,
                        n_requests: int,
                        max_new_tokens: int = 32) -> dict:
    """One concurrency level of the chaos-resume sweep: completed-
    stream rate, resume count, and the p99 total latency of resumed vs
    untouched streams (the price of a mid-stream failover)."""
    with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
        futs = [pool.submit(_chaos_request, gen_url,
                            f'chaos request {i}', max_new_tokens)
                for i in range(n_requests)]
        results = [f.result()
                   for f in concurrent.futures.as_completed(futs)]
    clean = sorted(r['duration_s'] for r in results
                   if r['completed'] and not r['resumed'])
    resumed = sorted(r['duration_s'] for r in results
                     if r['completed'] and r['resumed'])
    completed = sum(r['completed'] for r in results)
    out = {
        'concurrency': concurrency,
        'issued': n_requests,
        'completed': completed,
        'completed_rate': round(completed / n_requests, 4),
        'resumes': sum(r['resumed'] for r in results),
        'resumed_streams': len(resumed),
        'clean_total_p99_s': _pct(clean, 0.99),
        'resumed_total_p99_s': _pct(resumed, 0.99),
    }
    return out


def _chunked_build_engine(config, params, *, fused: bool, slots: int,
                          max_seq_len: int, page_size: int,
                          kv_dtype: str = 'bfloat16',
                          n_pages=None, prefix_cache: bool = False):
    from skypilot_tpu.infer import engine as engine_lib
    return engine_lib.InferenceEngine(
        config, params,
        engine_lib.EngineConfig(
            n_slots=slots, max_seq_len=max_seq_len,
            prefill_buckets=(64, 128), prefill_chunk=128,
            paged=True, page_size=page_size, n_pages=n_pages,
            prefix_cache=prefix_cache, kv_dtype=kv_dtype,
            fused_prefill=fused))


def _chunked_warm(eng, aggr_prompt: list) -> None:
    """Compile every program off the clock: standalone prefill (idle
    admission), then BOTH chunk buckets through the mid-decode path
    the measurement exercises (fused engines compile their mixed
    programs here, unfused their standalone ladder)."""
    a = eng.submit([9] * 16, max_new_tokens=120)
    while not a.output_tokens:
        eng.step()
    for warm_prompt in ([8] * 8, [9] * len(aggr_prompt)):
        r = eng.submit(warm_prompt, max_new_tokens=4)
        while not r.done:
            eng.step()
    eng.cancel(a)
    eng.run_until_idle()


def _chunked_victim_run(engine, conc: int, aggr_prompt: list,
                        repeats: int) -> dict:
    """Victims decode continuously; a long-prompt aggressor arrives
    mid-decode-batch ``repeats`` times. Records every victim
    inter-token gap from each aggressor's submission until its first
    token — the window a standalone prefill dispatch stalls — plus the
    aggressor's TTFT. Engine-level (in-process step loop): the stall
    being measured is a device-dispatch property, not an HTTP one."""
    victims = [engine.submit([3 + i] * 8, max_new_tokens=400)
               for i in range(conc)]
    while any(len(v.output_tokens) < 4 for v in victims):
        engine.step()
    itls, ttfts = [], []
    seen = {i: len(v.output_tokens) for i, v in enumerate(victims)}
    last = {i: None for i in range(len(victims))}
    for r in range(repeats):
        aggr = engine.submit(aggr_prompt, max_new_tokens=4)
        t0 = time.perf_counter()
        for i in range(len(victims)):
            last[i] = None          # fresh window per aggressor
        while not aggr.done:
            engine.step()
            now = time.perf_counter()
            for i, v in enumerate(victims):
                n = len(v.output_tokens)
                if n > seen[i]:
                    if last[i] is not None:
                        gap = (now - last[i]) / (n - seen[i])
                        itls.extend([gap] * (n - seen[i]))
                    last[i] = now
                    seen[i] = n
            if aggr.output_tokens and len(ttfts) == r:
                ttfts.append(time.perf_counter() - t0)
    for v in victims:
        engine.cancel(v)
    engine.run_until_idle()
    m = engine.metrics()
    # Recorder-derived step-time decomposition (the flight recorder's
    # ring over this run): where a step's wall clock actually went —
    # dispatch vs drain vs readback vs host shares.
    breakdown = engine.stepline_summary()
    breakdown.pop('enabled', None)
    itls.sort()
    ttfts.sort()
    return {
        'victim_itl_p50_ms': (round(_pct(itls, 0.50) * 1e3, 3)
                              if itls else None),
        'victim_itl_p99_ms': (round(_pct(itls, 0.99) * 1e3, 3)
                              if itls else None),
        'aggressor_ttft_p50_s': _pct(ttfts, 0.50),
        'itl_samples': len(itls),
        'fused_steps': m['fused_steps'],
        'decode_stall_steps': m['decode_stall_steps'],
        'prefill_tokens_per_step': m['prefill_tokens_per_step'],
        'step_time_breakdown': breakdown,
    }


def _chunked_kv_axis(config, params, *, slots: int, max_seq_len: int,
                     page_size: int) -> dict:
    """The int8 lever at a FIXED HBM byte budget: how many pages each
    kv_dtype keeps resident, and what that extra residency buys the
    prefix cache (hit-rate delta on a shared-prefix workload sized to
    overflow the bf16 pool)."""
    # Bytes one (k+v) page costs across all layers: values at their
    # dtype plus, for int8, one fp32 scale per row per head — the
    # closed form PagedKVCache.page_bytes reports.
    engines = {
        dt: (2 * config.n_layers * config.n_kv_heads * page_size
             * (config.head_dim * (1 if dt == 'int8' else 2)
                + (4 if dt == 'int8' else 0)))
        for dt in ('bfloat16', 'int8')}
    budget = 48 * engines['bfloat16']   # 48 bf16 pages of HBM
    axis = {'kv_page_bytes_bf16': engines['bfloat16'],
            'kv_page_bytes_int8': engines['int8'],
            'hbm_budget_bytes': budget}
    # 30 distinct 2-page cohort prefixes (60 cached pages when all
    # stay resident): they FIT the int8 pool at this budget (~76
    # pages at head_dim 16, more at production widths) and OVERFLOW
    # the 48-page bf16 one, so wave 2's hit rate is precisely what
    # the denser pages bought.
    n_cohorts = 30
    cohorts = [[(7 + c) % 250] * (2 * page_size)
               for c in range(n_cohorts)]
    for dt in ('bfloat16', 'int8'):
        n_pages = budget // engines[dt] + 1   # +1: the sink page
        eng = _chunked_build_engine(
            config, params, fused=True, slots=slots,
            max_seq_len=max_seq_len, page_size=page_size, kv_dtype=dt,
            n_pages=int(n_pages), prefix_cache=True)
        for wave in range(2):
            for c, prefix in enumerate(cohorts):
                eng.generate(
                    [prefix + [11 + c + 100 * wave] * 8],
                    max_new_tokens=4)
        m = eng.metrics()
        key = 'int8' if dt == 'int8' else 'bf16'
        axis[f'resident_pages_{key}'] = int(n_pages) - 1
        axis[f'prefix_hit_rate_{key}'] = m['prefix_hit_rate']
        axis[f'prefix_cached_pages_{key}'] = m['prefix_cached_pages']
        axis[f'prefix_evictions_{key}'] = m['prefix_evictions']
    axis['resident_page_ratio'] = round(
        axis['resident_pages_int8'] / axis['resident_pages_bf16'], 4)
    axis['prefix_hit_rate_delta'] = round(
        axis['prefix_hit_rate_int8'] - axis['prefix_hit_rate_bf16'], 4)
    return axis


def _run_chunked_sweep(args) -> dict:
    """--sweep chunked: in-process engines (no HTTP hop — the stall
    under test is the standalone prefill dispatch between decode
    dispatches, a device-step property), fused vs unfused at each
    concurrency, plus the kv-dtype residency axis."""
    import jax

    import dataclasses

    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import llama
    config = server_lib.MODELS[args.model]()
    if config.max_seq_len < args.max_seq_len:
        # The aggressor prompt must span several chunks; widening the
        # rope/cache horizon of a small preset is free.
        config = dataclasses.replace(config,
                                     max_seq_len=args.max_seq_len)
    params = llama.init_params(config, jax.random.PRNGKey(0))
    max_seq_len = min(args.max_seq_len, config.max_seq_len)
    page_size = min(args.page_size, 64)
    aggr_prompt = [5] * min(6 * 128, max_seq_len - 144)  # 6 chunks
    repeats = max(4, args.requests_per_level // 10)
    sweep = []
    for conc in args.concurrency:
        conc = min(conc, args.slots - 1)   # one slot for the aggressor
        level = {'concurrency': conc, 'aggressor_prompt_tokens':
                 len(aggr_prompt), 'repeats': repeats}
        for fused in (False, True):
            eng = _chunked_build_engine(
                config, params, fused=fused, slots=args.slots,
                max_seq_len=max_seq_len, page_size=page_size)
            _chunked_warm(eng, aggr_prompt)
            level['fused' if fused else 'unfused'] = (
                _chunked_victim_run(eng, conc, aggr_prompt, repeats))
        fp, up = level['fused'], level['unfused']
        if fp['victim_itl_p99_ms'] and up['victim_itl_p99_ms']:
            level['victim_itl_p99_improvement_x'] = round(
                up['victim_itl_p99_ms'] / fp['victim_itl_p99_ms'], 3)
            level['victim_itl_p50_improvement_x'] = round(
                up['victim_itl_p50_ms'] / fp['victim_itl_p50_ms'], 3)
        level['samples'] = fp['itl_samples'] + up['itl_samples']
        sweep.append(level)
    axis = _chunked_kv_axis(config, params, slots=args.slots,
                            max_seq_len=max_seq_len,
                            page_size=page_size)
    base = sweep[0] if sweep else {}
    head = {
        'metric': 'chunked_victim_itl_p99_improvement_x',
        'value': base.get('victim_itl_p99_improvement_x'),
        'unit': 'x (unfused victim itl p99 / fused victim itl p99, '
                'long-prompt aggressor arriving mid-decode-batch)',
        'victim_itl_p50_improvement_x': base.get(
            'victim_itl_p50_improvement_x'),
        'aggressor_ttft_fused_s': (base.get('fused') or {}).get(
            'aggressor_ttft_p50_s'),
        'aggressor_ttft_unfused_s': (base.get('unfused') or {}).get(
            'aggressor_ttft_p50_s'),
        'resident_page_ratio_int8_over_bf16': axis[
            'resident_page_ratio'],
        'prefix_hit_rate_delta_int8': axis['prefix_hit_rate_delta'],
        'fused_prefill': True,
    }
    return {
        **head,
        'sweep_mode': 'chunked',
        'sweep': sweep,
        'kv_dtype_axis': axis,
        'total_samples': sum(lv.get('samples', 0) for lv in sweep),
        'model': args.model,
        'slots': args.slots,
        'paged': True,
        'page_size': page_size,
        'device': jax.devices()[0].device_kind,
        'path': ('in-process engine step loop (fused vs unfused '
                 'mixed steps; engine-side per-token clock)'),
    }


def _coldstart_boot(args, cache_dir: str, boot_idx: int) -> dict:
    """One full server boot against a shared persistent compile
    cache: spawn → /health ready → first streamed token, plus the
    server's own cold-start stepline stamps (weights_loaded /
    compiled) pulled from /debug/stepline."""
    from skypilot_tpu.utils import common
    port = common.free_port()
    cmd = [sys.executable, '-m', 'skypilot_tpu.infer.server',
           '--port', str(port), '--model', args.model,
           '--slots', str(args.slots),
           '--max-seq-len', str(args.max_seq_len)]
    t0 = time.time()
    proc = subprocess.Popen(
        cmd, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        env={**os.environ, 'JAX_COMPILATION_CACHE_DIR': cache_dir})
    try:
        _wait_http(f'http://127.0.0.1:{port}/health', 600)
        ready_s = time.time() - t0
        ttft, _, _ = _streamed_request(
            f'http://127.0.0.1:{port}/generate', 'hello',
            max_new_tokens=4)
        stamps = {}
        try:
            snap = _get(f'http://127.0.0.1:{port}/debug/stepline')
            for ev in snap.get('events', ()):
                name = ev.get('event', '')
                if name.startswith('coldstart.'):
                    stamps[name.split('.', 1)[1]] = {
                        k: v for k, v in ev.items()
                        if k.endswith('_s')}
        except Exception:  # noqa: BLE001 — stamps are best-effort
            pass           # (--no-stepline builds have none)
        return {'boot': boot_idx,
                'time_to_ready_s': round(ready_s, 3),
                'first_token_s': round(ready_s + ttft, 3),
                'ttft_after_ready_s': round(ttft, 5),
                'stamps': stamps}
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def _run_coldstart_sweep(args) -> dict:
    """--sweep coldstart: the scale-to-zero wake path's replica half
    (docs/cost.md "Scale to zero"). Boot the real server TWICE against
    one persistent compile-cache dir — boot 1 compiles cold and
    populates the cache, boot 2 deserializes — and emit the cold-start
    curve (spawn → weights → compile → first token) for both, plus the
    ready-time ratio the cache buys. No improvement assertion: backends
    without persistent-cache support degrade to two cold boots."""
    import shutil

    from skypilot_tpu.utils import jax_env
    # A fixed directory under wherever this machine's cache is placed
    # (the directory is part of the cache key), emptied so boot 1 is
    # the cold one.
    cache = os.path.join(jax_env.compile_cache_dir(), 'coldstart-sweep')
    shutil.rmtree(cache, ignore_errors=True)
    boots = [_coldstart_boot(args, cache, i) for i in range(2)]
    cold, warm = boots[0], boots[1]
    ratio = (round(cold['time_to_ready_s'] / warm['time_to_ready_s'], 3)
             if warm['time_to_ready_s'] else None)
    return {
        'metric': 'coldstart_ready_ratio_cold_over_warm',
        'value': ratio,
        'unit': ('x (boot-1 cold-compile time-to-ready / boot-2 '
                 'cache-hit time-to-ready, same compile-cache dir)'),
        'cold_time_to_ready_s': cold['time_to_ready_s'],
        'warm_time_to_ready_s': warm['time_to_ready_s'],
        'cold_first_token_s': cold['first_token_s'],
        'warm_first_token_s': warm['first_token_s'],
        'sweep_mode': 'coldstart',
        'sweep': boots,
        'model': args.model,
        'slots': args.slots,
        'path': ('full server boot (spawn -> /health -> first '
                 'streamed token), persistent XLA compile cache '
                 'shared across boots'),
    }


def _run_lb_env(service: str, port: int, policy: str,
                env: dict) -> None:
    """LB child-process target with env knobs applied before import
    (the fleet-routing and sync-interval switches are read at LB
    construction)."""
    os.environ.update(env)
    from skypilot_tpu.serve import load_balancer
    load_balancer.run_load_balancer(service, policy, '127.0.0.1',
                                    port)


def _disagg_level(owner_url: str, fleet_url: str,
                  fleet_metrics_url: str, replica_metrics_urls: list,
                  donor_gen_url: str, concurrency: int,
                  n_requests: int, sys_tokens: int,
                  uniq_base: int) -> dict:
    """One concurrency level of the disaggregation sweep: the SAME
    shared-system-prompt cohort shape routed two ways. OWNER-ONLY
    pass (fleet routing off): the legacy lead-block affinity key sees
    the divergent tails and scatters the cohort across the ring, so
    every replica prefills the shared block for itself. FLEET pass
    (index armed): the block is computed ONCE on the prefill donor,
    the index routes the whole cohort at the decode replica, and the
    first request pulls the pages over the wire — per-pass hit rates
    are windowed from the replicas' own counters so neither pass can
    hide in cumulative totals."""
    tail = 16

    def cohort(base):
        shared = _block(base, sys_tokens)
        return lambda i: {'tokens': shared
                          + _block(base + 200003 + i, tail)}

    def hit_window(before, after):
        hits = (sum(m['prefix_hits'] for m in after)
                - sum(m['prefix_hits'] for m in before))
        lookups = hits + (sum(m['prefix_misses'] for m in after)
                          - sum(m['prefix_misses'] for m in before))
        return round(hits / lookups, 4) if lookups else 0.0

    # Owner-only pass: seed through the same LB (the seed's ring
    # owner warms first; the rest of the cohort scatters).
    pay = cohort(uniq_base)
    _streamed_request(owner_url, pay(0))
    r0 = [_get(u) for u in replica_metrics_urls]
    owner = _sweep_level(owner_url, concurrency, n_requests,
                         payload_for=lambda i: pay(i + 1))
    r1 = [_get(u) for u in replica_metrics_urls]

    # Fleet pass: the donor prefills the shared block once (a
    # prefill-role replica never serves under fleet routing — it
    # donates); wait for a sync tick to fold its radix summary.
    pay = cohort(uniq_base + 5_000_000)
    m_seed = _get(fleet_metrics_url)
    _streamed_request(donor_gen_url, pay(0))
    deadline = time.time() + 30
    while time.time() < deadline:
        if (_get(fleet_metrics_url).get('fleet_prefix_pages') or 0) \
                > (m_seed.get('fleet_prefix_pages') or 0):
            break
        time.sleep(0.2)
    else:
        raise RuntimeError('fleet prefix index never folded the '
                           'donor radix summary')
    f0 = [_get(u) for u in replica_metrics_urls]
    m0 = _get(fleet_metrics_url)
    fleet = _sweep_level(fleet_url, concurrency, n_requests,
                         payload_for=lambda i: pay(i + 1))
    time.sleep(1.2)   # one sync tick: the LB's kv rollup lags a poll
    f1 = [_get(u) for u in replica_metrics_urls]
    m1 = _get(fleet_metrics_url)

    out = {
        'concurrency': concurrency,
        'samples': owner['samples'] + fleet['samples'],
        'system_prompt_tokens': sys_tokens,
        'owner_only': owner,
        'fleet': fleet,
        'owner_hit_rate': hit_window(r0, r1),
        'fleet_hit_rate': hit_window(f0, f1),
        'fleet_prefix_hit_rate': m1.get('fleet_prefix_hit_rate'),
        'transfer_p99_s': m1.get('kv_transfer_p99_s'),
        'kv_transfers': (m1['kv_transfers_total']
                         - m0['kv_transfers_total']),
        'kv_transfer_failures': (m1['kv_transfer_failures']
                                 - m0['kv_transfer_failures']),
    }
    if owner['ttft_p50_s'] and fleet['ttft_p50_s']:
        out['ttft_improvement_x'] = round(
            owner['ttft_p50_s'] / fleet['ttft_p50_s'], 3)
    return out


def _run_disagg_sweep(args) -> dict:
    """--sweep disagg: prefill/decode disaggregation through TWO real
    LBs over the same two-replica int8 fleet — one with the fleet
    prefix index armed (the shipped default), one owner-only
    (SKY_TPU_LB_FLEET_ROUTING=0) — replicas in prefill/decode roles.
    The cohort's shared block sits INSIDE the legacy 64-token
    affinity lead with divergent tails: exactly the shape the
    lead-block key scatters and the indexed key unifies
    (docs/serving.md "Disaggregated prefill/decode")."""
    from skypilot_tpu.serve import load_balancing_policies as lbp
    from skypilot_tpu.utils import common
    tail = 16
    sys_tokens = min(args.shared_prefix_tokens,
                     lbp.AFFINITY_LEAD_TOKENS - tail)

    roles = ('prefill', 'decode')
    _refuse_unless_devices(len(roles), 'disagg')
    ports = [common.free_port() for _ in roles]
    procs = []
    for port, role in zip(ports, roles):
        cmd = [sys.executable, '-m', 'skypilot_tpu.infer.server',
               '--port', str(port), '--model', args.model,
               '--slots', str(args.slots),
               '--max-seq-len', str(args.max_seq_len),
               '--paged', '--page-size', str(args.page_size),
               '--prefix-cache', '--kv-dtype', 'int8',
               '--role', role]
        if args.n_pages:
            cmd += ['--n-pages', str(args.n_pages)]
        procs.append(subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                      stderr=subprocess.STDOUT))

    service = f'ttft-disagg-{os.getpid()}'
    owner_port, fleet_port = common.free_port(), common.free_port()
    sweep = []
    cold_s = None
    device = None
    try:
        for port in ports:
            _wait_http(f'http://127.0.0.1:{port}/health', 600)
        device = _replica_device(f'http://127.0.0.1:{ports[0]}')
        from skypilot_tpu.serve import state as serve_state
        from skypilot_tpu.serve.state import ReplicaStatus
        serve_state.add_service(service, spec_json='{}', task_yaml='',
                                lb_port=fleet_port,
                                lb_policy='cache_aware')
        rids = []
        for i, port in enumerate(ports):
            rid = serve_state.add_replica(service, f'disagg-r{i}', 1)
            serve_state.set_replica_url(rid,
                                        f'http://127.0.0.1:{port}')
            serve_state.set_replica_status(rid, ReplicaStatus.READY)
            rids.append(rid)
        sync = {'SKY_TPU_LB_SYNC_INTERVAL_S': '0.5'}
        lbs = [multiprocessing.Process(
                   target=_run_lb_env,
                   args=(service, p, 'cache_aware',
                         {**sync, 'SKY_TPU_LB_FLEET_ROUTING': on}))
               for p, on in ((owner_port, '0'), (fleet_port, '1'))]
        for lb in lbs:
            lb.start()
        try:
            for p in (owner_port, fleet_port):
                _wait_http(f'http://127.0.0.1:{p}/-/metrics', 60)
                deadline = time.time() + 30
                while time.time() < deadline:
                    m = _get(f'http://127.0.0.1:{p}/-/metrics')
                    if m.get('ready_replicas', 0) >= len(ports):
                        break
                    time.sleep(0.5)

            replica_metrics = [f'http://127.0.0.1:{p}/metrics'
                               for p in ports]
            donor_gen = f'http://127.0.0.1:{ports[0]}/generate'
            # Cold + warm: compile every replica's prefill buckets
            # off the clock with full-size unique payloads.
            cold_s = round(_streamed_request(
                donor_gen, {'tokens': _block(55, sys_tokens + tail)},
                timeout=600)[0], 4)
            for port in ports:
                _sweep_level(
                    f'http://127.0.0.1:{port}/generate',
                    max(args.concurrency), 2 * args.slots,
                    payload_for=lambda i: {
                        'tokens': _block(900001 + i,
                                         sys_tokens + tail)})

            for li, conc in enumerate(args.concurrency):
                sweep.append(_disagg_level(
                    f'http://127.0.0.1:{owner_port}/generate',
                    f'http://127.0.0.1:{fleet_port}/generate',
                    f'http://127.0.0.1:{fleet_port}/-/metrics',
                    replica_metrics, donor_gen, conc,
                    args.requests_per_level, sys_tokens,
                    uniq_base=(li + 1) * 1_000_000))
        finally:
            for lb in lbs:
                lb.terminate()
            for lb in lbs:
                lb.join(timeout=10)
            try:
                for rid in rids:
                    serve_state.remove_replica(rid)
                serve_state.remove_service(service)
            except Exception:  # noqa: BLE001 — cleanup is best-effort
                pass
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)

    base = sweep[0] if sweep else {}
    return {
        'metric': 'disagg_ttft_improvement_x',
        'value': base.get('ttft_improvement_x'),
        'unit': 'x (owner-only routed shared-cohort ttft p50 / '
                'fleet-index routed p50, shared block inside the '
                'legacy affinity lead window)',
        'fleet_prefix_hit_rate': base.get('fleet_prefix_hit_rate'),
        'transfer_p99_s': base.get('transfer_p99_s'),
        'owner_hit_rate': base.get('owner_hit_rate'),
        'fleet_hit_rate': base.get('fleet_hit_rate'),
        'kv_transfers_total': sum(
            lv.get('kv_transfers', 0) for lv in sweep),
        'kv_transfer_failures': sum(
            lv.get('kv_transfer_failures', 0) for lv in sweep),
        'sweep_mode': 'disagg',
        'cold_first_request_s': cold_s,
        'sweep': sweep,
        'total_samples': sum(lv.get('samples', 0) for lv in sweep),
        'model': args.model,
        'slots': args.slots,
        'paged': True,
        'page_size': args.page_size,
        'kv_dtype': 'int8',
        'roles': list(roles),
        'device': device,
        'path': ('client -> cache_aware LB (owner-only vs fleet '
                 'prefix index) -> prefill donor + decode puller '
                 '(int8 KV page streaming; client-side '
                 'send->first-byte clock)'),
    }


_REVISION_RE = re.compile(r'^TTFT_r(\d+)\.json$')


def _resolve_output(output: Optional[str],
                    clobber: bool) -> Optional[str]:
    """Bench artifacts are an append-only revision series:
    ``--output auto`` derives the next free ``TTFT_rNN.json`` from
    the files that actually exist (max + 1 — a hard-coded revision
    arg once overwrote r08 between r07 and r09), and an explicit
    path that already exists is refused unless ``--clobber`` says the
    overwrite is intentional."""
    if not output:
        return output
    if output == 'auto':
        revs = [int(m.group(1)) for m in
                (_REVISION_RE.match(name) for name in os.listdir('.'))
                if m]
        return f'TTFT_r{(max(revs) + 1 if revs else 1):02d}.json'
    if os.path.exists(output) and not clobber:
        raise SystemExit(
            f'refusing to overwrite existing {output!r} '
            f'(pass --clobber to allow, or --output auto for the '
            f'next free revision)')
    return output


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--requests-per-level', type=int, default=80)
    parser.add_argument('--concurrency', type=int, nargs='+',
                        default=[1, 4, 16])
    parser.add_argument('--model', default='1b',
                        help="infer/server.py model (default '1b': a "
                             'real ~1B-param LLaMA on the chip; random '
                             'weights — TTFT is a latency property of '
                             'the serving path, not the values)')
    parser.add_argument('--max-seq-len', type=int, default=None,
                        help='default 256 (1024 for --sweep '
                             'shared-prefix: the shared system block '
                             'must span many pages)')
    parser.add_argument('--slots', type=int, default=16)
    parser.add_argument('--tp', type=int, default=1)
    parser.add_argument('--quantize', action='store_true',
                        help='int8 weight-only (8B on one v5e chip)')
    parser.add_argument('--paged', action='store_true',
                        help='paged KV engine (block-table pool)')
    parser.add_argument('--page-size', type=int, default=64)
    parser.add_argument('--n-pages', type=int, default=None)
    parser.add_argument('--sweep', default='concurrency',
                        choices=['concurrency', 'shared-prefix',
                                 'chaos-resume', 'tenants',
                                 'speculative', 'chunked',
                                 'coldstart', 'disagg'],
                        help="'shared-prefix': the shared-system-"
                             'prompt workload (implies --paged '
                             '--prefix-cache) — per level, a cold '
                             'all-miss pass vs a shared-prefix pass, '
                             'emitting prefix_hit_rate, '
                             'tokens_prefill_saved and the TTFT '
                             "improvement into the json. 'chaos-"
                             "resume': mid-stream failover under a "
                             'ChaosProxy that severs streams after '
                             '--kill-after-chunks chunks — per level, '
                             'an uninterrupted pass vs a chaos pass, '
                             'emitting completed-request rate, resume '
                             'count, and the p99 latency a resumed '
                             "stream adds over an uninterrupted one. "
                             "'tenants': multi-tenant fairness — "
                             'replay a seeded 10:1 aggressor/victim '
                             'trace (tests/load_tests/loadgen.py) '
                             'with the X-SkyTpu-Tenant header, '
                             'emitting per-tenant ttft_p50/p99, '
                             'itl_p50/p99 and shed_rate per level '
                             '(pair with --scheduler wfq vs fcfs to '
                             "see the isolation win). 'speculative': "
                             'self-speculative decoding on a '
                             'template-heavy workload — per level, a '
                             'spec-off pass (per-request opt-out; '
                             'plain decode steps) vs a spec-on pass, '
                             'emitting accepted_len_mean, '
                             'spec_accept_rate, tokens_per_step, the '
                             'itl_improvement_x ratio and a '
                             'bit-identity probe into the json '
                             "(defaults --spec-k 6). 'chunked': "
                             'fused mixed steps — a long-prompt '
                             'aggressor arrives mid-decode-batch and '
                             'the victim decode ITL is measured '
                             'fused vs unfused (in-process engines; '
                             'implies --paged), plus the int8 '
                             'kv-dtype residency axis (resident '
                             'pages + prefix_hit_rate delta at a '
                             "fixed HBM budget). 'coldstart': the "
                             'scale-to-zero wake path — boot the real '
                             'server twice against one persistent '
                             'compile-cache dir and emit the '
                             'cold-start curve (spawn -> weights -> '
                             'compile -> first token) for the '
                             "cold-compile and cache-hit boots. "
                             "'disagg': prefill/decode "
                             'disaggregation — a shared-system-'
                             'prompt cohort through two real '
                             'cache_aware LBs over the same int8 '
                             'prefill+decode replica pair, owner-'
                             'only routing vs the fleet prefix '
                             'index, emitting fleet_prefix_hit_rate, '
                             'transfer_p99_s and ttft_improvement_x '
                             'per level (boots TWO engine processes '
                             '— refused on an accelerator host with '
                             'fewer than two devices: a chip belongs '
                             'to one process).')
    parser.add_argument('--spec-k', type=int, default=0,
                        help='speculative draft width for the replica '
                             '(0 = off; --sweep speculative defaults '
                             'it to 6)')
    parser.add_argument('--spec-ngram', type=int, default=3,
                        help='drafter n-gram width (forwarded)')
    parser.add_argument('--spec-max-new', type=int, default=64,
                        help='speculative sweep: tokens generated per '
                             'request (longer runs amortize the '
                             'drafting warm-up)')
    parser.add_argument('--scheduler', default=None,
                        choices=['fcfs', 'deadline', 'wfq'],
                        help='engine scheduling policy for the '
                             'replica (infer/sched/); defaults to '
                             "the server default (fcfs), or wfq for "
                             '--sweep tenants')
    parser.add_argument('--tenant-weights', default=None,
                        help="wfq weights, e.g. 'victim=2,"
                             "aggressor=1' (forwarded to the server)")
    parser.add_argument('--trace', default=None,
                        help='tenants sweep: replay this trace file '
                             '(loadgen JSONL) instead of synthesizing')
    parser.add_argument('--trace-seed', type=int, default=7,
                        help='tenants sweep: trace synthesis seed '
                             '(fixed seed = identical replayable '
                             'workload)')
    parser.add_argument('--trace-duration', type=float, default=6.0,
                        help='tenants sweep: seconds of trace per '
                             'level')
    parser.add_argument('--kill-after-chunks', type=int, default=6,
                        help='chaos-resume: sever the proxied stream '
                             'after this many response chunks')
    parser.add_argument('--prefix-cache', action='store_true',
                        help='enable shared-prefix KV reuse on the '
                             'replica (requires --paged)')
    parser.add_argument('--shared-prefix-tokens', type=int, default=768,
                        help='system-block length for --sweep '
                             'shared-prefix (multiple of --page-size '
                             'keeps the whole block cacheable)')
    parser.add_argument('--long-prompt-tokens', type=int, default=0,
                        help='adds a long-context lane to the sweep: '
                             'this many prompt chars per long request, '
                             'mixed 1-in-8 with short ones (exercises '
                             'chunked prefill + paged KV at depth)')
    parser.add_argument('--tokenizer', default=None,
                        help='tokenizer.json for the text path '
                             '(default: examples/tokenizer_8k.json '
                             "if present). The special value '128k' "
                             'derives a 128,256-entry tokenizer at '
                             'bench time (cached under ~/.sky_tpu) — '
                             'the 128k-vocab serving lane without a '
                             '24 MB file in the repo.')
    parser.add_argument('--output', default=None,
                        help="result json path. 'auto' derives the "
                             'next free TTFT_rNN.json from the files '
                             'already present (r08 was once lost to '
                             'an out-of-order hard-coded arg); an '
                             'explicit existing path refuses to '
                             'clobber without --clobber.')
    parser.add_argument('--clobber', action='store_true',
                        help='allow --output to overwrite an '
                             'existing file')
    args = parser.parse_args()
    args.output = _resolve_output(args.output, args.clobber)
    if args.sweep == 'shared-prefix':
        args.paged = True
        args.prefix_cache = True
        if args.max_seq_len is None:
            args.max_seq_len = 1024
    if args.sweep == 'chunked':
        args.paged = True
        if args.max_seq_len is None:
            # The aggressor prompt must span several chunks for the
            # stall to be visible.
            args.max_seq_len = 1024
    if args.sweep == 'disagg':
        args.paged = True
        args.prefix_cache = True
        if args.page_size == 64:
            # The shared block must cover several whole pages while
            # staying inside the 64-token legacy affinity lead.
            args.page_size = 16
    if args.max_seq_len is None:
        args.max_seq_len = 256
    if args.sweep == 'tenants' and args.scheduler is None:
        args.scheduler = 'wfq'
    if args.sweep == 'speculative' and not args.spec_k:
        args.spec_k = 6
    if args.prefix_cache and not args.paged:
        raise SystemExit('--prefix-cache requires --paged')

    # Bench-owns-the-chip: wait for the test suite / another bench to
    # release the accelerator before measuring (VERDICT r5 weak #2).
    from skypilot_tpu.utils import locks
    locks.acquire_chip_lock('bench_ttft')

    if args.sweep == 'chunked':
        # In-process engines (no server/LB hop): the stall under test
        # is the standalone prefill dispatch between decode
        # dispatches — a device-step property the HTTP path would only
        # blur with transport jitter.
        result = _run_chunked_sweep(args)
        print(json.dumps(result))
        if args.output:
            with open(args.output, 'w', encoding='utf-8') as f:
                json.dump(result, f, indent=1)
        return

    if args.sweep == 'coldstart':
        result = _run_coldstart_sweep(args)
        print(json.dumps(result))
        if args.output:
            with open(args.output, 'w', encoding='utf-8') as f:
                json.dump(result, f, indent=1)
        return

    if args.sweep == 'disagg':
        result = _run_disagg_sweep(args)
        print(json.dumps(result))
        if args.output:
            with open(args.output, 'w', encoding='utf-8') as f:
                json.dump(result, f, indent=1)
        return

    if args.tokenizer == '128k':
        from skypilot_tpu.infer import server as server_lib
        cache = os.path.expanduser('~/.sky_tpu/cache/tokenizer_128k.json')
        if not os.path.exists(cache):
            print(f'[bench_ttft] deriving 128k tokenizer -> {cache}',
                  file=sys.stderr)
            server_lib.synthesize_wordlevel_tokenizer(128256, cache)
        args.tokenizer = cache

    from skypilot_tpu.utils import common
    # Unique per run: a stale READY replica from a previous run (dead
    # port) would absorb half the traffic and corrupt the percentiles.
    service = f'ttft-bench-{os.getpid()}'
    infer_port = common.free_port()
    lb_port = common.free_port()

    # 1. Real inference server on the local accelerator.
    tokenizer = args.tokenizer
    if tokenizer is None:
        from skypilot_tpu.infer import server as server_lib
        default_tok = os.path.join(os.path.dirname(
            os.path.abspath(__file__)), 'examples', 'tokenizer_8k.json')
        # Only auto-attach when the model vocab can hold the
        # tokenizer's ids — `--model tiny` (vocab 256) must keep its
        # byte fallback instead of dying in the server's vocab check.
        if (os.path.exists(default_tok) and
                server_lib.MODELS[args.model]().vocab_size >= 8192):
            tokenizer = default_tok
    cmd = [sys.executable, '-m', 'skypilot_tpu.infer.server',
           '--port', str(infer_port), '--model', args.model,
           '--slots', str(args.slots),
           '--max-seq-len', str(args.max_seq_len), '--tp', str(args.tp)]
    if args.quantize:
        cmd.append('--quantize')
    if args.paged:
        cmd += ['--paged', '--page-size', str(args.page_size)]
        if args.n_pages:
            cmd += ['--n-pages', str(args.n_pages)]
    if args.prefix_cache:
        cmd.append('--prefix-cache')
    if args.spec_k:
        cmd += ['--spec-k', str(args.spec_k),
                '--spec-ngram', str(args.spec_ngram)]
    if args.scheduler:
        cmd += ['--scheduler', args.scheduler]
    if args.tenant_weights:
        cmd += ['--tenant-weights', args.tenant_weights]
    if args.sweep == 'tenants':
        # Fairness needs a finite admission bound to shed against —
        # the wfq quota split (and the fcfs counterexample) are both
        # measured off it.
        cmd += ['--max-queue-requests', str(4 * args.slots)]
    if tokenizer:
        cmd += ['--tokenizer', tokenizer]
    infer_proc = subprocess.Popen(
        cmd, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    sweep = []
    cold_s = None
    device = None
    try:
        _wait_http(f'http://127.0.0.1:{infer_port}/health', 600)
        device = _replica_device(f'http://127.0.0.1:{infer_port}')

        # 2. Register it as a ready replica; start the REAL serve LB.
        #    chaos-resume alternates replicas deterministically
        #    (round_robin) so ~half the streams ride the doomed proxy.
        from skypilot_tpu.serve import state as serve_state
        from skypilot_tpu.serve.state import ReplicaStatus
        lb_policy = ('round_robin' if args.sweep == 'chaos-resume'
                     else 'least_load')
        serve_state.add_service(service, spec_json='{}', task_yaml='',
                                lb_port=lb_port, lb_policy=lb_policy)
        rid = serve_state.add_replica(service, 'ttft-local', 1)
        serve_state.set_replica_url(rid, f'http://127.0.0.1:{infer_port}')
        serve_state.set_replica_status(rid, ReplicaStatus.READY)
        lb_proc = multiprocessing.Process(target=_run_lb,
                                          args=(service, lb_port,
                                                lb_policy))
        lb_proc.start()
        try:
            _wait_http(f'http://127.0.0.1:{lb_port}/-/metrics', 60)
            deadline = time.time() + 30
            while time.time() < deadline:
                m = _get(f'http://127.0.0.1:{lb_port}/-/metrics')
                if m.get('ready_replicas'):
                    break
                time.sleep(0.5)

            gen_url = f'http://127.0.0.1:{lb_port}/generate'
            metrics_url = f'http://127.0.0.1:{infer_port}/metrics'
            # 3. COLD: the first request eats any residual compile —
            #    reported separately, never mixed into warm percentiles.
            cold_s = round(_streamed_request(gen_url, 'cold request',
                                             timeout=600)[0], 4)
            if args.sweep == 'shared-prefix':
                # Warm with FULL-SIZE unique payloads so the big
                # prefill buckets compile off the clock.
                _sweep_level(
                    gen_url, max(args.concurrency), 2 * args.slots,
                    payload_for=lambda i: {
                        'tokens': _block(900001 + i,
                                         args.shared_prefix_tokens
                                         + 16)})
                for li, conc in enumerate(args.concurrency):
                    sweep.append(_shared_prefix_level(
                        gen_url, metrics_url, conc,
                        args.requests_per_level,
                        args.shared_prefix_tokens,
                        uniq_base=(li + 1) * 1_000_000))
            elif args.sweep == 'chaos-resume':
                # Importable because bench_ttft runs from the repo
                # root (same reason the tests can).
                from tests.chaos.chaos_proxy import ChaosProxy
                lb_metrics_url = f'http://127.0.0.1:{lb_port}/-/metrics'
                _sweep_level(gen_url, max(args.concurrency),
                             2 * args.slots)   # warm off the clock
                # Uninterrupted pass: the direct replica only.
                clean_levels = [
                    _chaos_resume_level(gen_url, conc,
                                        args.requests_per_level)
                    for conc in args.concurrency]
                # Arm the chaos: a second "replica" through a proxy
                # that severs every stream after N response chunks.
                proxy = ChaosProxy(
                    target_port=infer_port, kill_every_s=3600.0,
                    kill_after_chunks=args.kill_after_chunks).start()
                rid2 = serve_state.add_replica(service, 'ttft-chaos', 1)
                serve_state.set_replica_url(
                    rid2, f'http://127.0.0.1:{proxy.port}')
                serve_state.set_replica_status(rid2, ReplicaStatus.READY)
                try:
                    deadline = time.time() + 30
                    while time.time() < deadline:
                        m = _get(lb_metrics_url)
                        if m.get('ready_replicas', 0) >= 2:
                            break
                        time.sleep(0.5)
                    m0 = _get(lb_metrics_url)
                    chaos_levels = [
                        _chaos_resume_level(gen_url, conc,
                                            args.requests_per_level)
                        for conc in args.concurrency]
                    m1 = _get(lb_metrics_url)
                finally:
                    proxy.stop()
                    serve_state.remove_replica(rid2)
                for conc, cl, ch in zip(args.concurrency, clean_levels,
                                        chaos_levels):
                    lvl = {'concurrency': conc,
                           'samples': cl['issued'] + ch['issued'],
                           'uninterrupted': cl, 'chaos': ch,
                           'completed_rate': ch['completed_rate'],
                           'resumes': ch['resumes']}
                    if (ch['resumed_total_p99_s']
                            and cl['clean_total_p99_s']):
                        # The latency price of a mid-stream failover:
                        # resumed-stream p99 vs an untouched run.
                        lvl['resume_added_p99_s'] = round(
                            ch['resumed_total_p99_s']
                            - cl['clean_total_p99_s'], 5)
                    lvl['lb_requests_resumed'] = (
                        m1['requests_resumed'] - m0['requests_resumed'])
                    lvl['lb_requests_failed'] = (
                        m1['requests_failed'] - m0['requests_failed'])
                    sweep.append(lvl)
            elif args.sweep == 'tenants':
                lb_metrics_url = f'http://127.0.0.1:{lb_port}/-/metrics'
                # Warm the prefill buckets off the clock.
                _sweep_level(gen_url, max(args.concurrency),
                             2 * args.slots)
                for conc in args.concurrency:
                    sweep.append(_tenant_level(
                        gen_url, lb_metrics_url, conc,
                        args.trace_seed, args.trace_duration,
                        trace_path=args.trace))
            elif args.sweep == 'speculative':
                # Warm both programs (decode AND verify) off the
                # clock: one spec-off mini-pass, one spec-on.
                _sweep_level(
                    gen_url, max(args.concurrency), args.slots,
                    payload_for=lambda i: {
                        'tokens': _block(777 + i, 54),
                        'max_new_tokens': args.spec_max_new,
                        'spec': False})
                _sweep_level(
                    gen_url, max(args.concurrency), args.slots,
                    payload_for=lambda i: {
                        'tokens': _block(8777 + i, 54),
                        'max_new_tokens': args.spec_max_new,
                        'spec': True})
                for li, conc in enumerate(args.concurrency):
                    sweep.append(_speculative_level(
                        gen_url, metrics_url, conc,
                        args.requests_per_level, args.spec_k,
                        max_new=args.spec_max_new,
                        uniq_base=(li + 1) * 1_000_000))
            else:
                # Warm every concurrency level's batch shapes off the
                # clock.
                _sweep_level(gen_url, max(args.concurrency),
                             2 * args.slots, args.long_prompt_tokens)
                # 4. The sweep.
                for conc in args.concurrency:
                    sweep.append(_sweep_level(gen_url, conc,
                                              args.requests_per_level,
                                              args.long_prompt_tokens))
        finally:
            lb_proc.terminate()
            lb_proc.join(timeout=10)
            try:
                serve_state.remove_replica(rid)
                serve_state.remove_service(service)
            except Exception:  # noqa: BLE001 — cleanup is best-effort
                pass
    finally:
        infer_proc.terminate()
        infer_proc.wait(timeout=10)

    base = sweep[0] if sweep else {}
    if args.sweep == 'shared-prefix':
        head = {
            'metric': 'shared_prefix_ttft_improvement_x',
            'value': base.get('ttft_improvement_x'),
            'unit': 'x (cold p50 / shared p50, same prompt length)',
            'prefix_hit_rate': base.get('prefix_hit_rate'),
            'tokens_prefill_saved': sum(
                lv.get('tokens_prefill_saved', 0) for lv in sweep),
            'shared_ttft_p50_s': (base.get('shared') or {}).get(
                'ttft_p50_s'),
            'cold_ttft_p50_s': (base.get('cold') or {}).get(
                'ttft_p50_s'),
            'itl_ratio_shared_over_cold': base.get(
                'itl_ratio_shared_over_cold'),
            'prefix_cache': True,
        }
    elif args.sweep == 'chaos-resume':
        head = {
            'metric': 'chaos_resume_completed_rate',
            'value': base.get('completed_rate'),
            'unit': 'completed streams / issued (mid-stream kills '
                    'armed on half the fleet)',
            'resumes': sum(lv.get('resumes', 0) for lv in sweep),
            'resume_added_p99_s': base.get('resume_added_p99_s'),
            'lb_requests_resumed': sum(
                lv.get('lb_requests_resumed', 0) for lv in sweep),
            'lb_requests_failed': sum(
                lv.get('lb_requests_failed', 0) for lv in sweep),
            'kill_after_chunks': args.kill_after_chunks,
        }
    elif args.sweep == 'tenants':
        vict = (base.get('tenants') or {}).get('victim') or {}
        aggr = (base.get('tenants') or {}).get('aggressor') or {}
        head = {
            'metric': 'tenants_victim_ttft_p99_s',
            'value': vict.get('ttft_p99_s'),
            'unit': 'seconds (victim p99 TTFT under a 10:1 '
                    'aggressor tenant)',
            'victim_shed_rate': vict.get('shed_rate'),
            'aggressor_shed_rate': aggr.get('shed_rate'),
            'victim_queue_wait_p99_ms': vict.get('queue_wait_p99_ms'),
            'victim_itl_p99_ms': vict.get('itl_p99_ms'),
            'scheduler': args.scheduler,
            'trace_seed': args.trace_seed,
        }
    elif args.sweep == 'speculative':
        head = {
            'metric': 'speculative_itl_improvement_x',
            'value': base.get('itl_improvement_x'),
            'unit': 'x (spec-off itl p50 / spec-on itl p50, same '
                    'template-heavy workload)',
            'accepted_len_mean': base.get('accepted_len_mean'),
            'spec_accept_rate': base.get('spec_accept_rate'),
            'tokens_per_step': base.get('tokens_per_step'),
            'spec_on_itl_p50_ms': (base.get('spec_on') or {}).get(
                'itl_p50_ms'),
            'spec_off_itl_p50_ms': (base.get('spec_off') or {}).get(
                'itl_p50_ms'),
            'bit_identical': all(
                lv.get('bit_identical') for lv in sweep),
            'spec_k': args.spec_k,
        }
    else:
        head = {
            'metric': 'serve_ttft_warm_p50_s',
            'value': base.get('ttft_p50_s'),
            'unit': 'seconds',
            'ttft_warm_p99_s': base.get('ttft_p99_s'),
            'itl_p50_ms': base.get('itl_p50_ms'),
            'itl_p99_ms': base.get('itl_p99_ms'),
            'queue_wait_p50_ms': base.get('queue_wait_p50_ms'),
            'queue_wait_p99_ms': base.get('queue_wait_p99_ms'),
        }
    result = {
        **head,
        'sweep_mode': args.sweep,
        'cold_first_request_s': cold_s,
        'sweep': sweep,
        'total_samples': sum(lv.get('samples', lv.get('issued', 0))
                             for lv in sweep),
        'model': args.model,
        'tp': args.tp,
        'slots': args.slots,
        'quantize': args.quantize,
        'paged': args.paged,
        **({'page_size': args.page_size,
            'long_prompt_tokens': args.long_prompt_tokens}
           if args.paged or args.long_prompt_tokens else {}),
        **({'spec_k': args.spec_k, 'spec_ngram': args.spec_ngram}
           if args.spec_k else {}),
        'tokenizer': ('bpe-8k' if tokenizer else 'bytes'),
        'device': device,
        'path': ('client -> serve LB -> continuous-batching engine '
                 '(streamed; client-side send->first-byte clock)'),
    }
    print(json.dumps(result))
    if args.output:
        with open(args.output, 'w', encoding='utf-8') as f:
            json.dump(result, f, indent=1)


if __name__ == '__main__':
    main()
