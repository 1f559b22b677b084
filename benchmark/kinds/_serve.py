"""Driver shared by the serving kinds: one engine behind the real
server and load balancer, loaded by a client child.

The process that runs this holds the chip. It builds, exactly as
``skypilot_tpu.infer.server.main`` does, the model configuration, the
``InferenceEngine`` and the ``InferenceServer`` (the weights come from
``benchmark/weights.py``, made from the seed), and runs the server on
the main thread, which ``web.run_app`` wants for its signal handlers.
A second thread, which touches no device, does everything else: waits
for ``/health``, starts the load balancer and the client as CPU-only
children, warms every prefill bucket, scrapes ``/metrics`` and
``/debug/stepline`` around the window, takes the profiler trace in a
traced run, and ends the run by signalling its own process. The
comparison with the reference runs after ``run()`` has returned, the
peak memory has been read and the engine's state is freed.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

from benchmark import check as check_lib
from benchmark import trace_reduce
from benchmark import traffic as traffic_lib
from benchmark import weights as weights_lib
from benchmark import work
from benchmark.stats import percentile, token_gaps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _get_json(url: str, timeout: float = 10.0) -> Any:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return json.loads(e.read() or b'{}')


def _wait(what: str, probe, timeout: float, alive=None) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if alive is not None and not alive():
            raise RuntimeError(f'{what}: the process behind it exited')
        try:
            if probe():
                return
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(0.05)
    raise TimeoutError(f'{what}: not ready after {timeout:.0f}s')


def end_to_end(records: List[Dict[str, Any]], seconds: float,
               end_s: float) -> Dict[str, Optional[float]]:
    """The client's view of the whole window: every request that was
    due or started in it, all the time of it."""
    ttfts, tokens_done = [], 0
    for r in records:
        first = r['arrivals'][0][0] if r['arrivals'] else end_s
        ttfts.append(first - r['due_s'])
        if r['done'] and r['done_s'] <= seconds:
            tokens_done += r['prompt_len'] + len(r['tokens'])
    gaps = token_gaps(records)

    def gap_ms(p: float) -> Optional[float]:
        q = percentile(gaps, p)
        return None if q is None else q * 1e3
    return {'ttft_p90_s': percentile(ttfts, 0.90),
            'ttft_mean_s': sum(ttfts) / len(ttfts) if ttfts else None,
            'itl_p90_ms': gap_ms(0.90), 'itl_p50_ms': gap_ms(0.50),
            'itl_mean_ms': 1e3 * sum(gaps) / len(gaps) if gaps else None,
            'serve_tokens_per_s': tokens_done / seconds}


def _gap_quantiles(records: List[Dict[str, Any]]) -> Dict[str, float]:
    gaps = token_gaps(records)
    return {f'p{int(p * 100)}': 1e3 * (percentile(gaps, p) or 0.0)
            for p in (0.5, 0.9, 0.95, 0.99)}


class _Orchestrator(threading.Thread):
    """Everything around the server that touches no device."""

    def __init__(self, ctx: Dict[str, Any], run_dir: str, port: int,
                 vocab: int) -> None:
        super().__init__(name='bench-orchestrator', daemon=True)
        self.ctx, self.run_dir, self.port, self.vocab = ctx, run_dir, port, vocab
        self.url = f'http://127.0.0.1:{port}'
        self.error: Optional[str] = None
        self.out: Dict[str, Any] = {}
        self.stages: Dict[str, float] = {}
        self._children: List[subprocess.Popen] = []

    def _stage(self, name: str) -> None:
        """Seconds since the process started at which set-up reached
        ``name``: where ``setup_s`` went, for the notes."""
        self.stages[name] = time.time() - self.ctx['t0']

    def _child_env(self) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(JAX_PLATFORMS='cpu', PYTHONPATH=self.ctx['root'],
                   SKY_TPU_HOME=os.path.join(self.run_dir, 'home'))
        return env

    def _client(self, name: str, plan: Dict[str, Any], url: str,
                on_start=None) -> Dict[str, Any]:
        plan_path = os.path.join(self.run_dir, f'{name}.plan.json')
        out_path = os.path.join(self.run_dir, f'{name}.records.json')
        with open(plan_path, 'w', encoding='utf-8') as f:
            json.dump(plan, f)
        log = open(os.path.join(self.run_dir, f'{name}.log'), 'wb')
        proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, 'client.py'),
             '--plan', plan_path, '--url', url, '--out', out_path,
             '--seed', str(self.ctx['seed']), '--vocab', str(self.vocab)],
            env=self._child_env(), stdout=log, stderr=subprocess.STDOUT)
        self._children.append(proc)
        try:
            if on_start is not None:
                on_start()
            proc.wait(timeout=plan['seconds'] + plan['drain_s'] + 120)
        finally:
            log.close()
        if proc.returncode != 0:
            with open(log.name, 'rb') as f:
                tail = f.read()[-2000:].decode('utf-8', 'replace')
            raise RuntimeError(f'client {name} exited {proc.returncode}: '
                               f'{tail}')
        with open(out_path, encoding='utf-8') as f:
            return json.load(f)

    def _trace(self, t_go: float) -> None:
        """Trace a few seconds in the middle of the window."""
        import jax
        spec = self.ctx['cell']['trace']
        seconds = self.ctx['seconds']
        start = t_go + spec['at_share'] * seconds
        length = min(spec['seconds'], 0.5 * seconds)
        time.sleep(max(0.0, start - time.time()))
        log_dir = os.path.join(self.run_dir, 'trace')
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        m0, w0 = _get_json(f'{self.url}/metrics'), time.time()
        time.sleep(length)
        m1, w1 = _get_json(f'{self.url}/metrics'), time.time()
        jax.profiler.stop_trace()
        self.out['trace'] = {'log_dir': log_dir, 'wall': [w0, w1],
                             'metrics_start': m0, 'metrics_stop': m1}

    def run(self) -> None:
        lb = lb_log = None
        try:
            ctx = self.ctx
            # The load balancer reads the replica from serve/state and
            # needs no device: it comes up while the server warms.
            lb_port = _free_port()
            lb_url = f'http://127.0.0.1:{lb_port}'
            lb_log = open(os.path.join(self.run_dir, 'lb.log'), 'wb')
            lb = subprocess.Popen(
                [sys.executable, os.path.join(BENCH_DIR, 'lb_child.py'),
                 f'bench-{os.getpid()}', str(lb_port), self.url],
                env=self._child_env(), stdout=lb_log, stderr=subprocess.STDOUT)
            self._children.append(lb)

            def healthy() -> bool:
                body = _get_json(f'{self.url}/health')
                if body.get('status') in ('dead', 'corrupt'):
                    raise RuntimeError(f'server /health says {body}')
                return body.get('status') == 'ok'
            _wait('server /health', healthy, 900)
            self._stage('server_healthy_s')
            _wait('load balancer',
                  lambda: _get_json(f'{lb_url}/-/metrics').get('ready_replicas'),
                  120, alive=lambda: lb.poll() is None)
            self._stage('lb_ready_s')
            gen = f'{lb_url}/generate'
            warm_lens = ctx['config']['warmup']['prompt_lens']
            warm = self._client('warmup', {
                'loop': 'open', 'seconds': 0.0, 'drain_s': 600.0, 'clients': 0,
                'requests': [{'idx': 10_000_000 + i, 'due_s': 0.0,
                              'prompt_len': n,
                              'max_new': ctx['config']['warmup']['max_new']}
                             for i, n in enumerate(warm_lens)]}, gen)
            cold = [r for r in warm['records'] if not r['done']]
            if cold:
                raise RuntimeError(f'warm-up requests failed: {cold}')
            self._stage('warmed_s')
            self.out['metrics_before'] = _get_json(f'{self.url}/metrics')
            plan = traffic_lib.plan(ctx['traffic'], ctx['seed'], ctx['seconds'])
            self.out['plan'] = plan
            go: Dict[str, float] = {}

            def on_start():
                go['t'] = time.time()
                if ctx['trace']:
                    self._trace(go['t'])
            self.out['client'] = self._client('window', plan, gen, on_start)
            self.out['metrics_after'] = _get_json(f'{self.url}/metrics')
            self.out['stepline'] = _get_json(f'{self.url}/debug/stepline',
                                             timeout=60)
            self.out['lb_metrics'] = _get_json(f'{lb_url}/-/metrics')
        except BaseException:  # noqa: BLE001 — reported by the main thread
            self.error = traceback.format_exc()
        finally:
            if lb_log is not None:
                lb_log.close()
            for proc in self._children:
                if proc.poll() is None:
                    proc.terminate()
            for proc in self._children:
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            # web.run_app returns on SIGTERM; the server then parks its
            # engine loop and run() returns on the main thread.
            os.kill(os.getpid(), signal.SIGTERM)


def _to_program_params(tree: Dict[str, Any], act_dtype) -> Dict[str, Any]:
    """The benchmark's int8 tree in the program's own containers."""
    from skypilot_tpu.ops.quant import QuantArray

    def leaf(v):
        if isinstance(v, tuple):
            return QuantArray(q=v[0], scale=v[1].astype(act_dtype))
        return v.astype(act_dtype)
    return {'embed': leaf(tree['embed']),
            'layers': {k: leaf(v) for k, v in tree['layers'].items()},
            'final_norm': leaf(tree['final_norm']),
            'lm_head': leaf(tree['lm_head'])}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import llama
    from skypilot_tpu.utils import jax_env

    cfg, cell = ctx['config'], ctx['cell']
    notes: Dict[str, Any] = {'compile_cache_dir':
                             jax_env.attach_compile_cache()}
    act = cfg['precision']['activations']
    lcfg = llama.LlamaConfig(
        vocab_size=cfg['vocab_size'], dim=cfg['hidden_size'],
        n_layers=cfg['num_hidden_layers'],
        n_heads=cfg['num_attention_heads'],
        n_kv_heads=cfg['num_key_value_heads'],
        ffn_dim=cfg['intermediate_size'],
        max_seq_len=cfg['max_position_embeddings'],
        rope_theta=cfg['rope_theta'], norm_eps=cfg['rms_norm_eps'], dtype=act)
    if lcfg.head_dim != cfg['head_dim']:
        raise ValueError(f'head_dim {cfg["head_dim"]} is not hidden/heads')
    t = time.time()
    params = _to_program_params(weights_lib.init_all(cfg, ctx['seed']),
                                jnp.dtype(act))
    jax.block_until_ready(params)
    notes['weights_s'] = time.time() - t
    stages = {'devices_s': ctx['t_devices'] - ctx['t0'],
              'imports_s': t - ctx['t0'], 'weights_s': time.time() - ctx['t0']}
    engine = engine_lib.InferenceEngine(
        lcfg, params, engine_lib.EngineConfig(**cfg['engine']), seed=0)
    stages['engine_s'] = time.time() - ctx['t0']
    run_dir = tempfile.mkdtemp(prefix='skybench-')
    try:
        tokenizer = server_lib.Tokenizer(
            server_lib.synthesize_wordlevel_tokenizer(
                cfg['vocab_size'], os.path.join(run_dir, 'tokenizer.json')),
            vocab_limit=cfg['vocab_size'])
        server = server_lib.InferenceServer(engine, tokenizer,
                                            boot_t0=ctx['t0'])
        orch = _Orchestrator(ctx, run_dir, _free_port(), cfg['vocab_size'])
        orch.stages.update(stages)
        orch.start()
        server.run('127.0.0.1', orch.port)
        orch.join(timeout=60)
        if orch.error or orch.is_alive():
            raise RuntimeError(f'the run around the server failed:\n'
                               f'{orch.error or "orchestrator still alive"}')
        got = orch.out
        peak = max(((d.memory_stats() or {}).get('peak_bytes_in_use') or 0)
                   for d in jax.local_devices())
        # The engine's state goes before the reference runs.
        del server, engine, params, tokenizer
        gc.collect()
        for arr in jax.live_arrays():
            arr.delete()
        reduced = rows = None
        if 'trace' in got:
            rows = trace_reduce.load(
                trace_reduce.find_xplane(got['trace']['log_dir']))
            reduced = trace_reduce.reduce(rows)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)

    client, plan = got['client'], got['plan']
    records = client['records']
    seconds = ctx['seconds']
    e2e = end_to_end(records, seconds, client['end_s'])
    # Set-up runs from the process's start to the window's first send.
    # Its first stage, reaching the chip (the interpreter, ``import
    # jax``, the TPU runtime's start), is neither the benchmark's nor
    # the program's and took 8 to 15 s over the runs of one machine; the
    # per-layer ``setup.after_devices_s`` is set-up without it.
    e2e['setup_s'] = client['t0'] - ctx['t0']
    failed = [r for r in records if not r['done'] or r['error']]

    finished = [r for r in records if r['done'] and not r['error']
                and r['tokens']]
    sample = check_lib.pick_sample(finished, ctx['seed'],
                                   cell['check']['sample_requests'])
    t = time.time()
    found = check_lib.serve_gaps(cfg, ctx['seed'], [
        {'prompt': traffic_lib.request_tokens(
            ctx['seed'], r['idx'], r['prompt_len'], cfg['vocab_size']),
         'served': r['tokens']} for r in sample],
        pad_to=cell['check']['pad_to'], rows_pad=cell['check']['rows_pad'])
    notes['reference_s'] = time.time() - t
    before, after = got['metrics_before'], got['metrics_after']
    recompiled = sum(abs(after['compiled_programs'].get(k, 0) - v)
                     for k, v in before['compiled_programs'].items())
    short = sum(1 for r in finished if r['finish_reason'] == 'max_tokens'
                and len(r['tokens']) != r['max_new'])
    checks = check_lib.verdict(found, cell['check'], {
        'unanswered': len(failed), 'wrong_length': short,
        'recompiled_in_window': recompiled})
    correct = all(c.pop('ok') for c in checks.values())
    out_requests = [[r['due_s'], r['sent_s'],
                     r['arrivals'][0][0] if r['arrivals'] else None,
                     r['done_s'], r['prompt_len'], len(r['tokens']),
                     r['queue_wait_s']] for r in records]
    # The longest engine step of the window and where it spent its time:
    # a stall that lifts a tail shows here, under the stage that held it.
    steps = [st for st in got['stepline'].get('steps', [])
             if client['t0'] <= st['t'] <= client['t0'] + seconds]
    slowest = max(steps, key=lambda st: st['dur_s'], default=None)
    notes.update(
        setup_stages=dict(orch.stages, go_s=client['t0'] - ctx['t0']),
        slowest_step=slowest and {
            'at_s': slowest['t'] - client['t0'], 'kind': slowest['kind'],
            **{k: slowest[k] for k in ('dur_s', 'dispatch_s', 'drain_s',
                                       'readback_s', 'host_s')}},
        mismatch_share=found['served']['mismatch_share'],
        sample=[r['idx'] for r in sample],
        requests=len(records),
        preemptions=after.get('preemptions'),
        late_p95_ms=1e3 * (percentile(
            [r['sent_s'] - r['due_s'] for r in records
             if r['sent_s'] is not None], 0.95) or 0.0))

    out: Dict[str, Any] = {
        'correct': correct, 'attempted': len(records), 'failed': len(failed),
        'end_to_end': e2e, 'memory_peak_bytes': int(peak), 'checks': checks,
        'notes': notes,
        'run': {'cell': cell, 'config': cfg, 'traffic': ctx['traffic'],
                'plan': plan, 'seconds': seconds, 'records': records,
                'client': {'t0': client['t0'], 'end_s': client['end_s']},
                'setup': {'after_devices_s': client['t0'] - ctx['t_devices']},
                'metrics_before': before, 'metrics_after': after,
                'stepline': got['stepline'], 'trace': None},
        'extra': {'requests': out_requests,
                  'gaps_ms': _gap_quantiles(records)},
    }
    if reduced is not None:
        kind = jax.devices()[0].device_kind
        w0, w1 = got['trace']['wall']
        window_s = max(w1 - w0, reduced['span_s'])
        out['run']['trace'] = {
            'reduced': reduced, 'window_s': window_s,
            'wall_s': [w0 - client['t0'], w1 - client['t0']],
            'metrics_start': got['trace']['metrics_start'],
            'metrics_stop': got['trace']['metrics_stop'],
            'peak': work.peaks(kind)}
        out['device'] = {'busy_s': trace_reduce.busy_mean_s(reduced),
                         'window_s': window_s}
        out['breakdown'] = {
            'device_ops': trace_reduce.top_ops(reduced),
            'idle_gaps': [list(g) for g in reduced['gaps']]}
        first = min(r[3] for r in rows)
        out['extra'].update({'modules': reduced['modules'], 'ops': reduced['ops'],
                        'rows_sample': [r for r in rows
                                        if r[3] < first + 120_000_000][:6000],
                        'lines': sorted({(r[0], r[1]) for r in rows})})
    return out
