"""Driver of the family-driven serving kinds: ``_serve.py``'s run with
the model taken from a family module instead of being the dense block.

``_serve.run`` builds a ``LlamaConfig``, ``check.py`` imports the
Mistral reference and ``work.py`` counts the dense block, and none of
the three may be edited by the PR that adds a second family (PR 27).
This run takes the three model-specific things from the module the
configuration file names (``"family": "nemotron_h"`` ->
``benchmark/families/nemotron_h.py``): ``program(cfg, seed)`` (the
program's configuration object and parameters), ``serve_gaps(...)``
(``check.serve_gaps``'s contract through the family's reference) and
``work`` (its operation and byte counts). Everything around the server
is ``_serve``'s own: the orchestrator thread, the load balancer and
client children, ``end_to_end``, the verdict.

It also reduces a traced run's device time **by named scope**
(``scope_reduce.py``) into ``run['trace']['scopes']``, which the
family's per-layer readers read.

The part of this file that repeats ``_serve.run`` is a debt (ROADMAP):
a ``benchmark`` PR folds ``_serve.run`` onto this one, with the dense
block as the family ``llama``, and deletes the copy.
"""
from __future__ import annotations

import gc
import importlib
import os
import shutil
import tempfile
import time
from typing import Any, Dict

from benchmark import check as check_lib
from benchmark import scope_reduce
from benchmark import trace_reduce
from benchmark import traffic as traffic_lib
from benchmark.kinds import _serve
from benchmark.stats import percentile


def family_of(cfg: Dict[str, Any]):
    return importlib.import_module(f'benchmark.families.{cfg["family"]}')


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.utils import jax_env

    cfg, cell = ctx['config'], ctx['cell']
    family = family_of(cfg)
    notes: Dict[str, Any] = {'compile_cache_dir':
                             jax_env.attach_compile_cache()}
    t = time.time()
    config, params = family.program(cfg, ctx['seed'])
    jax.block_until_ready(params)
    notes['weights_s'] = time.time() - t
    stages = {'devices_s': ctx['t_devices'] - ctx['t0'],
              'imports_s': t - ctx['t0'], 'weights_s': time.time() - ctx['t0']}
    engine = engine_lib.InferenceEngine(
        config, params, engine_lib.EngineConfig(**cfg['engine']), seed=0)
    stages['engine_s'] = time.time() - ctx['t0']
    run_dir = tempfile.mkdtemp(prefix='skybench-')
    try:
        tokenizer = server_lib.Tokenizer(
            server_lib.synthesize_wordlevel_tokenizer(
                cfg['vocab_size'], os.path.join(run_dir, 'tokenizer.json')),
            vocab_limit=cfg['vocab_size'])
        server = server_lib.InferenceServer(engine, tokenizer,
                                            boot_t0=ctx['t0'])
        orch = _serve._Orchestrator(ctx, run_dir, _serve._free_port(),
                                    cfg['vocab_size'])
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
        reduced = rows = scopes = scope_rows = None
        if 'trace' in got:
            path = trace_reduce.find_xplane(got['trace']['log_dir'])
            rows = trace_reduce.load(path)
            reduced = trace_reduce.reduce(rows)
            scope_rows = scope_reduce.load(path)
            scopes = scope_reduce.by_scope(scope_rows)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)

    client, plan = got['client'], got['plan']
    records = client['records']
    seconds = ctx['seconds']
    e2e = _serve.end_to_end(records, seconds, client['end_s'])
    e2e['setup_s'] = client['t0'] - ctx['t0']
    failed = [r for r in records if not r['done'] or r['error']]
    finished = [r for r in records if r['done'] and not r['error']
                and r['tokens']]
    sample = check_lib.pick_sample(finished, ctx['seed'],
                                   cell['check']['sample_requests'])
    t = time.time()
    found = family.serve_gaps(cfg, ctx['seed'], [
        {'prompt': traffic_lib.request_tokens(
            ctx['seed'], r['idx'], r['prompt_len'], cfg['vocab_size']),
         'served': r['tokens']} for r in sample],
        controls=tuple(ctx.get('controls', ())),
        pad_to=cell['check']['pad_to'], rows_pad=cell['check']['rows_pad'],
        tie_margin=cell['check'].get('tie_margin', 0.0))
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
        logit_gap_mean_all=found['served'].get('logit_gap_mean_all'),
        compared_all=int(found['gaps'][None].size),
        router_flip_share=found.get('router_flip_share'),
        controls=found['controls'],
        sample=[r['idx'] for r in sample],
        requests=len(records),
        preemptions=after.get('preemptions'),
        state_bytes=after.get('state_bytes'),
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
        'extra': {'gaps_ms': _serve._gap_quantiles(records),
                  'requests': [[r['due_s'], r['sent_s'],
                                r['arrivals'][0][0] if r['arrivals'] else None,
                                r['done_s'], r['prompt_len'], len(r['tokens']),
                                r['queue_wait_s']] for r in records]},
    }
    if reduced is not None:
        kind = jax.devices()[0].device_kind
        w0, w1 = got['trace']['wall']
        window_s = max(w1 - w0, reduced['span_s'])
        out['run']['trace'] = {
            'reduced': reduced, 'scopes': scopes, 'window_s': window_s,
            'wall_s': [w0 - client['t0'], w1 - client['t0']],
            'metrics_start': got['trace']['metrics_start'],
            'metrics_stop': got['trace']['metrics_stop'],
            'peak': family.work.peaks(kind)}
        out['device'] = {'busy_s': trace_reduce.busy_mean_s(reduced),
                         'window_s': window_s}
        out['breakdown'] = {
            'device_ops': trace_reduce.top_ops(reduced),
            'idle_gaps': [list(g) for g in reduced['gaps']],
            'modules': {k: [v['count'], v['seconds']]
                        for k, v in reduced['modules'].items()},
            'scopes': scope_reduce.table(scopes)}
        # For a recorded sample (tests/benchmark/data): one decode step's
        # worth of rows from the middle of the stretch.
        mid = len(scope_rows) // 2
        out['extra']['scope_rows_sample'] = scope_rows[mid:mid + 4000]
    return out
