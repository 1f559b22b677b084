"""The benchmark's one command.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the machine it is
started on and prints, as the last line of its standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, traced, ``breakdown``; ``checks``, each number compared
beside its limit, comes last there and on standard error.

Without a TPU holding the chips the cell asks for it prints no result
and exits 2. ``--rehearse-cpu`` is the explicit tiny rehearsal on the
CPU (the cell file's ``rehearse`` overrides): it names its device as
the CPU and reports no device metric.
"""
from __future__ import annotations

import time

T0 = time.time()  # the process's start: `setup_s` runs from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402


def _args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearse-cpu', action='store_true')
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = _args(argv)
    if not os.path.isdir(os.path.join(ROOT, 'skypilot_tpu')):
        print('benchmark/run.py: the system under test (skypilot_tpu/) is '
              'not in this checkout', file=sys.stderr)
        return 2
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    if args.rehearse_cpu:
        os.environ['JAX_PLATFORMS'] = 'cpu'
        over = cell['cell'].get('rehearse', {})
        cell['config'] = manifest.deep_update(cell['config'],
                                              over.get('config', {}))
        cell['traffic'] = manifest.deep_update(cell['traffic'],
                                               over.get('traffic', {}))
        cell['cell'] = manifest.deep_update(cell['cell'],
                                            over.get('cell', {}))
    import jax
    devices = jax.devices()
    t_devices = time.time()   # jax has reached the chip
    platform, chips = devices[0].platform, cell['entry']['chips']
    if not args.rehearse_cpu and (platform != 'tpu' or len(devices) < chips):
        print(f'benchmark/run.py: {args.workload} needs {chips} TPU chip(s); '
              f'jax found {len(devices)} x {platform} '
              f'({devices[0].device_kind}). --rehearse-cpu is the tiny CPU '
              f'rehearsal.', file=sys.stderr)
        return 2

    ctx = {'t0': T0, 't_devices': t_devices, 'root': ROOT, 'bench': bench,
           'workload': args.workload, 'seed': args.seed,
           'seconds': args.seconds,
           'trace': bool(args.trace) and not args.rehearse_cpu,
           'rehearse': args.rehearse_cpu, **cell}
    out = manifest.kind(cell['cell']['kind']).run(ctx)

    section = 'per_layer' if args.trace else 'end_to_end'
    metrics: Dict[str, Any] = {}
    for m in manifest.metrics_of(bench, section, args.workload):
        if section == 'end_to_end':
            value = out['end_to_end'].get(m['name'])
        else:
            value = manifest.metric_reader(m['name']).read(out['run'])
        if value is not None:
            metrics[m['name']] = {'value': float(value), 'unit': m['unit']}
    device = {'platform': platform, 'kind': devices[0].device_kind,
              'count': len(devices),
              'memory_peak_bytes': out['memory_peak_bytes'],
              **out.get('device', {})}
    line: Dict[str, Any] = {
        'correct': bool(out['correct']), 'attempted': out['attempted'],
        'failed': out['failed'], 'metrics': metrics, 'device': device}
    if args.trace and out.get('breakdown'):
        line['breakdown'] = out['breakdown']
    if args.trace:
        line['end_to_end_traced'] = out['end_to_end']
    line['notes'] = out.get('notes', {})
    line['checks'] = out['checks']
    sys.stdout.flush()
    for name, c in out['checks'].items():
        print(f'check {name}: value={c["value"]} limit={c["limit"]}',
              file=sys.stderr)
    print(f'correct={line["correct"]}', file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
