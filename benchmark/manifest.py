"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell, a configuration, a traffic mix, a kind and a per-layer metric
each sit in files of their own; nothing here lists them. Adding one is
adding its files and its entry:

- cell ``c``: ``benchmark/workloads/c.json`` (kind, per-run settings,
  the check's sample and limit, the tiny rehearsal's overrides);
- configuration: the ``file`` its entry names;
- traffic mix ``t``: ``benchmark/mixes/t.json``;
- kind ``k``: ``benchmark/kinds/k.py`` with ``run(ctx)``;
- per-layer metric ``m``: ``benchmark/metrics/m.json`` and
  ``benchmark/metrics/m.py`` with ``read(run)``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT_RE = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


def read_json(path: str) -> Any:
    with open(path, encoding='utf-8') as f:
        return json.load(f)


def load(root: str = ROOT) -> Dict[str, Any]:
    return read_json(os.path.join(root, 'BENCHMARK.json'))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell(bench: Dict[str, Any], name: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one cell runs with: its entry, its own file, its
    configuration and its traffic mix."""
    entry = next((w for w in bench['workloads'] if w['name'] == name), None)
    if entry is None:
        raise KeyError(f'no workload {name!r} in BENCHMARK.json (have: '
                       f'{[w["name"] for w in bench["workloads"]]})')
    config_entry = next(c for c in bench['configs']
                        if c['name'] == entry['config'])
    return {
        'entry': entry,
        'cell': read_json(os.path.join(HERE, 'workloads', f'{name}.json')),
        'config': read_json(os.path.join(root, config_entry['file'])),
        'traffic': read_json(os.path.join(HERE, 'mixes',
                                      f'{entry["traffic"]}.json')),
    }


def kind(name: str):
    return _module(os.path.join(HERE, 'kinds', f'{name}.py'),
                   f'benchmark_kind_{name}')


def metric_reader(name: str):
    return _module(os.path.join(HERE, 'metrics', f'{name}.py'),
                   'benchmark_metric_' + re.sub(r'\W', '_', name))


def metric_file(name: str) -> Dict[str, Any]:
    return read_json(os.path.join(HERE, 'metrics', f'{name}.json'))


def metrics_of(bench: Dict[str, Any], section: str,
               workload: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` that ``workload`` reports: those that
    list it, and those that list no cells at all."""
    return [m for m in bench[section]
            if 'workloads' not in m or workload in m['workloads']]


def deep_update(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = (deep_update(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def problems(root: str = ROOT) -> List[str]:
    """What is inconsistent between ``BENCHMARK.json`` and the files it
    names; empty when all is well. The tests run this."""
    bench = load(root)
    bad: List[str] = []
    e2e = {m['name']: m for m in bench['end_to_end']}
    cells = {w['name']: w for w in bench['workloads']}
    configs = {c['name']: c for c in bench['configs']}
    for section in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        names = [x['name'] for x in bench[section]]
        bad += [f'{section}: bad name {n!r}' for n in names
                if not NAME_RE.match(n)]
        bad += [f'{section}: {n!r} twice' for n in set(names)
                if names.count(n) > 1]
    for m in bench['end_to_end'] + bench['per_layer']:
        if not UNIT_RE.match(m['unit']):
            bad.append(f'{m["name"]}: bad unit {m["unit"]!r}')
        if m['better'] not in ('lower', 'higher'):
            bad.append(f'{m["name"]}: better={m["better"]!r}')
        bad += [f'{m["name"]}: unknown cell {w!r}'
                for w in m.get('workloads', []) if w not in cells]
    if 'setup_s' not in e2e:
        bad.append('end_to_end lacks setup_s')
    for name, c in configs.items():
        path = os.path.join(root, c['file'])
        if not os.path.isfile(path):
            bad.append(f'config {name}: no file {c["file"]}')
            continue
        body = read_json(path)
        if sorted(body.get('reduced', [])) != sorted(c['reduced']):
            bad.append(f'config {name}: reduced differs from its file')
        if not any(w['config'] == name for w in cells.values()):
            bad.append(f'config {name}: used by no cell')
    for name, w in cells.items():
        if w['config'] not in configs:
            bad.append(f'cell {name}: unknown config {w["config"]!r}')
            continue
        for sub, fname in (('workloads', name), ('mixes', w['traffic'])):
            if not os.path.isfile(os.path.join(root, 'benchmark', sub,
                                               f'{fname}.json')):
                bad.append(f'cell {name}: no benchmark/{sub}/{fname}.json')
        body_path = os.path.join(root, 'benchmark', 'workloads',
                                 f'{name}.json')
        if os.path.isfile(body_path):
            k = read_json(body_path).get('kind')
            if not os.path.isfile(os.path.join(root, 'benchmark', 'kinds',
                                               f'{k}.py')):
                bad.append(f'cell {name}: no benchmark/kinds/{k}.py')
        mine = [m['name'] for m in metrics_of(bench, 'end_to_end', name)]
        if 'setup_s' not in mine or len(mine) < 2:
            bad.append(f'cell {name}: reports {mine}, needs setup_s and '
                       f'another end-to-end metric')
        if not metrics_of(bench, 'per_layer', name):
            bad.append(f'cell {name}: reports no per-layer metric')
    for m in bench['per_layer']:
        n = m['name']
        for ext in ('json', 'py'):
            if not os.path.isfile(os.path.join(root, 'benchmark', 'metrics',
                                               f'{n}.{ext}')):
                bad.append(f'metric {n}: no benchmark/metrics/{n}.{ext}')
        if m['moves'] not in e2e:
            bad.append(f'metric {n}: moves unknown {m["moves"]!r}')
            continue
        moved = e2e[m['moves']]
        for w in m.get('workloads', list(cells)):
            if 'workloads' in moved and w not in moved['workloads']:
                bad.append(f'metric {n}: cell {w} does not report '
                           f'{m["moves"]}')
        path = os.path.join(root, 'benchmark', 'metrics', f'{n}.json')
        if os.path.isfile(path):
            own = read_json(path)
            for key in ('unit', 'better', 'source', 'layer', 'moves'):
                if own.get(key) != m[key]:
                    bad.append(f'metric {n}: {key} differs from its file')
    return bad
