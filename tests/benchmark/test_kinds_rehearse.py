"""Each kind end to end at a tiny size on the CPU: the whole of a run
but the look for a chip (``--rehearse-cpu``), the real server, load
balancer and client child among it."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

BENCH = manifest.load()
CELLS = [w['name'] for w in BENCH['workloads']]


def _run(*argv, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)   # one CPU device: a one-chip cell
    return subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, 'run.py'), *argv],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)


@pytest.mark.parametrize('name', CELLS)
@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_prints_a_correct_result(name, trace):
    proc = _run('--workload', name, '--seed', str(2**31 + 11 + trace),
                '--seconds', '5', '--trace', str(trace), '--rehearse-cpu')
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == 'checks'
    assert line['correct'] is True, line['checks']
    assert line['attempted'] > 0 and line['failed'] == 0
    assert line['device']['platform'] == 'cpu'
    assert 'busy_s' not in line['device']
    want = {m['name'] for m in manifest.metrics_of(
        BENCH, 'per_layer' if trace else 'end_to_end', name)}
    got = set(line['metrics'])
    if trace:
        # no device on the CPU: what the profiler would feed stays out
        device = {m['name'] for m in BENCH['per_layer']
                  if m['source'] == 'device_trace'}
        assert got == want - device and got
    else:
        assert got == want
    for m in line['metrics'].values():
        # an end-to-end metric is never 0; a share among the per-layer
        # ones may be
        assert (m['value'] >= 0 if trace else m['value'] > 0) and m['unit']
    for c in line['checks'].values():
        assert set(c) == {'value', 'limit'}
    assert proc.stderr.strip().splitlines()[-1] == 'correct=True'


def test_a_run_without_a_tpu_prints_no_result():
    proc = _run('--workload', CELLS[0], '--seed', '1', '--seconds', '5',
                '--trace', '0', timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert 'needs 1 TPU chip' in proc.stderr
