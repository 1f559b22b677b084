"""What can be said about the chip path without a chip: where compiled
programs are cached, and that nothing which measures or smokes the chip
passes on a CPU unless asked to rehearse."""
import json
import os
import subprocess
import sys

import pytest

from skypilot_tpu.utils import jax_env

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(args, env_extra=None, timeout=60):
    env = {k: v for k, v in os.environ.items()
           if k != jax_env.CACHE_ENV}
    env['PYTHONPATH'] = ROOT
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_cache_resolver_env_beats_flag_beats_fixed_default(monkeypatch):
    monkeypatch.delenv(jax_env.CACHE_ENV, raising=False)
    default = jax_env.compile_cache_dir()
    # Fixed and inside the checkout: the path is part of the cache key.
    assert default == os.path.join(ROOT, '.jax_compile_cache')
    assert jax_env.compile_cache_dir('/flag') == '/flag'
    monkeypatch.setenv(jax_env.CACHE_ENV, '/placed')
    assert jax_env.compile_cache_dir('/flag') == '/placed'
    assert jax_env.compile_cache_dir() == '/placed'


def test_processes_attach_the_same_cache(tmp_path):
    """Two fresh processes report one directory in force: the fixed
    default when nothing places it, the environment's when set — and
    then no flag moves it."""
    report = ('import jax; from skypilot_tpu.utils import jax_env; '
              'print(jax_env.attach_compile_cache("{flag}")); '
              'print(jax.config.jax_compilation_cache_dir)')
    placed = str(tmp_path / 'placed')
    flag = str(tmp_path / 'flag')
    a = _run(['-c', report.format(flag=flag)],
             {jax_env.CACHE_ENV: placed})
    b = _run(['-c', 'from skypilot_tpu.utils import jax_env; '
                    'print(jax_env.compile_cache_dir())'])
    assert a.returncode == 0, a.stderr
    assert a.stdout.split() == [placed, placed]
    assert not os.path.exists(flag)
    assert b.stdout.strip() == jax_env.DEFAULT_CACHE_DIR


def test_chip_smoke_without_a_chip_fails_fast_and_prints_no_result(
        tmp_path):
    proc = _run(['chip_smoke.py', '--out', str(tmp_path)],
                {'JAX_PLATFORMS': 'cpu'}, timeout=120)
    assert proc.returncode != 0
    assert "needs 'tpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_runs_every_phase_green(tmp_path):
    """The explicit rehearsal drives the same phases as the chip run
    (4 host devices, so the four-chip phases too)."""
    proc = _run(['chip_smoke.py', '--rehearse-cpu', '--out',
                 str(tmp_path)], timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:]
    for phase in ('kernels', 'serve', 'train', 'train4', 'serve4',
                  'graft'):
        assert f'[smoke] {phase}: PASS' in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        'ok': True, 'rehearsal': True,
        'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 4}}


@pytest.mark.slow
def test_graft_entry_refuses_to_pass_without_a_tpu():
    """A graft entry told to expect a TPU (JAX_PLATFORMS=tpu) fails in
    jax rather than on a CPU mesh."""
    proc = _run(['__graft_entry__.py', '4'], {'JAX_PLATFORMS': 'tpu'},
                timeout=300)
    assert proc.returncode != 0
    assert 'all checks passed' not in proc.stdout
