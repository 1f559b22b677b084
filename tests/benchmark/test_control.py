"""The comparison that decides ``correct`` fails what it has to fail.

The control is the reference put in the program's place and computed in
the nearest precision below the configuration's bfloat16 activations,
the step that would tempt a later PR: W8A8, every weight product's left
input in int8 with one scale a token row. Its tokens must come out as
not correct under the cell's limits, read both ways: decoded greedily,
and as its first choice at each position of the reference's own tokens.
The reference's own tokens read 0. On the chip the same readings were
taken at the cell's own size (PERF.md section 2); here the size is the
rehearsal's, and so are the limits (the cell file's ``rehearse`` block).

The other tests drive a whole rehearsal run: once with the engine's
sampler altered underneath, once with the control's tokens standing in
the program's place (``calibrate.py --as-control``), and see ``correct``
come out false in the run's last line.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import check, manifest, traffic
from benchmark.reference import mistral as ref

BENCH = manifest.load()
CELLS = [w['name'] for w in BENCH['workloads']]


def _rehearsal(name):
    cell = manifest.cell(BENCH, name)
    over = cell['cell']['rehearse']
    cfg = manifest.deep_update(cell['config'], over['config'])
    limits = manifest.deep_update(cell['cell'], over['cell'])['check']
    return cfg, limits


def _greedy(cfg, seed, act, n_prompts=3, prompt_len=24, n_new=40):
    fwd = jax.jit(functools.partial(ref.forward, cfg, act=act))
    weights = check.reference_weights(cfg, seed)
    samples = []
    for i in range(n_prompts):
        prompt = traffic.request_tokens(seed, i, prompt_len,
                                        cfg['vocab_size'])
        seq, served = list(prompt) + [0] * n_new, []
        for j in range(n_new):
            tok = int(fwd(weights, jnp.asarray(seq))[prompt_len + j - 1]
                      .argmax())
            served.append(tok)
            seq[prompt_len + j] = tok
        samples.append({'prompt': prompt, 'served': served})
    return samples


def _ok(found, spec):
    return all(c['ok'] for c in check.verdict(found, spec, {}).values())


@pytest.mark.parametrize('seed', [5, 6, 2**31 + 7])
def test_the_control_comes_out_as_not_correct(seed):
    cfg, spec = _rehearsal(CELLS[0])
    control = spec['control']
    own = check.serve_gaps(cfg, seed, _greedy(cfg, seed, None),
                           controls=(control,), pad_to=(32,))
    assert _ok(own, spec) and own['served']['mismatch_share'] == 0
    # the control without decoding: its first choice at every position
    # of the reference's own tokens, in the program's place
    assert not _ok(dict(own, served=own['controls'][control]), spec)
    for name, limit in spec['limits'].items():
        assert own['controls'][control][name] > 3 * limit, name
    low = check.serve_gaps(cfg, seed, _greedy(cfg, seed, control),
                           pad_to=(32,))
    assert not _ok(low, spec), low['served']


def test_every_cell_states_its_limits_between_its_readings():
    for name in CELLS:
        c = manifest.cell(BENCH, name)['cell']['check']
        assert set(c['limits']) == {'logit_gap_max', 'logit_gap_mean'}
        for number, limit in c['limits'].items():
            read = c['readings'][number]
            assert read['program_max'] < limit < read['control_min'], number
            assert read['control_min'] >= 3 * read['program_max'], number
            # more room above the lower reading than below the upper
            assert limit / read['program_max'] > 1.5, number


def _whole_run(script, extra, name, seed):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), script),
         *extra, '--workload', name, '--seed', str(seed), '--seconds', '4',
         '--trace', '0', '--rehearse-cpu'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stderr.strip().splitlines()[-1] == 'correct=False'
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize('name', CELLS)
def test_an_altered_token_makes_a_whole_run_incorrect(name):
    line = _whole_run('faulty_run.py', ['alter_token'], name, 41)
    gap = line['checks']['logit_gap_max']
    assert line['correct'] is False and gap['value'] > gap['limit']


@pytest.mark.parametrize('name', CELLS)
def test_the_control_in_the_programs_place_makes_a_whole_run_incorrect(name):
    control = manifest.cell(BENCH, name)['cell']['check']['control']
    line = _whole_run('calibrate.py', ['--as-control', control, '--'],
                      name, 43)
    assert line['correct'] is False
    assert any(line['checks'][n]['value'] > line['checks'][n]['limit']
               for n in ('logit_gap_max', 'logit_gap_mean'))
