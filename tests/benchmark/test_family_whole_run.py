"""A whole rehearsal run of the family-driven cell with its control's
tokens standing in the program's place (``calibrate_family.py
--as-control <the cell file's control>``): ``correct`` comes out false
in the run's last line. (``test_control.py`` does this for the
dense-block cells through ``calibrate.py``, which wraps
``check.serve_gaps``; a family-driven kind compares through its
family's ``serve_gaps``, so that case of it is marked in
``conftest.py`` and this one stands for it.)

The control is the cell's own, the nearest precision below the stated
one that the comparison can fail (W8A8). The rehearsal compares every
request it finished (``rehearse.cell.check.sample_requests``), some 80
settled tokens of a 7-block model: over a quarter of that, W8A8 often
puts the same tokens first as the reference. On the chip the control
was read at the cell's own size (the cell file's ``readings``)."""
import json
import os
import subprocess
import sys

from benchmark import manifest

NAME = 'nemotron3-nano-serve.chat-bursty'


def test_the_control_in_the_programs_place_makes_a_whole_run_incorrect():
    control = manifest.cell(manifest.load(), NAME)['cell']['check']['control']
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), 'calibrate_family.py'),
         '--as-control', control, '--', '--workload', NAME, '--seed', '43',
         '--seconds', '4', '--trace', '0', '--rehearse-cpu'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stderr.strip().splitlines()[-1] == 'correct=False'
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line['correct'] is False and line['failed'] == 0
    assert any(line['checks'][n]['value'] > line['checks'][n]['limit']
               for n in ('logit_gap_max', 'logit_gap_mean'))
    assert line['notes']['controls'][control]['mismatch_share'] > 0
