"""``calibrate.py`` for a cell of a family-driven kind
(``benchmark/kinds/_serve_family.py``): never part of a benchmark run.

``python tests/benchmark/calibrate_family.py [options] -- <run.py
arguments>``. ``calibrate.py``'s ``--controls`` and ``--as-control``
wrap ``check.serve_gaps``, which such a kind does not call: its
comparison is its family's ``serve_gaps``
(``benchmark/families/<family>.py``). This wraps that one instead, the
same way; ``--set`` and ``--detail`` are ``calibrate.py``'s own.

- ``--controls bf16-state[,w8]``: the check also reads each control by
  the token that it puts first at the served positions.
- ``--as-control bf16-state``: the control's numbers stand in the
  program's place: the run's last line has to read ``correct: false``.
"""
import time

T0 = time.time()  # the process's start, handed to run.py below

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import calibrate  # noqa: E402


def instrument(family_name, controls, as_control, kept):
    family = importlib.import_module(f'benchmark.families.{family_name}')
    honest = family.serve_gaps

    def serve_gaps(*args, **kwargs):
        wanted = list(dict.fromkeys([*controls, *filter(None, [as_control])]))
        found = honest(*args, **{**kwargs, 'controls': wanted})
        kept['check'] = {
            'served': found['served'], 'controls': found['controls'],
            'gaps': {str(a): g.tolist() for a, g in found['gaps'].items()},
            'margins': np.asarray(found.get('margins', [])).tolist()}
        for name, got in found['controls'].items():
            print(f'control {name}: {json.dumps(got)}', file=sys.stderr)
        if as_control:
            found = dict(found, served=found['controls'][as_control])
        return found
    family.serve_gaps = serve_gaps


if __name__ == '__main__':
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--family', default='nemotron_h')
    ap.add_argument('--controls', default='')
    ap.add_argument('--as-control', default='')
    ap.add_argument('--set', action='append', default=[], metavar='K=V')
    ap.add_argument('--detail', default='')
    ap.add_argument('rest', nargs=argparse.REMAINDER)
    opts = ap.parse_args()
    kept = {}
    calibrate.instrument([], '', opts.set, '')
    instrument(opts.family, [c for c in opts.controls.split(',') if c],
               opts.as_control, kept)
    from benchmark import manifest, run
    if opts.detail:
        honest_kind = manifest.kind

        def kind(name):
            module = honest_kind(name)
            honest_run = module.run

            def wrapped(ctx):
                out = honest_run(ctx)
                with open(opts.detail, 'w', encoding='utf-8') as f:
                    json.dump({k: out.get(k) for k in (
                        'correct', 'end_to_end', 'checks', 'notes',
                        'extra')} | kept, f)
                return out
            module.run = wrapped
            return module
        manifest.kind = kind
    run.T0 = T0
    sys.exit(run.main([a for a in opts.rest if a != '--']))
