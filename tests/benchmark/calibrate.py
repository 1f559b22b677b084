"""Readings for defining a cell: never part of a benchmark run.

``python tests/benchmark/calibrate.py [options] -- <run.py arguments>``
drives ``benchmark/run.py`` as it is, with the builder's instruments
around it. ``benchmark/run.py`` itself has no option for any of this.

- ``--controls w8a8[,int8]``: the check also reads each control (the
  reference in a lower precision, ``benchmark/reference/mistral.py``)
  by the tokens that it puts first at the served positions.
- ``--as-control w8a8``: the control's tokens stand in the program's
  place, so that the run's last line shows what ``correct`` makes of
  them: it has to read false.
- ``--set K=V``: one number of the traffic mix changed, for the sweep
  that finds the knee (``--set rate_rps=2.5``).
- ``--detail FILE``: the whole result, every compared gap with it.
"""
import time

T0 = time.time()  # the process's start, handed to run.py below

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def instrument(controls, as_control, sets, detail):
    from benchmark import check, manifest
    kept = {}
    honest_gaps, honest_cell, honest_kind = (
        check.serve_gaps, manifest.cell, manifest.kind)

    def serve_gaps(*args, **kwargs):
        wanted = list(dict.fromkeys([*controls, *filter(None, [as_control])]))
        found = honest_gaps(*args, **{**kwargs, 'controls': wanted})
        kept['check'] = {
            'served': found['served'], 'controls': found['controls'],
            'gaps': {str(a): g.tolist() for a, g in found['gaps'].items()}}
        for name, got in found['controls'].items():
            print(f'control {name}: {json.dumps(got)}', file=sys.stderr)
        if as_control:
            found = dict(found, served=found['controls'][as_control])
        return found

    def cell(*args, **kwargs):
        out = honest_cell(*args, **kwargs)
        for item in sets:
            key, value = item.split('=', 1)
            out['traffic'][key] = json.loads(value)
        return out

    def kind(name):
        module = honest_kind(name)
        honest_run = module.run

        def run(ctx):
            out = honest_run(ctx)
            if detail:
                with open(detail, 'w', encoding='utf-8') as f:
                    json.dump({k: out.get(k) for k in (
                        'correct', 'end_to_end', 'checks', 'notes',
                        'extra')} | kept, f)
            return out
        module.run = run
        return module
    check.serve_gaps, manifest.cell, manifest.kind = serve_gaps, cell, kind


if __name__ == '__main__':
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--controls', default='')
    ap.add_argument('--as-control', default='')
    ap.add_argument('--set', action='append', default=[], metavar='K=V')
    ap.add_argument('--detail', default='')
    ap.add_argument('rest', nargs=argparse.REMAINDER)
    opts = ap.parse_args()
    instrument([c for c in opts.controls.split(',') if c], opts.as_control,
               opts.set, opts.detail)
    from benchmark import run
    run.T0 = T0
    sys.exit(run.main([a for a in opts.rest if a != '--']))
