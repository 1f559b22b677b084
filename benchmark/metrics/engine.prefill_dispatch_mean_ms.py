"""First prefill chunk dispatched to the last one dispatched."""
from benchmark.metrics import _phases


def read(run):
    return _phases.mean_ms(run, 'first_dispatch', 'prefill_dispatched')
