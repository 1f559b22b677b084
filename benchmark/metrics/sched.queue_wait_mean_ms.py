"""The scheduler's share of the time to first token, as a mean."""
from benchmark.metrics import _phases


def read(run):
    return _phases.mean_ms(run, 'submit', 'first_dispatch')
