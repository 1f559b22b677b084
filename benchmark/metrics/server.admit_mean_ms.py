"""The server's front end before the engine: handler entry to submit."""
from benchmark.metrics import _phases


def read(run):
    return _phases.mean_ms(run, 'submit.recv_t', 'submit')
