"""The first token's stamp to the first line flushed to the socket."""
from benchmark.metrics import _phases


def read(run):
    return _phases.mean_ms(run, 'first_token', 'first_flush')
