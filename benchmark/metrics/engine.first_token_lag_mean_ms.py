"""Last prefill chunk dispatched to the first token's stamp."""
from benchmark.metrics import _phases


def read(run):
    return _phases.mean_ms(run, 'prefill_dispatched', 'first_token')
