"""Of the keys the indexer scored, the share the selection kept."""
from benchmark.metrics import _dots3


def read(run):
    scored = _dots3.counter_delta(run, 'index_scored_keys')
    selected = _dots3.counter_delta(run, 'index_selected_keys')
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
