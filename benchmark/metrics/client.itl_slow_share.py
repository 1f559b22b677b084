"""Share of the token gaps longer than the file's ``slow_ms``."""
from benchmark.metrics import _common
from benchmark.stats import token_gaps


def read(run):
    gaps = token_gaps(run['records'])
    if not gaps:
        return None
    slow = _common.own_file(__file__)['slow_ms'] / 1e3
    return 100.0 * sum(1 for g in gaps if g > slow) / len(gaps)
