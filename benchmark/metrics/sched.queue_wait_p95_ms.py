"""The scheduler's share of the time to first token: p95 of the done
line's ``queue_wait_s``."""
from benchmark.metrics import _common


def read(run):
    waits = [r['queue_wait_s'] for r in run['records']
             if r.get('queue_wait_s') is not None]
    p = _common.percentile(waits, 0.95)
    return None if p is None else 1e3 * p
