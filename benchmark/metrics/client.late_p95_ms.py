"""How late the load generator ran: p95 of send time minus due time."""
from benchmark.metrics import _common


def read(run):
    late = [r['sent_s'] - r['due_s'] for r in run['records']
            if r['sent_s'] is not None]
    p = _common.percentile(late, 0.95)
    return None if p is None else 1e3 * p
