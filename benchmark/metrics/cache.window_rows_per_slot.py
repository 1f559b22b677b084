"""Rows a slot that holds pages keeps in a window layer."""
from benchmark.metrics import _dots3


def read(run):
    rows = _dots3.counter_delta(run, 'window_rows_live')
    slots = _dots3.counter_delta(run, 'cache_slots_live')
    if not rows or not slots:
        return None
    return rows / slots
