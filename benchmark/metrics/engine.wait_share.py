"""Share of the window the engine held no request."""
from benchmark.metrics import _engine_time


def read(run):
    return _engine_time.wait_share(run)
