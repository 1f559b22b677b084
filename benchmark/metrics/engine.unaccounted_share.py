"""What of the engine thread's time no record names."""
from benchmark.metrics import _engine_time


def read(run):
    return _engine_time.unaccounted_share(run)
