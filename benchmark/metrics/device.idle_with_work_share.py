"""Device idle while the engine had work, over the traced stretch."""
from benchmark.metrics import _engine_time


def read(run):
    return _engine_time.idle_with_work_share(run)
