"""The engine thread's CPU milliseconds a step."""
from benchmark.metrics import _engine_time


def read(run):
    return _engine_time.cpu_ms_per_step(run)
