"""Host-busy milliseconds an engine step, from the stepline."""
from benchmark.metrics import _common


def read(run):
    return _common.host_ms_per_step(run)
