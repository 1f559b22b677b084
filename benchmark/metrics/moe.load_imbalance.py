"""The fullest held expert's load over the mean load, over the window."""
from benchmark.metrics import _common


def read(run):
    most = _common.counter_delta(run, 'moe_expert_load_max')
    assignments = _common.counter_delta(run, 'moe_local_assignments')
    if not most or not assignments:
        return None
    return most * run['config']['n_routed_experts'] / assignments
