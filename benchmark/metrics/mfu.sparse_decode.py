"""The decode program's share of the bf16 peak over its own device
time."""
from benchmark import trace_reduce, work_dots3
from benchmark.metrics import _dots3


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _dots3.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['modules'], own['modules_match'])
    per_token = _dots3.assignments_per_token(run)
    contexts = _dots3.traced_decode_contexts(run)
    if not count or seconds <= 0 or per_token is None or not contexts:
        return None
    flops = work_dots3.decode_flops(run['config'], contexts, per_token)
    return 100.0 * flops / (seconds * trace['peak']['bf16_flops_per_s'])
