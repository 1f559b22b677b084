"""The decode program's share of the bf16 peak over its own device time."""
from benchmark import trace_reduce, work
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['modules'], own['modules_match'])
    contexts = _common.traced_decode_contexts(run)
    if not count or seconds <= 0 or not contexts:
        return None
    flops = work.forward_flops(run['config'], len(contexts),
                               float(sum(contexts)), len(contexts))
    return 100.0 * flops / (seconds * trace['peak']['bf16_flops_per_s'])
