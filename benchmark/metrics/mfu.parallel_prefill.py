"""The parallel-block prefill-chunk program's share of the bf16 peak
over its own device time."""
from benchmark import trace_reduce, work_falcon_h1
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['modules'], own['modules_match'])
    chunks = _common.traced_prefill_chunks(run)
    if not count or seconds <= 0 or not chunks:
        return None
    flops = work_falcon_h1.prefill_flops(run['config'], chunks)
    return 100.0 * flops / (seconds * trace['peak']['bf16_flops_per_s'])
