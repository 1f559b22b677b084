"""The prefill-chunk program's share of the bf16 peak over its own
device time: the algorithm's operations, the selection's among them."""
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
    chunks = _dots3.traced_prefill_chunks(run)
    if not count or seconds <= 0 or per_token is None or not chunks:
        return None
    flops = work_dots3.prefill_flops(run['config'], chunks, per_token)
    return 100.0 * flops / (seconds * trace['peak']['bf16_flops_per_s'])
