"""Scope ``attn`` of the prefill-chunk program against the roofline of
the algorithm's work there."""
from benchmark import scope_reduce, work_dots3
from benchmark.metrics import _dots3


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _dots3.own_file(__file__)
    seconds, count = scope_reduce.seconds_of(
        trace.get('scopes'), own['programs_match'], own['scope'])
    chunks = _dots3.traced_prefill_chunks(run)
    if not count or seconds <= 0 or not chunks:
        return None
    flops, bytes_ = work_dots3.attn_scope_work(run['config'], chunks)
    return work_dots3.roofline_share(flops, bytes_, seconds,
                                     trace['peak'])['percent']
