"""The indexer's scoring kernel against its roofline."""
from benchmark import trace_reduce, work_dots3
from benchmark.metrics import _dots3


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _dots3.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['ops'], own['ops_match'])
    chunks = _dots3.traced_prefill_chunks(run)
    if not count or seconds <= 0 or not chunks:
        return None
    flops, bytes_ = work_dots3.index_scores_work(run['config'], chunks)
    return work_dots3.roofline_share(flops, bytes_, seconds,
                                     trace['peak'])['percent']
