"""The paged decode attention kernel at a group of 5 against its
roofline."""
from benchmark import trace_reduce, work_falcon_h1
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['ops'], own['ops_match'])
    contexts = _common.traced_decode_contexts(run)
    if not count or seconds <= 0 or not contexts:
        return None
    flops, bytes_ = work_falcon_h1.paged_decode_work(
        run['config'], contexts, run['config']['engine']['page_size'])
    return work_falcon_h1.roofline_share(flops, bytes_, seconds,
                                         trace['peak'])['percent']
