"""Attention over the selection's kept rows against the roofline of
the algorithm's work (the chosen rows alone)."""
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
    contexts = _dots3.traced_decode_contexts(run)
    if not count or seconds <= 0 or not (chunks or contexts):
        return None
    flops, bytes_ = work_dots3.selected_attention_work(run['config'], chunks,
                                                       contexts)
    return work_dots3.roofline_share(flops, bytes_, seconds,
                                     trace['peak'])['percent']
