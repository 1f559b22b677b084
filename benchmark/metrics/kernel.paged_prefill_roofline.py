"""The paged prefill attention kernel against its roofline."""
from benchmark import trace_reduce, work
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['ops'], own['ops_match'])
    chunks = _common.traced_prefill_chunks(run)
    prefill = _common.counter_delta(run, 'prefill_tokens', traced=True)
    seen = sum(c for c, _ in chunks)
    if not count or seconds <= 0 or not seen or not prefill:
        return None
    flops, bytes_ = work.paged_prefill_work(run['config'], chunks)
    scale = prefill / seen
    return work.roofline_share(flops * scale, bytes_ * scale, seconds,
                               trace['peak'])['percent']
