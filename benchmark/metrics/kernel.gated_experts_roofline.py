"""The gated routed experts of both step programs against their
roofline."""
from benchmark import scope_reduce, work_dots3
from benchmark.metrics import _dots3


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _dots3.own_file(__file__)
    seconds, count = scope_reduce.seconds_of(
        trace.get('scopes'), own['programs_match'], own['scope'])
    assignments = _dots3.counter_delta(run, 'moe_local_assignments',
                                       traced=True)
    touched = _dots3.counter_delta(run, 'moe_experts_touched', traced=True)
    if not count or seconds <= 0 or not assignments or not touched:
        return None
    flops, bytes_ = work_dots3.gated_experts_work(run['config'], assignments,
                                                  touched)
    return work_dots3.roofline_share(flops, bytes_, seconds,
                                     trace['peak'])['percent']
