"""The routed experts of the decode program against their roofline."""
from benchmark import scope_reduce, work_nemotron_h
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = scope_reduce.seconds_of(
        trace.get('scopes'), own['programs_match'], own['scope'])
    assignments = _common.counter_delta(run, 'moe_local_assignments',
                                        traced=True)
    touched = _common.counter_delta(run, 'moe_experts_touched', traced=True)
    if not count or seconds <= 0 or not assignments or not touched:
        return None
    flops, bytes_ = work_nemotron_h.moe_experts_work(run['config'],
                                                     assignments, touched)
    return work_nemotron_h.roofline_share(flops, bytes_, seconds,
                                          trace['peak'])['percent']
