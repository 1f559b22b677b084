"""The Mamba-2 mixers of the parallel-block decode program against
their roofline."""
from benchmark import scope_reduce, work_falcon_h1
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = scope_reduce.seconds_of(
        trace.get('scopes'), own['programs_match'], own['scope'])
    slot_steps = _common.counter_delta(run, 'ssm_slot_steps', traced=True)
    steps = _common.counter_delta(run, 'decode_steps', traced=True)
    if not count or seconds <= 0 or not slot_steps or not steps:
        return None
    flops, bytes_ = work_falcon_h1.ssm_decode_work(run['config'],
                                                   slot_steps, steps)
    return work_falcon_h1.roofline_share(flops, bytes_, seconds,
                                         trace['peak'])['percent']
