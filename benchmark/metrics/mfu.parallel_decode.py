"""The parallel-block decode program's share of the bf16 peak over its
own device time."""
from benchmark import trace_reduce, work_falcon_h1
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['modules'], own['modules_match'])
    slot_steps = _common.counter_delta(run, 'ssm_slot_steps', traced=True)
    if not count or seconds <= 0 or not slot_steps:
        return None
    contexts = _common.traced_decode_contexts(run)
    flops = work_falcon_h1.decode_flops(run['config'], slot_steps,
                                        float(sum(contexts)))
    return 100.0 * flops / (seconds * trace['peak']['bf16_flops_per_s'])
