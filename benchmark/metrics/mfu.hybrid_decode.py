"""The hybrid decode program's share of the bf16 peak over its own
device time."""
from benchmark import trace_reduce, work_nemotron_h
from benchmark.metrics import _common


def read(run):
    trace = run['trace']
    if not trace:
        return None
    own = _common.own_file(__file__)
    seconds, count = trace_reduce.seconds_matching(
        trace['reduced']['modules'], own['modules_match'])
    slot_steps = _common.counter_delta(run, 'ssm_slot_steps', traced=True)
    assignments = _common.counter_delta(run, 'moe_local_assignments',
                                        traced=True)
    if not count or seconds <= 0 or not slot_steps or assignments is None:
        return None
    contexts = _common.traced_decode_contexts(run)
    flops = work_nemotron_h.decode_flops(
        run['config'], slot_steps, assignments, float(sum(contexts)))
    return 100.0 * flops / (seconds * trace['peak']['bf16_flops_per_s'])
