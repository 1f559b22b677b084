"""Slots whose recurrent state a decode step advanced, a step."""
from benchmark.metrics import _common


def read(run):
    slot_steps = _common.counter_delta(run, 'ssm_slot_steps')
    steps = _common.counter_delta(run, 'decode_steps')
    if not slot_steps or not steps:
        return None
    return slot_steps / steps
