"""Prompt tokens a recorded engine step, over the window."""
from benchmark.metrics import _common


def read(run):
    tokens = _common.counter_delta(run, 'prefill_tokens')
    steps = _common.counter_delta(run, 'stepline_steps')
    if not tokens or not steps:
        return None
    return tokens / steps
