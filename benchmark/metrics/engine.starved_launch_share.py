"""Launches with work in hand that found the device empty."""
from benchmark.metrics import _engine_time


def read(run):
    return _engine_time.starved_launch_share(run)
