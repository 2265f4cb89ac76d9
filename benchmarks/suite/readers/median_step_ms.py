"""Median seconds between two finished train steps in the window, on the
host's clock closed by ``block_until_ready``, in ms."""

import statistics


def read(context):
    times = context.get("step_times")
    return statistics.median(times) * 1e3 if times else None
