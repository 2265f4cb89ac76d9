"""How many of the window's steps took over ``OVER`` x the median step, on
the host's clock closed by ``block_until_ready``.  The cell's loop waits for
each step before it sends the next, so a stall of the host shows as a few
slow steps among steady ones, where a slower program moves the median."""

import statistics

OVER = 1.05


def count(times: list):
    if not times:
        return None
    limit = OVER * statistics.median(times)
    return sum(1 for t in times if t > limit)


def read(context):
    slow = count(context.get("step_times"))
    return None if slow is None else float(slow)
