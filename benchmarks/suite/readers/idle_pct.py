"""Share of the traced window in which no operation ran on the device
(mean over the chips used), from the profiler's trace."""


def read(context):
    trace = context.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
