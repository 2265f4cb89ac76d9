"""Executions of one named device operation per traced step, per device:
the events of the operations whose name ends in ``/<name>`` in the trace,
over the traced steps and the devices.  (Needed work counts a kernel once
a layer; a remat block runs its forward a second time unless the compiler
merges the two.)"""


def read(context, name: str):
    trace = context.get("trace")
    steps = context.get("trace_steps")
    if not trace or not steps:
        return None
    events = sum(count for key, count in trace["op_events"].items()
                 if key.endswith("/" + name))
    if not events:
        return None
    return events / (steps * max(trace["devices"], 1))
