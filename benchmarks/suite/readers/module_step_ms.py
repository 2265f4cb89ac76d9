"""Device time of one jitted program's executions in the trace, per step:
summed duration of the ``module`` events over their number, over the
``steps_key`` of the engine arguments each execution scans."""


def read(context, module: str, steps_key: str = ""):
    trace = context.get("trace")
    if not trace or module not in trace["modules"]:
        return None
    events = trace["module_events"][module]
    steps = context["cell"]["traffic"]["engine"][steps_key] if steps_key else 1
    return trace["modules"][module] / (events * steps) * 1e3
