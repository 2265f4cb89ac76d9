"""From a profiler trace (``*.xplane.pb``) to numbers: the device's busy and
idle seconds, each operation's own device time, and the longest idle gaps
laid to the programs around them.  Read with nothing but jax
(``jax.profiler.ProfileData``); checked on a small recorded trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def _plain(name: str) -> str:
    """Names that survive a recompile: ``jit_run_steps(4711)`` ->
    ``jit_run_steps``; an operation's event carries its whole HLO line
    (``%fusion.123 = bf16[...] fusion(...)``) -> ``fusion``, and a Mosaic
    kernel (``custom_call_target="tpu_custom_call"``) keeps that mark, as
    ``attention(tpu_custom_call)``: kernels have no other stable name yet."""
    text = name.strip()
    short = text.split(" = ", 1)[0].lstrip("%")
    short = re.sub(r"\(.*\)$", "", short)
    short = re.sub(r"[.\d]+$", "", short) or short
    if "tpu_custom_call" in text:
        short += "(tpu_custom_call)"
    return short


def _union(intervals: list) -> list:
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _module_at(modules: list, t: float) -> str:
    """The program running at ``t``; ``modules`` sorted by start."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return "no_module"


def reduce_events(devices: list, window_ns: tuple) -> dict:
    """``devices`` is one ``{"ops": [(start, end, name)], "modules":
    [...]}`` per device, times in ns; ``window_ns`` the traced span."""
    window_s = (window_ns[1] - window_ns[0]) / 1e9
    n = len(devices)
    busy, ops, counts, gaps, module_s, module_n = [], {}, {}, {}, {}, {}
    for dev in devices:
        modules = sorted(dev["modules"])
        base = dev["ops"] or dev["modules"]
        merged = _union([(s, e) for s, e, _ in base])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        # Lay each operation to the program (module) running at its start.
        ordered = sorted(dev["ops"], key=lambda e: (e[0], -e[1]))
        for (start, _, name), own in zip(ordered, _own_times(ordered)):
            key = f"{_plain(_module_at(modules, start))}/{_plain(name)}"
            ops[key] = ops.get(key, 0.0) + own / 1e9 / n
            counts[key] = counts.get(key, 0) + 1
        for start, end, name in modules:
            key = _plain(name)
            module_s[key] = module_s.get(key, 0.0) + (end - start) / 1e9 / n
            module_n[key] = module_n.get(key, 0) + 1
        edges = [[window_ns[0], window_ns[0]]] + merged + [
            [window_ns[1], window_ns[1]]]
        for (_, idle_from), (idle_to, _) in zip(edges, edges[1:]):
            if idle_to <= idle_from:
                continue
            before = _plain(_module_at(modules, idle_from - 1))
            after = _plain(_module_at(modules, idle_to + 1))
            key = f"{before}>{after}"
            gaps[key] = gaps.get(key, 0.0) + (idle_to - idle_from) / 1e9 / n
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n if n else 0.0,
        "busy_by_device_s": busy,
        "devices": n,
        "ops": dict(top(ops)),
        "op_events": counts,
        "modules": dict(top(module_s)),
        "module_events": {k: v / max(n, 1) for k, v in module_n.items()},
        "idle_gaps": dict(top(gaps)),
    }


def _own_times(ordered: list) -> list:
    """Each event's duration less the events nested inside it (a ``while``
    holds its body's operations); ``ordered`` by (start, -end)."""
    own = [end - start for start, end, _ in ordered]
    stack: list = []
    for i, (start, end, _) in enumerate(ordered):
        while stack and ordered[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(end, ordered[stack[-1]][1]) - start
        stack.append(i)
    return own


def read_xplane(path: str) -> tuple[list, tuple]:
    """The device planes' operation and module events of one trace file,
    and the span from the first event of any plane to the last."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, lo, hi = [], None, None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        dev = {"ops": [], "modules": [], "name": plane.name}
        for line in plane.lines:
            keep = is_device and line.name in (OPS_LINE, MODULES_LINE)
            for event in line.events:
                start = float(event.start_ns)
                end = start + float(event.duration_ns)
                lo = start if lo is None else min(lo, start)
                hi = end if hi is None else max(hi, end)
                if keep:
                    dev["ops" if line.name == OPS_LINE else "modules"].append(
                        (start, end, event.name)
                    )
        if is_device and (dev["ops"] or dev["modules"]):
            devices.append(dev)
    return devices, (lo or 0.0, hi or 0.0)


def reduce_file(path: str, trim: bool = False) -> dict:
    """``trim`` takes the window from the first device event to the last
    instead of the whole trace: starting and stopping the profiler inside a
    live server stalls it, and those stalls are no part of its idle time."""
    devices, window = read_xplane(path)
    if trim:
        events = [e for d in devices for e in d["ops"] + d["modules"]]
        if events:
            window = (min(e[0] for e in events), max(e[1] for e in events))
    return reduce_events(devices, window)


def reduce_dir(trace_dir: str, trim: bool = False) -> dict | None:
    """Reduce the one ``*.xplane.pb`` under ``trace_dir``; None when the
    trace holds no device plane (a CPU rehearsal)."""
    found = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        return None
    out = reduce_file(found[-1], trim)
    return out if out["devices"] else None


def breakdown(trace: dict) -> dict:
    """The result line's ``breakdown``: ten of each, longest first."""
    return {
        "device_ops": [[k, v] for k, v in list(trace["ops"].items())[:10]],
        "idle_gaps": [[k, v] for k, v in list(trace["idle_gaps"].items())[:10]],
    }
