"""From a profiler trace (``*.xplane.pb``) to numbers: the device's busy and
idle seconds, each operation's own device time by instruction and by the
scope (``op_name``) the compiler kept for it, and the longest idle gaps laid
to the programs around them.  Read with nothing but jax
(``jax.profiler.ProfileData``) and a few lines that walk the file's
protobuf wire format for what ``ProfileData`` does not hand over: the
device plane's event metadata, where each instruction's ``op_name`` is
(stat ``tf_op``).  Checked on a small recorded trace.
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


def _instruction(name: str) -> str:
    """``fusion.12`` of an event named by its HLO line, ``%fusion.12 = ...``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _program_id(module: str) -> int | None:
    """``4711`` of a module event named ``jit_step(4711)``."""
    found = re.search(r"\((\d+)\)$", module)
    return int(found.group(1)) if found else None


def reduce_events(devices: list, window_ns: tuple,
                  scopes: dict | None = None) -> dict:
    """``devices`` is one ``{"ops": [(start, end, name)], "modules":
    [...]}`` per device, times in ns; ``window_ns`` the traced span;
    ``scopes`` is ``{(program id, instruction): op_name}`` (``read_scopes``).
    ``"scopes"`` in the result is each operation's own time by ``op_name``,
    ``""`` holding what has none: it sums to the busy time."""
    window_s = (window_ns[1] - window_ns[0]) / 1e9
    n = len(devices)
    busy, ops, counts, gaps, module_s, module_n = [], {}, {}, {}, {}, {}
    scoped: dict = {}
    for dev in devices:
        modules = sorted(dev["modules"])
        base = dev["ops"] or dev["modules"]
        merged = _union([(s, e) for s, e, _ in base])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        # Lay each operation to the program (module) running at its start.
        ordered = sorted(dev["ops"], key=lambda e: (e[0], -e[1]))
        for (start, _, name), own in zip(ordered, _own_times(ordered)):
            module = _module_at(modules, start)
            key = f"{_plain(module)}/{_plain(name)}"
            ops[key] = ops.get(key, 0.0) + own / 1e9 / n
            counts[key] = counts.get(key, 0) + 1
            if scopes is not None:
                scope = scopes.get(
                    (_program_id(module), _instruction(name)), "")
                scoped[scope] = scoped.get(scope, 0.0) + own / 1e9 / n
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
        "scopes": dict(top(scoped)),
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


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a view of its bytes (a sub-message, a
    string); fixed-width fields are passed over."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _map_entry(buf) -> tuple[int, object]:
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def read_scopes(path: str) -> dict:
    """``{(program id, instruction name): op_name}`` from the device planes'
    event metadata of one trace file: the profiler keeps, for every
    instruction it saw run, its HLO line as the metadata's name and, where
    the compiler kept one, ``op_name:op_type`` as the stat ``tf_op``.
    Instructions without one (copies, the ``while`` itself) are left out.

    Field numbers are ``tsl/profiler/protobuf/xplane.proto``'s: XSpace
    planes = 1; XPlane name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata name = 2, stats = 5; XStatMetadata name = 2; XStat
    metadata_id = 1, uint64_value = 3, str_value = 5, ref_value = 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    text = lambda view: bytes(view).decode("utf-8", "replace")  # noqa: E731
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = text(value)
            elif field == 4:
                events.append(_map_entry(value)[1])
            elif field == 5:
                key, meta = _map_entry(value)
                stat_names[key] = text(dict(_fields(meta)).get(2, b""))
        if not name.startswith("/device:TPU:"):
            continue
        for meta in events:
            line, program, op_name = "", None, None
            for field, value in _fields(meta):
                if field == 2:
                    line = text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    kind = stat_names.get(stat.get(1))
                    if kind == "program_id":
                        program = stat.get(3)
                    elif kind == "tf_op":
                        op_name = (text(stat[5]) if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if op_name:
                # ``op_name:op_type``; jax leaves the type empty.
                out[(program, _instruction(line))] = op_name.rpartition(":")[0]
    return out


def reduce_file(path: str, trim: bool = False) -> dict:
    """``trim`` takes the window from the first device event to the last
    instead of the whole trace: starting and stopping the profiler inside a
    live server stalls it, and those stalls are no part of its idle time."""
    devices, window = read_xplane(path)
    if trim:
        events = [e for d in devices for e in d["ops"] + d["modules"]]
        if events:
            window = (min(e[0] for e in events), max(e[1] for e in events))
    return reduce_events(devices, window, read_scopes(path))


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
