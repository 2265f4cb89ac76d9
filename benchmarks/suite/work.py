"""Operations and bytes the algorithm NEEDS, from the shapes, whatever
implements it; and the table of peaks.  A later PR that replaces a kernel or
stops reading a rectangle moves a share honestly, and never past 100%:
recomputed operations, padding and unused cache slots are not counted.
"""

from __future__ import annotations

import json
import os

from benchmarks.suite import archs

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """One chip's peaks by ``device_kind``; an unknown kind is an error."""
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(table has: {', '.join(table)})")
    return table[device_kind]


def visible_pairs(seq: int, window: int | None) -> int:
    """Query-key pairs a causal (sliding-window) attention over ``seq``
    positions has to score: sum over t of min(t + 1, window)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def serve_flops(config: dict, tokens: int) -> float:
    """2 x matmul weights for every prompt and output token processed
    (attention over the context is left out: an undercount)."""
    return 2.0 * archs.load(config).matmul_parameters(config) * tokens


def decode_step_bytes(config: dict, live_tokens: float) -> float:
    """What one decode step has to read: every matmul weight once, and the
    K and V of the tokens alive in the lanes (not the ``max_seq``
    rectangle)."""
    arch = archs.load(config)
    return (
        arch.matmul_parameters(config) * _bytes(config["weight_dtype"])
        + live_tokens * arch.kv_bytes_per_token(config)
    )


def roofline_seconds(work: dict, peak: dict, chips: int = 1) -> tuple:
    """``(least seconds, which bound binds)``."""
    compute = work["flops"] / (peak["bf16_flops_per_s"] * chips)
    memory = work["bytes"] / (peak["hbm_bytes_per_s"] * chips)
    return (compute, "compute") if compute >= memory else (memory, "memory")


def _bytes(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]
