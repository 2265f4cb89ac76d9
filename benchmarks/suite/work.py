"""Operations and bytes the algorithm NEEDS, from the shapes, whatever
implements it; and the table of peaks.  A later PR that replaces a kernel or
stops reading a rectangle moves a share honestly, and never past 100%:
recomputed operations, padding and unused cache slots are not counted.
"""

from __future__ import annotations

import json
import os

from benchmarks.suite import weights

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """One chip's peaks by ``device_kind``; an unknown kind is an error."""
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(table has: {', '.join(table)})")
    return table[device_kind]


def matmul_parameters(config: dict) -> int:
    """Weights that multiply every token: the layers' six kernels and the
    output head.  The embedding is a lookup, the norms are vectors."""
    s = weights.sizes(config)
    per_layer = (
        s["D"] * s["H"] * s["hd"] * 2          # q, o
        + s["D"] * s["KV"] * s["hd"] * 2       # k, v
        + s["D"] * s["F"] * 2                  # wi, wo
    )
    return s["L"] * per_layer + s["D"] * s["V"]


def visible_pairs(seq: int, window: int | None) -> int:
    """Query-key pairs a causal (sliding-window) attention over ``seq``
    positions has to score: sum over t of min(t + 1, window)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_forward_flops(config: dict, seq: int) -> int:
    """QK^T and PV over the visible pairs, every head, one sequence, one
    layer: 2 matmuls x 2 FLOPs x head_dim each pair."""
    s = weights.sizes(config)
    return 4 * s["hd"] * s["H"] * visible_pairs(seq, config["sliding_window"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward plus backward (twice the forward), no recompute: the matmul
    weights at 2 FLOPs each and attention inside the window."""
    s = weights.sizes(config)
    forward = 2 * matmul_parameters(config) + (
        s["L"] * attention_forward_flops(config, seq) / seq
    )
    return 3.0 * forward


def serve_flops(config: dict, tokens: int) -> float:
    """2 x matmul weights for every prompt and output token processed
    (attention over the context is left out: an undercount)."""
    return 2.0 * matmul_parameters(config) * tokens


def kv_bytes_per_token(config: dict) -> int:
    s = weights.sizes(config)
    return s["L"] * 2 * s["KV"] * s["hd"] * _bytes(config["activation_dtype"])


def decode_step_bytes(config: dict, live_tokens: float) -> float:
    """What one decode step has to read: every matmul weight once, and the
    K and V of the tokens alive in the lanes (not the ``max_seq``
    rectangle)."""
    return (
        matmul_parameters(config) * _bytes(config["weight_dtype"])
        + live_tokens * kv_bytes_per_token(config)
    )


def flash_step_work(config: dict, batch: int, seq: int) -> dict:
    """The flash kernels' needed work in one train step over ``batch``
    sequences, all layers: forward once and the backward's four matmuls
    (twice the forward); bytes are Q, K, V, O once forward, and Q, K, V, O,
    dO in, dQ, dK, dV out backward."""
    s = weights.sizes(config)
    act = _bytes(config["activation_dtype"])
    forward = attention_forward_flops(config, seq)
    q_bytes = seq * s["H"] * s["hd"] * act
    kv_bytes = seq * s["KV"] * s["hd"] * act
    forward_bytes = 2 * q_bytes + 2 * kv_bytes
    backward_bytes = 4 * q_bytes + 4 * kv_bytes
    n = batch * s["L"]
    return {"flops": 3 * forward * n,
            "bytes": (forward_bytes + backward_bytes) * n}


def roofline_seconds(work: dict, peak: dict, chips: int = 1) -> tuple:
    """``(least seconds, which bound binds)``."""
    compute = work["flops"] / (peak["bf16_flops_per_s"] * chips)
    memory = work["bytes"] / (peak["hbm_bytes_per_s"] * chips)
    return (compute, "compute") if compute >= memory else (memory, "memory")


def _bytes(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]
