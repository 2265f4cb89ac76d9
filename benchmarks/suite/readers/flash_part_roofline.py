"""One flash kernel's share of its own roofline in the train step: the least
time the chip could take for that kernel's needed work at the cell's shapes
(the larger of FLOPs/peak and bytes/peak), over the summed device time of
the operations named ``<kernel>(tpu_custom_call)``, per traced step.

The three parts split ``work.flash_step_work``'s needed work, and sum to it.
``fwd``: the forward once a layer (QK^T and PV; reads Q, K, V, writes O).
``dkdv``: dV = P^T dO, dP = dO V^T and dK = dS^T Q, three of the backward's
four matmuls, 1.5 x the forward's FLOPs, and the K/V side of its bytes (K,
V in, dK, dV out).  ``dq``: dQ = dS K, 0.5 x the forward's FLOPs, and the Q
side (Q, O, dO in, dQ out).  S and P recomputed by a kernel, and a forward
run again by remat, count for nothing: where remat's second forward runs,
the forward's share is at most 50%; dK/dV's, which recomputes S beside its
three, at most 75%; dQ's, which recomputes two matmuls to keep one, a third.
"""

from benchmarks.suite import weights, work

KERNELS = {"fwd": "flash_fwd", "dkdv": "flash_bwd_dkdv", "dq": "flash_bwd_dq"}


def part_work(config: dict, batch: int, seq: int, part: str) -> dict:
    """Needed FLOPs and bytes of one kernel in one train step, all layers."""
    s = weights.sizes(config)
    act = work._bytes(config["activation_dtype"])
    forward = work.attention_forward_flops(config, seq)
    q_bytes = seq * s["H"] * s["hd"] * act
    kv_bytes = seq * s["KV"] * s["hd"] * act
    flops, moved = {
        "fwd": (forward, 2 * q_bytes + 2 * kv_bytes),
        "dkdv": (1.5 * forward, 4 * kv_bytes),
        "dq": (0.5 * forward, 4 * q_bytes),
    }[part]
    n = batch * s["L"]
    return {"flops": flops * n, "bytes": moved * n}


def read(context, part: str):
    trace = context.get("trace")
    steps = context.get("trace_steps")
    if not trace or not steps or not context["require_tpu"]:
        return None
    suffix = f"/{KERNELS[part]}(tpu_custom_call)"
    took = sum(seconds for name, seconds in trace["ops"].items()
               if name.endswith(suffix))
    if took <= 0:
        return None  # a program whose kernels are not named apart
    peak = work.peaks(context["device"]["kind"])
    job = context["cell"]["traffic"]
    needed, _ = work.roofline_seconds(
        part_work(context["cell"]["config"], job["batch"], job["sequence"],
                  part),
        peak, context["chips"])
    return 100.0 * needed * steps / took
