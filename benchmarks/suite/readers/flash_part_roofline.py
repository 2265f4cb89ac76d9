"""One flash kernel's share of its own roofline in the train step: the least
time the chip could take for that kernel's needed work at the cell's shapes
(the larger of FLOPs/peak and bytes/peak; the architecture's
``kernel_work``), over the summed device time of the operations named
``<kernel>(tpu_custom_call)``, per traced step.

S and P recomputed by a kernel, and a forward run again by remat, count for
nothing: where remat's second forward runs, the forward's share is at most
50%; dK/dV's, which recomputes S beside its three matmuls, at most 75%;
dQ's, which recomputes two matmuls to keep one, a third.
"""

from benchmarks.suite import archs, work

KERNELS = {"fwd": "flash_fwd", "dkdv": "flash_bwd_dkdv", "dq": "flash_bwd_dq"}


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
    config = context["cell"]["config"]
    needed, _ = work.roofline_seconds(
        archs.load(config).kernel_work(
            config, context["cell"]["traffic"], KERNELS[part]),
        peak, context["chips"])
    return 100.0 * needed * steps / took
