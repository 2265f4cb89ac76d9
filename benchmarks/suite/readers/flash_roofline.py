"""The flash kernels' share of their roofline in the train step: the least
time the chip could take for the forward and both backward kernels' needed
work at the cell's shapes (the larger of FLOPs/peak and bytes/peak, over
the three kernels' summed work), over the summed device time of the
operations whose name matches ``pattern`` (the Mosaic custom calls), per
traced step."""

import re

from benchmarks.suite import archs, work
from benchmarks.suite.readers.flash_part_roofline import KERNELS


def read(context, pattern: str):
    trace = context.get("trace")
    steps = context.get("trace_steps")
    if not trace or not steps or not context["require_tpu"]:
        return None
    took = sum(
        seconds for name, seconds in trace["ops"].items()
        if re.search(pattern, name)
    )
    if took <= 0:
        return None
    peak = work.peaks(context["device"]["kind"])
    config, job = context["cell"]["config"], context["cell"]["traffic"]
    arch = archs.load(config)
    parts = [arch.kernel_work(config, job, k) for k in KERNELS.values()]
    needed, _ = work.roofline_seconds(
        {"flops": sum(p["flops"] for p in parts),
         "bytes": sum(p["bytes"] for p in parts)},
        peak, context["chips"])
    return 100.0 * needed * steps / took
