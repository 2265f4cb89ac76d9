"""The decode step's share of its memory roofline: the bytes one step has to
read (every matmul weight once, the live tokens' K and V) over the chip's
bytes/s, over the step's device time in the trace.  Live tokens are the
lanes' mean context, each request weighted by the steps it decodes."""

from benchmarks.suite import work
from benchmarks.suite.readers import module_step_ms


def read(context, module: str, steps_key: str):
    step_ms = module_step_ms.read(context, module, steps_key)
    records = [r for r in context.get("records") or [] if r.get("ok")]
    if step_ms is None or not records or not context["require_tpu"]:
        return None
    peak = work.peaks(context["device"]["kind"])
    steps = sum(r["budget"] for r in records)
    mean_context = sum(
        r["budget"] * (r["n_prompt"] + r["budget"] / 2) for r in records
    ) / steps
    lanes = context["cell"]["traffic"]["engine"]["max_batch"]
    needed = work.decode_step_bytes(
        context["cell"]["config"], lanes * mean_context)
    return 100.0 * (needed / peak["hbm_bytes_per_s"]) / (step_ms / 1e3)
