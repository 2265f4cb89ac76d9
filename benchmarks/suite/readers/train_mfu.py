"""The whole train step's share of the chips' peak: required forward and
backward FLOPs per token (the architecture's count: no recompute) x
tokens/s over chips x peak bf16 FLOP/s."""

from benchmarks.suite import archs, work


def read(context):
    if not context["require_tpu"]:
        return None  # a CPU rehearsal has no peak to take a share of
    peak = work.peaks(context["device"]["kind"])
    config = context["cell"]["config"]
    per_token = archs.load(config).train_flops_per_token(
        config, context["cell"]["traffic"])
    rate = context["end_to_end"]["train_tok_s"]
    return 100.0 * per_token * rate / (
        peak["bf16_flops_per_s"] * context["chips"])
