"""The whole serving step's share of the chip's peak: 2 x matmul weights x
(prompt tokens of requests first answered in the window + output tokens
delivered in it) over window x peak bf16 FLOP/s."""

from benchmarks.suite import work


def read(context):
    if not context["require_tpu"]:
        return None  # a CPU rehearsal has no peak to take a share of
    peak = work.peaks(context["device"]["kind"])
    tokens = context["prompt_tokens_window"] + context["out_tokens_window"]
    flops = work.serve_flops(context["cell"]["config"], tokens)
    return 100.0 * flops / (
        context["window_s"] * peak["bf16_flops_per_s"] * context["chips"])
