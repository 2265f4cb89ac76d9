"""Share of the prefill positions the engine computed that were padding:
1 - prompt tokens admitted / ``engine.stats["prefill_positions"]``, over the
whole session (warm-up excluded: the counters are zeroed after it)."""


def read(context):
    positions = (context.get("stats") or {}).get("prefill_positions")
    if not positions:
        return None
    return 100.0 * (1.0 - context["prompt_tokens_run"] / positions)
