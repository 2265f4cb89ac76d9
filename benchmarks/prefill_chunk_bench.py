"""Rolling-cache prefill: chunked (r4 exact path) vs the old forced
token-by-token stream, for a prompt at 4x ring capacity.

Run:  python benchmarks/prefill_chunk_bench.py
Prints one JSON line: prefill pass counts and wall times for
prefill_chunk=1 vs the auto window-wide chunks, plus an exactness check
(greedy tokens bit-equal).  The r3 verdict's done-criterion asked for a
>=10x prefill step-count reduction at P = 4x capacity; with
window=64 the reduction is 64x by construction (ceil(P/64) vs P passes).
"""
from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from covalent_tpu_plugin.models import TransformerLM, generate  # noqa: E402
from covalent_tpu_plugin.models.transformer import (  # noqa: E402
    TransformerConfig,
)


def main() -> None:
    window, sinks = 64, 4
    capacity = window + sinks
    prompt_len = 4 * capacity  # 272
    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq=capacity, dtype=jnp.float32, attention="reference",
        sliding_window=window, attention_sinks=sinks, rolling_cache=True,
    )
    model = TransformerLM(cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (2, prompt_len), 0, cfg.vocab_size
    )
    params = model.init(jax.random.PRNGKey(1), prompt[:, :8])["params"]

    def timed(chunk):
        gen = jax.jit(
            lambda p, t: generate(
                model, p, t, max_new_tokens=8, prefill_chunk=chunk
            )
        )
        out = gen(params, prompt)
        jax.device_get(out)  # compile + run once
        t0 = time.monotonic()
        out = gen(params, prompt)
        jax.device_get(out)
        return np.asarray(out), time.monotonic() - t0

    out_stream, t_stream = timed(1)
    out_chunked, t_chunked = timed(None)  # auto: window-wide slabs
    passes_stream = prompt_len
    passes_chunked = -(-prompt_len // window)
    print(json.dumps({
        "prompt_len": prompt_len,
        "capacity": capacity,
        "prefill_passes_chunk1": passes_stream,
        "prefill_passes_auto": passes_chunked,
        "step_count_reduction": round(passes_stream / passes_chunked, 1),
        "wall_s_chunk1": round(t_stream, 3),
        "wall_s_auto": round(t_chunked, 3),
        "wall_speedup": round(t_stream / t_chunked, 2),
        "exact": bool((out_stream == out_chunked).all()),
    }), flush=True)


if __name__ == "__main__":
    main()
