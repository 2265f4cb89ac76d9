"""The serving stack on one model: sampling, beam search, speculative
decoding, and the bf16/int8 weight casts.

Everything here has an exactness oracle in tests/; this script is the
tour.  Run:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/serve_lm.py
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax


import jax.numpy as jnp
import numpy as np

from covalent_tpu_plugin.models import (
    TransformerConfig,
    TransformerLM,
    beam_search,
    generate,
    inference_params,
    quantize_lm,
    speculative_generate,
)

CONFIG = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    d_ff=128,
    max_seq=64,
    dtype=jnp.float32,
    attention="reference",
    scan_layers=False,  # unrolled: the serving mode
)


def main() -> None:
    model = TransformerLM(CONFIG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CONFIG.vocab_size)
    params = inference_params(  # bf16 serving cast... kept f32 here (CPU demo)
        model.init(jax.random.PRNGKey(0), prompt)["params"]
    )

    greedy = generate(model, params, prompt, 12)
    print("greedy:       ", np.asarray(greedy)[0, 8:])

    sampled = generate(
        model, params, prompt, 12, temperature=0.8,
        rng=jax.random.PRNGKey(42), top_k=40, top_p=0.95,
    )
    print("top-k/top-p:  ", np.asarray(sampled)[0, 8:])

    stopped = generate(
        model, params, prompt, 12,
        eos_token_id=int(np.asarray(greedy)[0, 9]),  # force an early stop
        pad_token_id=0,
    )
    print("eos-stopped:  ", np.asarray(stopped)[0, 8:])

    tokens, scores = beam_search(model, params, prompt, 12, beam_width=4)
    print("beam best:    ", np.asarray(tokens)[0, 0, 8:],
          "score", float(scores[0, 0]))

    draft = TransformerLM(
        dataclasses.replace(CONFIG, d_model=32, n_layers=1, n_heads=2, d_ff=64)
    )
    draft_params = draft.init(jax.random.PRNGKey(3), prompt)["params"]
    spec, stats = speculative_generate(
        model, params, draft, draft_params, prompt, 12, draft_len=4,
        return_stats=True,
    )
    print("speculative:  ", np.asarray(spec)[0, 8:],
          f"({int(stats['rounds'])} target passes vs 12 sequential)")
    assert (np.asarray(spec) == np.asarray(greedy)).all()  # exact, any draft

    qmodel, qparams = quantize_lm(model, params)
    q = generate(qmodel, qparams, prompt, 12)
    print("int8 weights: ", np.asarray(q)[0, 8:])

    # Continuous batching: 6 ragged requests with their own token
    # budgets through 2 slots — each row bit-equal to its own generate().
    from covalent_tpu_plugin.models import continuous_generate

    requests = [
        np.asarray(
            jax.random.randint(jax.random.PRNGKey(10 + i), (4 + i % 3,),
                               0, CONFIG.vocab_size), np.int32,
        )
        for i in range(6)
    ]
    budgets = [4, 12, 6, 9, 3, 12]
    served = continuous_generate(
        model, params, requests, budgets, max_batch=2, sync_steps=4
    )
    for r, b, o in zip(requests, budgets, served):
        assert (o == np.asarray(generate(model, params, r[None], b))[0]).all()
    print(f"continuous:    {len(served)} ragged requests through 2 slots, "
          "each bit-equal to its own generate()")


if __name__ == "__main__":
    main()
