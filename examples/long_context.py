"""The long-context stack on one model: sliding-window attention, the
banded ring (window x sequence parallelism), and StreamingLLM-style
unbounded decode with a pinned-sink rolling cache.

Everything here has an exactness oracle in tests/; this script is the
tour.  Run:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/long_context.py
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax


import jax.numpy as jnp
import numpy as np

from covalent_tpu_plugin.models import TransformerConfig, TransformerLM, generate
from covalent_tpu_plugin.ops.ring_attention import sequence_parallel_attention
from covalent_tpu_plugin.parallel import MeshPlan, make_mesh

# A windowed model: each query sees the last 16 positions plus the 2
# anchor (sink) tokens.  On TPU the flash kernels visit only the band's
# tiles, so training compute AND K/V traffic scale O(S*w), not O(S^2).
CONFIG = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    d_ff=128,
    max_seq=64,
    dtype=jnp.float32,
    attention="reference",       # flash on TPU ("auto")
    sliding_window=16,
    attention_sinks=2,
)


def windowed_training_forward() -> None:
    model = TransformerLM(CONFIG)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 256)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    print(f"windowed+sinks forward: logits {logits.shape}")


def banded_ring() -> None:
    """Window x sequence parallelism: an 8-device ring that only runs the
    hops the band can reach (here 2 of 8 — S/n=16 per shard, w=24)."""
    mesh = make_mesh(MeshPlan(seq=8))
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(2 + i), (1, 4, 128, 16))
        for i in range(3)
    )
    out = sequence_parallel_attention(q, k, v, mesh, causal=True, window=24)
    from covalent_tpu_plugin.ops.attention import mha_reference

    ref = mha_reference(q, k, v, causal=True, window=24)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"banded ring over {mesh.shape['seq']} devices: max err {err:.2e}")


def unbounded_decode() -> None:
    """Rolling cache + sinks: generate far past max_seq at O(window)
    memory; the 2 sink slots pin the first tokens forever."""
    rolling = TransformerLM(dataclasses.replace(CONFIG, rolling_cache=True))
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0, 256)
    params = rolling.init(jax.random.PRNGKey(1), prompt)["params"]
    n_new = CONFIG.max_seq * 3  # 192 >> max_seq=64
    out = generate(rolling, params, prompt, n_new)
    arr = np.asarray(out)
    assert arr.shape == (1, 6 + n_new)
    print(
        f"rolling+sinks decode: {n_new} tokens past a {CONFIG.max_seq}-token "
        f"max_seq with a {CONFIG.sliding_window + CONFIG.attention_sinks}-slot cache"
    )


def long_prompt_streaming() -> None:
    """A prompt far past the ring's capacity streams in window-wide
    chunks (the r4 exact chunked prefill): ceil(P/window) prefill passes
    instead of P sequential steps, bit-identical to the token-by-token
    stream."""
    rolling = TransformerLM(dataclasses.replace(CONFIG, rolling_cache=True))
    capacity = CONFIG.sliding_window + CONFIG.attention_sinks  # 18
    prompt = jax.random.randint(
        jax.random.PRNGKey(6), (1, 4 * capacity), 0, 256
    )
    params = rolling.init(jax.random.PRNGKey(1), prompt[:, :8])["params"]
    fast = generate(rolling, params, prompt, 12)          # auto chunks
    slow = generate(rolling, params, prompt, 12, prefill_chunk=1)
    assert (np.asarray(fast) == np.asarray(slow)).all()
    passes = -(-prompt.shape[1] // CONFIG.sliding_window)
    print(
        f"long-prompt streaming: {prompt.shape[1]}-token prompt through a "
        f"{capacity}-slot ring in {passes} prefill passes (vs "
        f"{prompt.shape[1]} token-by-token), bit-exact"
    )


def main() -> None:
    windowed_training_forward()
    banded_ring()
    unbounded_decode()
    long_prompt_streaming()


if __name__ == "__main__":
    main()
