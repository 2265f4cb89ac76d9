"""Sliding-window attention: kernel-vs-oracle, tile-skip coverage,
model-level decode/pipeline consistency.

The decisive properties: the flash kernels (fwd + both backward sweeps)
match a handcrafted dense windowed softmax bit-for-tolerance at window
sizes that exercise the band's tile geometry (window inside one tile,
spanning tiles, larger than the sequence); cached decode equals full
recompute for a windowed model; the pipeline path stays equal to dense.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from covalent_tpu_plugin.models import TransformerConfig, TransformerLM, generate
from covalent_tpu_plugin.ops.attention import flash_attention, mha_reference


def dense_window_oracle(q, k, v, window):
    """Straight-line windowed causal softmax, no shared code with either
    implementation under test."""
    s_q, s_k = q.shape[2], k.shape[2]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (q.shape[-1] ** -0.5)
    qi = np.arange(s_q)[:, None]
    ki = np.arange(s_k)[None, :]
    visible = jnp.asarray((qi >= ki) & (qi - ki < window))
    scores = jnp.where(visible, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))


def qkv(b=1, h=2, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (b, h, s, d), dtype) for key in ks)


@pytest.mark.parametrize("window", [1, 37, 128, 200, 10_000])
def test_reference_matches_handwritten_oracle(window):
    q, k, v = qkv()
    want = np.asarray(dense_window_oracle(q, k, v, window))
    got = np.asarray(
        mha_reference(q, k, v, causal=True, window=window), np.float32
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [1, 37, 128, 200, 10_000])
def test_flash_forward_matches_reference(window):
    # block 64x64 => a 4x4 tile grid at s=256: the window tile-skip
    # branch really executes (a wrong skip bound zeroes live tiles here;
    # default blocks would fit the whole sequence in one tile and pass).
    q, k, v = qkv()
    want = np.asarray(
        mha_reference(q, k, v, causal=True, window=window), np.float32
    )
    got = np.asarray(
        flash_attention(
            q, k, v, causal=True, window=window, block_q=64, block_k=64
        ),
        np.float32,
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [300, 1500, 10_000])
def test_flash_backward_matches_reference_multitile(window):
    # s=2048 with the fixed 1024 backward tile edge => 2x2 tile grids in
    # both backward sweeps, so their window skip predicates execute.
    q, k, v = qkv(s=2048, h=1)

    def loss(fn):
        return lambda q, k, v: (
            fn(q, k, v).astype(jnp.float32) * jnp.cos(jnp.arange(64.0))
        ).sum()

    g_ref = jax.grad(
        loss(lambda q, k, v: mha_reference(q, k, v, causal=True, window=window)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, causal=True, window=window)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-5, rtol=5e-5,
        )


@pytest.mark.parametrize("window", [37, 128, 10_000])
def test_flash_backward_matches_reference(window):
    q, k, v = qkv(s=256)

    def loss(fn):
        return lambda q, k, v: (
            fn(q, k, v).astype(jnp.float32) * jnp.cos(jnp.arange(64.0))
        ).sum()

    g_ref = jax.grad(
        loss(lambda q, k, v: mha_reference(q, k, v, causal=True, window=window)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, causal=True, window=window)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-5, rtol=3e-5,
        )


def test_banded_grid_static_geometry():
    """The band-only grid really shrinks the inner sweep: at S=16k,
    w=1k with the default fwd/bwd tiles the key-tile (and query-tile)
    sweeps drop from 16 steps to 2 — this is the DMA-skip that turns the
    windowed win from ~2x into ~O(S/w)."""
    from covalent_tpu_plugin.ops.attention import (
        _banded_n_inner_kt, _banded_n_inner_qt,
    )

    assert _banded_n_inner_kt(16384, 16384, 512, 1024, 1024) == 2
    assert _banded_n_inner_qt(16384, 16384, 1024, 1024, 1024) == 2
    # Window >= sequence: no shrink possible, full grid (None) expected.
    assert _banded_n_inner_kt(256, 256, 64, 64, 10_000) is None
    assert _banded_n_inner_qt(256, 256, 64, 64, 10_000) is None
    # Tiny window still visits >= 1 tile per query tile.
    assert _banded_n_inner_kt(256, 256, 64, 64, 1) == 1
    # Sinks add a leading sink-tile run: one extra step here (sinks <= 64
    # fit one tile), still far below the 16-tile full sweep.
    assert _banded_n_inner_kt(16384, 16384, 512, 1024, 1024, sinks=4) == 3
    # Overlap folds into the sink run (band lo clamps to the sink tiles).
    assert _banded_n_inner_kt(256, 256, 64, 64, 37, sinks=4) == 3


@pytest.mark.parametrize("bq,bk", [(64, 128), (128, 64), (64, 64)])
def test_banded_grid_clamped_edges_exact(bq, bk):
    """Block shapes where the band's first tiles clamp at 0 and the causal
    edge produces duplicate (dead) DMA steps: liveness must come from grid
    arithmetic, not the clamped position tiles, or edge tiles double-count."""
    q, k, v = qkv(s=512)
    for window in (100, 130, 257):
        want = np.asarray(
            mha_reference(q, k, v, causal=True, window=window), np.float32
        )
        got = np.asarray(
            flash_attention(
                q, k, v, causal=True, window=window, block_q=bq, block_k=bk
            ),
            np.float32,
        )
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_banded_backward_gqa_exact():
    """Banded dk/dv sweep must still sum gradients over the GQA group."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))

    def loss(fn):
        return lambda q, k, v: (
            fn(q, k, v).astype(jnp.float32) * jnp.cos(jnp.arange(64.0))
        ).sum()

    g_ref = jax.grad(
        loss(lambda q, k, v: mha_reference(q, k, v, causal=True, window=150)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=150, block_q=128, block_k=128
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-5, rtol=5e-5,
        )


def test_windowed_block_picker():
    """Windowed defaults follow the r4 hardware sweep's winners:
    (512, 512) for w <= 512, (1024, 1024) wider; full-attention calls keep the full-attention defaults; fitted
    down for short sequences; explicit blocks always win."""
    from covalent_tpu_plugin.ops.attention import (
        _DEFAULT_BLOCK_K,
        _DEFAULT_BLOCK_Q,
        _fit_block,
        _pick_windowed_blocks,
    )

    assert _pick_windowed_blocks(16384, 16384, 512) == (512, 512)
    assert _pick_windowed_blocks(16384, 16384, 1024) == (1024, 1024)
    assert _pick_windowed_blocks(4096, 4096, 2048) == (1024, 1024)
    # The picker feeds _fit_block, so short sequences still tile.
    bq, bk = _pick_windowed_blocks(256, 256, 1024)
    assert _fit_block(bq, 256) == 256 and _fit_block(bk, 256) == 256
    # Full attention unaffected by the windowed table.
    assert (_DEFAULT_BLOCK_Q, _DEFAULT_BLOCK_K) == (512, 1024)
    # End to end: a windowed call with default blocks stays exact.
    q, k, v = qkv(s=1024)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, window=600),
                   np.float32),
        np.asarray(mha_reference(q, k, v, causal=True, window=600),
                   np.float32),
        atol=2e-5, rtol=2e-5,
    )


def test_window_equals_full_causal_when_wider_than_sequence():
    q, k, v = qkv(s=128)
    full = np.asarray(flash_attention(q, k, v, causal=True), np.float32)
    windowed = np.asarray(
        flash_attention(q, k, v, causal=True, window=128), np.float32
    )
    np.testing.assert_allclose(windowed, full, atol=2e-6, rtol=2e-6)


def test_window_validation():
    q, k, v = qkv(s=128)
    with pytest.raises(ValueError, match="requires causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window must be"):
        mha_reference(q, k, v, causal=True, window=0)


BASE = TransformerConfig(
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=4,
    d_ff=64,
    max_seq=32,
    dtype=jnp.float32,
    attention="reference",
    sliding_window=6,
)


def test_windowed_model_cached_decode_matches_recompute():
    """The decode path's cache band mask must agree with the training
    forward's window mask token-for-token."""
    model = TransformerLM(BASE)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, BASE.vocab_size)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]

    got = generate(model, params, prompt, max_new_tokens=8)
    tokens = prompt
    for _ in range(8):  # naive full-recompute oracle
        logits = model.apply({"params": params}, tokens)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        tokens = jnp.concatenate([tokens, nxt[:, None].astype(jnp.int32)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(tokens))


def test_windowed_model_differs_from_unwindowed():
    model = TransformerLM(BASE)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 0, BASE.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    full_model = TransformerLM(dataclasses.replace(BASE, sliding_window=None))
    assert not np.allclose(
        np.asarray(model.apply({"params": params}, tokens)),
        np.asarray(full_model.apply({"params": params}, tokens)),
    )


def test_windowed_pipeline_matches_dense():
    from covalent_tpu_plugin.models.pipeline_lm import pipeline_lm_forward
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh

    cfg = dataclasses.replace(BASE, scan_layers=True, n_layers=4)
    mesh = make_mesh(MeshPlan(pipe=4))
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits_pp = pipeline_lm_forward(model, params, tokens, mesh, n_micro=2)
    logits_ref = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_pp), np.asarray(logits_ref), atol=2e-4, rtol=2e-4
    )


def test_windowed_ring_model_matches_reference_model():
    """sliding_window + attention='ring' compose (the banded ring): the
    model's logits must equal the windowed reference-attention model's."""
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(seq=2, data=4))
    cfg = dataclasses.replace(BASE, attention="ring", mesh=mesh)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0, BASE.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    ref_model = TransformerLM(dataclasses.replace(BASE))
    got = model.apply({"params": params}, tokens)
    want = ref_model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
    )


def test_config_rejects_nonpositive_window():
    with pytest.raises(ValueError, match="sliding_window must be"):
        dataclasses.replace(BASE, sliding_window=0)


ROLLING = dataclasses.replace(BASE, rolling_cache=True)


def test_rolling_cache_matches_standard_within_max_seq():
    """While total length fits max_seq, the ring must produce exactly the
    standard windowed cache's tokens AND logits — including after the
    ring wraps.  (Token-only comparison once hid a phantom-slot bug whose
    logit error didn't happen to flip an argmax.)"""
    from covalent_tpu_plugin.models.decode import _decode_model, init_cache

    model = TransformerLM(BASE)
    rolling = TransformerLM(ROLLING)
    for seed in (1, 2, 3):
        prompt = jax.random.randint(
            jax.random.PRNGKey(seed), (2, 4), 0, BASE.vocab_size
        )
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]
        # Prefill logits bit-for-tolerance, not just their argmax.
        std_logits, _ = _decode_model(model).apply(
            {"params": params, "cache": init_cache(model, 2)}, prompt,
            mutable=["cache"],
        )
        roll_logits, _ = _decode_model(rolling).apply(
            {"params": params, "cache": init_cache(rolling, 2)}, prompt,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(roll_logits), np.asarray(std_logits),
            atol=1e-5, rtol=1e-5,
        )
        want = generate(model, params, prompt, 20)  # wraps the ring 3x
        got = generate(rolling, params, prompt, 20)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rolling_cache_generates_past_max_seq():
    """The point of the ring: generation beyond max_seq at O(window)
    memory, with finite outputs and an intact prompt."""
    model = TransformerLM(ROLLING)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, BASE.vocab_size)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    n_new = BASE.max_seq + 10  # 42 > max_seq=32
    out = jax.jit(lambda p, t: generate(model, p, t, n_new))(params, prompt)
    assert out.shape == (1, 5 + n_new)
    arr = np.asarray(out)
    np.testing.assert_array_equal(arr[:, :5], np.asarray(prompt))
    assert (arr >= 0).all() and (arr < BASE.vocab_size).all()
    # The ring really is window-sized, not max_seq-sized.
    from covalent_tpu_plugin.models.decode import init_cache

    cache = init_cache(model, 1)
    k_leaves = [
        leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if any(getattr(e, "key", None) == "cached_k" for e in path)
    ]
    assert all(leaf.shape[-3] == BASE.sliding_window for leaf in k_leaves)


def test_rolling_cache_validation():
    with pytest.raises(ValueError, match="rolling_cache requires"):
        dataclasses.replace(BASE, sliding_window=None, rolling_cache=True)
    model = TransformerLM(ROLLING)
    long_prompt = jnp.zeros((1, 10), jnp.int32)  # > window of 6
    params = TransformerLM(BASE).init(
        jax.random.PRNGKey(0), long_prompt[:, :4]
    )["params"]
    # Past-capacity prompts stream by default (auto chunk = window, r4);
    # only wider-than-window chunks stay rejected (two slab tokens would
    # scatter into one ring slot).
    out = generate(model, params, long_prompt, 4)
    assert out.shape == (1, 14)
    with pytest.raises(ValueError, match="exceed sliding_window"):
        generate(model, params, long_prompt, 4, prefill_chunk=7)
    # Speculative decoding refuses rolling models outright.
    from covalent_tpu_plugin.models import speculative_generate

    with pytest.raises(ValueError, match="rolling_cache"):
        speculative_generate(
            model, params, model, params, long_prompt[:, :4], 4
        )


def test_rolling_chunked_prefill_exact_past_capacity():
    """The r4 exact chunked prefill: a past-capacity prompt streamed in
    chunks of ANY width <= sliding_window must reproduce the
    prefill_chunk=1 stream (the long-established exact path) bit for
    bit — logits at the boundary and every generated token.  Chunk 5
    does not divide P=24, so the last slab is ragged; chunk 6 == window
    is the new auto-default."""
    from covalent_tpu_plugin.models.decode import _decode_model, init_cache

    model = TransformerLM(ROLLING)
    prompt = jax.random.randint(
        jax.random.PRNGKey(5), (2, 24), 0, BASE.vocab_size  # 4x capacity
    )
    params = TransformerLM(BASE).init(
        jax.random.PRNGKey(0), prompt[:, :4]
    )["params"]

    def stream_logits(chunk):
        """Last-position logits after prefilling the prompt in chunks."""
        decoder = _decode_model(model)
        cache = init_cache(model, 2)
        for start in range(0, prompt.shape[1], chunk):
            logits, mutated = decoder.apply(
                {"params": params, "cache": cache},
                prompt[:, start:start + chunk], mutable=["cache"],
            )
            cache = mutated["cache"]
        return np.asarray(logits[:, -1])

    want_logits = stream_logits(1)
    want_tokens = np.asarray(
        generate(model, params, prompt, 8, prefill_chunk=1)
    )
    for chunk in (2, 3, 5, 6):
        np.testing.assert_allclose(
            stream_logits(chunk), want_logits, atol=1e-5, rtol=1e-5,
            err_msg=f"chunk={chunk}",
        )
        np.testing.assert_array_equal(
            np.asarray(
                generate(model, params, prompt, 8, prefill_chunk=chunk)
            ),
            want_tokens, err_msg=f"chunk={chunk}",
        )
    # The auto default (prefill_chunk unset) matches too.
    np.testing.assert_array_equal(
        np.asarray(generate(model, params, prompt, 8)), want_tokens
    )


def test_rolling_chunked_prefill_exact_with_quantized_kv():
    """Chunked past-capacity prefill composes with the int8 KV cache:
    the slab branch must quantise/dequantise exactly like the cache
    branch, so chunk=window reproduces the chunk=1 token stream."""
    cfg = dataclasses.replace(ROLLING, quantized_kv_cache=True)
    model = TransformerLM(cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(6), (2, 24), 0, BASE.vocab_size
    )
    params = TransformerLM(BASE).init(
        jax.random.PRNGKey(0), prompt[:, :4]
    )["params"]
    want = np.asarray(generate(model, params, prompt, 8, prefill_chunk=1))
    got = np.asarray(generate(model, params, prompt, 8))
    np.testing.assert_array_equal(got, want)
