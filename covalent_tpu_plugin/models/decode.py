"""Autoregressive generation with a per-layer KV cache.

The training-side ``TransformerLM`` recomputes attention over the full
prefix; generation instead runs the model in ``decode=True`` mode: one
batched *prefill* pass pushes the whole prompt's K/V into each layer's
cache (flax "cache" collection), then each decode step appends a single
token at the cache cursor and attends the cached prefix — a step costs
O(S·D) attention reads instead of O(S²·D) recompute, and time-to-first-
token is one forward pass, not P sequential steps.

The decode loop is a ``lax.while_loop`` writing into a fixed (B, P+N)
token buffer — fully jittable, one compilation for any prompt content of
a given shape, with an early exit once every row has emitted EOS (when
``eos_token_id`` is set; otherwise it runs the full ``max_new_tokens``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ._jitcache import cached_jit
from .transformer import TransformerLM


def _decode_model(model: TransformerLM) -> TransformerLM:
    if model.config.decode:
        return model
    return TransformerLM(dataclasses.replace(model.config, decode=True))


def init_cache(model: TransformerLM, batch_size: int) -> Any:
    """Zeroed per-layer KV cache sized ``config.max_seq``.

    Shapes come from ``jax.eval_shape`` over the decoder's init — no
    parameters are ever materialised (a bare init would sample the full
    weight set just to throw it away).
    """
    decoder = _decode_model(model)
    abstract = jax.eval_shape(
        lambda rng, tokens: decoder.init(rng, tokens),
        jax.random.PRNGKey(0),
        jnp.zeros((batch_size, 1), jnp.int32),
    )

    def materialise(path, leaf):
        if any(getattr(e, "key", None) == "slot_positions" for e in path):
            # The rolling cache's "never written" sentinel is -1; zeroing
            # it would make every empty ring slot claim absolute position
            # 0 and leak phantom zero-K/V entries into early softmaxes.
            return jnp.full(leaf.shape, -1, leaf.dtype)
        return jnp.zeros(leaf.shape, leaf.dtype)

    return jax.tree_util.tree_map_with_path(materialise, abstract["cache"])


def inference_params(params: Any) -> Any:
    """Cast f32 master weights to bf16 for serving.

    Decode steps are HBM-bandwidth-bound — every step re-reads the full
    weight set — so halving the bytes is a direct speedup (+10% tokens/s
    scanned, +48% with ``scan_layers=False`` on the 125M decode in an
    earlier v5e sweep; not re-measured on this installation).  Non-f32
    leaves (e.g. int
    embeddings) pass through untouched; training should keep the f32
    masters, this is a serving-side copy.
    """
    return jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
        params,
    )


def _filter_top_k(logits: jax.Array, top_k: int) -> jax.Array:
    """Mask all but the ``top_k`` largest logits per row to NEG_INF.

    ``jax.lax.top_k`` keeps the shape static, so the filter is jittable for
    any fixed ``top_k``.
    """
    from ..ops.attention import NEG_INF

    kth = jax.lax.top_k(logits, top_k)[0][..., -1:]  # (..., 1)
    return jnp.where(logits < kth, NEG_INF, logits)


def _filter_min_p(logits: jax.Array, min_p: float) -> jax.Array:
    """min-p filter: keep tokens whose probability is at least ``min_p``
    times the most likely token's — a relative floor that adapts to the
    distribution's confidence (tight on peaked steps, permissive on flat
    ones), unlike top-k/top-p's absolute budgets."""
    from ..ops.attention import NEG_INF

    logprobs = jax.nn.log_softmax(logits, axis=-1)
    floor = jnp.max(logprobs, axis=-1, keepdims=True) + jnp.log(min_p)
    return jnp.where(logprobs < floor, NEG_INF, logits)


def _apply_repetition_penalty(
    logits: jax.Array, seen: jax.Array, penalty: float
) -> jax.Array:
    """CTRL-style repetition penalty over the ``seen`` token multiset:
    logits of already-emitted tokens divide by ``penalty`` when positive
    and multiply when negative (the HF convention), making repeats
    uniformly less likely.  ``seen`` is (B, L) int32 with -1 padding for
    not-yet-written slots."""
    batch, vocab = logits.shape
    safe = jnp.where(seen >= 0, seen, vocab)  # -1 pads -> overflow column
    appeared = jnp.zeros((batch, vocab + 1), bool).at[
        jnp.arange(batch)[:, None], safe
    ].set(True)[:, :vocab]
    penalised = jnp.where(
        logits > 0, logits / penalty, logits * penalty
    )
    return jnp.where(appeared, penalised, logits)


def _filter_top_p(logits: jax.Array, top_p: float) -> jax.Array:
    """Nucleus filter: keep the smallest prefix of the sorted distribution
    whose cumulative probability reaches ``top_p``; mask the rest.

    Static-shape formulation: sort once, compute the cumulative softmax
    mass *before* each position, and mask tokens whose preceding mass
    already covers ``top_p`` (the first token always survives).

    Tie semantics: the filter thresholds by logit *value*, so every token
    tied with the cutoff logit survives and the kept nucleus can exceed
    ``top_p`` mass by the tied tokens' probability (HF masks by sorted
    index instead, arbitrarily breaking the tie by sort order).  Keeping
    all equal-probability tokens is the deliberate choice here: which of
    two identical-logit tokens "ranks" first is numerically meaningless.
    """
    from ..ops.attention import NEG_INF

    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    cutoff_idx = jnp.sum((mass_before < top_p).astype(jnp.int32), axis=-1)
    # Logit value at the last kept (sorted) position is the threshold.
    threshold = jnp.take_along_axis(
        sorted_logits, jnp.maximum(cutoff_idx - 1, 0)[..., None], axis=-1
    )
    return jnp.where(logits < threshold, NEG_INF, logits)


def _generate_traced(
    model: TransformerLM,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_token_id: int | None = None,
    pad_token_id: int | None = None,
    prefill_chunk: int | None = None,
    min_p: float | None = None,
    repetition_penalty: float | None = None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` ((B, P) int32).

    ``temperature=0`` is greedy argmax; otherwise softmax sampling at the
    given temperature (requires ``rng``), optionally restricted to the
    ``top_k`` highest logits, the ``top_p`` nucleus, and/or the ``min_p``
    relative-probability floor (applied in that order, the
    HF/transformers convention).  ``repetition_penalty`` (CTRL-style,
    works for greedy AND sampling) divides positive / multiplies
    negative logits of every token already in the row's buffer before
    the other filters.  ``eos_token_id`` stops a row
    once it emits EOS: its remaining slots fill with ``pad_token_id``
    (default: the EOS id), and the loop exits early when every row has
    finished.  ``prefill_chunk`` streams the prompt into the caches in
    fixed-size slabs instead of one pass — the decode cache attends a
    chunk's queries against everything already cached, so the result is
    exact while prefill activation memory is bounded O(chunk·S) for long
    prompts.  With ``rolling_cache``, prompts past the ring capacity
    stream in chunks of at most ``sliding_window`` tokens (the default
    when unset) — exact at any such width, ~window× fewer prefill steps
    than the old forced token-by-token stream.  Returns the full
    (B, P+N) token buffer.  Wrap in
    ``jax.jit`` for repeated use — everything inside is a single compiled
    loop.
    """
    decoder = _decode_model(model)
    config = decoder.config
    batch, prompt_len = prompt.shape
    total = prompt_len + max(max_new_tokens, 0)
    if config.rolling_cache:
        # The circular cache frees generation from max_seq: prompts past
        # capacity stream in as chunks of at most ``sliding_window``
        # tokens.  Any such chunk is EXACT — the decode step attends the
        # pre-write ring snapshot plus the slab itself, so a wrapping
        # scatter can no longer erase band-edge entries earlier slab rows
        # need (the r3 lossy case that forced prefill_chunk=1 and made
        # long-prompt prefill O(P) sequential steps).  Wider-than-window
        # chunks would land two slab tokens in one ring slot (an
        # order-undefined scatter), so they stay rejected.
        capacity = config.sliding_window + config.attention_sinks
        if prompt_len > capacity:
            if prefill_chunk is None:
                prefill_chunk = config.sliding_window
            if prefill_chunk > config.sliding_window:
                raise ValueError(
                    f"rolling_cache prefill chunks of {prefill_chunk} "
                    f"exceed sliding_window ({config.sliding_window}): "
                    "two slab tokens would scatter into the same ring "
                    "slot; use prefill_chunk <= sliding_window"
                )
    elif total > config.max_seq:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds config.max_seq ({config.max_seq})"
        )
    # Argument-shape validation fires even for max_new_tokens <= 0 (a bad
    # combination is a caller bug worth surfacing); the rng requirement
    # only applies when sampling will actually happen, preserving the
    # original "zero new tokens is identity" contract.
    if temperature <= 0 and (
        top_k is not None or top_p is not None or min_p is not None
    ):
        raise ValueError(
            "top_k/top_p/min_p require sampling (temperature > 0)"
        )
    if top_k is not None and not 1 <= top_k <= config.vocab_size:
        raise ValueError(f"top_k must be in [1, {config.vocab_size}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if min_p is not None and not 0.0 < min_p <= 1.0:
        raise ValueError(f"min_p must be in (0, 1], got {min_p}")
    if repetition_penalty is not None and repetition_penalty <= 0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}"
        )
    if pad_token_id is not None and eos_token_id is None:
        raise ValueError("pad_token_id requires eos_token_id")
    if prefill_chunk is not None and prefill_chunk < 1:
        raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
    if max_new_tokens <= 0:
        return prompt.astype(jnp.int32)
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache = init_cache(model, batch)
    buffer = jnp.zeros((batch, total), jnp.int32)
    buffer = jax.lax.dynamic_update_slice(buffer, prompt, (0, 0))

    def choose(step_logits, rng, buffer, written):
        rng, sample_key = jax.random.split(rng)
        step_logits = step_logits.astype(jnp.float32)
        if repetition_penalty is not None:
            # Unwritten buffer slots hold token 0 — mask them to -1 so a
            # legitimate token id 0 is only penalised once it appears.
            cols = jnp.arange(buffer.shape[1])[None, :]
            seen = jnp.where(cols < written, buffer, -1)
            step_logits = _apply_repetition_penalty(
                step_logits, seen, repetition_penalty
            )
        if temperature > 0:
            scaled = step_logits / temperature
            if top_k is not None:
                scaled = _filter_top_k(scaled, top_k)
            if top_p is not None:
                scaled = _filter_top_p(scaled, top_p)
            if min_p is not None:
                scaled = _filter_min_p(scaled, min_p)
            chosen = jax.random.categorical(sample_key, scaled, axis=-1)
        else:
            chosen = jnp.argmax(step_logits, axis=-1)
        return chosen.astype(jnp.int32), rng

    pad = eos_token_id if pad_token_id is None else pad_token_id

    def finish(chosen, done):
        """Apply EOS bookkeeping to a step's chosen tokens."""
        if eos_token_id is None:
            return chosen, done
        chosen = jnp.where(done, jnp.int32(pad), chosen)
        return chosen, done | (chosen == eos_token_id)

    # Prefill: batched pass(es) push the whole prompt into the caches and
    # yield the first generated token from the prompt's last logits.
    # Chunked prefill is exact (each slab attends the cached prefix with
    # per-row causal visibility); the chunk count is static so this is a
    # plain Python loop of at most two compiled shapes.
    if prefill_chunk is None or prefill_chunk >= prompt_len:
        chunks = [prompt]
    else:
        chunks = [
            prompt[:, start:start + prefill_chunk]
            for start in range(0, prompt_len, prefill_chunk)
        ]
    for slab in chunks:
        prefill_logits, mutated = decoder.apply(
            {"params": params, "cache": cache}, slab, mutable=["cache"]
        )
        cache = mutated["cache"]
    first, rng = choose(
        prefill_logits[:, -1], rng, buffer, jnp.asarray(prompt_len)
    )
    done = jnp.zeros((batch,), bool)
    first, done = finish(first, done)
    buffer = jax.lax.dynamic_update_slice(
        buffer, first[:, None], (0, prompt_len)
    )

    def body(carry):
        buffer, cache, rng, t, done = carry
        token = jax.lax.dynamic_slice(buffer, (0, t), (batch, 1))
        logits, mutated = decoder.apply(
            {"params": params, "cache": cache}, token, mutable=["cache"]
        )
        cache = mutated["cache"]
        chosen, rng = choose(logits[:, 0], rng, buffer, t + 1)
        chosen, done = finish(chosen, done)
        buffer = jax.lax.dynamic_update_slice(
            buffer, chosen[:, None], (0, t + 1)
        )
        return buffer, cache, rng, t + 1, done

    def cond(carry):
        _, _, _, t, done = carry
        return (t < total - 1) & ~jnp.all(done)

    buffer, _, _, t, done = jax.lax.while_loop(
        cond, body, (buffer, cache, rng, jnp.asarray(prompt_len), done)
    )
    if eos_token_id is not None:
        # An early exit (all rows done) leaves columns > t unwritten;
        # stamp them with the pad token so finished rows read uniformly.
        # Without early exit t == total-1 and this is a no-op.
        cols = jnp.arange(total)[None, :]
        buffer = jnp.where(cols > t, jnp.int32(pad), buffer)
    return buffer


def _generate_jit(model, max_new_tokens, temperature, top_k, top_p,
                  eos_token_id, pad_token_id, prefill_chunk, min_p,
                  repetition_penalty, has_rng):
    """One compiled executable per static generate() configuration
    (shared cache + rationale: models/_jitcache.py)."""

    def make():
        def run(params, prompt, rng):
            return _generate_traced(
                model, params, prompt, max_new_tokens, temperature,
                rng if has_rng else None, top_k, top_p, eos_token_id,
                pad_token_id, prefill_chunk, min_p, repetition_penalty,
            )

        return run

    return cached_jit(
        ("generate", model, max_new_tokens, temperature, top_k, top_p,
         eos_token_id, pad_token_id, prefill_chunk, min_p,
         repetition_penalty, has_rng),
        make,
    )


def generate(
    model: TransformerLM,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_token_id: int | None = None,
    pad_token_id: int | None = None,
    prefill_chunk: int | None = None,
    min_p: float | None = None,
    repetition_penalty: float | None = None,
) -> jax.Array:
    """Jit-cached wrapper around the traced generate body — see
    `_generate_traced` for the full semantics docstring.  Static knobs
    key a compiled-executable cache, so repeated plain calls (tests,
    serving oracles) pay one compile per configuration
    instead of eager per-token dispatch."""
    if max_new_tokens <= 0:
        # Preserve the eager identity contract (validation still fires
        # inside the traced body for the normal path).
        return _generate_traced(
            model, params, prompt, max_new_tokens, temperature, rng,
            top_k, top_p, eos_token_id, pad_token_id, prefill_chunk,
            min_p, repetition_penalty,
        )
    fn = _generate_jit(
        model, int(max_new_tokens),
        float(temperature),
        top_k, top_p, eos_token_id, pad_token_id, prefill_chunk, min_p,
        repetition_penalty, rng is not None,
    )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return fn(params, jnp.asarray(prompt), rng)
