"""Continuous batching: a fixed-slot serving loop with rolling admission.

Plain ``generate()`` batches a FIXED set of prompts: every row starts and
(effectively) finishes together, so a 10-token answer waits for the
500-token answer sharing its batch.  Production serving (vLLM-style)
instead runs a fixed number of SLOTS and admits a new request the moment
a slot finishes — no request waits on an unrelated long one, and the
accelerator never idles while work is queued.  The reference plugin has
no serving path at all (SURVEY §2; reference ``ssh.py`` runs opaque
pickled callables); this is a beyond-parity subsystem.

TPU-native design — the pieces map to the compilation model:

* **Static shapes.** ``max_batch`` slots and one (B, L) token buffer,
  compiled once.  Finished slots keep stepping on frozen tokens (their
  logits are ignored) — the standard static-shape trade.
* **Per-slot cache via vmap.**  Each slot owns a lane of a vmapped KV
  cache, so per-slot cursors, rotary offsets, and masks come from
  ``jax.vmap`` over the single-row decode step — no scalar-cursor
  surgery in the model.  A lane's numerics are exactly a batch-1
  ``generate()``'s (no cross-batch reductions anywhere), which is what
  makes the bit-equality oracle in the tests possible.  Caveat shared
  with plain batched ``generate()``: on backends whose batched-matmul
  tiling rounds differently than the batch-1 shape (TPU MXU at bf16),
  near-tie argmaxes can flip vs the batch-1 oracle; on CPU (f32 and
  bf16) equality is bit-exact.
* **Admission at scan boundaries.**  The device runs ``sync_steps``
  decode steps per jitted call (``lax.scan``); the host only looks at
  the tiny (B,) state vectors between calls, harvests finished rows,
  zeroes their cache lanes, and writes the next queued prompt into the
  slot.  One host round-trip per ``sync_steps`` tokens instead of one
  per token — the knob trades admission latency against host chatter.
* **Bucketed batched prefill at admission** (``prefill="batched"``, the
  default).  An admitted prompt runs ONE single-lane prefill pass padded
  to a power-of-two bucket, then enters the shared decode loop — time to
  first token is one pass, not ``len(prompt)`` interleaved steps.  The
  padding trick is exact: pad K/V land at slots ``>= len(prompt)``, the
  cursor is rewound to ``len(prompt)``, and the causal mask only ever
  exposes slot ``k`` to queries at positions ``>= k`` — by which step
  the decode loop has overwritten it with the real token's K/V.
  Compiles one prefill per bucket size (a handful for a whole serving
  mix).  ``prefill="stream"`` keeps the zero-extra-compiles chunk-1
  interleave: the prompt streams through the shared step loop one token
  per step.

Greedy and temperature/top-k sampling are supported; EOS finishes a slot
early.  Sampling note: greedy outputs are identical across prefill
modes, but SAMPLED outputs are not reproducible across them — batched
admission draws each first token from a dedicated admission key chain
(``fold_in(rng, 0x5E1)``) while streaming draws it from the shared loop
stream; pin ``prefill`` as well as ``rng`` for reproducible sampling.
``rolling_cache`` models are refused (slot reset assumes the plain
cache layout).
"""

from __future__ import annotations

import collections
import functools
import hashlib
import os
import pickle
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .decode import _decode_model, _filter_top_k, init_cache
from .quant import SERVING_MODES, mode_variant
from .speculative import _set_cursor, make_lane_spec_round
from .transformer import TransformerLM

#: Wire format version of a serialized KV bundle (prefill_only's output).
KV_BUNDLE_VERSION = 1

#: Environment knob bounding the NAMED slots of a multi-adapter bank
#: (the identity base rides an extra slot 0 on top of this).
ADAPTERS_MAX_ENV = "COVALENT_TPU_SERVE_ADAPTERS_MAX"


class RollingCacheUnsupported(ValueError):
    """Typed refusal: continuous serving assumes the plain cache layout.

    ``rolling_cache`` models ring-rotate their KV slots, and the slot-reset
    trick at admission (zero the lane, rewind the cursor) assumes the plain
    append-only layout.  A :class:`ValueError` subclass for back-compat,
    duck-tagged for the dispatch layers: the serving RPC surfaces this as a
    PERMANENT fault (``fault_label``/``fault_transient`` — the resilience
    classifier's self-classification hook), so a misconfigured session is
    refused once instead of burning gang retries on a deterministic error.
    """

    fault_label = "serve_model_unsupported"
    fault_transient = False


class AdapterUnsupported(ValueError):
    """Typed refusal: this engine cannot host the requested adapter set.

    Raised for deterministic construction/attach errors — a model that
    already carries adapters (the quant.py contract: quantize the base
    first, then attach — ``lora.quantize_then_lora``), a rank/shape
    geometry that does not match the bank template, an exhausted bank.
    Duck-tagged PERMANENT like :class:`RollingCacheUnsupported`, so the
    dispatch layers refuse once instead of burning gang retries.
    """

    fault_label = "serve_model_unsupported"
    fault_transient = False


class _AdapterDecoder:
    """Hashable decode-model wrapper resolving a per-lane adapter index
    against a stacked adapter bank INSIDE the compiled programs.

    With a bank configured, the serving state wraps each cache lane as
    ``{"kv": <model cache>, "adapter": <int32 bank slot>}`` and the
    params as ``{"base": [non-adapter leaves], "bank": [stacked adapter
    leaves, each (n_slots, ...)]}``.  ``apply`` gathers every bank leaf
    at the lane's slot (``jnp.take(leaf, idx, axis=0)`` — a batched
    gather under the serving loop's vmap), reassembles the full LoRA
    tree, and delegates to the wrapped decoder on the inner cache; the
    adapter index rides the returned cache untouched.  The wrapper
    hashes on ``(decoder, treedef, mask)``, so the jitted factory
    caches (:func:`_make_run_steps` and friends) treat it exactly like
    a plain decoder static — ONE compiled step serves every adapter,
    and attaching a new adapter is a bank scatter, never a recompile.
    """

    __slots__ = ("decoder", "treedef", "mask")

    def __init__(self, decoder, treedef, mask) -> None:
        self.decoder = decoder
        self.treedef = treedef
        self.mask = tuple(bool(m) for m in mask)

    @property
    def config(self):
        return self.decoder.config

    def __eq__(self, other) -> bool:
        return (
            type(other) is _AdapterDecoder
            and self.decoder == other.decoder
            and self.treedef == other.treedef
            and self.mask == other.mask
        )

    def __hash__(self) -> int:
        return hash((self.decoder, self.treedef, self.mask))

    def _merge(self, params, idx):
        base = iter(params["base"])
        bank = iter(params["bank"])
        leaves = [
            jnp.take(next(bank), idx, axis=0) if m else next(base)
            for m in self.mask
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def apply(self, variables, tokens, mutable=()):
        cache = variables["cache"]
        merged = self._merge(variables["params"], cache["adapter"])
        out = self.decoder.apply(
            {"params": merged, "cache": cache["kv"]}, tokens,
            mutable=mutable,
        )
        if mutable:
            logits, mutated = out
            return logits, {"cache": {
                "kv": mutated["cache"], "adapter": cache["adapter"],
            }}
        return out


class BlockUnsupported(ValueError):
    """Typed refusal: continuous serving has no cache for this block form.

    Latent attention would cache its latent, routed experts decode through
    grouped products, n residual streams change what a lane's state is,
    and attention that differs by layer wants a cache a layer type: the
    engine has none of the four, and running such a model through the
    plain K/V path would serve wrong tokens.  Duck-tagged PERMANENT
    like :class:`RollingCacheUnsupported`."""

    fault_label = "serve_model_unsupported"
    fault_transient = False


def _require_plain_cache(config, what: str) -> None:
    if config.rolling_cache:
        raise RollingCacheUnsupported(
            f"{what} does not support rolling_cache models "
            "(slot reset assumes the plain cache layout)"
        )
    held = [name
            for name in ("latent", "routed", "streams", "attention_types")
            if getattr(config, name, None) is not None]
    if held:
        raise BlockUnsupported(
            f"{what} does not serve a model with {', '.join(held)} set "
            "(latent attention, routed experts, residual streams, attention "
            "by layer): the train path runs it, the engine has no cache for "
            "it yet"
        )


def _choose_tokens(logits, key, temperature, top_k):
    """Shared greedy/sampling rule for the loop and the prefill."""
    logits = logits.astype(jnp.float32)
    if temperature > 0:
        scaled = logits / temperature
        if top_k is not None:
            scaled = _filter_top_k(scaled, top_k)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.lru_cache(maxsize=64)
def _make_admit(decoder, temperature, top_k, eos_token_id, batch, bucket, g,
                adapters=False):
    """One fused, donated admission wave: batch-prefill ``g`` prompts and
    scatter their cache lanes, buffer rows, and cursors in a SINGLE
    compiled call.

    Round 4's serving wall loss traced to admission overhead: every
    admitted request paid its own single-lane prefill dispatch plus one
    eager ``.at[slot].set`` per cache leaf (each a full-tree device
    copy).  Here the whole wave is one executable with the serving state
    donated, so XLA updates the caches in place and the prefill runs as
    ONE (g, bucket) batched pass — admission cost scales with waves, not
    requests.

    Exactness of the padded pass: pad positions' K/V land at slots
    >= plen; with the cursor rewound to ``plen`` they are dead until the
    decode loop overwrites them (the causal mask shows slot k only to
    queries at positions >= k) — same trick as speculative decoding's
    cache rewind (models/speculative.py).  Rows whose ``slots`` entry is
    out of range (the group padded up to a power of two) are dropped by
    the scatters (``mode="drop"``), so padding never touches live state.

    With ``adapters=True`` (a multi-adapter bank: the cache lanes are
    ``{"kv": ..., "adapter": ...}`` wraps and ``decoder`` is an
    :class:`_AdapterDecoder`) the wave takes one extra ``aidxs (g,)``
    argument — each row's bank slot, written into its zero lane BEFORE
    the prefill so the pass gathers that adapter's weights.  Mixed
    adapters co-batch in one wave; the plain signature is untouched.
    """

    def _wave(params, state, rows, padded, plens, slots, caps_in, keys,
              aidxs):
        # rows (g, length) full buffer rows; padded (g, bucket) prompt
        # tokens; plens/caps_in/slots (g,); keys (g, 2) admission keys.
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = state

        def lane_prefill(tokens, pl, key, aidx):
            zero = jax.tree_util.tree_map(
                lambda c: jnp.zeros(c.shape[1:], c.dtype), caches
            )
            if adapters:
                zero = {**zero, "adapter": aidx}
            logits, mutated = decoder.apply(
                {"params": params, "cache": zero}, tokens[None],
                mutable=["cache"],
            )
            cache = _set_cursor(mutated["cache"], pl)
            last = jnp.take_along_axis(
                logits, (pl - 1)[None, None, None], axis=1
            )[0, 0]  # (V,)
            first = _choose_tokens(
                last[None, :], key, temperature, top_k
            )[0]
            return cache, first

        new_lanes, firsts = jax.vmap(lane_prefill)(padded, plens, keys,
                                                   aidxs)
        caches = jax.tree_util.tree_map(
            lambda c, nl: c.at[slots].set(nl, mode="drop"),
            caches, new_lanes,
        )
        rows = rows.at[jnp.arange(g), plens].set(firsts)
        buffer = buffer.at[slots].set(rows, mode="drop")
        pos = pos.at[slots].set(plens, mode="drop")
        plen = plen.at[slots].set(plens, mode="drop")
        row_cap = row_cap.at[slots].set(caps_in, mode="drop")
        n_gen = n_gen.at[slots].set(
            jnp.ones((g,), jnp.int32), mode="drop"
        )
        fin = caps_in <= 1
        if eos_token_id is not None:
            fin = fin | (firsts == eos_token_id)
        done = done.at[slots].set(fin, mode="drop")
        return caches, buffer, pos, plen, row_cap, n_gen, done, rng

    if adapters:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def admit_wave(params, state, rows, padded, plens, slots, caps_in,
                       keys, aidxs):
            return _wave(params, state, rows, padded, plens, slots,
                         caps_in, keys, aidxs)

        return admit_wave

    @functools.partial(jax.jit, donate_argnums=(1,))
    def admit_wave(params, state, rows, padded, plens, slots, caps_in,
                   keys):
        return _wave(params, state, rows, padded, plens, slots, caps_in,
                     keys, jnp.zeros((g,), jnp.int32))

    return admit_wave


@functools.lru_cache(maxsize=64)
def _make_prefix_admit(decoder, temperature, top_k, eos_token_id, batch,
                       bucket, g, prefix_len):
    """Fused admission wave for prompts sharing the session's prefilled
    prefix: every lane starts from the SHARED prefix cache lane (computed
    once per engine) and prefills only its suffix, padded to ``bucket``.

    This is the shared-prefix fast path: on the dominant traffic shape —
    a common system prompt ahead of a short user turn — per-request
    prefill work drops from ``bucket(prompt)`` to ``bucket(suffix)``
    positions.  Exactness is the same two tricks the full-prefill wave
    uses, shifted by ``prefix_len``: the suffix pass appends K/V at the
    prefix cursor (queries at absolute position ``prefix_len + j`` see
    the cached prefix plus the causal suffix — exactly what one full
    pass computes for those positions), and pad K/V land at slots
    ``>= prefix_len + suffix_len`` where the rewound cursor keeps them
    dead until the decode loop overwrites them.  ``prefix_lane`` rides
    as a traced argument (broadcast across the vmapped lanes), so one
    compiled wave serves every prefix of the same length.
    """

    @functools.partial(jax.jit, donate_argnums=(1,))
    def admit_wave(params, state, prefix_lane, rows, padded, slens, slots,
                   caps_in, keys):
        # rows (g, length) full buffer rows (prefix + suffix); padded
        # (g, bucket) SUFFIX tokens; slens (g,) suffix lengths;
        # slots/caps_in (g,); keys (g, 2) admission keys.
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = state

        def lane_prefill(tokens, sl, key):
            logits, mutated = decoder.apply(
                {"params": params, "cache": prefix_lane}, tokens[None],
                mutable=["cache"],
            )
            cache = _set_cursor(mutated["cache"], prefix_len + sl)
            last = jnp.take_along_axis(
                logits, (sl - 1)[None, None, None], axis=1
            )[0, 0]  # (V,)
            first = _choose_tokens(
                last[None, :], key, temperature, top_k
            )[0]
            return cache, first

        new_lanes, firsts = jax.vmap(lane_prefill)(padded, slens, keys)
        plens = prefix_len + slens
        caches = jax.tree_util.tree_map(
            lambda c, nl: c.at[slots].set(nl, mode="drop"),
            caches, new_lanes,
        )
        rows = rows.at[jnp.arange(g), plens].set(firsts)
        buffer = buffer.at[slots].set(rows, mode="drop")
        pos = pos.at[slots].set(plens, mode="drop")
        plen = plen.at[slots].set(plens, mode="drop")
        row_cap = row_cap.at[slots].set(caps_in, mode="drop")
        n_gen = n_gen.at[slots].set(
            jnp.ones((g,), jnp.int32), mode="drop"
        )
        fin = caps_in <= 1
        if eos_token_id is not None:
            fin = fin | (firsts == eos_token_id)
        done = done.at[slots].set(fin, mode="drop")
        return caches, buffer, pos, plen, row_cap, n_gen, done, rng

    return admit_wave


@functools.lru_cache(maxsize=32)
def _make_kv_admit(eos_token_id, batch, g):
    """Fused scatter for admissions whose prefill already happened
    elsewhere (an imported KV bundle): no decoder pass at all — the wave
    only scatters the imported cache lanes, buffer rows (first generated
    token included, computed by the *prefill* tier), cursors, and budgets
    into the donated serving state.  ``mode="drop"`` pads exactly like
    the prefill waves."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def admit_wave(state, new_lanes, rows, plens, firsts, slots, caps_in):
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = state
        caches = jax.tree_util.tree_map(
            lambda c, nl: c.at[slots].set(nl, mode="drop"),
            caches, new_lanes,
        )
        buffer = buffer.at[slots].set(rows, mode="drop")
        pos = pos.at[slots].set(plens, mode="drop")
        plen = plen.at[slots].set(plens, mode="drop")
        row_cap = row_cap.at[slots].set(caps_in, mode="drop")
        n_gen = n_gen.at[slots].set(
            jnp.ones((g,), jnp.int32), mode="drop"
        )
        fin = caps_in <= 1
        if eos_token_id is not None:
            fin = fin | (firsts == eos_token_id)
        done = done.at[slots].set(fin, mode="drop")
        return caches, buffer, pos, plen, row_cap, n_gen, done, rng

    return admit_wave


@functools.lru_cache(maxsize=32)
def _make_draft_admit(draft_decoder, batch, bucket, g):
    """Fused DRAFT-cache admission wave for speculative decoding: one
    batched full-prompt prefill through the draft model, lanes scattered
    into the donated draft cache stack.

    Always full-prompt (the draft skips the prefix tree — its prefill is
    a small fraction of the target's and sharing lanes across two models
    would double the tree's memory for little win).  Stale positions past
    the rewound cursor stay dead until the first spec round's repair slab
    overwrites them — the admission waves' usual exactness argument.
    """

    @functools.partial(jax.jit, donate_argnums=(1,))
    def admit_wave(d_params, dcaches, padded, plens, slots):
        def lane_prefill(tokens, pl):
            zero = jax.tree_util.tree_map(
                lambda c: jnp.zeros(c.shape[1:], c.dtype), dcaches
            )
            _, mutated = draft_decoder.apply(
                {"params": d_params, "cache": zero}, tokens[None],
                mutable=["cache"],
            )
            return _set_cursor(mutated["cache"], pl)

        lanes = jax.vmap(lane_prefill)(padded, plens)
        return jax.tree_util.tree_map(
            lambda c, nl: c.at[slots].set(nl, mode="drop"), dcaches, lanes
        )

    return admit_wave


@functools.lru_cache(maxsize=32)
def _make_spec_run_steps(decoder, draft_decoder, eos_token_id, length,
                         draft_len, rounds, batch):
    """Jitted speculative serving chunk: ``rounds`` draft-and-verify
    rounds across every lane per compiled call (cached on its statics,
    like :func:`_make_run_steps`).

    Each round is :func:`..speculative.make_lane_spec_round` vmapped over
    the slots — the verify slab is ONE fused target pass per wave, every
    lane's ``draft_len + 1`` candidate positions scored together.  The
    serving state AND the draft cache stack are donated; the returned
    ``(proposed, accepted)`` counters are the chunk's summed draft
    agreement (the accept-rate numerator/denominator the serving metrics
    export).  The rng chain rides untouched: the continuous spec path is
    greedy-only (the engine refuses a draft on sampled sessions), so
    unlike :func:`_make_run_steps` no keys are consumed.
    """
    lane_round = make_lane_spec_round(
        decoder, draft_decoder, eos_token_id, length, draft_len
    )

    def one_round(params, draft_params, carry, _):
        state, dcaches, proposed, accepted = carry
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = state
        (caches, dcaches, buffer, pos, n_gen, done, prop, acc) = jax.vmap(
            lane_round, in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0)
        )(params, draft_params, caches, dcaches, buffer, pos, row_cap,
          n_gen, done)
        state = (caches, buffer, pos, plen, row_cap, n_gen, done, rng)
        return (
            state, dcaches,
            proposed + jnp.sum(prop), accepted + jnp.sum(acc),
        ), None

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def run_steps(params, draft_params, state, dcaches):
        (state, dcaches, proposed, accepted), _ = jax.lax.scan(
            functools.partial(one_round, params, draft_params),
            (state, dcaches, jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32)),
            None, length=rounds,
        )
        return state, dcaches, proposed, accepted

    return run_steps


@functools.lru_cache(maxsize=64)
def _make_lane_prefill(decoder, temperature, top_k, bucket):
    """Standalone single-lane full prefill (``prefill_only``'s slow path).

    Structurally the SAME computation as ``_make_admit``'s inner
    ``lane_prefill`` — bucketed pass on a zero lane, cursor rewind, first
    token from the last real position — vmapped over a leading dim of 1
    so the compiled program matches the admission wave's lane exactly
    (the bit-equality contract between a disaggregated prefill and the
    in-place admission path rests on it)."""

    @jax.jit
    def prefill(params, lane_zero, padded, plens, keys):
        # padded (1, bucket); plens (1,); keys (1, 2).
        def lane_prefill(tokens, pl, key):
            logits, mutated = decoder.apply(
                {"params": params, "cache": lane_zero}, tokens[None],
                mutable=["cache"],
            )
            cache = _set_cursor(mutated["cache"], pl)
            last = jnp.take_along_axis(
                logits, (pl - 1)[None, None, None], axis=1
            )[0, 0]
            first = _choose_tokens(
                last[None, :], key, temperature, top_k
            )[0]
            return cache, first

        lanes, firsts = jax.vmap(lane_prefill)(padded, plens, keys)
        return (
            jax.tree_util.tree_map(lambda c: c[0], lanes), firsts[0]
        )

    return prefill


@functools.lru_cache(maxsize=64)
def _make_lane_prefix_prefill(decoder, temperature, top_k, bucket,
                              prefix_len):
    """Standalone single-lane suffix prefill on a cached prefix lane
    (``prefill_only``'s fast path), mirroring ``_make_prefix_admit``'s
    inner lane the same way :func:`_make_lane_prefill` mirrors the full
    wave."""

    @jax.jit
    def prefill(params, prefix_lane, padded, slens, keys):
        # padded (1, bucket) SUFFIX tokens; slens (1,); keys (1, 2).
        def lane_prefill(tokens, sl, key):
            logits, mutated = decoder.apply(
                {"params": params, "cache": prefix_lane}, tokens[None],
                mutable=["cache"],
            )
            cache = _set_cursor(mutated["cache"], prefix_len + sl)
            last = jnp.take_along_axis(
                logits, (sl - 1)[None, None, None], axis=1
            )[0, 0]
            first = _choose_tokens(
                last[None, :], key, temperature, top_k
            )[0]
            return cache, first

        lanes, firsts = jax.vmap(lane_prefill)(padded, slens, keys)
        return (
            jax.tree_util.tree_map(lambda c: c[0], lanes), firsts[0]
        )

    return prefill


def _tokens_digest(tokens: np.ndarray) -> str:
    """Content key of a token prefix (the prefix tree's index)."""
    return hashlib.sha256(
        np.ascontiguousarray(tokens, np.int32).tobytes()
    ).hexdigest()


class _PrefixEntry:
    """One cached KV lane: the exact tokens it prefilled, cursor parked
    at ``tokens.size``.  ``pinned`` marks the constructor-supplied
    ``shared_prefix`` template, exempt from LRU eviction.  ``aslot`` is
    the adapter bank slot whose weights computed the lane (0 = base) —
    a lane is only ever reused under the SAME adapter, because K/V from
    another adapter's weights would silently corrupt the stream."""

    __slots__ = ("tokens", "lane", "pinned", "aslot")

    def __init__(self, tokens: np.ndarray, lane: Any, pinned: bool,
                 aslot: int = 0) -> None:
        self.tokens = tokens
        self.lane = lane
        self.pinned = pinned
        self.aslot = aslot


@functools.lru_cache(maxsize=32)
def _make_run_steps(decoder, temperature, top_k, eos_token_id,
                    length, sync_steps, batch):
    """Jitted ``sync_steps``-long serving scan, cached on its statics.

    A per-call ``@jax.jit`` over a closure would retrace and recompile
    the whole scanned model on EVERY ``continuous_generate`` call (jit
    caches key on the function object); caching the compiled callable on
    the hashable statics (the flax module itself plus the loop
    constants) makes repeat calls with the same serving shape reuse one
    executable, like ``generate()`` under a caller's jit.  ``params``
    ride as a traced argument.
    """
    rows = jnp.arange(batch)

    def choose(logits, key):
        return _choose_tokens(logits, key, temperature, top_k)

    def one_step(params, state, _):
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = state

        def row_step(cache, token):
            logits, mutated = decoder.apply(
                {"params": params, "cache": cache}, token[None, :],
                mutable=["cache"],
            )
            return mutated["cache"], logits[0, -1]

        token = jnp.take_along_axis(buffer, pos[:, None], axis=1)  # (B, 1)
        caches, logits = jax.vmap(row_step)(caches, token)
        rng, key = jax.random.split(rng)
        nxt = choose(logits, key)  # (B,)
        in_prompt = (pos + 1) < plen
        write_idx = jnp.minimum(pos + 1, length - 1)
        prompt_next = buffer[rows, write_idx]
        gen_now = (~in_prompt) & (~done)
        # Prompt rows "write back" their own next token (a no-op), so one
        # scatter serves streaming prefill and decode alike.
        buffer = buffer.at[rows, write_idx].set(
            jnp.where(gen_now, nxt, prompt_next)
        )
        n_gen = n_gen + gen_now.astype(jnp.int32)
        if eos_token_id is not None:
            done = done | (gen_now & (nxt == eos_token_id))
        done = done | (n_gen >= row_cap)
        # Frozen rows hold position (their lane keeps stepping on the
        # same token; logits are ignored, cache writes past the row's
        # used region are reset at admission).
        pos = jnp.where(done, pos, pos + 1)
        return (caches, buffer, pos, plen, row_cap, n_gen, done, rng), None

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run_steps(params, state):
        # State donation lets XLA update the (B, layers, S, ...) caches in
        # place: without it every sync chunk copies the full serving
        # state tree host-visibly, which round 4's wall numbers showed
        # dominating the toy-scale loop.
        state, _ = jax.lax.scan(
            functools.partial(one_step, params), state, None,
            length=sync_steps,
        )
        return state

    return run_steps


def continuous_generate(
    model: TransformerLM,
    params: Any,
    prompts: Sequence[np.ndarray],
    max_new_tokens: int | Sequence[int],
    *,
    max_batch: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    rng: jax.Array | None = None,
    eos_token_id: int | None = None,
    pad_token_id: int | None = None,
    sync_steps: int = 8,
    prefill: str = "batched",
    stats: dict | None = None,
) -> list[np.ndarray]:
    """Serve ``prompts`` (each a 1-D int32 array) through ``max_batch``
    continuously-refilled slots; returns one trimmed output sequence per
    prompt, in the input order.

    Each output is ``prompt + generated`` where generation stops at
    the request's token budget or its EOS (the EOS token is included).
    ``max_new_tokens`` is one shared budget (int) or one per request —
    mixed-length workloads are continuous batching's home turf: a slot
    whose request hits its own budget is refilled immediately instead of
    idling until the longest request in a static batch finishes.  Greedy
    rows are bit-identical to ``generate(model, params, prompt[None],
    cap_i)`` on batch-rounding-invariant backends (CPU f32/bf16; see the
    module docstring for the TPU-bf16 caveat shared with plain batched
    decode) — admission order cannot change tokens, only latency.

    ``stats``, when given, is filled with host-loop counters:
    ``prefill_passes`` (fused admission waves dispatched — the cost that
    was one pass PER REQUEST before round 5), ``sync_fetches`` (blocking
    host round-trips), and ``device_chunks`` (``sync_steps``-long scans
    dispatched).
    """
    config = _decode_model(model).config
    _require_plain_cache(config, "continuous_generate")
    caps = None
    if isinstance(max_new_tokens, (float, np.floating)):
        max_new_tokens = int(max_new_tokens)  # old int-like float contract
    if not isinstance(max_new_tokens, (int, np.integer)):
        caps = [int(c) for c in max_new_tokens]
        if len(caps) != len(prompts):
            raise ValueError(
                f"per-request max_new_tokens has {len(caps)} entries for "
                f"{len(prompts)} prompts"
            )
        if any(c < 1 for c in caps):
            raise ValueError("every per-request max_new_tokens must be >= 1")
    elif max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if sync_steps < 1:
        raise ValueError(f"sync_steps must be >= 1, got {sync_steps}")
    if prefill not in ("batched", "stream"):
        raise ValueError(
            f'prefill must be "batched" or "stream", got {prefill!r}'
        )
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if temperature <= 0 and top_k is not None:
        raise ValueError("top_k requires sampling (temperature > 0)")
    if top_k is not None and not 1 <= top_k <= config.vocab_size:
        raise ValueError(
            f"top_k must be in [1, {config.vocab_size}], got {top_k}"
        )
    prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    if not prompts:
        return []
    if any(p.size < 1 for p in prompts):
        raise ValueError("every prompt needs at least one token")
    if caps is None:
        caps = [int(max_new_tokens)] * len(prompts)
    length = max(p.size + c for p, c in zip(prompts, caps))
    if length > config.max_seq:
        raise ValueError(
            f"worst-case prompt + budget ({length}) exceeds "
            f"config.max_seq ({config.max_seq})"
        )
    batch = min(max_batch, len(prompts))
    decoder = _decode_model(model)
    pad = pad_token_id
    if pad is None:
        pad = eos_token_id if eos_token_id is not None else 0
    if rng is None:
        rng = jax.random.PRNGKey(0)
    # The serving state (rng included) is donated to the jitted chunk and
    # admission calls; a private copy keeps the CALLER's key buffer alive
    # for their next call with the same array.
    rng = jnp.array(rng, copy=True)

    # One cache lane per slot: stack B single-row caches.  Lane shape
    # keeps the model's own batch dim of 1, so the vmapped step calls the
    # decoder exactly as a batch-1 generate() would.
    lane = init_cache(model, 1)
    caches = jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(
            leaf[None], (batch,) + leaf.shape
        ).copy(),
        lane,
    )
    lane_zero = jax.tree_util.tree_map(jnp.zeros_like, lane)

    run_steps = _make_run_steps(
        decoder, float(temperature), top_k, eos_token_id,
        int(length), int(sync_steps), int(batch),
    )

    # --- host-side slot management ---------------------------------------
    queue = [
        (i, p, c) for i, (p, c) in enumerate(zip(prompts, caps))
    ]  # (original index, tokens, budget)
    outputs: list[np.ndarray | None] = [None] * len(prompts)
    buffer = np.full((batch, length), pad, np.int32)
    pos = np.zeros(batch, np.int32)
    plen = np.ones(batch, np.int32)
    row_cap = np.ones(batch, np.int32)
    n_gen = np.zeros(batch, np.int32)
    done = np.ones(batch, bool)  # empty slots are "done" until admitted
    slot_req = [-1] * batch  # original request index per slot

    adm_rng = {"key": jax.random.fold_in(rng, 0x5E1)}
    # Host-side lower bound on decode steps until each slot can finish
    # (exact without EOS; with EOS a slot may finish earlier, which only
    # delays its harvest, never corrupts it — frozen rows hold position).
    min_left = [0] * batch
    if stats is not None:
        stats.update(prefill_passes=0, sync_fetches=0, device_chunks=0)

    def _count(key, by=1):
        if stats is not None:
            stats[key] += by

    def admit_stream(state, slot):
        """Streaming admission: the prompt replays through the shared
        step loop one token per step (zero extra compiles)."""
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = state
        req_idx, tokens, cap = queue.pop(0)
        slot_req[slot] = req_idx
        min_left[slot] = tokens.size - 1 + cap
        row = np.full((length,), pad, np.int32)
        row[: tokens.size] = tokens
        buffer = buffer.at[slot].set(jnp.asarray(row))
        plen = plen.at[slot].set(tokens.size)
        row_cap = row_cap.at[slot].set(cap)
        pos = pos.at[slot].set(0)
        n_gen = n_gen.at[slot].set(0)
        done = done.at[slot].set(False)
        caches = jax.tree_util.tree_map(
            lambda c, z: c.at[slot].set(z), caches, lane_zero
        )
        return caches, buffer, pos, plen, row_cap, n_gen, done, rng

    def admit_group(state, free_slots):
        """Admit up to ``len(free_slots)`` queued requests in fused
        waves: one `_make_admit` call per prompt bucket, each group
        padded to a power of two to bound the compile count at
        O(buckets x log2(batch))."""
        if prefill == "stream":
            for slot in free_slots:
                if queue:
                    state = admit_stream(state, slot)
            return state
        picked = []  # (slot, req_idx, tokens, cap, key, bucket)
        for slot in free_slots:
            if not queue:
                break
            req_idx, tokens, cap = queue.pop(0)
            slot_req[slot] = req_idx
            min_left[slot] = cap - 1
            bucket = min(
                1 << (int(tokens.size) - 1).bit_length(), config.max_seq
            )
            # The documented per-admission key chain: one split per
            # admitted request, in admission order, regardless of how
            # admissions group into waves.
            adm_rng["key"], key = jax.random.split(adm_rng["key"])
            picked.append((slot, req_idx, tokens, cap, key, bucket))
        for bucket in sorted({p[5] for p in picked}):
            group = [p for p in picked if p[5] == bucket]
            g = 1 << (len(group) - 1).bit_length()  # pad to power of two
            rows = np.full((g, length), pad, np.int32)
            padded = np.full((g, bucket), pad, np.int32)
            plens = np.ones(g, np.int32)
            slots = np.full(g, batch, np.int32)  # OOB rows are dropped
            caps_in = np.ones(g, np.int32)
            keys = [jax.random.PRNGKey(0)] * g
            for r, (slot, _, tokens, cap, key, _) in enumerate(group):
                rows[r, : tokens.size] = tokens
                padded[r, : tokens.size] = tokens
                plens[r] = tokens.size
                slots[r] = slot
                caps_in[r] = cap
                keys[r] = key
            wave = _make_admit(
                decoder, float(temperature), top_k, eos_token_id,
                int(batch), int(bucket), int(g),
            )
            state = wave(
                params, state, jnp.asarray(rows), jnp.asarray(padded),
                jnp.asarray(plens), jnp.asarray(slots),
                jnp.asarray(caps_in), jnp.stack(keys),
            )
            _count("prefill_passes")
        return state

    state = (
        caches, jnp.asarray(buffer), jnp.asarray(pos), jnp.asarray(plen),
        jnp.asarray(row_cap), jnp.asarray(n_gen), jnp.asarray(done), rng,
    )
    state = admit_group(state, list(range(batch)))

    while True:
        # Run as many sync chunks as the host can PROVE are finish-free
        # before paying a blocking fetch: with no EOS the per-slot budget
        # bound is exact, so fetches happen only at boundaries where a
        # request can actually complete.  With EOS the loop always stays
        # at one chunk per fetch — a slot can finish any step, and
        # multi-chunking would keep stepping frozen rows for up to the
        # residual cap after every live row has stopped.
        active = [s for s in range(batch) if slot_req[s] >= 0]
        chunks = 1
        if eos_token_id is None:
            # Without EOS the budget bound is exact, so this skips only
            # provably finish-free fetches.  With EOS a slot can finish
            # any step, and multi-chunking would keep stepping frozen
            # rows for up to the residual cap after every live row has
            # stopped — one chunk per fetch stays the honest choice.
            bound = min((min_left[s] for s in active), default=1)
            chunks = max(1, -(-bound // sync_steps))
        for _ in range(chunks):
            state = run_steps(params, state)
        _count("device_chunks", chunks)
        for s in active:
            min_left[s] = max(min_left[s] - chunks * sync_steps, 0)
        done_h = np.asarray(state[6])
        _count("sync_fetches")
        finished = [
            s for s in range(batch) if done_h[s] and slot_req[s] >= 0
        ]
        if finished:
            # Bulk-harvest: ONE fetch each of buffer/plen/n_gen per sync
            # boundary instead of three per finished slot — every fetch
            # blocks on the device, and this loop's host chatter is the
            # serving throughput floor.
            # Admissions below only mutate freed slots, so the
            # pre-admission snapshot stays valid for the other rows.
            buffer_h = np.asarray(state[1])
            plen_h = np.asarray(state[3])
            n_gen_h = np.asarray(state[5])
            for slot in finished:
                keep = int(plen_h[slot]) + int(n_gen_h[slot])
                outputs[slot_req[slot]] = buffer_h[slot, :keep].copy()
                slot_req[slot] = -1
            if queue:
                state = admit_group(state, finished)
        if not queue and all(r < 0 for r in slot_req):
            break
    return outputs  # type: ignore[return-value]


class ContinuousEngine:
    """Incremental continuous batching for a *resident* model server.

    ``continuous_generate`` serves one closed batch of prompts and
    returns; a serving session needs the same fixed-slot loop held open
    indefinitely, with requests admitted and harvested as they come.
    This class is that loop turned inside out, implementing the worker
    harness's duck-typed serving-engine surface
    (``slots`` / :meth:`admit` / :meth:`step` / :meth:`cancel`):

    * construction loads ``params`` and builds the jitted admission and
      decode programs ONCE (shared, via the same ``_make_admit`` /
      ``_make_run_steps`` caches ``continuous_generate`` compiles
      through, so a session and a batch call with the same shape reuse
      one executable);
    * :meth:`admit` queues a request for a free slot — admissions flush
      in the same fused, bucketed prefill waves as ``continuous_generate``
      (one compiled call per bucket per flush, first token included);
    * :meth:`step` runs ONE ``sync_steps`` decode chunk across every busy
      lane and returns the fresh tokens per request since the last chunk
      — the incremental stream a serving session pushes to its callers,
      so time-to-first-token is one chunk, not end-of-response.

    Numerics are ``continuous_generate``'s exactly: each lane is a vmapped
    batch-1 decode, greedy rows bit-identical to ``generate()`` on
    batch-rounding-invariant backends, and sampled requests draw from the
    dedicated admission key chain.  Buffer width is static
    (``length``, default ``config.max_seq``) — the price of compiling
    once for a session's whole lifetime.

    **Prefix tree.**  Prefill reuse is generalized beyond one static
    ``shared_prefix``: the engine keeps a small LRU *prefix tree* of
    reusable KV lanes keyed by token-prefix digest.  Every admission's
    post-prefill lane is inserted (cursor parked at the prompt length),
    and a later prompt reuses the DEEPEST cached lane sharing a common
    prefix with it — including a *partial* reuse, where a lane prefilled
    for ``[a b c d]`` serves a prompt ``[a b x ...]`` rewound to the
    2-token common prefix (positions past the rewound cursor are dead
    until overwritten, the same exactness argument as pad positions).
    Repeated prompts therefore hit warm KV (the previous admission's
    lane rewound one position) without any configuration; a
    ``shared_prefix`` still seeds a pinned, never-evicted entry.
    Numerics are unchanged: greedy outputs stay bit-identical to the
    full-prefill road (asserted against the oracle in
    ``tests/test_continuous.py``) and hits strictly shrink
    ``stats["prefill_positions"]``.  ``prefix_cache_size`` bounds the
    unpinned entries (0 disables reuse caching); ``prefix_min_tokens``
    is the shortest reusable prefix worth a dedicated compiled wave.

    **KV export/import (disaggregated prefill/decode).**
    :meth:`prefill_only` runs the admission prefill for one prompt and
    returns a serializable KV *bundle* — cache lane, cursor, first
    generated token, rng/sampling fingerprint — without occupying a
    decode slot; :meth:`admit_from_kv` scatters an imported bundle into
    a free slot and goes straight to decode.  A prefill-tier engine and
    a decode-tier engine composed this way stream greedy tokens
    bit-identical to one engine doing both (the serving tier's
    ``DisaggregatedSet`` rides exactly this pair through the CAS).

    **Speculative decoding (``draft_model``).**  With a draft model the
    greedy decode loop becomes draft-and-verify: each chunk runs
    ``sync_steps // (draft_len + 1)`` rounds in which every lane drafts
    ``draft_len`` tokens autoregressively through the small model, then
    the target scores all lanes' ``draft_len + 1`` slabs in ONE fused
    vmapped pass and commits the longest agreeing prefix plus its own
    choice at the first disagreement.  Every committed token is the
    target's greedy pick, so spec streams are **bit-identical** to the
    same engine without a draft; ``stats`` grows
    ``spec_proposed``/``spec_accepted`` (the accept-rate feed).  Any
    construction-time refusal — sampled session, vocab mismatch,
    rolling-cache draft, missing ``max_seq`` headroom for the verify
    slab (``length + draft_len``) — silently falls back to the plain
    loop (``spec_refusals`` counts it, ``_spec_refusal`` names it).

    **Decode-mode lane groups (``decode_modes`` + per-request
    ``quality``).**  Beyond the fp lanes, the engine can build int8 /
    kv-quant / full-quant groups (:func:`..quant.mode_variant` twins,
    each a private sub-engine with its own slots, prefix tree, and spec
    loop).  A request's ``params["quality"]`` selects its group; unknown
    or refused modes fall back to fp bit-exact (``mode_refusals``).  KV
    bundles carry a ``quant`` fingerprint and only admit into the
    matching group — a mismatch raises, and the session harness degrades
    to a full prefill.  ``stats["mode_tokens_<mode>"]`` counts per-group
    output tokens.

    **Multi-adapter bank (``adapters`` — batched LoRA multiplexing).**
    ``adapters={name: lora_params}`` keeps the BASE weights resident
    once and stacks every adapter's rank-r ``lora_a``/``lora_b`` leaves
    into ``[n_slots, ...]`` bank arrays; each lane carries an int32 bank
    slot in its cache tree and the compiled programs gather the lane's
    adapter INSIDE the jit (:class:`_AdapterDecoder`) — one compiled
    step serves every adapter, heterogeneous-adapter traffic co-batches
    in the same fused decode and admission waves, and slot 0's zero-B
    identity makes a base lane bit-equal to the plain engine.  A
    request's ``params["adapter"]`` selects by name (unknown names
    refuse cleanly); :meth:`attach_adapter` splices a new adapter — or
    hot-swaps a live name with zero drops — into the RUNNING session
    (bank scatter, never a recompile), bounded by
    ``COVALENT_TPU_SERVE_ADAPTERS_MAX`` (default 8).  Composes with
    ``decode_modes`` via ``quantize_then_lora`` semantics (each
    quantized group attaches the same adapters over its quantized base;
    refusals degrade to fp) and with the prefix tree / KV bundles via
    adapter-scoped keys and name+digest fingerprints — cross-adapter
    K/V reuse is structurally impossible.  Speculative decoding refuses
    adapter banks (plain-loop fallback).  Per-adapter
    ``stats["adapter_tokens_<name>"]`` / ``adapter_requests_<name>``
    feed the serving metrics.
    """

    def __init__(
        self,
        model: TransformerLM,
        params: Any,
        *,
        max_batch: int = 4,
        temperature: float = 0.0,
        top_k: int | None = None,
        rng: jax.Array | None = None,
        eos_token_id: int | None = None,
        pad_token_id: int | None = None,
        sync_steps: int = 8,
        max_new_tokens: int = 16,
        length: int | None = None,
        shared_prefix: Sequence[int] | None = None,
        prefix_cache_size: int = 8,
        prefix_min_tokens: int = 4,
        decode_modes: Sequence[str] = ("fp",),
        draft_model: TransformerLM | None = None,
        draft_params: Any = None,
        draft_len: int = 4,
        adapters: dict[str, Any] | None = None,
        adapter_rank: int | None = None,
        adapter_alpha: float = 16.0,
        adapters_max: int | None = None,
    ) -> None:
        decoder = _decode_model(model)
        config = decoder.config
        _require_plain_cache(config, "ContinuousEngine")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if sync_steps < 1:
            raise ValueError(f"sync_steps must be >= 1, got {sync_steps}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if temperature <= 0 and top_k is not None:
            raise ValueError("top_k requires sampling (temperature > 0)")
        if top_k is not None and not 1 <= top_k <= config.vocab_size:
            raise ValueError(
                f"top_k must be in [1, {config.vocab_size}], got {top_k}"
            )
        self._length = int(length or config.max_seq)
        if not 2 <= self._length <= config.max_seq:
            raise ValueError(
                f"length must be in [2, {config.max_seq}], got {self._length}"
            )
        #: host-loop counters (created early: adapter installs seed their
        #: per-name token keys here): prefix-tree hit/miss accounting,
        #: the prefill positions each admission paid, the KV plane's
        #: traffic, spec/mode refusals, and the adapter bank's lifecycle.
        self.stats: dict[str, int] = {
            "prefix_hits": 0, "prefix_misses": 0, "prefill_positions": 0,
            "prefix_evictions": 0, "kv_admits": 0, "kv_exports": 0,
            "spec_rounds": 0, "spec_proposed": 0, "spec_accepted": 0,
            "spec_refusals": 0, "mode_refusals": 0,
            "adapter_prefix_blocked": 0, "adapter_attaches": 0,
            "adapter_detaches": 0, "adapter_swaps": 0,
        }
        #: prefix digest -> _PrefixEntry, oldest-insert first (LRU order
        #: maintained by move_to_end on every hit).
        self._prefix_tree: "collections.OrderedDict[str, _PrefixEntry]" = (
            collections.OrderedDict()
        )
        self._prefix_cache_size = max(0, int(prefix_cache_size))
        self._prefix_min = max(1, int(prefix_min_tokens))

        # -- multi-adapter bank (batched LoRA multiplexing) ----------------
        # One resident base plus up to adapters_max named rank-r adapters:
        # the lora_a/lora_b leaves stack into [n_slots, ...] bank arrays,
        # every lane carries an int32 bank slot, and the compiled
        # programs gather each lane's adapter inside the jit
        # (_AdapterDecoder) — rank-r GEMMs on top of the shared base
        # pass, one compiled step for ALL adapters.  Slot 0 holds the
        # zero-B identity adapter, so a base lane is bit-equal to the
        # plain engine's.
        self._bank: list | None = None
        self._adapter_slot: dict[str, int] = {}
        self._adapter_digests: dict[str, str] = {}
        self._adapter_free: list[int] = []
        self._adapter_retired: list[int] = []
        self._slot_refs: list[int] = []
        self._rid_adapter: dict[str, tuple[int, str]] = {}
        self._adapter_rank = 0
        self._adapter_alpha = float(adapter_alpha)
        self._adapters_max = 0
        if adapters is not None or adapter_rank is not None:
            from .lora import add_lora, lora_mask

            if getattr(config, "lora_rank", 0):
                raise AdapterUnsupported(
                    "the adapter bank needs the BASE model, and this one "
                    f"already carries adapters (lora_rank="
                    f"{config.lora_rank}) — serve the base and attach "
                    "adapters on top (lora.quantize_then_lora order)"
                )
            adapters = {str(k): v for k, v in (adapters or {}).items()}
            rank = adapter_rank
            if rank is None:
                if not adapters:
                    raise AdapterUnsupported(
                        "an empty bank needs adapter_rank to size its "
                        "template"
                    )
                try:
                    rank = int(np.asarray(self._adapter_payload_leaves(
                        next(iter(adapters.values()))
                    )[0]).shape[-1])
                except (ValueError, IndexError, TypeError) as exc:
                    raise AdapterUnsupported(
                        f"cannot infer the adapter rank: {exc}"
                    ) from exc
            if int(rank) < 1:
                raise AdapterUnsupported(
                    f"adapter_rank must be >= 1, got {rank}"
                )
            limit = adapters_max
            if limit is None:
                limit = int(os.environ.get(ADAPTERS_MAX_ENV) or 8)
            if int(limit) < max(1, len(adapters)):
                raise AdapterUnsupported(
                    f"{len(adapters)} adapters exceed the bank's "
                    f"{limit} named slots ({ADAPTERS_MAX_ENV})"
                )
            try:
                lmodel, filled = add_lora(
                    model, params, rank=int(rank),
                    alpha=float(adapter_alpha),
                )
            except ValueError as exc:
                raise AdapterUnsupported(str(exc)) from exc
            self._adapter_rank = int(rank)
            self._adapters_max = int(limit)
            leaves, lora_treedef = jax.tree_util.tree_flatten(filled)
            mask = tuple(
                bool(m)
                for m in jax.tree_util.tree_leaves(lora_mask(filled))
            )
            self._bank_base = [
                leaf for leaf, m in zip(leaves, mask) if not m
            ]
            template = [leaf for leaf, m in zip(leaves, mask) if m]
            self._adapter_shapes = [
                (tuple(leaf.shape), jnp.dtype(leaf.dtype))
                for leaf in template
            ]
            n_slots = int(limit) + 1  # + the pinned identity at slot 0
            self._bank = [
                jnp.zeros((n_slots,) + leaf.shape, leaf.dtype).at[0].set(
                    leaf
                )
                for leaf in template
            ]
            self._adapter_free = list(range(1, n_slots))
            self._slot_refs = [0] * n_slots
            decoder = _AdapterDecoder(
                _decode_model(lmodel), lora_treedef, mask
            )
            for name, payload in adapters.items():
                self._install_adapter(name, payload)
            self.stats.setdefault("adapter_tokens_base", 0)

        self._decoder = decoder
        self._config = config
        self._params = (
            {"base": self._bank_base, "bank": self._bank}
            if self._bank is not None else params
        )
        self._temperature = float(temperature)
        self._top_k = top_k
        self._eos = eos_token_id
        pad = pad_token_id
        if pad is None:
            pad = eos_token_id if eos_token_id is not None else 0
        self._pad = int(pad)
        self._sync = int(sync_steps)
        self._default_cap = int(max_new_tokens)
        self.slots = batch = int(max_batch)

        if rng is None:
            rng = jax.random.PRNGKey(0)
        rng = jnp.array(rng, copy=True)
        lane = init_cache(model, 1)
        caches = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(
                leaf[None], (batch,) + leaf.shape
            ).copy(),
            lane,
        )
        if self._bank is not None:
            # Each lane's bank slot rides the cache tree itself, so the
            # donated jitted programs carry it without signature changes.
            caches = {"kv": caches, "adapter": jnp.zeros(batch, jnp.int32)}
        self._state = (
            caches,
            jnp.full((batch, self._length), self._pad, jnp.int32),
            jnp.zeros(batch, jnp.int32),   # pos
            jnp.ones(batch, jnp.int32),    # plen
            jnp.ones(batch, jnp.int32),    # row_cap
            jnp.zeros(batch, jnp.int32),   # n_gen
            jnp.ones(batch, bool),         # done (empty slots are "done")
            rng,
        )
        self._run_steps = _make_run_steps(
            decoder, self._temperature, top_k, eos_token_id,
            self._length, self._sync, batch,
        )
        self._adm_key = jax.random.fold_in(rng, 0x5E1)
        #: slot -> rid (None = free), and generated tokens already streamed.
        self._slot_rid: list[str | None] = [None] * batch
        self._reported = [0] * batch
        self._rid_slot: dict[str, int] = {}
        #: admissions awaiting a flush: (rid, tokens, cap, bank slot).
        self._pending: list[tuple[str, np.ndarray, int, int]] = []
        #: KV-bundle admissions awaiting a flush:
        #: (rid, tokens, cap, first token, imported lane, bank slot).
        self._pending_kv: list[
            tuple[str, np.ndarray, int, int, Any, int]
        ] = []
        #: canonical lane layout: the treedef every imported KV bundle is
        #: rebuilt against and the shape/dtype table it is validated by.
        lane_leaves, self._lane_treedef = jax.tree_util.tree_flatten(lane)
        self._lane_shapes = [
            (tuple(leaf.shape), jnp.dtype(leaf.dtype))
            for leaf in lane_leaves
        ]
        if shared_prefix is not None:
            ptoks = np.asarray(shared_prefix, np.int32).reshape(-1)
            if ptoks.size < 1:
                raise ValueError("shared_prefix needs at least one token")
            if ptoks.size + 2 > self._length:
                raise ValueError(
                    f"shared_prefix ({ptoks.size} tokens) leaves no room "
                    f"for a suffix + generation inside the session's "
                    f"static length ({self._length})"
                )
            # Prefill the shared prefix ONCE per engine (per replica):
            # one exact-length pass on a zero lane, cursor parked at the
            # prefix boundary.  It seeds the prefix tree as a PINNED
            # entry — every prefix-matching admission copies this lane
            # instead of re-running the prefix positions, and LRU churn
            # can never evict it.
            zero = jax.tree_util.tree_map(jnp.zeros_like, lane)
            if self._bank is not None:
                zero = {"kv": zero, "adapter": jnp.zeros((), jnp.int32)}
            _logits, mutated = decoder.apply(
                {"params": self._params, "cache": zero},
                jnp.asarray(ptoks)[None],
                mutable=["cache"],
            )
            prefix_lane = _set_cursor(mutated["cache"], int(ptoks.size))
            self._insert_prefix(ptoks, lambda: prefix_lane, pinned=True)

        # -- speculative decoding (greedy draft-and-verify) ----------------
        # The draft proposes draft_len tokens per lane per round; the
        # target verifies each lane's slab in the fused vmapped pass.
        # Every committed token is the target's own greedy choice, so a
        # spec session's streams are bit-identical to this engine without
        # the draft — which is also the fallback on ANY refusal below
        # (recorded in stats["spec_refusals"] + _spec_refusal, never an
        # error: a serving session must come up degraded, not dead).
        self._draft = None
        self._draft_params = None
        self._draft_caches = None
        self._spec_run = None
        self._spec_rounds = 0
        self._spec_refusal: str | None = None
        self._draft_len = int(draft_len)
        if draft_model is not None:
            if self._draft_len < 1:
                raise ValueError(
                    f"draft_len must be >= 1, got {draft_len}"
                )
            ddecoder = _decode_model(draft_model)
            dconfig = ddecoder.config
            reason = None
            if self._bank is not None:
                reason = (
                    "multi-adapter session (the draft-verify loop runs "
                    "one shared draft; adapter banks fall back to the "
                    "plain loop)"
                )
            elif self._temperature > 0:
                reason = (
                    "sampled session (the continuous verify path is "
                    "greedy-only; use speculative_sample offline)"
                )
            elif dconfig.vocab_size != config.vocab_size:
                reason = (
                    f"draft vocab {dconfig.vocab_size} != target "
                    f"{config.vocab_size}"
                )
            elif dconfig.rolling_cache:
                reason = "draft model uses rolling_cache"
            elif self._length + self._draft_len > config.max_seq:
                reason = (
                    f"target max_seq {config.max_seq} < length + "
                    f"draft_len = {self._length + self._draft_len} "
                    "(verify slabs need scratch headroom)"
                )
            elif self._length + self._draft_len > dconfig.max_seq:
                reason = (
                    f"draft max_seq {dconfig.max_seq} < length + "
                    f"draft_len = {self._length + self._draft_len}"
                )
            if reason is None:
                self._draft = ddecoder
                self._draft_params = draft_params
                dlane = init_cache(draft_model, 1)
                self._draft_caches = jax.tree_util.tree_map(
                    lambda leaf: jnp.broadcast_to(
                        leaf[None], (batch,) + leaf.shape
                    ).copy(),
                    dlane,
                )
                # A plain chunk decodes sync_steps tokens; a spec chunk
                # commits 1..draft_len+1 per round, so this many rounds
                # keeps the admission-latency granularity comparable.
                self._spec_rounds = max(
                    1, self._sync // (self._draft_len + 1)
                )
                self._spec_run = _make_spec_run_steps(
                    decoder, ddecoder, eos_token_id, self._length,
                    self._draft_len, self._spec_rounds, batch,
                )
            else:
                self._spec_refusal = reason
                self.stats["spec_refusals"] += 1

        # -- decode-mode lane groups (per-request quality routing) ---------
        # Each non-fp mode is a full sub-engine over the mode_variant
        # model twin: its own slots, prefix tree, compiled programs, and
        # (when a draft is configured) its own spec verify loop against
        # ITS target — so an int8 lane's spec commits the int8 model's
        # greedy choices.  The primary stays the fp group and the single
        # public surface; total concurrency across all groups is bounded
        # by ``slots`` (the ``busy`` property sums the groups), trading
        # lane memory for never refusing a routed request that the
        # session-level admission already accepted.  A mode that REFUSES
        # to build (quantize_lm on MoE/scanned/LoRA models) is recorded
        # and its requests fall back to fp, bit-exact.
        modes = tuple(dict.fromkeys(decode_modes or ("fp",)))
        for mode in modes:
            if mode not in SERVING_MODES:
                raise ValueError(
                    f"unknown decode mode {mode!r}; expected a subset "
                    f"of {SERVING_MODES}"
                )
        if "fp" not in modes:
            raise ValueError(
                "decode_modes must include 'fp' (the bit-exact fallback "
                "lane every refusal degrades to)"
            )
        self._mode = "fp"
        self._subs: dict[str, ContinuousEngine] = {}
        self._sub_stats_seen: dict[str, dict[str, int]] = {}
        self._rid_mode: dict[str, str] = {}
        self._mode_refusal: dict[str, str] = {}
        for mode in modes:
            if mode == "fp":
                continue
            sub_kwargs: dict[str, Any] = {}
            if self._bank is not None:
                # quantize_then_lora composition: the twin quantizes the
                # BASE model, then the sub-engine attaches the SAME
                # adapter set on top — exactly lora.quantize_then_lora's
                # order.  A variant the composition refuses (quantize_lm
                # on MoE/scanned bases, adapter-template mismatch) is a
                # recorded per-mode refusal with fp fallback, never an
                # error.
                sub_kwargs = dict(
                    adapters=adapters,
                    adapter_rank=self._adapter_rank,
                    adapter_alpha=self._adapter_alpha,
                    adapters_max=self._adapters_max,
                )
            try:
                sub_model, sub_params = mode_variant(model, params, mode)
                sub = ContinuousEngine(
                    sub_model, sub_params,
                    max_batch=max_batch, temperature=temperature,
                    top_k=top_k, rng=rng, eos_token_id=eos_token_id,
                    pad_token_id=pad_token_id, sync_steps=sync_steps,
                    max_new_tokens=max_new_tokens, length=self._length,
                    shared_prefix=shared_prefix,
                    prefix_cache_size=prefix_cache_size,
                    prefix_min_tokens=prefix_min_tokens,
                    draft_model=draft_model, draft_params=draft_params,
                    draft_len=draft_len,
                    **sub_kwargs,
                )
            except ValueError as exc:
                self._mode_refusal[mode] = str(exc)
                self.stats["mode_refusals"] += 1
                continue
            sub._mode = mode
            self._subs[mode] = sub
            self._sub_stats_seen[mode] = {}
        for mode in modes:
            self.stats.setdefault(f"mode_tokens_{mode}", 0)

    # -- serving-engine surface -------------------------------------------

    def _dup(self, rid: str) -> bool:
        """True when ``rid`` is already admitted anywhere: a live or
        pending lane here, or routed to a mode group."""
        return (
            rid in self._rid_slot
            or rid in self._rid_mode
            or any(p[0] == rid for p in self._pending)
            or any(p[0] == rid for p in self._pending_kv)
        )

    def _route_mode(self, params: dict) -> str:
        """Resolve a request's ``quality`` knob to a decode mode.

        ``None``/``"exact"``/``"fp"`` → the fp lane.  A known mode with a
        built lane group → that group.  Anything else — an unknown value,
        or a mode this session refused/never configured — falls back to
        the bit-exact fp lane and counts a ``mode_refusals`` (a serving
        session degrades, it does not reject a request over a knob).
        """
        quality = params.get("quality")
        if quality is None:
            return self._mode
        mode = "fp" if quality == "exact" else str(quality)
        if mode == self._mode or mode in self._subs:
            return mode
        self.stats["mode_refusals"] += 1
        return self._mode

    def admit(self, rid: str, prompt, params: dict | None = None) -> None:
        """Reserve a lane for one request (flushed at the next step).

        ``params`` may carry ``max_new_tokens`` and ``quality`` (a
        decode-mode name — see :func:`..quant.mode_variant`; unknown or
        unavailable modes fall back to the bit-exact fp lane); everything
        else (temperature, top_k, EOS) is session-static — the compiled
        programs key on them.  Raises on malformed prompts, so the
        session rejects the request instead of wedging a lane.
        """
        params = params or {}
        if self._dup(rid):
            raise ValueError(f"request id {rid!r} already admitted")
        mode = self._route_mode(params)
        if mode != self._mode:
            if self.busy >= self.slots:
                raise RuntimeError("no free lane (all slots busy)")
            self._subs[mode].admit(rid, prompt, params)
            self._rid_mode[rid] = mode
            return
        tokens = np.asarray(prompt, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("prompt needs at least one token")
        cap = int(params.get("max_new_tokens", self._default_cap))
        if cap < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {cap}")
        if tokens.size + cap > self._length:
            raise ValueError(
                f"prompt + budget ({tokens.size + cap}) exceeds the "
                f"session's static length ({self._length})"
            )
        if self.busy >= self.slots:
            raise RuntimeError("no free lane (all slots busy)")
        aslot, aname = self._resolve_adapter(params)
        if self._bank is not None:
            self._rid_adapter[rid] = (aslot, aname)
            self._slot_refs[aslot] += 1
            key = f"adapter_requests_{aname}"
            self.stats[key] = self.stats.get(key, 0) + 1
        self._pending.append((rid, tokens, cap, aslot))

    # -- multi-adapter bank surface ----------------------------------------

    @staticmethod
    def _adapter_payload_leaves(payload) -> list:
        """Normalize an adapter payload to its ordered leaf list.

        Accepts the CAS registry's bundle dict (``{"leaves": [...]}``),
        a bare leaf list (the wire form), or a full LoRA params tree
        (:func:`..lora.adapter_leaves` extracts the adapter leaves in
        flatten order — identical across the float and quantized model
        twins, which is what lets ONE trained adapter splice into every
        decode-mode lane group).
        """
        if isinstance(payload, dict) and "leaves" in payload:
            return list(payload["leaves"])
        if isinstance(payload, (list, tuple)):
            return list(payload)
        from .lora import adapter_leaves

        return adapter_leaves(payload)

    def _install_adapter(self, name: str, payload) -> str:
        """Write one adapter into a free bank slot; returns its digest.

        A re-install under a live name is the zero-drop hot swap: the
        NEW generation takes a fresh slot and the name repoints to it —
        lanes already decoding keep gathering the old slot's weights
        until they finish (the retired slot is only reclaimed once its
        in-flight refcount drains), while every subsequent admission
        resolves the new generation.  No lane is ever touched mid-wave.
        """
        if (
            not name or name == "base"
            or not all(ch.isalnum() or ch in "._-" for ch in name)
        ):
            raise AdapterUnsupported(
                f"invalid adapter name {name!r} ('base' is reserved; "
                "names are [A-Za-z0-9._-])"
            )
        try:
            leaves = self._adapter_payload_leaves(payload)
        except (ValueError, KeyError, TypeError) as exc:
            raise AdapterUnsupported(
                f"adapter {name!r} payload is not an adapter: {exc}"
            ) from exc
        if len(leaves) != len(self._adapter_shapes):
            raise AdapterUnsupported(
                f"adapter {name!r} has {len(leaves)} leaves; this bank's "
                f"template has {len(self._adapter_shapes)}"
            )
        cast = []
        for leaf, (shape, dtype) in zip(leaves, self._adapter_shapes):
            arr = np.asarray(leaf)
            if tuple(arr.shape) != shape:
                raise AdapterUnsupported(
                    f"adapter {name!r} leaf {tuple(arr.shape)} does not "
                    f"match the bank template {shape} (rank/geometry "
                    "mismatch)"
                )
            cast.append(arr.astype(dtype))
        if isinstance(payload, dict) and payload.get("digest"):
            digest = str(payload["digest"])
        else:
            from .lora import adapter_digest

            digest = adapter_digest(cast)
        self._reclaim_adapter_slots()
        if not self._adapter_free:
            raise AdapterUnsupported(
                f"adapter bank is full ({self._adapters_max} named slots,"
                f" {ADAPTERS_MAX_ENV}); detach one or raise the limit"
            )
        slot = self._adapter_free.pop(0)
        for i, arr in enumerate(cast):
            self._bank[i] = self._bank[i].at[slot].set(jnp.asarray(arr))
        old = self._adapter_slot.get(name)
        self._adapter_slot[name] = slot
        self._adapter_digests[name] = digest
        self.stats.setdefault(f"adapter_tokens_{name}", 0)
        if old is not None:
            self._adapter_retired.append(old)
            self._purge_prefix_slot(old)
            self.stats["adapter_swaps"] += 1
        return digest

    def attach_adapter(self, name: str, payload) -> str:
        """Splice an adapter into the RUNNING session; returns its
        digest.  Live traffic keeps decoding throughout — attachment is
        a bank scatter plus a name-table write, never a recompile (the
        compiled programs key on the bank's static shape).  Re-attaching
        a live name hot-swaps it with zero drops (see
        :meth:`_install_adapter`).  Propagates to every decode-mode lane
        group, so a ``quality``-routed request finds the adapter in its
        quantized group too (quantize_then_lora composition).
        """
        if self._bank is None:
            raise AdapterUnsupported(
                "this session hosts no adapter bank (construct the "
                "engine with adapters= or adapter_rank=)"
            )
        digest = self._install_adapter(name, payload)
        for sub in self._subs.values():
            if sub._bank is not None:
                sub.attach_adapter(name, payload)
        self.stats["adapter_attaches"] += 1
        return digest

    def detach_adapter(self, name: str) -> None:
        """Retire a named adapter: new requests refuse it immediately;
        its bank slot is reclaimed once in-flight lanes drain."""
        slot = self._adapter_slot.pop(name, None)
        if slot is None:
            raise ValueError(
                f"unknown adapter {name!r}; attached: "
                f"{sorted(self._adapter_slot) or 'none'}"
            )
        self._adapter_digests.pop(name, None)
        self._adapter_retired.append(slot)
        self._purge_prefix_slot(slot)
        self._reclaim_adapter_slots()
        for sub in self._subs.values():
            if sub._bank is not None and name in sub._adapter_slot:
                sub.detach_adapter(name)
        self.stats["adapter_detaches"] += 1

    @property
    def adapters(self) -> tuple[str, ...]:
        """Currently attached adapter names (insertion order)."""
        return tuple(self._adapter_slot)

    @property
    def adapter_digests(self) -> dict[str, str]:
        """name -> content digest of the attached generation."""
        return dict(self._adapter_digests)

    def _reclaim_adapter_slots(self) -> None:
        """Return retired bank slots whose in-flight lanes drained."""
        still = []
        for slot in self._adapter_retired:
            if self._slot_refs[slot] == 0:
                self._adapter_free.append(slot)
            else:
                still.append(slot)
        self._adapter_retired = still

    def _purge_prefix_slot(self, aslot: int) -> None:
        """Drop prefix-tree lanes computed under a retired bank slot —
        their K/V embeds the OLD generation's weights."""
        stale = [
            d for d, e in self._prefix_tree.items() if e.aslot == aslot
        ]
        for d in stale:
            del self._prefix_tree[d]

    def _release_adapter(self, rid: str) -> None:
        """Drop one request's hold on its bank slot (idempotent)."""
        entry = self._rid_adapter.pop(rid, None)
        if entry is not None and self._slot_refs:
            slot = entry[0]
            self._slot_refs[slot] = max(0, self._slot_refs[slot] - 1)

    def _resolve_adapter(self, params: dict) -> tuple[int, str]:
        """``params["adapter"]`` -> (bank slot, name); base is slot 0.

        Unknown names raise :class:`ValueError` — the session REFUSES
        the request cleanly instead of silently serving base weights.
        """
        name = str(params.get("adapter") or "")
        if self._bank is None:
            if name and name != "base":
                raise ValueError(
                    f"unknown adapter {name!r} (this session hosts no "
                    "adapter bank)"
                )
            return 0, "base"
        if not name or name == "base":
            return 0, "base"
        slot = self._adapter_slot.get(name)
        if slot is None:
            raise ValueError(
                f"unknown adapter {name!r}; attached: "
                f"{sorted(self._adapter_slot) or 'none'}"
            )
        return slot, name

    # -- disaggregated prefill/decode surface ------------------------------

    def prefill_only(self, prompt, params: dict | None = None) -> bytes:
        """Run the admission prefill for one prompt WITHOUT taking a
        decode slot; returns a serialized KV bundle.

        The bundle carries everything :meth:`admit_from_kv` needs to
        skip prefill entirely on another engine of the same model: the
        prompt, the prefilled cache lane (cursor parked at the prompt
        length), the first generated token, and the admission rng /
        sampling fingerprint.  The prefill itself is the admission
        wave's exact computation (prefix-tree hits included — a prefill
        tier warms its own tree), so a decode engine admitting the
        bundle streams greedy tokens bit-identical to one engine doing
        both phases.  Consumes one key from this engine's admission
        chain, like a normal admission.

        The bundle carries a quantization fingerprint (``quant``: this
        lane group's decode mode) validated by :meth:`admit_from_kv`
        exactly like the sampling fingerprint; a request's ``quality``
        knob routes the prefill to the matching mode group, so a
        ``kv_quant``/``full_quant`` prefill ships int8 KV leaves —
        roughly 2-4x smaller on the wire than the fp lane's f32/bf16.
        """
        params = params or {}
        mode = self._route_mode(params)
        if mode != self._mode:
            return self._subs[mode].prefill_only(prompt, params)
        tokens = np.asarray(prompt, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("prompt needs at least one token")
        if tokens.size + 1 > self._length:
            raise ValueError(
                f"prompt ({tokens.size} tokens) leaves no room for "
                f"generation inside the session's static length "
                f"({self._length})"
            )
        aslot, aname = self._resolve_adapter(params)
        self._adm_key, key = jax.random.split(self._adm_key)
        m, lane_m, _entry_digest = self._lookup_prefix(tokens, aslot)
        if m:
            bucket = min(
                1 << (int(tokens.size) - m - 1).bit_length(),
                self._config.max_seq - m,
            )
            suffix = tokens[m:]
            padded = np.full((1, bucket), self._pad, np.int32)
            padded[0, : suffix.size] = suffix
            fn = _make_lane_prefix_prefill(
                self._decoder, self._temperature, self._top_k,
                int(bucket), int(m),
            )
            lane, first = fn(
                self._params, lane_m, jnp.asarray(padded),
                jnp.asarray([suffix.size], jnp.int32), key[None],
            )
            self.stats["prefix_hits"] += 1
        else:
            bucket = min(
                1 << (int(tokens.size) - 1).bit_length(),
                self._config.max_seq,
            )
            padded = np.full((1, bucket), self._pad, np.int32)
            padded[0, : tokens.size] = tokens
            lane_zero = jax.tree_util.tree_unflatten(
                self._lane_treedef,
                [
                    jnp.zeros(shape, dtype)
                    for shape, dtype in self._lane_shapes
                ],
            )
            if self._bank is not None:
                lane_zero = {
                    "kv": lane_zero,
                    "adapter": jnp.asarray(aslot, jnp.int32),
                }
            fn = _make_lane_prefill(
                self._decoder, self._temperature, self._top_k, int(bucket),
            )
            lane, first = fn(
                self._params, lane_zero, jnp.asarray(padded),
                jnp.asarray([tokens.size], jnp.int32), key[None],
            )
            if self._prefix_tree:
                self.stats["prefix_misses"] += 1
        self.stats["prefill_positions"] += bucket
        self.stats["kv_exports"] += 1
        self._insert_prefix(tokens, lambda: lane, aslot=aslot)
        # The bank slot index is ENGINE-LOCAL — the wire form carries the
        # adapter NAME + content digest, and the importer re-wraps the
        # inner lane with ITS local slot (refusing a name it does not
        # host, or a digest from a superseded generation).
        leaves = jax.tree_util.tree_leaves(
            lane["kv"] if self._bank is not None else lane
        )
        bundle = {
            "v": KV_BUNDLE_VERSION,
            "prompt": [int(t) for t in tokens],
            "first": int(first),
            "plen": int(tokens.size),
            "rng": np.asarray(key),
            "temperature": self._temperature,
            "top_k": self._top_k,
            "eos": self._eos,
            "quant": self._mode,
            "adapter": "" if aname == "base" else aname,
            "adapter_digest": self._adapter_digests.get(aname, ""),
            "leaves": [np.asarray(leaf) for leaf in leaves],
        }
        return pickle.dumps(bundle, protocol=4)

    def admit_from_kv(
        self, rid: str, bundle, params: dict | None = None
    ) -> None:
        """Reserve a lane for a request whose prefill already ran
        elsewhere (flushed at the next step, like :meth:`admit`).

        ``bundle`` is :meth:`prefill_only`'s bytes (or the already
        unpickled dict).  The lane is validated leaf-by-leaf against
        this engine's cache layout, and the bundle's sampling
        fingerprint (temperature / top_k / eos) against this engine's
        statics — a bundle from a different model shape OR a
        differently-configured engine raises :class:`ValueError` so the
        session falls back to a full prefill instead of decoding a
        stream whose first token was drawn under different rules.  The
        bundle's QUANTIZATION fingerprint (``quant``, default ``fp`` for
        pre-0.17 bundles) routes it to the matching decode-mode lane
        group; a bundle for a mode this session never built raises the
        same way — degrade to full prefill, never decode fp tokens
        against int8 K/V.  No admission key is consumed (the first token
        was drawn by the prefill tier).
        """
        params = params or {}
        if isinstance(bundle, (bytes, bytearray)):
            bundle = pickle.loads(bytes(bundle))
        if not isinstance(bundle, dict) or int(
            bundle.get("v") or 0
        ) != KV_BUNDLE_VERSION:
            raise ValueError("unrecognized KV bundle")
        if self._dup(rid):
            raise ValueError(f"request id {rid!r} already admitted")
        quant = str(bundle.get("quant", "fp") or "fp")
        if quant != self._mode:
            sub = self._subs.get(quant)
            if sub is None:
                raise ValueError(
                    f"KV bundle quantization fingerprint {quant!r} does "
                    f"not match this engine's {self._mode!r} and no "
                    f"{quant!r} lane group is configured"
                )
            if self.busy >= self.slots:
                raise RuntimeError("no free lane (all slots busy)")
            sub._admit_from_kv_dict(rid, bundle, params)
            self._rid_mode[rid] = quant
            return
        self._admit_from_kv_dict(rid, bundle, params)

    def _admit_from_kv_dict(
        self, rid: str, bundle: dict, params: dict
    ) -> None:
        """Validate + queue one unpickled bundle into THIS lane group."""
        quant = str(bundle.get("quant", "fp") or "fp")
        if quant != self._mode:
            raise ValueError(
                f"KV bundle quantization fingerprint {quant!r} does not "
                f"match this lane group's {self._mode!r}"
            )
        fingerprint = (
            float(bundle.get("temperature", 0.0) or 0.0),
            bundle.get("top_k"),
            bundle.get("eos"),
        )
        ours = (self._temperature, self._top_k, self._eos)
        if fingerprint != ours:
            raise ValueError(
                f"KV bundle sampling fingerprint {fingerprint} does not "
                f"match this engine's {ours}"
            )
        if self._dup(rid):
            raise ValueError(f"request id {rid!r} already admitted")
        tokens = np.asarray(bundle.get("prompt") or (), np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("KV bundle has an empty prompt")
        cap = int(params.get("max_new_tokens", self._default_cap))
        if cap < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {cap}")
        if tokens.size + cap > self._length:
            raise ValueError(
                f"prompt + budget ({tokens.size + cap}) exceeds the "
                f"session's static length ({self._length})"
            )
        if self.busy >= self.slots:
            raise RuntimeError("no free lane (all slots busy)")
        aname = str(bundle.get("adapter") or "")
        if aname and self._bank is None:
            raise ValueError(
                f"KV bundle was prefilled under adapter {aname!r} and "
                "this session hosts no adapter bank"
            )
        aslot, alabel = self._resolve_adapter(
            {"adapter": aname} if aname else {}
        )
        if aname:
            want = str(bundle.get("adapter_digest") or "")
            have = self._adapter_digests.get(alabel, "")
            if want and have and want != have:
                raise ValueError(
                    f"KV bundle adapter digest {want[:12]} does not match "
                    f"the attached {aname!r} generation {have[:12]} "
                    "(stale bundle after a hot swap)"
                )
        leaves = bundle.get("leaves")
        if not isinstance(leaves, (list, tuple)) or len(leaves) != len(
            self._lane_shapes
        ):
            raise ValueError(
                "KV bundle does not match this engine's cache layout "
                f"({len(leaves) if isinstance(leaves, (list, tuple)) else 0}"
                f" leaves, want {len(self._lane_shapes)})"
            )
        imported = []
        for leaf, (shape, dtype) in zip(leaves, self._lane_shapes):
            arr = np.asarray(leaf)
            if tuple(arr.shape) != shape or jnp.dtype(arr.dtype) != dtype:
                raise ValueError(
                    f"KV bundle lane leaf {arr.shape}/{arr.dtype} does "
                    f"not match this engine's {shape}/{dtype}"
                )
            imported.append(jnp.asarray(arr))
        lane = jax.tree_util.tree_unflatten(self._lane_treedef, imported)
        if self._bank is not None:
            lane = {"kv": lane, "adapter": jnp.asarray(aslot, jnp.int32)}
            self._rid_adapter[rid] = (aslot, alabel)
            self._slot_refs[aslot] += 1
            key = f"adapter_requests_{alabel}"
            self.stats[key] = self.stats.get(key, 0) + 1
        first = int(bundle.get("first") or 0)
        self._pending_kv.append((rid, tokens, cap, first, lane, aslot))
        self.stats["kv_admits"] += 1

    def step(self) -> list[dict]:
        """Flush admissions, run one sync chunk, return fresh tokens.

        One event per request with new output since the previous chunk:
        ``{"rid", "tokens": [int, ...], "done": bool}`` — the first
        event includes the admission-prefill token, the final one the
        EOS (when configured), exactly the rows ``continuous_generate``
        would return, just delivered incrementally.  Busy decode-mode
        lane groups step in the same call (their events merge in), and
        per-mode token counters plus the groups' own stats fold into
        :attr:`stats` here, so one dict stays the whole session's view.
        """
        if self._bank is not None:
            self._reclaim_adapter_slots()
        events = self._step_local()
        fresh = sum(len(ev["tokens"]) for ev in events)
        if fresh:
            key = f"mode_tokens_{self._mode}"
            self.stats[key] = self.stats.get(key, 0) + fresh
        for mode, sub in self._subs.items():
            if not sub.busy:
                continue
            for ev in sub.step():
                if ev.get("done"):
                    self._rid_mode.pop(ev["rid"], None)
                events.append(ev)
        self._sync_sub_stats()
        return events

    def _sync_sub_stats(self) -> None:
        """Delta-merge the mode groups' counters into the primary's
        stats dict: subs keep counting monotonically, the primary adds
        only what is new since its last sync — ``engine.stats`` stays a
        plain live dict covering every lane group."""
        for mode, sub in self._subs.items():
            seen = self._sub_stats_seen[mode]
            for key, value in sub.stats.items():
                if not isinstance(value, int):
                    continue
                delta = value - seen.get(key, 0)
                if delta:
                    self.stats[key] = self.stats.get(key, 0) + delta
                    seen[key] = value

    def _step_local(self) -> list[dict]:
        """One sync chunk on THIS lane group only (plain or speculative
        decode, whichever the session resolved to at construction)."""
        # Each phase is annotated for the profiler: a capture's host plane
        # then says what the engine did in each device gap.
        if self._pending or self._pending_kv:
            with jax.profiler.TraceAnnotation("serve.engine.admit_wave"):
                self._flush_admissions()
        if not self._rid_slot:
            return []
        with jax.profiler.TraceAnnotation("serve.engine.run_steps"):
            # The dispatch alone: the call returns before the device ends.
            if self._spec_run is not None:
                (self._state, self._draft_caches, proposed, accepted) = (
                    self._spec_run(
                        self._params, self._draft_params, self._state,
                        self._draft_caches,
                    )
                )
            else:
                self._state = self._run_steps(self._params, self._state)
        with jax.profiler.TraceAnnotation("serve.engine.harvest"):
            # The blocking reads: the host waits here for the chunk.
            if self._spec_run is not None:
                self.stats["spec_rounds"] += self._spec_rounds
                self.stats["spec_proposed"] += int(proposed)
                self.stats["spec_accepted"] += int(accepted)
            buffer_h = np.asarray(self._state[1])
            plen_h = np.asarray(self._state[3])
            n_gen_h = np.asarray(self._state[5])
            done_h = np.asarray(self._state[6])
        events: list[dict] = []
        for slot in range(self.slots):
            rid = self._slot_rid[slot]
            if rid is None:
                continue
            total = int(n_gen_h[slot])
            start = int(plen_h[slot]) + self._reported[slot]
            fresh = buffer_h[slot, start: int(plen_h[slot]) + total]
            finished = bool(done_h[slot])
            if fresh.size or finished:
                events.append({
                    "rid": rid,
                    "tokens": [int(t) for t in fresh],
                    "done": finished,
                })
            if self._bank is not None and fresh.size:
                aname = self._rid_adapter.get(rid, (0, "base"))[1]
                key = f"adapter_tokens_{aname}"
                self.stats[key] = self.stats.get(key, 0) + int(fresh.size)
            self._reported[slot] += int(fresh.size)
            if finished:
                self._slot_rid[slot] = None
                self._rid_slot.pop(rid, None)
                self._release_adapter(rid)
        return events

    def cancel(self, rid: str) -> None:
        """Free a request's lane early (deadline/disconnect).

        The lane is marked done device-side — the scan freezes it like any
        finished row — and freed for re-admission (which resets the lane's
        cache and buffer anyway).
        """
        mode = self._rid_mode.pop(rid, None)
        if mode is not None:
            sub = self._subs.get(mode)
            if sub is not None:
                sub.cancel(rid)
            return
        self._pending = [p for p in self._pending if p[0] != rid]
        self._pending_kv = [p for p in self._pending_kv if p[0] != rid]
        self._release_adapter(rid)
        slot = self._rid_slot.pop(rid, None)
        if slot is None:
            return
        caches, buffer, pos, plen, row_cap, n_gen, done, rng = self._state
        self._state = (
            caches, buffer, pos, plen, row_cap, n_gen,
            done.at[slot].set(True), rng,
        )
        self._slot_rid[slot] = None

    def close(self) -> None:
        """Drop device state so the backend can reclaim the cache lanes."""
        self._state = None
        self._draft_caches = None
        self._pending.clear()
        self._pending_kv.clear()
        self._prefix_tree.clear()
        self._rid_slot.clear()
        self._slot_rid = [None] * self.slots
        self._rid_adapter.clear()
        self._slot_refs = [0] * len(self._slot_refs)
        for sub in self._subs.values():
            sub.close()
        self._rid_mode.clear()

    @property
    def busy(self) -> int:
        return (
            len(self._rid_slot) + len(self._pending)
            + len(self._pending_kv)
            + sum(sub.busy for sub in self._subs.values())
        )

    @property
    def spec_active(self) -> bool:
        """True when any lane group is verifying draft proposals — the
        harness keys its ``spec_verify`` waterfall attribution on this."""
        return self._spec_run is not None or any(
            sub._spec_run is not None for sub in self._subs.values()
        )

    @property
    def decode_modes(self) -> tuple[str, ...]:
        """The built lane groups, fp first (refused modes absent)."""
        return (self._mode,) + tuple(self._subs)

    # -- internals ---------------------------------------------------------

    def _lookup_prefix(
        self, tokens: np.ndarray, aslot: int = 0
    ) -> tuple[int, Any, str]:
        """``(m, lane, entry_digest)`` of the deepest cached prefix
        usable for ``tokens`` — ``(0, None, "")`` when none qualifies.

        An entry is usable at depth ``m`` when its first ``m`` tokens
        equal the prompt's (m capped at ``len(prompt) - 1``: the suffix
        pass needs at least one position to read first-token logits
        from) and ``m >= prefix_min_tokens``.  A partial match rewinds
        the entry's lane cursor to ``m`` — positions past the rewound
        cursor hold stale K/V that stays dead until the suffix pass
        overwrites it, the same exactness argument the pad positions
        ride.  Touches the winning entry's LRU slot; counts nothing
        (callers own the hit/miss stats) EXCEPT the adapter fence:
        entries are scoped to the bank slot whose weights computed them,
        so a cross-adapter prompt match never reuses another adapter's
        K/V — the admission degrades to a full prefill (byte-equal, just
        slower) and ``stats["adapter_prefix_blocked"]`` counts the
        would-have-hit.
        """
        best_m, best_digest, best_entry = 0, "", None
        blocked = False
        limit_all = int(tokens.size) - 1
        for digest, entry in self._prefix_tree.items():
            limit = min(int(entry.tokens.size), limit_all)
            if limit < self._prefix_min:
                continue
            if entry.aslot != aslot:
                eq = entry.tokens[:limit] == tokens[:limit]
                m = limit if bool(eq.all()) else int(np.argmin(eq))
                if m >= self._prefix_min:
                    blocked = True
                continue
            if limit <= best_m:
                continue
            eq = entry.tokens[:limit] == tokens[:limit]
            m = limit if bool(eq.all()) else int(np.argmin(eq))
            if m >= self._prefix_min and m > best_m:
                best_m, best_digest, best_entry = m, digest, entry
        if best_entry is None:
            if blocked:
                self.stats["adapter_prefix_blocked"] += 1
            return 0, None, ""
        self._prefix_tree.move_to_end(best_digest)
        lane = best_entry.lane
        if best_m != int(best_entry.tokens.size):
            lane = _set_cursor(lane, best_m)
        return best_m, lane, best_digest

    def _insert_prefix(
        self, tokens: np.ndarray, lane_fn: Callable[[], Any],
        pinned: bool = False, aslot: int = 0,
    ) -> None:
        """Cache one prefilled lane under its token digest (LRU-bounded).

        ``lane_fn`` defers the (device-gather) lane materialization until
        the entry is known to be fresh and cacheable; pinned entries
        (the constructor's ``shared_prefix``) never count against the
        bound and never evict.  The key is scoped by the adapter bank
        slot (``aslot``), so the same prompt under two adapters is two
        entries — cross-adapter reuse is structurally impossible.
        """
        if not pinned and (
            self._prefix_cache_size <= 0
            or int(tokens.size) < self._prefix_min + 1
        ):
            return
        digest = f"{int(aslot)}:{_tokens_digest(tokens)}"
        if digest in self._prefix_tree:
            self._prefix_tree.move_to_end(digest)
            return
        self._prefix_tree[digest] = _PrefixEntry(
            np.array(tokens, np.int32, copy=True), lane_fn(), pinned,
            int(aslot),
        )
        unpinned = [
            d for d, e in self._prefix_tree.items() if not e.pinned
        ]
        while len(unpinned) > self._prefix_cache_size:
            del self._prefix_tree[unpinned.pop(0)]
            self.stats["prefix_evictions"] += 1

    def _flush_admissions(self) -> None:
        """Admit pending requests in fused bucketed waves (one compiled
        call per bucket per path), mirroring ``continuous_generate``'s
        ``admit_group`` — including the per-admission key chain, which is
        split in admission order BEFORE the prefix partition so sampled
        streams draw identically whichever prefill road they take.

        A prompt with a usable prefix-tree lane prefills only its suffix
        on top of it (``_make_prefix_admit``, grouped by entry + depth +
        bucket); everything else takes the full-prompt wave; KV-bundle
        admissions skip prefill entirely (``_make_kv_admit``).  After
        the waves run, each freshly prefilled lane is inserted back into
        the prefix tree, so repeated prompts and shared prefixes across
        later requests hit warm KV.
        """
        if not (self._pending or self._pending_kv):
            return
        free = [s for s in range(self.slots) if self._slot_rid[s] is None]
        picked: list[tuple[int, np.ndarray, int, Any, int, int]] = []
        #: (entry digest, m, bucket) ->
        #:   (lane, [(slot, tokens, cap, key, aslot)]) — entry digests
        #: are adapter-scoped, so a group is adapter-homogeneous and the
        #: reused lane already carries the right bank slot.
        picked_prefix: dict[tuple[str, int, int], tuple[Any, list]] = {}
        picked_kv: list[tuple[int, np.ndarray, int, int, Any, int]] = []
        while self._pending and free:
            rid, tokens, cap, aslot = self._pending.pop(0)
            slot = free.pop(0)
            self._slot_rid[slot] = rid
            self._rid_slot[rid] = slot
            self._reported[slot] = 0
            self._adm_key, key = jax.random.split(self._adm_key)
            m, lane_m, entry_digest = self._lookup_prefix(tokens, aslot)
            if m:
                # Pad K/V land at cache slots >= m + suffix length, so
                # the bucket is capped to what fits BEYOND the reused
                # prefix (admit() already bounded prompt + budget).
                bucket = min(
                    1 << (int(tokens.size) - m - 1).bit_length(),
                    self._config.max_seq - m,
                )
                self.stats["prefix_hits"] += 1
                self.stats["prefill_positions"] += bucket
                lane_g, group = picked_prefix.setdefault(
                    (entry_digest, m, bucket), (lane_m, [])
                )
                group.append((slot, tokens, cap, key, aslot))
            else:
                bucket = min(
                    1 << (int(tokens.size) - 1).bit_length(),
                    self._config.max_seq,
                )
                if self._prefix_tree:
                    self.stats["prefix_misses"] += 1
                self.stats["prefill_positions"] += bucket
                picked.append((slot, tokens, cap, key, bucket, aslot))
        while self._pending_kv and free:
            rid, tokens, cap, first, lane, aslot = self._pending_kv.pop(0)
            slot = free.pop(0)
            self._slot_rid[slot] = rid
            self._rid_slot[rid] = slot
            self._reported[slot] = 0
            picked_kv.append((slot, tokens, cap, first, lane, aslot))
        for bucket in sorted({p[4] for p in picked}):
            group = [p for p in picked if p[4] == bucket]
            g = 1 << (len(group) - 1).bit_length()
            rows = np.full((g, self._length), self._pad, np.int32)
            padded = np.full((g, bucket), self._pad, np.int32)
            plens = np.ones(g, np.int32)
            slots = np.full(g, self.slots, np.int32)  # OOB rows dropped
            caps_in = np.ones(g, np.int32)
            aidxs = np.zeros(g, np.int32)
            keys = [jax.random.PRNGKey(0)] * g
            for r, (slot, tokens, cap, key, _, aslot) in enumerate(group):
                rows[r, : tokens.size] = tokens
                padded[r, : tokens.size] = tokens
                plens[r] = tokens.size
                slots[r] = slot
                caps_in[r] = cap
                aidxs[r] = aslot
                keys[r] = key
            wave = _make_admit(
                self._decoder, self._temperature, self._top_k, self._eos,
                int(self.slots), int(bucket), int(g),
                adapters=self._bank is not None,
            )
            args = [
                self._params, self._state, jnp.asarray(rows),
                jnp.asarray(padded), jnp.asarray(plens),
                jnp.asarray(slots), jnp.asarray(caps_in), jnp.stack(keys),
            ]
            if self._bank is not None:
                args.append(jnp.asarray(aidxs))
            self._state = wave(*args)
        for (_entry, m, bucket), (lane_m, group) in picked_prefix.items():
            g = 1 << (len(group) - 1).bit_length()
            rows = np.full((g, self._length), self._pad, np.int32)
            padded = np.full((g, bucket), self._pad, np.int32)
            slens = np.ones(g, np.int32)
            slots = np.full(g, self.slots, np.int32)  # OOB rows dropped
            caps_in = np.ones(g, np.int32)
            keys = [jax.random.PRNGKey(0)] * g
            for r, (slot, tokens, cap, key, _aslot) in enumerate(group):
                suffix = tokens[m:]
                rows[r, : tokens.size] = tokens
                padded[r, : suffix.size] = suffix
                slens[r] = suffix.size
                slots[r] = slot
                caps_in[r] = cap
                keys[r] = key
            wave = _make_prefix_admit(
                self._decoder, self._temperature, self._top_k, self._eos,
                int(self.slots), int(bucket), int(g), int(m),
            )
            self._state = wave(
                self._params, self._state, lane_m,
                jnp.asarray(rows), jnp.asarray(padded),
                jnp.asarray(slens), jnp.asarray(slots),
                jnp.asarray(caps_in), jnp.stack(keys),
            )
        if picked_kv:
            g = 1 << (len(picked_kv) - 1).bit_length()
            rows = np.full((g, self._length), self._pad, np.int32)
            plens = np.ones(g, np.int32)
            firsts = np.zeros(g, np.int32)
            slots = np.full(g, self.slots, np.int32)  # OOB rows dropped
            caps_in = np.ones(g, np.int32)
            lanes = [p[4] for p in picked_kv]
            lanes += [lanes[0]] * (g - len(lanes))  # padded rows drop
            for r, (slot, tokens, cap, first, _lane, _aslot) in enumerate(
                picked_kv
            ):
                rows[r, : tokens.size] = tokens
                rows[r, tokens.size] = first
                plens[r] = tokens.size
                firsts[r] = first
                slots[r] = slot
                caps_in[r] = cap
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *lanes
            )
            wave = _make_kv_admit(self._eos, int(self.slots), int(g))
            self._state = wave(
                self._state, stacked, jnp.asarray(rows),
                jnp.asarray(plens), jnp.asarray(firsts),
                jnp.asarray(slots), jnp.asarray(caps_in),
            )
        # Draft lanes: every admission (full, prefix-hit, or KV-import)
        # also full-prompt-prefills the DRAFT model's lane for its slot,
        # in fused bucketed waves like the target's — the spec rounds'
        # repair slab picks up from the parked cursor.  KV bundles ship
        # only target K/V, so an imported admission pays this small pass
        # too; the draft is the cheap model by construction.
        if self._draft is not None:
            admitted = (
                [(slot, tokens) for slot, tokens, *_ in picked]
                + [
                    (slot, tokens)
                    for _key, (_lane, group) in picked_prefix.items()
                    for slot, tokens, *_rest in group
                ]
                + [(slot, tokens) for slot, tokens, *_ in picked_kv]
            )
            by_bucket: dict[int, list] = {}
            for slot, tokens in admitted:
                bucket = min(
                    1 << (int(tokens.size) - 1).bit_length(),
                    self._draft.config.max_seq,
                )
                by_bucket.setdefault(bucket, []).append((slot, tokens))
            for bucket in sorted(by_bucket):
                group = by_bucket[bucket]
                g = 1 << (len(group) - 1).bit_length()
                padded = np.full((g, bucket), self._pad, np.int32)
                plens = np.ones(g, np.int32)
                slots = np.full(g, self.slots, np.int32)  # OOB drop
                for r, (slot, tokens) in enumerate(group):
                    padded[r, : tokens.size] = tokens
                    plens[r] = tokens.size
                    slots[r] = slot
                wave = _make_draft_admit(
                    self._draft, int(self.slots), int(bucket), int(g)
                )
                self._draft_caches = wave(
                    self._draft_params, self._draft_caches,
                    jnp.asarray(padded), jnp.asarray(plens),
                    jnp.asarray(slots),
                )
        # Feed the tree: every admission's post-wave lane (cursor already
        # parked at the prompt length by its wave — or carried by the
        # imported bundle) becomes a reusable prefix for later prompts.
        if self._prefix_cache_size > 0:
            state = self._state
            candidates = [
                (p[0], p[1], p[5]) for p in picked
            ] + [
                (slot, tokens, aslot)
                for _, (_lane, group) in picked_prefix.items()
                for slot, tokens, _cap, _key, aslot in group
            ] + [
                (p[0], p[1], p[5]) for p in picked_kv
            ]
            with jax.profiler.TraceAnnotation("serve.engine.insert_prefix"):
                for slot, tokens, aslot in candidates:
                    self._insert_prefix(
                        tokens,
                        lambda slot=slot: jax.tree_util.tree_map(
                            lambda c: c[slot], state[0]
                        ),
                        aslot=aslot,
                    )


def lm_engine_factory(model: TransformerLM, params: Any, **engine_kwargs):
    """A zero-arg serving-session factory for an LM.

    The returned closure is what ``serving.open_session`` cloudpickles
    into the CAS; called inside the resident worker it builds the
    :class:`ContinuousEngine` (loading params and compiling the decode/
    prefill programs ONCE for the session's lifetime).  Note cloudpickle
    serializes this module by *reference* — workers must be able to
    import the package (or the caller registers it by value via
    ``cloudpickle.register_pickle_by_value``).

    ``params`` travel inside the pickle, so hand this host (numpy) arrays.
    A dispatcher that materialised them on an accelerator holds that
    accelerator, and the worker on the same host can then never have it:
    there, write a factory that builds or loads the params in the worker
    (``examples/serve_lattice.py``).
    """
    def factory() -> ContinuousEngine:
        return ContinuousEngine(model, params, **engine_kwargs)

    return factory
