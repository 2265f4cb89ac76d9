"""Decoder-only transformer LM, mesh-first (BASELINE config 5: 125M pretrain).

Every parameter is annotated with *logical* axis names via
``nn.with_partitioning``; the rules in
:mod:`covalent_tpu_plugin.parallel.sharding` map them onto the physical
mesh (heads/mlp/vocab -> ``tensor``, embed -> ``fsdp``, activations ->
``batch``/``seq``), so the one module definition runs data-parallel on a
single host or tensor+sequence-parallel across a pod with no code changes —
XLA inserts the collectives.

TPU-minded choices: bfloat16 activations (MXU-native), dimensions multiples
of 128 (MXU tiling), RMSNorm + rotary embeddings (no learned position
table), layers rolled up with ``nn.scan`` (one compiled block, weights
stacked on a ``layers`` axis) and optionally rematerialised
(``jax.checkpoint``) to trade FLOPs for HBM.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import (
    NEG_INF,
    flash_attention,
    flash_attention_sharded,
    mha_reference,
    on_tpu,
)
from ..ops.ring_attention import sequence_parallel_attention
from .latent import LatentAttention, LatentAttentionConfig
from .layers import (
    MlpBlock,
    RMSNorm,
    YarnConfig,
    rotary as _rotary,
    yarn_inv_freq,
)
from .moe import MoEMlp, RoutedExperts, RoutedExpertsConfig
from .quant import dense_general
from .streams import ResidualStreamsConfig, StreamMix


@dataclasses.dataclass(frozen=True)
class AttentionType:
    """What one type of layer's attention may set for itself, for a model
    that mixes window and full layers.  ``name`` is the ``jax.named_scope``
    the type's kernel call stands under, forward and backward."""

    name: str
    n_heads: int
    sliding_window: int | None = None
    rope_base: float = 10000.0
    #: the leading share of a head's dims that turn; the rest pass.
    rope_share: float = 1.0
    yarn: YarnConfig | None = None
    #: a gate a head on the kernel's output, ahead of the out projection:
    #: ``sigmoid(x W_g)`` of the sublayer's input (arXiv:2505.06708).
    gate: bool = False


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    #: kv heads for grouped-query attention; None = n_heads (plain MHA).
    n_kv_heads: int | None = None
    d_ff: int = 3072
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16        # activations
    param_dtype: Any = jnp.float32   # master weights
    #: lm_head matmul dtype.  f32 is the conservative default; bf16 runs the
    #: head on the MXU's fast path (the loss re-casts to f32 for softmax).
    logits_dtype: Any = jnp.float32
    attention: str = "auto"      # auto | flash | reference | ring | ulysses
    #: incremental decoding: layers keep a (max_seq) K/V cache in the flax
    #: "cache" collection and consume one token slice per apply.
    decode: bool = False
    #: mixture-of-experts: > 0 replaces every block's MLP with a Switch-
    #: style top-1 MoE of that many experts (models/moe.py); the "expert"
    #: logical axis shards them over the tensor mesh axis.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    remat: bool = False
    #: "full" recomputes everything in backward; "dots" saves matmul outputs
    #: (jax dots_with_no_batch_dims_saveable) — ~half the recompute FLOPs for
    #: a modest activation-memory increase.
    remat_policy: str = "full"
    #: False lets XLA merge remat's second forward with the first wherever
    #: it judges that free, and keep what the first made (unrolled layers:
    #: it merges every one of a plain block's, so remat then buys no
    #: memory); True fences each layer's recompute off, which is what
    #: saves the memory, at the price of running it, and under "full"
    #: keeps the products ``FENCE_KEEPS`` names, which it then skips.
    remat_prevent_cse: bool = False
    #: lax.scan over the block stack keeps compile time O(1) in depth, but
    #: blocks XLA from fusing/scheduling across block boundaries — unrolled
    #: (False) was ~33% faster on the train step at 12 layers in an earlier
    #: v5e sweep (not re-measured on this installation).  Scan stays the
    #: default for compile-latency-sensitive paths; flip it off for long
    #: runs.
    scan_layers: bool = True
    #: device mesh: required for attention="ring"; with attention="flash"
    #: it switches the kernel to the shard_map (collective-free) path.
    mesh: Any = None
    #: weight-only int8 serving: every dense layer stores an int8 kernel +
    #: per-channel scale (models/quant.py).  Build via quantize_lm(), not
    #: by hand — the param tree shape changes.
    quantized: bool = False
    #: sliding-window (Mistral-style local) attention: each query sees
    #: only the `sliding_window` most recent positions.  Flash grids visit
    #: only the band's tiles (compute AND DMA O(S·w)); with
    #: attention="ring" the banded ring truncates to the hops the band
    #: reaches (ops/ring_attention.py).
    sliding_window: int | None = None
    #: StreamingLLM-style circular KV cache for decode: cache length is
    #: `sliding_window + attention_sinks` instead of `max_seq` and
    #: generation can run past max_seq at O(window) memory.  Requires
    #: sliding_window; exact for the generate() flow at ANY chunking —
    #: multi-token slabs attend the pre-write ring snapshot plus the slab
    #: itself, so a wrapping write cannot erase entries earlier slab rows
    #: still need (slabs stay <= sliding_window so the scatter never
    #: lands two slab tokens in one slot).
    rolling_cache: bool = False
    #: attention sinks (StreamingLLM): the first `attention_sinks`
    #: positions stay visible to every query alongside the sliding band,
    #: and the rolling cache pins their slots (never overwritten).  Known
    #: to stabilise long windowed decode where window-only attention
    #: drifts once position 0 rolls out of the band.  Requires
    #: sliding_window; for sequence parallelism use attention="ulysses"
    #: (the rotating ring cannot keep shard 0's sinks resident).
    attention_sinks: int = 0
    #: rotary embedding wavelength base (theta).  10k is the GPT-NeoX/
    #: llama default; raising it (e.g. 500k, llama-3 style) stretches the
    #: position resolution for long-context training — the standard knob
    #: behind context extension.
    rope_base: float = 10000.0
    #: int8 KV cache for decode: cached K/V store as int8 with one f32
    #: scale per (batch, position, kv head), halving the per-step cache
    #: reads and the cache's HBM footprint vs bf16 (4x vs f32).  Decode
    #: is cache-bandwidth-bound at long contexts, so this is the standard
    #: serving lever; quantization error is ~1e-2 relative (not exact —
    #: tests pin logit cosine > 0.999).  Orthogonal to `quantized`
    #: (weight int8): compose both for fully-int8 serving reads.
    quantized_kv_cache: bool = False
    #: LoRA fine-tuning (models/lora.py): > 0 attaches rank-r adapters to
    #: the targeted denses.  Build via add_lora()/quantize_then_lora().
    lora_rank: int = 0
    lora_alpha: float = 16.0
    #: which dense layers get adapters (attention + MLP, not the lm_head).
    lora_targets: tuple = (
        "q_proj", "k_proj", "v_proj", "out_proj", "wi", "wo",
    )
    #: gated MLP: ``wo(act(wg x) * (wi x))`` (SwiGLU with "silu") in place
    #: of ``wo(act(wi x))``.
    mlp_gated: bool = False
    mlp_activation: str = "gelu"
    #: one kind a layer, "dense" (``MlpBlock``) or "moe" (routed experts),
    #: for a model whose leading layers are dense and the rest expert
    #: layers; None = every layer alike ("moe" where ``moe_experts`` or
    #: ``routed`` is set).  Mixed kinds need ``scan_layers=False``.
    layer_kinds: tuple | None = None
    #: latent attention (models/latent.py) in place of ``Attention``.
    latent: LatentAttentionConfig | None = None
    #: top-k routed gated experts (sigmoid or softmax scores) with shared
    #: ones and a held share (models/moe.py ``RoutedExperts``) in the "moe"
    #: layers.
    routed: RoutedExpertsConfig | None = None
    #: n residual streams mixed around every sublayer (models/streams.py).
    streams: ResidualStreamsConfig | None = None
    #: a head's width; None = ``d_model // n_heads``.  A width of its own
    #: where heads x width is not the model's (48 x 128 over 3072).
    head_dim: int | None = None
    #: attention that differs by layer: the types, and each layer's by
    #: name.  They replace ``n_heads``, ``sliding_window`` and ``rope_base``
    #: in ``Attention`` (train path; mixed types need ``scan_layers=False``).
    #: None = every layer alike, by the fields above.
    attention_types: tuple | None = None
    attention_kinds: tuple | None = None
    #: an RMSNorm over each query and key head's width, ahead of rotary.
    qk_norm: bool = False
    #: block diffusion (``models/diffusion.py``): the model takes a noisy
    #: and a clean copy of each row, ``[noisy ; clean]``, both turned from
    #: position 0, under the block-diffusion mask over blocks of this many
    #: tokens (``ops/attention.py`` ``diffusion_visible``); ``max_seq``
    #: bounds the two copies together.  None = causal.
    diffusion_block: int | None = None

    def __post_init__(self):
        if self.diffusion_block is not None and (
                self.diffusion_block < 1 or self.decode
                or self.sliding_window is not None or self.latent is not None
                or self.attention in ("ring", "ulysses")):
            raise ValueError(
                "diffusion_block is a block length >= 1 on the train path: "
                "no decode, window, latent attention or ring/ulysses")
        if (self.attention_types is None) != (self.attention_kinds is None):
            raise ValueError(
                "attention_types and attention_kinds are given together")
        if self.attention_types is not None:
            names = [t.name for t in self.attention_types]
            if len(self.attention_kinds) != self.n_layers or set(
                    self.attention_kinds) - set(names):
                raise ValueError(
                    f"attention_kinds must name one of {names} for each of "
                    f"{self.n_layers} layers, got {self.attention_kinds!r}")
            if self.latent is not None or self.decode:
                raise ValueError(
                    "attention by layer runs Attention on the train path")
            if self.scan_layers and len(set(self.attention_kinds)) > 1:
                raise ValueError(
                    "layers of two attention types cannot be scanned: "
                    "scan_layers=False")
        kinds = self.layer_kinds
        if kinds is not None:
            if len(kinds) != self.n_layers or set(kinds) - {"dense", "moe"}:
                raise ValueError(
                    f"layer_kinds must give 'dense' or 'moe' for each of "
                    f"{self.n_layers} layers, got {kinds!r}"
                )
            if "moe" in kinds and not (self.routed or self.moe_experts > 0):
                raise ValueError("a 'moe' layer needs routed or moe_experts")
            if self.scan_layers and len(set(kinds)) > 1:
                raise ValueError(
                    "layers of two kinds cannot be scanned: scan_layers=False"
                )
        if self.sliding_window is not None and self.sliding_window < 1:
            # Validated here (not only in the kernels) because the cached
            # decode path masks the band itself — a 0/negative window there
            # would silently attend nothing and softmax over garbage.
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}"
            )
        if self.rolling_cache and self.sliding_window is None:
            raise ValueError("rolling_cache requires sliding_window")
        if self.attention_sinks:
            if self.attention_sinks < 0:
                raise ValueError(
                    f"attention_sinks must be >= 0, got {self.attention_sinks}"
                )
            if self.sliding_window is None:
                raise ValueError("attention_sinks require sliding_window")

    @property
    def head_width(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def attention_of(self, layer: int) -> AttentionType | None:
        if self.attention_types is None:
            return None
        name = self.attention_kinds[layer]
        return next(t for t in self.attention_types if t.name == name)

    def kind_of(self, layer: int) -> str:
        if self.layer_kinds is not None:
            return self.layer_kinds[layer]
        return "moe" if self.routed or self.moe_experts > 0 else "dense"


#: What a fenced checkpoint keeps of a layer's forward, by the names the
#: sites give (``jax.checkpoint_name``); the rest it recomputes.  Each is
#: a product the recompute would otherwise make again: a gated MLP's two
#: products (``MlpBlock``: a dense layer's, a shared expert's), the
#: router's choice and weights and the held experts' grouped order
#: (``models/moe.py``, ahead of the conditional: nothing is kept from
#: inside it).  The router's scores are recomputed: kept beside the MLPs'
#: products, they move the held experts' token rows out of VMEM in
#: ``xing4-29b.train-8k``, and the rows' gathers there run three times as
#: long.  Attention's flash forward still runs again in the recompute.  An
#: unfenced checkpoint keeps nothing, since the compiler merges its
#: recompute with the forward anyway.
FENCE_KEEPS = (
    "mlp_gate", "mlp_up", "router_chosen", "router_weights", "moe_order",
    "moe_group_sizes",
)


def lm_125m_config(**overrides) -> TransformerConfig:
    """GPT-2-small-class preset (~125M params with a 32k vocab)."""
    return TransformerConfig(**overrides)


class Attention(nn.Module):
    config: TransformerConfig
    #: this layer's type where attention differs by layer; None = the
    #: configuration's own heads, window and rotary base.
    kind: AttentionType | None = None

    @nn.compact
    def __call__(self, x):
        cfg, kind = self.config, self.kind
        dense = lambda name, features, axes: dense_general(  # noqa: E731
            cfg.quantized,
            features=features,
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
            kernel_axes=axes,
            name=name,
            lora_rank=cfg.lora_rank if name in cfg.lora_targets else 0,
            lora_alpha=cfg.lora_alpha,
        )
        n_heads = cfg.n_heads if kind is None else kind.n_heads
        window = cfg.sliding_window if kind is None else kind.sliding_window
        kv_heads = cfg.n_kv_heads or n_heads
        if n_heads % kv_heads:
            raise ValueError(
                f"n_heads {n_heads} must be divisible by n_kv_heads {kv_heads}"
            )
        # GQA kv projections take the "kv_heads" logical axis (replicated
        # across tensor shards by DEFAULT_RULES) — the small kv head count
        # generally doesn't divide the tensor axis the way "heads" must.
        kv_axis = "heads" if kv_heads == n_heads else "kv_heads"
        q = dense("q_proj", (n_heads, cfg.head_width), ("embed", "heads", "kv"))(x)
        k = dense("k_proj", (kv_heads, cfg.head_width), ("embed", kv_axis, "kv"))(x)
        v = dense("v_proj", (kv_heads, cfg.head_width), ("embed", kv_axis, "kv"))(x)
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
        k = nn.with_logical_constraint(k, ("batch", "seq", kv_axis, "kv"))
        v = nn.with_logical_constraint(v, ("batch", "seq", kv_axis, "kv"))
        if cfg.qk_norm:
            q = RMSNorm(cfg.dtype, axis_name="kv", name="q_norm")(q)
            k = RMSNorm(cfg.dtype, axis_name="kv", name="k_norm")(k)

        if cfg.decode:
            return self._decode_step(q, k, v, kv_heads)

        # Block diffusion: both copies of the row turn from position 0.
        positions = None
        masking = {}
        if cfg.diffusion_block is not None:
            positions = jnp.arange(x.shape[1]) % (x.shape[1] // 2)
            masking = {"diffusion_block": cfg.diffusion_block}
        if kind is None:
            q = _rotary(q, base=cfg.rope_base, positions=positions)
            k = _rotary(k, base=cfg.rope_base, positions=positions)
            scope = contextlib.nullcontext()
        else:
            turned = int(cfg.head_width * kind.rope_share)
            amplitude = 1.0 if kind.yarn is None else kind.yarn.attention_factor
            freqs = yarn_inv_freq(turned, kind.rope_base, kind.yarn)
            q, k = (_rotary(t, freqs=freqs, rotary_dim=turned,
                            amplitude=amplitude, positions=positions)
                    for t in (q, k))
            scope = jax.named_scope(kind.name)

        # (B, S, H, D) -> (B, H, S, D) for the attention kernels
        qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        impl = cfg.attention
        if impl == "auto":
            impl = "flash" if on_tpu() else "reference"
        if impl in ("ring", "ulysses"):
            if cfg.mesh is None:
                raise ValueError(f"attention={impl!r} requires config.mesh")
            if cfg.attention_sinks and impl == "ring":
                # Sink columns live on shard 0 only; every hop would need
                # them resident (a broadcast, not a rotation).  Use
                # attention='ulysses' — its full-sequence local attention
                # composes with sinks unchanged.
                raise ValueError(
                    "attention_sinks are unsupported with attention='ring'"
                    " — use attention='ulysses'"
                )
            if impl == "ring" and kv_heads != n_heads:
                # Ring shards over sequence, not heads: materialising the
                # group repeat is cheap relative to the ring's kv transfers.
                # (Ulysses repeats internally only when needed.)
                group = n_heads // kv_heads
                kh = jnp.repeat(kh, group, axis=1)
                vh = jnp.repeat(vh, group, axis=1)
            # sliding_window composes: the banded ring masks each hop by
            # global positions and (contiguous layout) truncates the ring
            # to the hops intersecting the band; ulysses swaps
            # sequence<->heads and runs the banded full-sequence kernel
            # locally (ops/ring_attention.py).
            with scope:
                out = sequence_parallel_attention(
                    qh, kh, vh, cfg.mesh, causal=True,
                    window=window, sinks=cfg.attention_sinks,
                    impl="ulysses" if impl == "ulysses" else None,
                )
        elif impl == "flash":
            with scope:
                if cfg.mesh is not None:
                    # Bare pallas_call is opaque to sharding propagation —
                    # under a sharded jit it would all-gather Q/K/V to every
                    # device; the shard_map wrapper keeps each (batch, head)
                    # block local.
                    out = flash_attention_sharded(
                        qh, kh, vh, cfg.mesh, causal=True,
                        window=window, sinks=cfg.attention_sinks, **masking,
                    )
                else:
                    out = flash_attention(
                        qh, kh, vh, causal=True, window=window,
                        sinks=cfg.attention_sinks, **masking,
                    )
        else:
            with scope:
                out = mha_reference(
                    qh, kh, vh, causal=True, window=window,
                    sinks=cfg.attention_sinks, **masking,
                )
        out = out.transpose(0, 2, 1, 3)
        if kind is not None and kind.gate:
            gate = dense("gate_proj", n_heads, ("embed", "heads"))(x)
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(gate)[..., None]

        out = self._out_proj(out)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))

    def _out_proj(self, out):
        cfg = self.config
        return dense_general(
            cfg.quantized,
            features=cfg.d_model,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            # residual-output kernel: depth-scaled init (GPT-2 convention,
            # matching MlpBlock's wo) keeps residual-stream variance flat
            kernel_init=nn.initializers.normal(0.02 / (2 * cfg.n_layers) ** 0.5),
            kernel_axes=("heads", "kv", "embed"),
            name="out_proj",
            lora_rank=cfg.lora_rank if "out_proj" in cfg.lora_targets else 0,
            lora_alpha=cfg.lora_alpha,
        )(out)

    def _decode_step(self, q, k, v, kv_heads: int):
        """Incremental attention against the layer's K/V cache.

        A multi-token call is a *prefill*: the whole slab's K/V land in the
        cache at the cursor, then the slab attends the cache with per-row
        causal visibility — correct at cursor 0 (classic prefill) and at a
        non-zero cursor (chunked prefill keeps its cached context).  A
        single-token call is a decode step.  The cache lives in the flax
        "cache" collection (zero-initialised via ``decode=True`` init);
        decode is bandwidth-bound, so the attention is a plain einsum — no
        flash.

        Contract for direct cache users: the cursor plus the slab must not
        exceed ``max_seq`` — the cursor is traced, so an overflow cannot be
        detected here; ``generate()`` enforces it for the packaged path
        (``dynamic_update_slice`` would clamp and silently corrupt slots).
        """
        cfg = self.config
        batch, slab = q.shape[:2]
        rolling = cfg.rolling_cache
        sinks = cfg.attention_sinks
        # Rolling ring = pinned sink slots [0, sinks) + circular band
        # region [sinks, sinks + window).
        cache_len = (
            cfg.sliding_window + sinks if rolling else cfg.max_seq
        )
        if slab > cache_len:
            raise ValueError(
                f"slab of {slab} tokens exceeds the cache length {cache_len}"
            )
        quant_kv = cfg.quantized_kv_cache
        kv_dtype = jnp.int8 if quant_kv else cfg.dtype
        cached_k = self.variable(
            "cache", "cached_k", jnp.zeros,
            (batch, cache_len, kv_heads, cfg.head_width), kv_dtype,
        )
        cached_v = self.variable(
            "cache", "cached_v", jnp.zeros,
            (batch, cache_len, kv_heads, cfg.head_width), kv_dtype,
        )
        if quant_kv:
            # One f32 scale per (batch, slot, kv head): zero-init means
            # never-written slots dequantise to exact zeros, same as the
            # unquantised cache (and they are masked anyway).
            k_scale = self.variable(
                "cache", "k_scale", jnp.zeros,
                (batch, cache_len, kv_heads, 1), jnp.float32,
            )
            v_scale = self.variable(
                "cache", "v_scale", jnp.zeros,
                (batch, cache_len, kv_heads, 1), jnp.float32,
            )
        cursor = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if rolling:
            # Which absolute position each circular slot currently holds;
            # -1 = never written.  Makes the band mask exact across wraps
            # with no modular-arithmetic reconstruction.
            slot_pos = self.variable(
                "cache", "slot_positions",
                lambda: jnp.full((cache_len,), -1, jnp.int32),
            )
        if self.is_initializing():
            # init only materialises the zeroed cache; no attention math.
            return self._out_proj(jnp.zeros_like(q))

        pos = cursor.value
        q = _rotary(q, base=cfg.rope_base, offset=pos)
        k = _rotary(k, base=cfg.rope_base, offset=pos)
        q_positions = pos + jnp.arange(slab)

        def quantize(x):
            """Symmetric per-(b, s, h) int8: scale = amax/127 over D."""
            amax = jnp.max(
                jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True
            )
            scale = jnp.maximum(amax, 1e-8) / 127.0
            qx = jnp.clip(
                jnp.round(x.astype(jnp.float32) / scale), -127, 127
            ).astype(jnp.int8)
            return qx, scale

        if quant_kv:
            k_store, k_s = quantize(k)
            v_store, v_s = quantize(v)
        else:
            k_store, v_store = k.astype(cfg.dtype), v.astype(cfg.dtype)
        # Rolling multi-token slabs attend the PRE-write cache plus the
        # slab itself (concatenated): the scatter below may overwrite ring
        # slots that earlier slab rows still need (slot p+j-W dies when
        # slab token j lands), so post-write attention would silently drop
        # band-edge entries for every row but the last — the r3
        # "documented-lossy" case that forced prefill_chunk=1.  With the
        # pre-write snapshot every chunk <= sliding_window is EXACT: in-
        # slab context comes from the slab branch, pre-slab context from
        # slots the scatter has not yet touched (row i's oldest band need
        # is p+i-W+1 > p-W-1+L-W ... all alive pre-write).
        pre_k, pre_v = cached_k.value, cached_v.value
        if quant_kv:
            pre_ks, pre_vs = k_scale.value, v_scale.value
        if rolling:
            pre_sp = slot_pos.value
        if rolling:
            # Circular write: token at absolute position p lands in slot
            # p (pinned) while p < sinks, else sinks + (p - sinks) % W —
            # sink tokens are never overwritten by the rolling band (a
            # scatter — dynamic_update_slice can't wrap).
            if sinks:
                idx = jnp.where(
                    q_positions < sinks,
                    q_positions,
                    sinks + (q_positions - sinks) % cfg.sliding_window,
                )
            else:
                idx = q_positions % cache_len
            cached_k.value = cached_k.value.at[:, idx].set(k_store)
            cached_v.value = cached_v.value.at[:, idx].set(v_store)
            if quant_kv:
                k_scale.value = k_scale.value.at[:, idx].set(k_s)
                v_scale.value = v_scale.value.at[:, idx].set(v_s)
            slot_pos.value = slot_pos.value.at[idx].set(q_positions)
        else:
            cached_k.value = jax.lax.dynamic_update_slice(
                cached_k.value, k_store, (0, pos, 0, 0)
            )
            cached_v.value = jax.lax.dynamic_update_slice(
                cached_v.value, v_store, (0, pos, 0, 0)
            )
            if quant_kv:
                k_scale.value = jax.lax.dynamic_update_slice(
                    k_scale.value, k_s, (0, pos, 0, 0)
                )
                v_scale.value = jax.lax.dynamic_update_slice(
                    v_scale.value, v_s, (0, pos, 0, 0)
                )
        cursor.value = pos + slab

        # One path for prefill slabs AND single-token steps: the slab's
        # queries attend the attend-set with per-row causal visibility
        # (query at absolute position pos+i sees columns <= pos+i), so
        # chunked prefill at a non-zero cursor keeps its cached context.
        # The attend-set is the post-write cache except for rolling
        # multi-token slabs, which use the pre-write snapshot + the slab
        # itself (the exact-chunked-prefill path; see the snapshot note).
        # Column-position vector: the mask reads each column's recorded
        # absolute position (-1 = never written), which is exact across
        # ring wraps with no modular reconstruction; non-rolling slots ARE
        # their positions.
        if rolling and slab > 1:
            attend_k = jnp.concatenate([pre_k, k_store], axis=1)
            attend_v = jnp.concatenate([pre_v, v_store], axis=1)
            if quant_kv:
                attend_ks = jnp.concatenate([pre_ks, k_s], axis=1)
                attend_vs = jnp.concatenate([pre_vs, v_s], axis=1)
            col_pos = jnp.concatenate([pre_sp, q_positions])
        else:
            attend_k, attend_v = cached_k.value, cached_v.value
            if quant_kv:
                attend_ks, attend_vs = k_scale.value, v_scale.value
            col_pos = (
                slot_pos.value if rolling else jnp.arange(cache_len)
            )
        group = cfg.n_heads // kv_heads
        qg = q.reshape(batch, slab, kv_heads, group, cfg.head_width)
        scores = jnp.einsum(
            "bqhgd,bshd->bhgqs", qg, attend_k.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ) * (cfg.head_width**-0.5)
        if quant_kv:
            # The scale is constant over D, so it factors out of the dot:
            # apply per-(b, s, h) AFTER the matmul — HBM reads stay int8.
            scores = scores * jnp.transpose(
                attend_ks[..., 0], (0, 2, 1)
            )[:, :, None, None, :]
        # Band mask by column position: a query sees a column iff it is
        # written, causal-past, and in the band — sink positions stay
        # visible at any distance (their slots are pinned in the rolling
        # ring, so they are always present to see).
        sp = col_pos[None, :]
        visible = (sp >= 0) & (sp <= q_positions[:, None])
        if cfg.sliding_window is not None:
            in_band = sp > q_positions[:, None] - cfg.sliding_window
            if sinks:
                in_band |= sp < sinks
            visible &= in_band
        scores = jnp.where(visible[None, None, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if quant_kv:
            # Fold the V scale into the probabilities (constant over D).
            probs = probs * jnp.transpose(
                attend_vs[..., 0], (0, 2, 1)
            )[:, :, None, None, :]
        probs = probs.astype(cfg.dtype)
        out = jnp.einsum(
            "bhgqs,bshd->bqhgd", probs, attend_v.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        out = out.reshape(batch, slab, cfg.n_heads, cfg.head_width)
        return self._out_proj(out.astype(cfg.dtype))


class Block(nn.Module):
    """Attention, then an MLP of this layer's ``kind``, each behind its own
    pre-norm: added to the one residual stream, or (``config.streams``)
    mixed into the n streams ``x`` then is, ``(B, n, S, C)``."""

    config: TransformerConfig
    kind: str = "dense"
    attention: AttentionType | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.latent is not None:
            attention = LatentAttention(cfg, name="attention")
        else:
            attention = Attention(cfg, kind=self.attention, name="attention")
        if self.kind != "moe":
            mlp = MlpBlock(cfg, name="mlp")
        elif cfg.routed is not None:
            mlp = RoutedExperts(cfg, name="moe")
        else:
            mlp = MoEMlp(cfg, name="moe")
        for tag, sublayer in (("attn", attention), ("mlp", mlp)):
            norm = RMSNorm(cfg.dtype, name=f"ln_{tag}")
            if cfg.streams is None:
                x = x + sublayer(norm(x))
                continue
            streams = StreamMix(cfg, name=f"hc_{tag}")
            u, x, coefficients = streams.before(x)
            x = streams.mix(x, sublayer(norm(u)), coefficients)
        axes = ("batch", "seq", "embed")
        if cfg.streams is not None:
            axes = ("batch", None, "seq", "embed")
        return nn.with_logical_constraint(x, axes)


class TransformerLM(nn.Module):
    """Causal LM: tokens (B, S) -> logits (B, S, vocab)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_features: bool = False):
        cfg = self.config
        if tokens.shape[-1] > cfg.max_seq:
            raise ValueError(
                f"sequence length {tokens.shape[-1]} exceeds config.max_seq "
                f"{cfg.max_seq}"
            )
        embedding = self.param(
            "embedding",
            nn.with_partitioning(nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        x = jnp.asarray(embedding, cfg.dtype)[tokens]
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        if cfg.streams is not None:
            # Every stream starts as the embedding.
            x = jnp.broadcast_to(
                x[:, None], (x.shape[0], cfg.streams.n) + x.shape[1:])

        block_cls = Block
        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            elif cfg.remat_policy != "full":
                raise ValueError(
                    f"remat_policy must be 'full' or 'dots', got {cfg.remat_policy!r}"
                )
            elif cfg.remat_prevent_cse:
                policy = jax.checkpoint_policies.save_only_these_names(
                    *FENCE_KEEPS)
            block_cls = nn.remat(
                Block, prevent_cse=cfg.remat_prevent_cse, policy=policy)
        if cfg.scan_layers:
            x, _ = nn.scan(
                lambda module, carry, _: (module(carry), None),
                # "intermediates" must be declared or scan silently drops
                # sown values (the MoE load-balance aux loss rides there).
                variable_axes={"params": 0, "cache": 0, "intermediates": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_cls(cfg, kind=cfg.kind_of(0), attention=cfg.attention_of(0),
                        name="layers"), x, None)
        else:
            for i in range(cfg.n_layers):
                x = block_cls(cfg, kind=cfg.kind_of(i),
                              attention=cfg.attention_of(i),
                              name=f"layer_{i}")(x)

        if cfg.streams is not None:
            x = jnp.sum(x.astype(jnp.float32), axis=1).astype(cfg.dtype)
        x = RMSNorm(cfg.dtype, name="ln_final")(x)
        if return_features:
            # The fused-xent training path (ops/xent.py) consumes the
            # final features and the lm_head kernel directly, so the
            # (B, S, vocab) logits tensor is never materialised.  Safe to
            # skip the head here: apply() with unused params is fine, and
            # init() always runs the full path (return_features defaults
            # False) so the lm_head params always exist.
            return x
        logits = dense_general(
            cfg.quantized,
            features=cfg.vocab_size,
            dtype=cfg.logits_dtype,  # f32 default; bf16 for the MXU fast path
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
            kernel_axes=("embed", "vocab"),
            name="lm_head",
            lora_rank=cfg.lora_rank if "lm_head" in cfg.lora_targets else 0,
            lora_alpha=cfg.lora_alpha,
        )(x)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))

    def parameter_count(self, params) -> int:
        return sum(
            leaf.size for leaf in jax.tree_util.tree_leaves(params)
        )
