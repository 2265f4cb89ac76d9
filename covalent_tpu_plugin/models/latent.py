"""Latent attention (DeepSeek-V2's MLA, arXiv:2405.04434): queries and
keys/values are projected down to a small latent, normalised, and projected
up again a head at a time; a head scores with ``qk_nope_head_dim`` dims that
carry no position and ``qk_rope_head_dim`` rotary dims whose key part all
heads share, and mixes values of ``v_head_dim``.

Per token ``h``::

    c_q  = RMSNorm(h W_qa)                          (q_lora_rank)
    [q_nope, q_rope] a head = c_q W_qb
    [c_kv, k_rope] = h W_kva;  c_kv <- RMSNorm(c_kv)  (kv_lora_rank, rope)
    [k_nope, v] a head = c_kv W_kvb
    scores = (q_nope . k_nope + rope(q_rope) . rope(k_rope)) * scale

The train path materialises K and V a head (the latent is what a serving
cache would hold; no engine here takes this block yet).  The scores' width
(nope + rope) and the values' differ, which the flash kernels take as they
come (``ops/attention.py``).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import (
    flash_attention,
    flash_attention_sharded,
    mha_reference,
    on_tpu,
)
from .layers import RMSNorm, YarnConfig, rotary, yarn_inv_freq


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    #: YaRN (arXiv:2309.00071) over the rotary dims; factor 1 = plain rotary.
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5``, times the square of YaRN's attention
        factor ``0.1 * mscale_all_dim * ln(factor) + 1`` where the
        frequencies are scaled."""
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def rope_amplitude(self) -> float:
        """What cos and sin are multiplied by: the ratio of the two mscales
        (1 where they are equal)."""
        return _yarn_mscale(self.rope_factor, self.rope_mscale) / _yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim)

    @property
    def yarn(self) -> YarnConfig:
        return YarnConfig(
            factor=self.rope_factor, original_max=self.rope_original_max,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
            attention_factor=self.rope_amplitude)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


class LatentProjections(nn.Module):
    """x -> (q, k, v) a head: (B, S, H, nope + rope) twice, (B, S, H, v)."""

    config: object  # TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, lat = self.config, self.config.latent
        heads = cfg.n_heads

        def dense(name, features, axes):
            return nn.DenseGeneral(
                features=features, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name,
                kernel_init=nn.with_partitioning(
                    nn.initializers.normal(0.02), axes))

        c_q = RMSNorm(cfg.dtype, name="q_a_norm")(
            dense("q_a", lat.q_lora_rank, ("embed", None))(x))
        q = dense("q_b", (heads, lat.qk_head_dim), (None, "heads", "kv"))(c_q)
        kv = dense("kv_a", lat.kv_lora_rank + lat.qk_rope_head_dim,
                   ("embed", None))(x)
        c_kv = RMSNorm(cfg.dtype, name="kv_a_norm")(
            kv[..., : lat.kv_lora_rank])
        k_rope = kv[..., None, lat.kv_lora_rank:]  # one head, shared by all
        kv = dense("kv_b", (heads, lat.qk_nope_head_dim + lat.v_head_dim),
                   (None, "heads", "kv"))(c_kv)
        k_nope, v = (kv[..., : lat.qk_nope_head_dim],
                     kv[..., lat.qk_nope_head_dim:])

        yarn = lat.yarn
        freqs = yarn_inv_freq(lat.qk_rope_head_dim, cfg.rope_base, yarn)
        q_rope = rotary(q[..., lat.qk_nope_head_dim:], freqs=freqs,
                        amplitude=yarn.attention_factor)
        k_rope = rotary(k_rope, freqs=freqs, amplitude=yarn.attention_factor)
        q = jnp.concatenate([q[..., : lat.qk_nope_head_dim], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1]
                                      + (lat.qk_rope_head_dim,))], axis=-1)
        return q, k, v


class LatentAttention(nn.Module):
    config: object  # TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, lat = self.config, self.config.latent
        if cfg.decode:
            raise NotImplementedError(
                "latent attention has no decode cache yet (train path only)")
        if cfg.sliding_window is not None:
            raise ValueError("latent attention takes no sliding window")
        q, k, v = LatentProjections(cfg, name="latent_proj")(x)
        q, k, v = (
            nn.with_logical_constraint(t, ("batch", "seq", "heads", "kv"))
            for t in (q, k, v))
        qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        impl = cfg.attention
        if impl == "auto":
            impl = "flash" if on_tpu() else "reference"
        if impl == "flash":
            if cfg.mesh is not None:
                # As in ``Attention``: the shard_map keeps each (batch,
                # head) block local under a sharded jit.
                out = flash_attention_sharded(
                    qh, kh, vh, cfg.mesh, causal=True, scale=lat.softmax_scale)
            else:
                out = flash_attention(
                    qh, kh, vh, causal=True, scale=lat.softmax_scale)
        elif impl == "reference":
            out = mha_reference(
                qh, kh, vh, causal=True, scale=lat.softmax_scale)
        else:
            raise ValueError(
                f"latent attention runs attention='flash' or 'reference', "
                f"got {impl!r}")
        out = out.transpose(0, 2, 1, 3)
        out = nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="out_proj",
            kernel_init=nn.with_partitioning(
                nn.initializers.normal(0.02 / (2 * cfg.n_layers) ** 0.5),
                ("heads", "kv", "embed")),
        )(out)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))
