"""What every block form shares: the norm, the rotary embedding and the MLP."""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .quant import dense_general


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN (arXiv:2309.00071) over the rotary dims: wavelengths longer
    than ``original_max`` positions stretched by ``factor``, those that
    turn more than ``beta_fast`` times in it kept, a ramp between; cos and
    sin multiplied by ``attention_factor`` where a model gives one."""

    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def yarn_inv_freq(dim: int, base: float, yarn: YarnConfig | None):
    """The ``dim // 2`` inverse frequencies: ``base``'s geometric run, each
    divided by ``factor`` where its wavelength exceeds the original context
    (fewer than ``beta_slow`` turns in it), kept where it makes more than
    ``beta_fast`` turns, a linear ramp between.  No ``yarn``, or a factor
    of 1: the plain run."""
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None or yarn.factor <= 1:
        return plain.astype(np.float32)

    def turns_at(turns):
        return dim * math.log(yarn.original_max / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(turns_at(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_at(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / yarn.factor * ramp + plain * (1 - ramp)).astype(
        np.float32)


def rotary(x: jax.Array, base: float = 10000.0, offset=0,
           freqs=None, rotary_dim: int | None = None,
           amplitude: float = 1.0) -> jax.Array:
    """Rotary position embedding over (B, S, H, D) with D even.

    ``offset`` shifts the position index — incremental decoding applies the
    embedding for absolute position ``offset + t`` to a length-1 slice.
    ``freqs`` ((D/2,) inverse frequencies) replaces ``base``'s geometric
    run, for a model that scales them (YaRN); ``amplitude`` multiplies cos
    and sin (YaRN's attention factor).  ``rotary_dim`` under D turns the
    first that many dims and passes the rest (a partial rotary factor).
    """
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = rotary(x[..., :rotary_dim], base, offset, freqs,
                        amplitude=amplitude)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    _, seq_len, _, head_dim = x.shape
    half = head_dim // 2
    if freqs is None:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    freqs = jnp.asarray(freqs, jnp.float32)
    positions = offset + jnp.arange(seq_len, dtype=jnp.float32)
    angles = positions[:, None] * freqs[None, :]

    def table(fn):
        values = fn(angles) if amplitude == 1.0 else fn(angles) * amplitude
        return values[None, :, None, :].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_partitioning(nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
        return (norm * scale).astype(self.dtype)


class MlpBlock(nn.Module):
    """``wo(act(wi x))``, or gated (``config.mlp_gated``): ``wo(act(wg x) *
    (wi x))``.  ``d_ff`` overrides the configuration's width (a shared
    expert beside routed ones has its own)."""

    config: Any  # TransformerConfig
    d_ff: int | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        d_ff = self.d_ff or cfg.d_ff
        act = {"gelu": nn.gelu, "silu": nn.silu}[cfg.mlp_activation]

        def dense(name, features, axes, init):
            return dense_general(
                cfg.quantized,
                features=features,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(init),
                kernel_axes=axes,
                name=name,
                lora_rank=cfg.lora_rank if name in cfg.lora_targets else 0,
                lora_alpha=cfg.lora_alpha,
            )

        h = dense("wi", d_ff, ("embed", "mlp"), 0.02)(x)
        h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
        if cfg.mlp_gated:
            gate = dense("wg", d_ff, ("embed", "mlp"), 0.02)(x)
            h = act(nn.with_logical_constraint(
                gate, ("batch", "seq", "mlp"))) * h
        else:
            h = act(h)
        h = dense("wo", cfg.d_model, ("mlp", "embed"),
                  0.02 / (2 * cfg.n_layers) ** 0.5)(h)
        return nn.with_logical_constraint(h, ("batch", "seq", "embed"))
