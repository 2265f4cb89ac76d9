"""Weight-only int8 quantization for serving.

Decode is HBM-bandwidth-bound — every step re-reads the full weight set —
so shrinking the bytes is a direct speedup: bf16 halves them
(:func:`..decode.inference_params`) and int8 halves them again.  The
scheme is the standard TPU-friendly weight-only symmetric quantization:

* each dense kernel is stored as **int8** with a **per-output-channel
  f32 scale** (``scale = amax(|w|, input_axes) / 127``);
* the matmul runs ``x @ kernel.astype(bf16)`` — the int8 tensor is what
  crosses HBM, the cast happens in registers on the way to the MXU —
  then multiplies the per-channel scale into the output;
* activations stay bf16 (no activation quantization, no calibration
  data needed), embeddings/norms are untouched.

Usage::

    qmodel, qparams = quantize_lm(model, params)   # f32/bf16 masters in
    out = generate(qmodel, qparams, prompt, n)     # same API as before

``TransformerConfig.quantized=True`` swaps every dense layer for
:class:`QuantDenseGeneral`; :func:`quantize_lm` builds that config, a
structure template via ``jax.eval_shape`` (no weights materialised), and
converts the trained parameters into it.  Reference has no serving path
at all (SURVEY §5 long-context: ABSENT); this is net-new capability.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


def _as_tuple(value) -> tuple:
    return tuple(value) if isinstance(value, (tuple, list)) else (value,)


class QuantDenseGeneral(nn.Module):
    """``nn.DenseGeneral`` twin consuming int8 kernels + per-channel scales.

    Declares the same module name and a ``kernel`` param of the same shape
    (dtype int8) plus a ``scale`` param shaped like the output features, so
    a quantized checkpoint lines up 1:1 with the dense model's tree.  No
    bias (none of the transformer's denses use one).
    """

    features: Any                 # int or tuple, as nn.DenseGeneral
    kernel_axes: Sequence[str]    # logical partition axes for the kernel
    axis: Any = -1                # contraction axes on the input
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        features = _as_tuple(self.features)
        axis = _as_tuple(self.axis)
        axis = tuple(a % x.ndim for a in axis)
        contract_shape = tuple(x.shape[a] for a in axis)
        kernel_shape = contract_shape + features

        kernel = self.param(
            "kernel",
            nn.with_partitioning(
                nn.initializers.zeros_init(), tuple(self.kernel_axes)
            ),
            kernel_shape,
            jnp.int8,
        )
        scale = self.param(
            "scale",
            nn.with_partitioning(
                nn.initializers.ones_init(),
                tuple(self.kernel_axes)[len(contract_shape):],
            ),
            features,
            self.param_dtype,
        )
        # int8 crosses HBM; the bf16 cast is register-resident on the way
        # to the MXU.  Contraction dims mirror nn.DenseGeneral's.
        y = jax.lax.dot_general(
            x.astype(self.dtype),
            kernel.astype(self.dtype),
            ((axis, tuple(range(len(axis)))), ((), ())),
        )
        return y * scale.astype(self.dtype)


def dense_general(
    quantized: bool,
    *,
    features,
    kernel_axes: Sequence[str],
    kernel_init,
    axis=-1,
    dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
    name: str,
    lora_rank: int = 0,
    lora_alpha: float = 16.0,
):
    """The transformer's one dense-layer factory.

    Float, int8-serving, or either with LoRA adapters on top — all four
    combinations share param names, so checkpoints line up across modes.
    """
    if lora_rank:
        from .lora import LoRADenseGeneral  # deferred: lora imports quant

        return LoRADenseGeneral(
            features=features,
            kernel_axes=tuple(kernel_axes),
            rank=lora_rank,
            alpha=lora_alpha,
            axis=axis,
            dtype=dtype,
            param_dtype=param_dtype,
            quantized=quantized,
            kernel_init=kernel_init,
            name=name,
        )
    if quantized:
        return QuantDenseGeneral(
            features=features,
            kernel_axes=tuple(kernel_axes),
            axis=axis,
            dtype=dtype,
            param_dtype=param_dtype,
            name=name,
        )
    return nn.DenseGeneral(
        features=features,
        axis=axis,
        use_bias=False,
        dtype=dtype,
        param_dtype=param_dtype,
        kernel_init=nn.with_partitioning(kernel_init, tuple(kernel_axes)),
        name=name,
    )


def quantize_array(w: jax.Array, n_feature_dims: int):
    """Symmetric per-output-channel int8: returns (q, scale).

    Input (contraction) axes are the leading ``w.ndim - n_feature_dims``
    dims, matching ``nn.DenseGeneral``'s kernel layout.
    """
    input_axes = tuple(range(w.ndim - n_feature_dims))
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=input_axes)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


#: The serving tier's closed decode-mode set (the per-request ``quality``
#: knob's values).  ``fp`` is the bit-exact reference lane every refusal
#: falls back to; the others trade exactness for HBM bytes.
SERVING_MODES = ("fp", "int8", "kv_quant", "full_quant")


def mode_variant(model, params, mode: str) -> tuple[Any, Any]:
    """``(model, params)`` twin for one serving decode mode.

    * ``fp`` — the inputs, untouched (bit-exact reference lane);
    * ``int8`` — weight-only int8 via :func:`quantize_lm`;
    * ``kv_quant`` — same weights, int8 KV cache
      (``TransformerConfig.quantized_kv_cache``);
    * ``full_quant`` — both.

    Raises :class:`ValueError` on an unknown mode name (a config typo —
    callers should fail loudly) and propagates :func:`quantize_lm`'s
    refusals (MoE / scanned / LoRA models), which the serving engine
    treats as a per-mode refusal with fp fallback rather than an error.
    """
    if mode not in SERVING_MODES:
        raise ValueError(
            f"unknown decode mode {mode!r}; expected one of {SERVING_MODES}"
        )
    if mode == "fp":
        return model, params
    from .transformer import TransformerLM

    if mode == "int8":
        return quantize_lm(model, params)
    if mode == "kv_quant":
        return (
            TransformerLM(
                dataclasses.replace(model.config, quantized_kv_cache=True)
            ),
            params,
        )
    qmodel, qparams = quantize_lm(model, params)
    return (
        TransformerLM(
            dataclasses.replace(qmodel.config, quantized_kv_cache=True)
        ),
        qparams,
    )


def quantize_lm(model, params) -> tuple[Any, Any]:
    """(quantized model, quantized params) from a trained LM.

    Builds the ``quantized=True`` twin config, takes its parameter
    *structure* via ``jax.eval_shape`` (no weights materialised), and fills
    it: int8 ``kernel`` + f32 ``scale`` pairs from the float kernels,
    everything else (embeddings, norms) copied through.  Requires
    ``scan_layers=False`` — a scanned kernel's leading layer axis is
    indistinguishable from a contraction axis in the stacked tree, and
    unrolled was the faster serving mode in an earlier v5e sweep (not
    re-measured on this installation).  Compose with
    :func:`..decode.inference_params` to also cast the float leftovers to
    bf16.
    """
    from .transformer import TransformerLM

    config = model.config
    if config.scan_layers:
        raise ValueError(
            "quantize_lm requires scan_layers=False (serve unrolled)"
        )
    if config.moe_experts:
        raise ValueError("quantize_lm does not support MoE models yet")
    if config.lora_rank:
        raise ValueError(
            "quantize the base first, then attach adapters "
            "(lora.quantize_then_lora)"
        )
    from ..parallel.sharding import unbox

    qmodel = TransformerLM(dataclasses.replace(config, quantized=True))
    template = unbox(
        jax.eval_shape(
            lambda: qmodel.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)
            )["params"]
        )
    )

    def fill(template_node, params_node):
        if not isinstance(template_node, dict):
            return params_node
        if (
            "kernel" in template_node
            and getattr(template_node["kernel"], "dtype", None) == jnp.int8
        ):
            n_feature_dims = len(template_node["scale"].shape)
            q, scale = quantize_array(params_node["kernel"], n_feature_dims)
            extra = {
                k: params_node[k] for k in params_node if k != "kernel"
            }
            return {"kernel": q, "scale": scale, **extra}
        return {
            key: fill(template_node[key], params_node[key])
            for key in template_node
        }

    # Work on unboxed trees: Partitioned metadata doesn't survive a
    # structural rewrite, and serving shardings come from the quant
    # model's own init when needed.
    return qmodel, fill(template, unbox(params))
