"""Fused (vocab-chunked) softmax cross-entropy: logits never touch HBM.

The standard LM loss path materialises a (tokens, vocab) f32 logits
tensor — at the 125M bench shape (8×1024 tokens, 32k vocab) that is
~1 GB written by the lm_head matmul, re-read by the softmax, and visited
again in the backward, on a chip whose usual bottleneck is exactly that
HBM bandwidth (its gain has not been measured on this installation).
The reference
stack has no analog (it runs opaque callables, SURVEY §2); this is a
TPU-first component in the spirit of flash attention applied to the
classifier: stream over vocabulary chunks, keep each (T, chunk) logits
tile in registers/VMEM, and carry only the O(T) online log-sum-exp state
(same rescaling trick as the attention kernels' running softmax).

Forward: one pass over chunks of ``W`` — ``s = x @ W_c`` (bf16 inputs on
the MXU's native path, f32 accumulation), online ``(m, l)`` update, and
the label logit gathered when its chunk flies by.  Backward: recompute
``s`` per chunk (FLOPs for bandwidth, the flash trade), form
``softmax - onehot`` in registers, and accumulate ``dx`` / emit ``dW``
chunks.  Peak live memory is O(T·chunk + T·d) instead of O(T·V).

``jax.grad`` composes through the ``custom_vjp``; under ``shard_map`` /
pjit the matmuls shard like any dense layer (vocab axis on the chunked
dimension).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _chunks(vocab: int, chunk: int) -> int:
    if vocab % chunk:
        raise ValueError(
            f"vocab size {vocab} must be divisible by chunk {chunk}"
        )
    return vocab // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_cross_entropy(x, w, labels, chunk: int = 8192):
    """Mean cross-entropy of ``softmax(x @ w)`` against integer labels.

    ``x``: (T, d) features (bf16 on TPU), ``w``: (d, V) lm_head kernel,
    ``labels``: (T,) int32.  Bit-for-bit it matches a bf16-input,
    f32-accumulated logits matmul followed by a stable log-softmax — NOT
    the f32-input matmul path (which is the point: that path runs at
    half MXU rate and writes the full logits tensor).
    """
    loss, _ = _fused_xent_fwd(x, w, labels, chunk)
    return loss


def _logits_chunk(x, w, j, chunk):
    wc = jax.lax.dynamic_slice_in_dim(w, j * chunk, chunk, axis=1)
    return jax.lax.dot_general(
        x, wc.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ), wc


def _fused_xent_fwd(x, w, labels, chunk):
    tokens = x.shape[0]
    n = _chunks(w.shape[1], chunk)
    labels = labels.astype(jnp.int32)

    def body(carry, j):
        m, l, lab = carry
        s, _ = _logits_chunk(x, w, j, chunk)  # (T, chunk) f32
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(s - m_new[:, None]), axis=-1
        )
        idx = labels - j * chunk
        in_chunk = (idx >= 0) & (idx < chunk)
        got = jnp.take_along_axis(
            s, jnp.clip(idx, 0, chunk - 1)[:, None], axis=1
        )[:, 0]
        lab = jnp.where(in_chunk, got, lab)
        return (m_new, l, lab), None

    init = (
        jnp.full((tokens,), -jnp.inf, jnp.float32),
        jnp.zeros((tokens,), jnp.float32),
        jnp.zeros((tokens,), jnp.float32),
    )
    (m, l, lab), _ = jax.lax.scan(body, init, jnp.arange(n))
    lse = m + jnp.log(l)
    loss = jnp.mean(lse - lab)
    return loss, (x, w, labels, lse)


def _fused_xent_bwd(chunk, res, g):
    x, w, labels, lse = res
    tokens = x.shape[0]
    n = _chunks(w.shape[1], chunk)
    coef = (g / tokens).astype(jnp.float32)
    cols = jnp.arange(chunk)[None, :]

    def body(dx, j):
        s, wc = _logits_chunk(x, w, j, chunk)
        p = jnp.exp(s - lse[:, None])  # softmax chunk, recomputed
        idx = (labels - j * chunk)[:, None]
        p = p - (cols == idx).astype(jnp.float32)  # subtract onehot
        dl = (p * coef).astype(x.dtype)  # (T, chunk) back on the MXU path
        dx = dx + jax.lax.dot_general(
            dl, wc.astype(x.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dwc = jax.lax.dot_general(
            x, dl,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dx, dwc.astype(w.dtype)

    dx, dw_chunks = jax.lax.scan(
        body, jnp.zeros(x.shape, jnp.float32), jnp.arange(n)
    )
    # (n, d, chunk) -> (d, n*chunk) = (d, V): column j*chunk+c is chunk
    # j's column c, which is exactly the reshape of the moved axis.
    dw = jnp.moveaxis(dw_chunks, 0, 1).reshape(w.shape)
    d_labels = np.zeros(labels.shape, jax.dtypes.float0)
    return dx.astype(x.dtype), dw, d_labels


fused_cross_entropy.defvjp(_fused_xent_fwd, _fused_xent_bwd)
