"""Fused (row-tiled) softmax cross-entropy: logits never touch HBM whole.

The standard LM loss path materialises a (tokens, vocab) f32 logits
tensor, written by the lm_head matmul, re-read by the softmax, and
visited again in the backward.  The reference stack has no analog (it
runs opaque callables, SURVEY §2); this is a TPU-first component in the
spirit of flash attention applied to the classifier: walk the rows
(tokens) in tiles, each tile's scores the whole vocabulary wide, so a
tile's log-sum-exp is complete the moment its scores exist and nothing
has to be made twice.

Every score is computed once.  Under ``jax.grad`` the ``custom_vjp``'s
forward rule forms, tile by tile, ``s = x_t @ W`` (bf16 inputs on the
MXU's native path, f32 accumulation), the tile's share of the loss,
``dl = (softmax - onehot) / T`` rounded to the input dtype,
``dx_t = dl @ W^T`` and ``dW += x_t^T @ dl`` (an f32 carry in ``W``'s own
layout): three vocabulary-sized matmuls, which is what the gradients
need.  The residuals are ``dx`` and ``dW``; the backward rule scales them
by the incoming cotangent.  Without ``grad`` the primal runs the same
tiles and the score matmul alone.

``chunk`` bounds the scores live at once, at tokens x chunk elements;
the row tile follows from it (``_row_tiles``).  Peak live memory is
O(T·chunk + d·V) instead of O(T·V).

Under pjit the matmuls shard like any dense layer.  Tiles are strided
(tile j holds rows j, j + n, j + 2n, ...), so with the rows sharded over
a mesh's data axes every tile holds rows of every shard, and ``W``'s
vocabulary axis stays whole inside the loop, so a vocab-sharded head
reduces its log-sum-exp across shards once a tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _row_tiles(tokens: int, vocab: int, chunk: int) -> tuple[int, int]:
    """(rows a tile, tiles): the largest power of two of rows whose scores,
    the whole vocabulary wide, stay within tokens x chunk elements; all the
    rows in one tile where that bound allows it.  ``chunk`` need not divide
    the vocabulary (an eighth of 100,352 is 49 x 256): it only bounds."""
    bound = max(1, tokens * chunk // vocab)
    tile = tokens if bound >= tokens else 1 << (bound.bit_length() - 1)
    return tile, -(-tokens // tile)


def _tiled(a, tile, n):
    """(T, ...) -> (n, tile, ...), strided; rows past T are zeros."""
    pad = [(0, tile * n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.swapaxes(jnp.pad(a, pad).reshape(tile, n, *a.shape[1:]), 0, 1)


def _tiles(x, w, labels, chunk, weights=None):
    """The scan's inputs: x, labels and the rows' weights by tile, and the
    head in the input dtype.  A row weighs 1/T where the caller gives no
    ``weights``; a padded row weighs nothing."""
    tokens = x.shape[0]
    tile, n = _row_tiles(tokens, w.shape[1], chunk)
    if weights is None:
        weight = jnp.full((tokens,), 1.0 / tokens, jnp.float32)
    else:
        weight = weights.astype(jnp.float32)
    return (
        _tiled(x, tile, n),
        _tiled(labels.astype(jnp.int32), tile, n),
        _tiled(weight, tile, n),
    ), w.astype(x.dtype)


def _scores(x_t, w, labels_t):
    """One tile's scores (f32), where its labels sit, and each row's
    log-sum-exp less its label's score."""
    s = jax.lax.dot_general(
        x_t, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    hit = jnp.arange(w.shape[1])[None, :] == labels_t[:, None]
    m = jnp.max(s, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(s - m[:, None]), axis=-1))
    # A masked sum, not a gather: it shards with a vocab-sharded head.
    nll = lse - jnp.sum(jnp.where(hit, s, 0.0), axis=-1)
    return s, hit, lse, nll


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_cross_entropy(x, w, labels, chunk: int = 8192, weights=None):
    """Mean cross-entropy of ``softmax(x @ w)`` against integer labels.

    ``x``: (T, d) features (bf16 on TPU), ``w``: (d, V) lm_head kernel,
    ``labels``: (T,) int32, ``chunk``: the bound on the live scores (T x
    chunk elements).  It matches a bf16-input,
    f32-accumulated logits matmul followed by a stable log-softmax — NOT
    the f32-input matmul path (which is the point: that path runs at
    half MXU rate and writes the full logits tensor).  ``weights`` ((T,),
    no gradient) replaces the mean's 1/T a row: the loss is then the
    weighted sum.
    """
    tiles, w = _tiles(x, w, labels, chunk, weights)

    def body(_, t):
        x_t, labels_t, weight_t = t
        *_, nll = _scores(x_t, w, labels_t)
        return None, jnp.sum(weight_t * nll)

    _, parts = jax.lax.scan(body, None, tiles)
    return jnp.sum(parts)


def _fused_xent_fwd(x, w, labels, chunk, weights):
    tiles, wc = _tiles(x, w, labels, chunk, weights)

    def body(dw, t):
        x_t, labels_t, weight_t = t
        s, hit, lse, nll = _scores(x_t, wc, labels_t)
        p = jnp.exp(s - lse[:, None]) - hit.astype(jnp.float32)
        dl = (p * weight_t[:, None]).astype(x.dtype)  # back on the MXU path
        dx_t = jax.lax.dot_general(
            dl, wc,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dw = dw + jax.lax.dot_general(
            x_t, dl,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dw, (jnp.sum(weight_t * nll), dx_t.astype(x.dtype))

    dw, (parts, dx) = jax.lax.scan(
        body, jnp.zeros(w.shape, jnp.float32), tiles
    )
    # (n, tile, d) -> (T, d): the strided tiling undone, the padding cut.
    dx = jnp.swapaxes(dx, 0, 1).reshape(-1, x.shape[1])[: x.shape[0]]
    return jnp.sum(parts), (dx, dw.astype(w.dtype))


def _fused_xent_bwd(chunk, res, g):
    dx, dw = res
    d_labels = np.zeros(dx.shape[:1], jax.dtypes.float0)
    return (dx * g).astype(dx.dtype), (dw * g).astype(dw.dtype), d_labels, None


fused_cross_entropy.defvjp(_fused_xent_fwd, _fused_xent_bwd)
