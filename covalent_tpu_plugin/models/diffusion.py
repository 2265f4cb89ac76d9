"""Block-diffusion training (BD3-LM, arXiv:2503.09573).

A row's S clean tokens ``x0`` fall in blocks of B.  Each block draws a
noise level ``t ~ U[t_min, 1]`` and masks each of its tokens, independently,
with probability ``t`` (the mask token's id in its place), giving ``xt``.
The model runs once over the two copies side by side, ``[xt ; x0]``, both
turned from position 0, under the block-diffusion mask
(``TransformerConfig.diffusion_block``): a noisy block is denoised from
itself and the clean blocks before it.  The loss is each masked token's
cross-entropy against its clean id, weighted ``1 / t`` of its block, on the
noisy half alone, divided by S.

The noise is a pure function of a key and the row, drawn inside the train
step under ``jax.named_scope("bd_noise")``; the key is the caller's
(``noise_key``: a row of ids -> a key).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def noise(key, x0, block: int, mask_id: int, *, t_min: float):
    """``(xt, weight)`` for one row ``x0`` (S,): the row with each block's
    masked tokens replaced by ``mask_id``, and each token's loss weight,
    ``1 / t`` of its block where it is masked and 0 elsewhere.  ``t_min``
    is the least level a block draws, so a weight is at most
    ``1 / t_min``."""
    seq = x0.shape[0]
    if seq % block:
        raise ValueError(f"a row of {seq} is not whole blocks of {block}")
    k_level, k_mask = jax.random.split(key)
    level = jnp.repeat(jax.random.uniform(
        k_level, (seq // block,), minval=t_min, maxval=1.0), block)
    masked = jax.random.uniform(k_mask, (seq,)) < level
    return (jnp.where(masked, mask_id, x0).astype(x0.dtype),
            jnp.where(masked, 1.0 / level, 0.0))


def diffusion_loss(params, apply_fn, batch, *, block: int, mask_id: int,
                   noise_key, t_min: float, vocab_chunk: int = 8192):
    """The block-diffusion loss over a ``{"tokens": (B, S + 1)}`` batch
    (the first S ids of a row are its ``x0``), for ``make_train_step``:
    ``(loss, counts)``, the counts the step's masked tokens (``masked``)
    and, where routed layers counted, their rows (``moe``: see
    ``models/moe.py`` ``collect_moe_stats``).  The head is the fused loss
    (``ops/xent.py``) over the noisy half's features, a row weighing its
    noise weight over B x S."""
    from ..ops.xent import fused_cross_entropy
    from .moe import collect_moe_stats
    from .train import head_kernel

    x0 = batch["tokens"][:, :-1]
    rows, seq = x0.shape
    with jax.named_scope("bd_noise"):
        xt, weight = jax.vmap(
            lambda row: noise(noise_key(row), row, block, mask_id,
                              t_min=t_min))(x0)
    feats, sown = apply_fn(
        {"params": params}, jnp.concatenate([xt, x0], axis=1),
        mutable=["intermediates"], return_features=True)
    noisy = feats[:, :seq].reshape(-1, feats.shape[-1])
    loss = fused_cross_entropy(
        noisy, head_kernel(params), x0.reshape(-1), vocab_chunk,
        weight.reshape(-1) / (rows * seq))
    counts = {"masked": jnp.sum(weight > 0).astype(jnp.float32)}
    routed = collect_moe_stats(sown.get("intermediates", {}))
    if routed is not None:
        counts["moe"] = routed
    return loss, counts
