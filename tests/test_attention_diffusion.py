"""The block-diffusion mask (BD3-LM, arXiv:2503.09573) in the three flash
kernels (``ops/attention.py``, interpreted here): forward and gradients
against ``mha_reference`` under the same mask, at a block length of 4 and
at one that divides no tile, and grids that visit the live tiles alone."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from covalent_tpu_plugin.ops import attention
from covalent_tpu_plugin.ops.attention import (
    diffusion_tiles,
    diffusion_visible,
    flash_attention,
    mha_reference,
)


def _inputs(half, heads=4, kv=2, dim=16):
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    n = 2 * half
    q = jax.random.normal(keys[0], (1, heads, n, dim), jnp.float32)
    k = jax.random.normal(keys[1], (1, kv, n, dim), jnp.float32)
    v = jax.random.normal(keys[2], (1, kv, n, dim), jnp.float32)
    g = jax.random.normal(keys[3], (1, heads, n, dim), jnp.float32)
    return q, k, v, g


def _dense_tiles(half, block, block_q, block_k):
    """``diffusion_tiles`` worked out from the dense mask."""
    n = 2 * half
    mask = np.asarray(diffusion_visible(
        np.arange(n)[:, None], np.arange(n)[None, :], half, block))
    tiles = mask.reshape(n // block_q, block_q, n // block_k, block_k)
    seen, whole = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    return [(i, j, not whole[i, j]) for i in range(n // block_q)
            for j in range(n // block_k) if seen[i, j]]


def test_the_mask_is_the_block_diffusion_one():
    """Written out for two blocks of 2 over a half of 4: noisy rows see
    their own noisy block and the clean blocks before it, clean rows the
    clean blocks up to theirs."""
    n = np.arange(8)
    got = np.asarray(diffusion_visible(n[:, None], n[None, :], 4, 2))
    want = np.array([
        # noisy 0..3     clean 0..3
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 1, 1],
        [0, 0, 0, 0, 1, 1, 1, 1],
    ], bool)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("half,block,block_q,block_k", [
    (128, 4, 32, 64),     # SDAR's block length
    (192, 48, 64, 32),    # a block length that divides no tile
], ids=["block-4", "block-48"])
def test_kernels_match_the_dense_oracle_forward_and_back(
        half, block, block_q, block_k):
    """float32 through the interpreter against the dense oracle: the same
    mask, the same sums reassociated, so 1e-5 on the output and 2e-5 on
    each gradient (a bfloat16 kernel reads 1e-2 here)."""
    q, k, v, g = _inputs(half)

    def flash(q, k, v):
        return flash_attention(q, k, v, diffusion_block=block,
                               block_q=block_q, block_k=block_k)

    def dense(q, k, v):
        return mha_reference(q, k, v, diffusion_block=block)

    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(flash, q, k, v)
        want, pull_want = jax.vjp(dense, q, k, v)
        grads, grads_want = pull(g), pull_want(g)
    np.testing.assert_allclose(out, want, atol=1e-5)
    for got, ref in zip(grads, grads_want):
        np.testing.assert_allclose(got, ref, atol=2e-5)


@pytest.mark.parametrize("half,block,block_q,block_k", [
    (128, 4, 32, 64), (192, 48, 64, 32), (8192, 4, 512, 1024),
    (8192, 4, 1024, 1024),
], ids=["block-4", "block-48", "cell-forward", "cell-backward"])
def test_the_live_tiles_are_the_mask_s(half, block, block_q, block_k):
    """Every tile with a visible pair, and only those, in query-tile order;
    the masked ones are those not wholly visible.  At the cell's tiles the
    live share of the causal grid's tiles over 2S is the ~0.6 the pruning
    buys."""
    live = diffusion_tiles(half, block, block_q, block_k)
    if half <= 256:
        assert live == _dense_tiles(half, block, block_q, block_k)
    n_q, n_k = 2 * half // block_q, 2 * half // block_k
    causal = sum(min(n_k, (i + 1) * block_q // block_k + (
        (i + 1) * block_q % block_k > 0)) for i in range(n_q))
    if half == 8192:
        assert len(live) == {512: 160, 1024: 80}[block_q]
        assert len(live) / causal < 0.6


def test_the_grids_visit_the_live_tiles_alone():
    """The three pallas_calls' grids as traced: (batch, heads, live) for the
    forward and dq sweeps, (batch, kv heads, live x group) for dK/dV."""
    half, block = 128, 4
    q, k, v, g = _inputs(half)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, diffusion_block=block) * g)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    forward = len(diffusion_tiles(half, block, 128, 128))
    backward = len(diffusion_tiles(half, block, 128, 128))
    assert grids == {"flash_fwd": (1, 4, forward),
                     "flash_bwd_dkdv": (1, 2, 2 * backward),
                     "flash_bwd_dq": (1, 4, backward)}


def test_the_diffusion_mask_refuses_what_it_does_not_take():
    q, k, v, _ = _inputs(64)
    with pytest.raises(ValueError, match="no window or positions"):
        flash_attention(q, k, v, diffusion_block=4, window=16)
    with pytest.raises(ValueError, match="does not divide the half"):
        flash_attention(q, k, v, diffusion_block=4, block_q=48)
    assert attention._diffusion_fit(None, 512, 8192) == 512
