"""Block-diffusion training (``models/diffusion.py``) and the model's side
of it (``TransformerConfig.diffusion_block``, ``qk_norm``): the noise a
block at a time, and what each half of ``[noisy ; clean]`` may see through
the whole model."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
from covalent_tpu_plugin.models.diffusion import noise

#: The least noise level these tests draw at.
T_MIN = 1e-3


def test_noise_masks_each_block_at_its_own_level():
    x0 = jnp.arange(4096, dtype=jnp.int32) % 100
    xt, weight = noise(jax.random.PRNGKey(3), x0, 4, 999, t_min=T_MIN)
    masked = np.asarray(weight > 0)
    np.testing.assert_array_equal(np.asarray(xt == 999), masked)
    np.testing.assert_array_equal(np.asarray(xt)[~masked],
                                  np.asarray(x0)[~masked])
    # A masked token weighs 1 / t of its block: one level a block, inside
    # [T_MIN, 1], so a weight of 1 to 1 / T_MIN.
    blocks = np.asarray(weight).reshape(-1, 4)
    for row in blocks:
        assert len(set(row[row > 0].tolist())) <= 1
    assert np.all(blocks[blocks > 0] >= 1.0)
    assert np.all(blocks[blocks > 0] <= 1.0 / T_MIN)
    # U[1e-3, 1] masks about half; the same key draws the same noise.
    assert 0.45 < masked.mean() < 0.55
    again = noise(jax.random.PRNGKey(3), x0, 4, 999, t_min=T_MIN)
    np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(weight))
    with pytest.raises(ValueError, match="whole blocks"):
        noise(jax.random.PRNGKey(3), x0[:10], 4, 999, t_min=T_MIN)


def _model(**changes):
    return TransformerLM(TransformerConfig(**{
        "vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 64, "max_seq": 64, "dtype": jnp.float32,
        "attention": "flash", "qk_norm": True, "diffusion_block": 4,
        **changes}))


def test_each_half_sees_what_the_block_mask_lets_it_see():
    """Through the whole model: the clean half never sees the noisy half,
    and a noisy block sees the clean blocks before it and none after."""
    lm = _model()
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 64, (1, 64)), jnp.int32)
    params = meta.unbox(lm.init(jax.random.PRNGKey(0), rows)["params"])
    # The scanned layers' q/k norms: a scale a head's width, each layer.
    assert params["layers"]["attention"]["q_norm"]["scale"].shape == (2, 8)
    assert params["layers"]["attention"]["k_norm"]["scale"].shape == (2, 8)

    apply = jax.jit(lambda rows: lm.apply(
        {"params": params}, rows, return_features=True))

    def features(rows):
        return np.asarray(apply(rows))[0]

    base = features(rows)
    noisy = rows.at[0, :32].set((rows[0, :32] + 1) % 64)
    np.testing.assert_array_equal(features(noisy)[32:], base[32:])
    # Change clean position 13 (block 3): noisy blocks 0-3 do not see it.
    clean = features(rows.at[0, 32 + 13].set((rows[0, 32 + 13] + 1) % 64))
    np.testing.assert_array_equal(clean[:16], base[:16])
    assert np.abs(clean[16:32] - base[16:32]).max(axis=-1).min() > 0
    # The flash kernels and the dense oracle agree through the model.
    dense = _model(attention="reference")
    np.testing.assert_allclose(
        np.asarray(dense.apply({"params": params}, rows,
                               return_features=True))[0], base, atol=2e-5)


@pytest.mark.parametrize("changes", [
    {"decode": True}, {"sliding_window": 8}, {"attention": "ring"},
    {"diffusion_block": 0},
], ids=["decode", "window", "ring", "zero"])
def test_block_diffusion_is_a_train_path_setting(changes):
    with pytest.raises(ValueError, match="diffusion_block"):
        _model(**changes)
