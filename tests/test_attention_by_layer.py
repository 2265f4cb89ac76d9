"""Attention whose settings differ by layer (``models/transformer.py``
``AttentionType``) against the plain reference (``benchmarks/suite/archs/
laguna.py``): a full layer whose heads turn by half under YaRN and a window
layer with its own head count, each with its gate a head; the softmax
router against the reference's; the rotary share; and what the
configuration refuses."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.archs import laguna as arch
from covalent_tpu_plugin.models import serve
from covalent_tpu_plugin.models.layers import YarnConfig, rotary, yarn_inv_freq
from covalent_tpu_plugin.models.moe import RoutedExpertsConfig, Router
from covalent_tpu_plugin.models.transformer import (
    Attention,
    AttentionType,
    TransformerConfig,
    TransformerLM,
)
from tests.benchsuite import laguna_toy

SEED = 2**31 + 1601
CONFIG = laguna_toy.CONFIG


@pytest.mark.parametrize("impl", ["flash", "reference"])
@pytest.mark.parametrize("layer,kind", [(0, "full_attention"),
                                        (1, "sliding_attention")])
def test_a_layers_attention_matches_the_reference(layer, kind, impl):
    cfg = arch.model_config(CONFIG, max_seq=64, attention=impl)
    module = Attention(cfg, kind=cfg.attention_of(layer))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    params = laguna_toy.fill(
        module.init(jax.random.PRNGKey(0), x)["params"],
        (f"layer_{layer}", "attention"), CONFIG, SEED)
    w = laguna_toy.layer_leaves(CONFIG, SEED, layer)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([
            arch.gated_attention(row, w, CONFIG, kind) for row in x])
    np.testing.assert_allclose(
        module.apply({"params": params}, x), want, atol=2e-5)
    # The gate is a leaf a head, and the heads are the layer type's own.
    assert params["gate_proj"]["kernel"].shape == (
        64, CONFIG["num_attention_heads_per_layer"][layer])


def test_the_softmax_router_matches_the_reference_and_has_no_bias_leaf():
    cfg = arch.model_config(CONFIG, max_seq=64)
    module = Router(cfg)
    tokens = jax.random.normal(jax.random.PRNGKey(5), (128, 64))
    params = laguna_toy.fill(
        module.init(jax.random.PRNGKey(0), tokens)["params"],
        ("layer_1", "moe", "router"), CONFIG, SEED)
    assert set(params) == {"gate"}
    chosen, weights = module.apply({"params": params}, tokens)
    w = laguna_toy.layer_leaves(CONFIG, SEED, 1)
    with jax.default_matmul_precision("highest"):
        gates = arch.route(tokens, w, CONFIG)
    got = jnp.zeros_like(gates).at[
        jnp.arange(128)[:, None], chosen].set(weights)
    np.testing.assert_allclose(got, gates, atol=1e-6)
    # Renormalised over the chosen three and scaled by 2.5.
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    with pytest.raises(ValueError, match="score"):
        RoutedExpertsConfig(n_experts=16, top_k=3, d_ff=32, score="tanh")


def test_a_rotary_share_turns_the_leading_dims_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 32, 2, 16))
    yarn = YarnConfig(factor=4, original_max=16, attention_factor=1.25)
    freqs = yarn_inv_freq(8, 10000.0, yarn)
    got = rotary(x, freqs=freqs, rotary_dim=8, amplitude=1.25)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        got[..., :8], 1.25 * rotary(x[..., :8], freqs=freqs), rtol=1e-5,
        atol=1e-6)
    # Position 0 turns by nothing: the amplitude alone.
    np.testing.assert_allclose(got[:, 0, :, :8], 1.25 * x[:, 0, :, :8],
                               rtol=1e-6)
    # All the dims, no amplitude: the call every other model makes.
    np.testing.assert_array_equal(
        rotary(x, rotary_dim=16), rotary(x))
    # YaRN keeps the fast dims and stretches the slow ones by the factor.
    plain = yarn_inv_freq(8, 10000.0, None)
    assert freqs[0] == plain[0] and freqs[-1] == pytest.approx(plain[-1] / 4)


def test_a_head_width_of_its_own_and_what_the_configuration_refuses():
    cfg = arch.model_config(CONFIG, max_seq=64)
    assert cfg.head_width == 16 and cfg.d_model // cfg.n_heads == 16
    wide = dataclasses.replace(cfg, head_dim=32)
    assert wide.head_width == 32
    assert TransformerConfig(d_model=64, n_heads=4).head_width == 16
    assert cfg.attention_of(1).name == "attn_sliding"
    assert TransformerConfig().attention_of(0) is None
    full = AttentionType("attn_full", 4)
    with pytest.raises(ValueError, match="together"):
        TransformerConfig(attention_types=(full,))
    with pytest.raises(ValueError, match="attention_kinds must name"):
        TransformerConfig(n_layers=2, attention_types=(full,),
                          attention_kinds=("attn_full", "attn_other"))
    with pytest.raises(ValueError, match="cannot be scanned"):
        dataclasses.replace(cfg, scan_layers=True)
    with pytest.raises(ValueError, match="train path"):
        dataclasses.replace(cfg, decode=True)
    with pytest.raises(serve.BlockUnsupported, match="attention_types"):
        serve._require_plain_cache(
            dataclasses.replace(cfg, routed=None, layer_kinds=None),
            "continuous serving")


def test_one_type_for_every_layer_scans():
    """Layers of one attention type are equal blocks: ``scan_layers``
    takes them."""
    kind = AttentionType("attn_sliding", 4, sliding_window=8, gate=True)
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=64, max_seq=32, attention="reference",
        attention_types=(kind,), attention_kinds=("attn_sliding",) * 3)
    lm = TransformerLM(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = lm.init(jax.random.PRNGKey(0), tokens)["params"]
    kernel = params["layers"]["attention"]["q_proj"]["kernel"]
    assert jnp.shape(getattr(kernel, "value", kernel)) == (3, 32, 4, 16)
    assert lm.apply({"params": params}, tokens).shape == (1, 16, 64)
