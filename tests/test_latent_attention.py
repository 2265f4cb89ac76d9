"""Latent attention against the plain reference (``benchmarks/suite/archs/
xing4_0.py``), and the three flash kernels at unequal query/key and value
widths against ``mha_reference``: forward and gradients, seeded, at toy
sizes in the interpreter."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.archs import xing4_0 as arch
from covalent_tpu_plugin.models.latent import (
    LatentAttention,
    LatentAttentionConfig,
    yarn_inv_freq,
)
from covalent_tpu_plugin.ops.attention import flash_attention, mha_reference
from tests.benchsuite import xing4_toy


def _jit_grad(fn, argnums):
    return jax.jit(jax.grad(fn, argnums))


SEED = 2**31 + 1201


def _qkv(heads, kv_heads, seq, dk, dv):
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, heads, seq, dk))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, kv_heads, seq, dk))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, kv_heads, seq, dv))
    return q, k, v


@pytest.mark.parametrize("heads,kv_heads,window", [
    (2, 2, None),    # latent attention's case: a K and a V a head
    (4, 2, None),    # grouped queries
    (2, 2, 96),      # the banded grid
])
def test_kernels_at_unequal_widths_match_the_dense_oracle(
        heads, kv_heads, window):
    q, k, v = _qkv(heads, kv_heads, 256, 48, 32)
    scale = 0.21  # not the width's inverse square root: the kernels take it
    kwargs = {"scale": scale, "window": window}
    out = flash_attention(q, k, v, block_q=128, block_k=128, **kwargs)
    want = mha_reference(q, k, v, **kwargs)
    assert out.shape == (1, heads, 256, 32)
    np.testing.assert_allclose(out, want, atol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    got = jax.grad(loss(lambda *a: flash_attention(*a, **kwargs)), (0, 1, 2))(
        q, k, v)
    ref = jax.grad(loss(lambda *a: mha_reference(*a, **kwargs)), (0, 1, 2))(
        q, k, v)
    for g, r, like in zip(got, ref, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, r, atol=5e-5)


def test_default_scale_is_the_score_widths_and_widths_must_agree():
    q, k, v = _qkv(2, 2, 128, 48, 32)
    np.testing.assert_allclose(
        flash_attention(q, k, v), mha_reference(q, k, v, scale=48 ** -0.5),
        atol=2e-5)
    with pytest.raises(ValueError, match="scores' width"):
        flash_attention(q, k[..., :32], v)


def test_yarn_frequencies_and_scale_match_the_reference():
    config = xing4_toy.CONFIG
    lat = arch.model_config(config, max_seq=64).latent
    np.testing.assert_allclose(
        yarn_inv_freq(config["qk_rope_head_dim"], config["rope_theta"],
                      lat.yarn),
        arch.yarn_frequencies(config), rtol=1e-6)
    # Published sizes: 64 rope dims, factor 64 over 4096 positions.
    full = LatentAttentionConfig(768, 512, 128, 64, 128, rope_factor=64,
                                 rope_mscale=1, rope_mscale_all_dim=1)
    assert full.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    freqs = yarn_inv_freq(64, 10000.0, full.yarn)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freqs[0] == pytest.approx(plain[0])            # fast dims kept
    assert freqs[-1] == pytest.approx(plain[-1] / 64)     # slow dims scaled


@pytest.mark.parametrize("impl", ["flash", "reference"])
def test_latent_attention_matches_the_reference(impl):
    config = xing4_toy.CONFIG
    module = LatentAttention(arch.model_config(
        config, max_seq=128, attention=impl))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64))
    params = xing4_toy.fill(
        module.init(jax.random.PRNGKey(0), x)["params"],
        ("layer_1", "attention"), config, SEED)
    w = xing4_toy.layer_leaves(config, SEED, 1)

    def program(params, x):
        return module.apply({"params": params}, x)

    def reference(w, x):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([arch.latent_attention(row, w, config)
                              for row in x])

    np.testing.assert_allclose(program(params, x), reference(w, x), atol=2e-5)
    weight = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    gp, gx = _jit_grad(
        lambda p, x: jnp.sum(program(p, x) * weight), (0, 1))(params, x)
    gr, gxr = _jit_grad(
        lambda w, x: jnp.sum(reference(w, x) * weight), (0, 1))(w, x)
    np.testing.assert_allclose(gx, gxr, atol=5e-5)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert len(flat) == 7
    lead = (jax.tree_util.DictKey("layer_1"), jax.tree_util.DictKey("attention"))
    for path, g in flat:
        name = arch.leaf_name(lead + tuple(path)).split(".", 1)[1]
        np.testing.assert_allclose(
            g.reshape(gr[name].shape), gr[name], atol=5e-5, err_msg=name)


def test_head_shares_output_projections_add_up_to_whole_attention():
    """The guide's share test for heads: 8 chips hold one head each of an
    8-head layer (their slices of W_qb, W_kvb and W_o; the latent
    projections whole); their output-projection parts sum to the uncut
    reference's attention."""
    whole = xing4_toy.with_sizes(num_attention_heads=8, num_key_value_heads=8)
    w = xing4_toy.layer_leaves(whole, SEED, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 64))
    with jax.default_matmul_precision("highest"):
        want = arch.latent_attention(x[0], w, whole)
    share = xing4_toy.with_sizes(num_attention_heads=1, num_key_value_heads=1)
    module = LatentAttention(arch.model_config(
        share, max_seq=64, attention="flash"))
    template = module.init(jax.random.PRNGKey(0), x)["params"]
    qk, v = 16 + 8, 16
    total = 0.0
    for h in range(8):
        params = jax.tree_util.tree_map(lambda a: a, template)
        proj = dict(params["latent_proj"])
        for name in ("q_a", "kv_a"):
            proj[name] = {"kernel": w[name]}
        proj["q_a_norm"] = {"scale": w["q_a_norm"]}
        proj["kv_a_norm"] = {"scale": w["kv_a_norm"]}
        proj["q_b"] = {"kernel": w["q_b"].reshape(24, 8, qk)[:, h:h + 1]}
        proj["kv_b"] = {"kernel": w["kv_b"].reshape(16, 8, 16 + v)[:, h:h + 1]}
        params = {"latent_proj": proj, "out_proj": {
            "kernel": w["o"].reshape(8, v, 64)[h:h + 1]}}
        total = total + module.apply({"params": params}, x)[0]
    np.testing.assert_allclose(total, want, atol=2e-5)
