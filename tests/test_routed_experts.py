"""The routed expert layer against the plain reference (``benchmarks/
suite/archs/xing4_0.py``): choices, weights, output and gradients; no row
lost under a planted bias that sends most rows to one expert; and the
guide's share test, in which the routed parts of all the shares plus the
shared expert once equal the uncut layer."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.archs import xing4_0 as arch
from covalent_tpu_plugin.models.moe import RoutedExperts, Router
from tests.benchsuite import xing4_toy


def _jit_grad(fn, argnums):
    return jax.jit(jax.grad(fn, argnums))


SEED = 2**31 + 1301


def _layer(config, x, bias=None):
    """(module, its parameters from the seed, the reference's leaves)."""
    module = RoutedExperts(arch.model_config(config, max_seq=x.shape[1]))
    params = xing4_toy.fill(
        module.init(jax.random.PRNGKey(0), x)["params"],
        ("layer_1", "moe"), config, SEED)
    w = xing4_toy.layer_leaves(config, SEED, 1)
    if bias is not None:
        params["router"]["bias"] = bias
        w["router_bias"] = bias
    return module, params, w


def _reference(w, x, config):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([arch.experts(row, w, config) for row in x])


def _x(batch=2, seq=64):
    return jax.random.normal(jax.random.PRNGKey(11), (batch, seq, 64))


def test_choices_and_weights_match_the_reference():
    config, x = xing4_toy.CONFIG, _x()
    cfg = arch.model_config(config, max_seq=64)
    router = Router(cfg)
    tokens = x.reshape(-1, 64)
    params = xing4_toy.fill(
        router.init(jax.random.PRNGKey(0), tokens)["params"],
        ("layer_1", "moe", "router"), config, SEED)
    chosen, weights = router.apply({"params": params}, tokens)
    assert chosen.shape == weights.shape == (128, 2)
    w = xing4_toy.layer_leaves(config, SEED, 1)
    with jax.default_matmul_precision("highest"):
        gates = arch.route(tokens, w, config)
    assert int((gates > 0).sum()) == 128 * 2          # top-2 of 8, no ties
    np.testing.assert_allclose(
        jnp.take_along_axis(gates, chosen, axis=-1), weights, rtol=1e-5)
    # Normalised over the chosen and scaled: a token's weights sum to 2.
    np.testing.assert_allclose(weights.sum(-1), 2.0, rtol=1e-5)


def test_output_and_gradients_match_the_reference():
    config, x = xing4_toy.CONFIG, _x()
    module, params, w = _layer(config, x)
    out = module.apply({"params": params}, x)
    np.testing.assert_allclose(out, _reference(w, x, config), atol=2e-5)
    weight = jax.random.normal(jax.random.PRNGKey(12), x.shape)
    gp, gx = _jit_grad(lambda p, x: jnp.sum(
        module.apply({"params": p}, x) * weight), (0, 1))(params, x)
    gr, gxr = _jit_grad(lambda w, x: jnp.sum(
        _reference(w, x, config) * weight), (0, 1))(w, x)
    np.testing.assert_allclose(gx, gxr, atol=5e-5)
    lead = (jax.tree_util.DictKey("layer_1"), jax.tree_util.DictKey("moe"))
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert len(flat) == 8
    for path, g in flat:
        name = arch.leaf_name(lead + tuple(path)).split(".", 1)[1]
        np.testing.assert_allclose(
            g.reshape(gr[name].shape), gr[name], atol=5e-5, err_msg=name)
    assert not np.any(np.asarray(gr["router_bias"]))  # no gradient reaches it


def test_a_planted_bias_overloads_one_expert_and_no_row_is_lost():
    config, x = xing4_toy.CONFIG, _x()
    # Expert 3 (held: this share is experts 2 and 3) is every token's first
    # choice; the second falls where the scores put it.
    bias = jnp.zeros((8,)).at[3].set(10.0)
    module, params, w = _layer(config, x, bias)
    out, sown = module.apply({"params": params}, x, mutable=["intermediates"])
    np.testing.assert_allclose(out, _reference(w, x, config), atol=2e-5)
    (stats,) = sown["intermediates"]["experts"]["moe_stats"]
    held_rows, load_ratio, dropped = (float(v) for v in stats)
    with jax.default_matmul_precision("highest"):
        gates = arch.route(x.reshape(-1, 64), w, config)
    assert held_rows == float((gates[:, 2:4] > 0).sum()) >= 128
    assert dropped == 0
    assert load_ratio > 1.5  # 128 rows in one expert of two


@pytest.mark.parametrize("shares", [8, 4])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        shares):
    """Each chip routes over all 8 experts and computes its own."""
    x = _x(1, 64)
    uncut = xing4_toy.with_sizes(held_experts=[0, 8])
    w = xing4_toy.layer_leaves(uncut, SEED, 1)
    want = _reference(w, x, uncut)
    with jax.default_matmul_precision("highest"):
        shared = arch.gated(
            x, w["shared_wg"], w["shared_wu"], w["shared_wd"])
    each = 8 // shares
    total, rows = shared, 0.0
    for c in range(shares):
        first = c * each
        config = xing4_toy.with_sizes(held_experts=[first, each])
        module = RoutedExperts(arch.model_config(config, max_seq=64))
        params = xing4_toy.fill(
            module.init(jax.random.PRNGKey(0), x)["params"],
            ("layer_1", "moe"), config, SEED)
        # The share's slice of the uncut layer's experts; the router and
        # the shared expert are every chip's alike (same names, same seed).
        for name in ("wg", "wu", "wd"):
            params["experts"][name] = w[f"experts_{name}"][first:first + each]
        out, sown = module.apply(
            {"params": params}, x, mutable=["intermediates"])
        total = total + (out - shared)
        rows += float(sown["intermediates"]["experts"]["moe_stats"][0][0])
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert rows == 64 * 2  # every (token, choice) pair got a row somewhere
