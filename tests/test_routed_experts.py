"""The routed expert layer against the plain reference (``benchmarks/
suite/archs/xing4_0.py``): choices, weights, output and gradients; no row
lost under a planted bias that sends most rows to one expert; the bounded
row buffer against the whole one; and the guide's share test, in which the
routed parts of all the shares plus the shared expert once equal the uncut
layer."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.archs import xing4_0 as arch
from covalent_tpu_plugin.models import moe
from covalent_tpu_plugin.models.moe import HeldExperts, RoutedExperts, Router
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


def _stats(module, params, x):
    out, sown = module.apply({"params": params}, x, mutable=["intermediates"])
    (stats,) = sown["intermediates"]["experts"]["moe_stats"]
    return out, [float(v) for v in stats]


def test_a_planted_bias_overloads_one_expert_and_no_row_is_lost():
    config, x = xing4_toy.CONFIG, _x()
    # Expert 3 (held: this share is experts 2 and 3) is every token's first
    # choice; the second falls where the scores put it.
    bias = jnp.zeros((8,)).at[3].set(10.0)
    module, params, w = _layer(config, x, bias)
    out, (held_rows, load_ratio, dropped, whole) = _stats(module, params, x)
    np.testing.assert_allclose(out, _reference(w, x, config), atol=2e-5)
    with jax.default_matmul_precision("highest"):
        gates = arch.route(x.reshape(-1, 64), w, config)
    assert held_rows == float((gates[:, 2:4] > 0).sum()) >= 128
    assert dropped == 0
    assert load_ratio > 1.5  # 128 rows in one expert of two
    assert whole == 0  # a quarter of the experts held: one buffer, no other

    # The same two of SIXTEEN experts: the buffer has room for 512 of the
    # 1,024 pairs, the planted bias sends more, and the layer takes the
    # whole buffer; left even, 128 or so come and the bounded one does.
    config, x = xing4_toy.with_sizes(router_width=16), _x(2, 256)
    assert moe.buffer_rows(1024, 2, 16) == 512
    for bias, took_whole in ((jnp.zeros((16,)).at[3].set(10.0), 1),
                             (jnp.zeros((16,)), 0)):
        module, params, w = _layer(config, x, bias)
        out, (held_rows, _, dropped, whole) = _stats(module, params, x)
        np.testing.assert_allclose(out, _reference(w, x, config), atol=2e-5)
        assert (held_rows > 512) == bool(took_whole)
        assert (dropped, whole) == (0, took_whole)


def _held_layer(n_held_pairs):
    """``HeldExperts`` of two of sixteen experts over 512 tokens' two
    choices, ``n_held_pairs`` of the 1,024 pairs on a held expert: a
    function of (parameters, tokens, weights) -> (result, statistics)."""
    config = xing4_toy.with_sizes(router_width=16)
    module = HeldExperts(arch.model_config(config, max_seq=512))
    rng = np.random.default_rng(n_held_pairs)
    chosen = rng.choice(np.r_[0:2, 4:16], size=1024)
    chosen[rng.permutation(1024)[:n_held_pairs]] = rng.choice(
        [2, 3], size=n_held_pairs)
    chosen = jnp.asarray(chosen.reshape(2, 512).T)
    keys = jax.random.split(jax.random.PRNGKey(n_held_pairs), 3)
    tokens = jax.random.normal(keys[0], (512, 64))
    weights = jax.random.uniform(keys[1], (512, 2), minval=0.5)
    params = jax.tree.map(
        lambda leaf: 0.3 * jax.random.normal(keys[2], leaf.shape),
        module.init(keys[2], tokens, chosen, weights)["params"])

    def apply(params, tokens, weights):
        out, sown = module.apply(
            {"params": params}, tokens, chosen, weights,
            mutable=["intermediates"])
        return out, sown["intermediates"]["moe_stats"][0]

    return apply, (params, tokens, weights)


@pytest.mark.parametrize("n_held_pairs, takes_whole", [
    (100, 0), (512, 0), (513, 1), (0, 0)],
    ids=["well-under", "exactly-the-bound", "one-over", "none-held"])
def test_the_bounded_buffer_gives_what_the_whole_buffer_gives(
        n_held_pairs, takes_whole, monkeypatch):
    apply, operands = _held_layer(n_held_pairs)
    weight = jax.random.normal(jax.random.PRNGKey(13), (512, 64))

    def loss(*operands):
        out, stats = apply(*operands)
        return jnp.sum(out * weight), (out, stats)

    def run():
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*operands)
        return out, [float(v) for v in stats], grads

    out, stats, grads = run()
    assert stats == [n_held_pairs, stats[1], 0, takes_whole]
    # One buffer with room for every pair, as where every expert is held.
    monkeypatch.setattr(moe, "buffer_rows", lambda pairs, held, n: pairs)
    want, want_stats, want_grads = run()
    assert want_stats == stats[:3] + [0]
    assert bool(np.any(np.asarray(out))) == bool(n_held_pairs)
    # To a float32 rounding of each array's own scale: the products sum a
    # kernel's gradient over a buffer of another length.
    for got, wanted in zip(jax.tree.leaves((out, grads)),
                           jax.tree.leaves((want, want_grads))):
        np.testing.assert_allclose(
            got, wanted, rtol=0, atol=2e-6 * float(jnp.abs(wanted).max()))
    assert len(jax.tree.leaves(grads)) == 5  # three kernels, tokens, weights


def test_a_buffer_with_room_for_every_pair_lowers_to_no_conditional():
    def conditionals(config, seq):
        x = _x(2, seq)
        module, params, _ = _layer(config, x)
        text = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
            module.apply({"params": p}, x)))).lower(params, x).compiler_ir(
                dialect="hlo").as_hlo_text()
        return text.count(" conditional(")

    assert conditionals(xing4_toy.CONFIG, 64) == 0
    # Two of sixteen held and 1,024 pairs: one forward, one backward.
    assert conditionals(xing4_toy.with_sizes(router_width=16), 256) == 2


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
