"""The n-stream residual path against the plain reference (``benchmarks/
suite/archs/xing4_0.py``): a sublayer's mixing forward and in its
gradients, the mixing matrix doubly stochastic, and the whole model (a
dense layer, an expert layer, four streams, latent attention) in loss and
gradients, through ``lm_loss`` as the train step calls it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite import reference
from benchmarks.suite.archs import xing4_0 as arch
from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
from covalent_tpu_plugin.models.streams import StreamMix, sinkhorn_knopp
from tests.benchsuite import xing4_toy

SEED = 2**31 + 1401


def _sublayer(u):
    return jnp.tanh(u) * 0.5 + u[..., ::-1] * 0.25


def _streams(batch=2, seq=32):
    # Streams that differ, as they do after the first sublayer.
    return jax.random.normal(jax.random.PRNGKey(21), (batch, 4, seq, 64))


def _mixer(config, x):
    module = StreamMix(arch.model_config(config, max_seq=x.shape[2]))
    params = xing4_toy.fill(
        module.init(jax.random.PRNGKey(0), x,
                    method=StreamMix.coefficients)["params"],
        ("layer_1", "hc_mlp"), config, SEED)
    # Away from its start values, so that every coefficient matters.
    params["alpha"] = jnp.asarray([0.7, -0.4, 0.6])
    params["b_pre"] = jnp.asarray([0.3, -0.2, 0.1, 0.0])
    w = xing4_toy.layer_leaves(config, SEED, 1)
    w["hc_mlp.alpha"], w["hc_mlp.b_pre"] = params["alpha"], params["b_pre"]
    return module, params, w


def _program(module, params, x):
    u, coefficients = module.apply(
        {"params": params}, x, method=StreamMix.coefficients)
    return module.apply({"params": params}, x, _sublayer(u), coefficients,
                        method=StreamMix.mix), coefficients


def test_the_mixing_matrix_is_doubly_stochastic():
    config, x = xing4_toy.CONFIG, _streams()
    module, params, _ = _mixer(config, x)
    _, (post, res) = _program(module, params, x)
    assert res.shape == (2, 4, 4, 32) and post.shape == (2, 4, 32)
    assert float(res.min()) > 0
    np.testing.assert_allclose(res.sum(axis=2), 1.0, atol=1e-3)  # rows
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-3)  # columns
    assert float(post.min()) > 0 and float(post.max()) < 2
    # Twenty rounds from a matrix far from balanced: rows exact (they are
    # normalised last), columns still settling.
    rough = jnp.exp(jax.random.normal(
        jax.random.PRNGKey(1), (1, 4, 4, 8)))
    done = sinkhorn_knopp(rough, 20, 1e-6)
    np.testing.assert_allclose(done.sum(axis=2), 1.0, atol=1e-5)  # last: rows
    np.testing.assert_allclose(done.sum(axis=1), 1.0, atol=2e-2)


def test_bfloat16_streams_hand_the_sublayer_a_float32_input():
    """``u`` leaves the mix in float32 whatever the streams are held in
    (its cotangent has to stay orthogonal to it: ``models/streams.py``),
    and with equal streams, where ``H_pre``'s true gradient is what the
    norm's epsilon leaves, no rounding noise takes its place."""
    config = dict(xing4_toy.CONFIG, activation_dtype="bfloat16")
    x = _streams().astype(jnp.bfloat16)
    module, params, _ = _mixer(config, x)
    u, _ = module.apply({"params": params}, x, method=StreamMix.coefficients)
    out, _ = _program(module, params, x)
    assert u.dtype == jnp.float32 and out.dtype == jnp.bfloat16

    def normed(u):  # what every sublayer does first
        return u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-6)

    def loss(b_pre, x):
        u, _ = module.apply({"params": {**params, "b_pre": b_pre}}, x,
                            method=StreamMix.coefficients)
        return jnp.sum(jnp.sin(3.0 * normed(u)))

    equal = jnp.broadcast_to(x[:, :1], x.shape)
    got = jax.grad(loss)(params["b_pre"], equal)
    want = jax.grad(loss)(params["b_pre"], equal.astype(jnp.float32))
    assert float(jnp.abs(want).max()) < 1e-3  # all but cancelled
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_sublayers_mixing_matches_the_reference_forward_and_back():
    config, x = xing4_toy.CONFIG, _streams()
    module, params, w = _mixer(config, x)

    def reference_(w, x):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([
                arch.mixed(row, w, "hc_mlp", config, _sublayer) for row in x])

    out, _ = _program(module, params, x)
    np.testing.assert_allclose(out, reference_(w, x), atol=2e-5)
    weight = jax.random.normal(jax.random.PRNGKey(22), x.shape)
    gp, gx = jax.jit(jax.grad(lambda p, x: jnp.sum(
        _program(module, p, x)[0] * weight), (0, 1)))(params, x)
    gr, gxr = jax.jit(jax.grad(lambda w, x: jnp.sum(
        reference_(w, x) * weight), (0, 1)))(w, x)
    np.testing.assert_allclose(gx, gxr, atol=5e-5)
    assert set(gp) == {"phi", "phi_res", "alpha", "b_pre", "b_post", "b_res"}
    for name, g in gp.items():
        want = gr[f"hc_mlp.{name}"]
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(
            g.reshape(want.shape), want, atol=5e-5, err_msg=name)


def test_the_whole_model_matches_the_reference_in_loss_and_gradients():
    from flax.core import meta

    from benchmarks.suite import program as suite_program

    config, job = xing4_toy.CONFIG, xing4_toy.JOB
    lm, loss_fn = arch.program(config, job, None)
    tokens = jax.random.randint(
        jax.random.PRNGKey(23), (2, job["sequence"] + 1), 0,
        config["vocab_size"])
    template = meta.unbox(jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]))
    params = suite_program.place_weights(template, config, SEED)
    w = reference.all_leaves(config, SEED, jnp.float32)

    def program(p):
        loss, counted = loss_fn(p, lm.apply, {"tokens": tokens})
        return loss, counted

    (loss, counted), gp = jax.jit(
        jax.value_and_grad(program, has_aux=True))(params)
    want, gr = jax.jit(jax.value_and_grad(
        lambda w: reference.batch_loss(w, tokens, config, jnp.float32)))(w)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    # One expert layer: no row dropped, and the one buffer it has sufficed.
    assert counted.shape == (1, 4) and not counted[0, 2:].any()
    for path, g in jax.tree_util.tree_flatten_with_path(gp)[0]:
        name = arch.leaf_name(path)
        scale = max(float(jnp.abs(gr[name]).max()), 1e-3)
        np.testing.assert_allclose(
            g.reshape(gr[name].shape) / scale, gr[name] / scale, atol=2e-3,
            err_msg=name)


def test_layers_of_two_kinds_and_what_the_configuration_refuses():
    from covalent_tpu_plugin.models.moe import RoutedExpertsConfig

    routed = RoutedExpertsConfig(n_experts=8, top_k=2, d_ff=32, held=(0, 2))
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=16,
        dtype=jnp.float32, scan_layers=False, attention="reference",
        layer_kinds=("dense", "moe"), routed=routed, mlp_gated=True,
        mlp_activation="silu")
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    assert "mlp" in params["layer_0"] and "moe" in params["layer_1"]
    assert set(params["layer_0"]["mlp"]) == {"wg", "wi", "wo"}
    assert params["layer_1"]["moe"]["experts"]["wg"].value.shape == (2, 32, 32)
    with pytest.raises(ValueError, match="scan_layers"):
        TransformerConfig(n_layers=2, layer_kinds=("dense", "moe"),
                          routed=routed)
    with pytest.raises(ValueError, match="layer_kinds"):
        TransformerConfig(n_layers=2, layer_kinds=("dense",), routed=routed)
    with pytest.raises(ValueError, match="routed or moe_experts"):
        TransformerConfig(n_layers=1, layer_kinds=("moe",), scan_layers=False)
