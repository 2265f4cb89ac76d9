"""What a fenced checkpoint keeps (``models/transformer.py`` ``FENCE_KEEPS``):
a gated MLP's two products, the router's choice and weights and the held
experts' grouped order.

At toy widths on the CPU: a stack like ``laguna`` (a gated dense layer with
full attention, then a routed layer with a shared expert under a band) and
one like ``xing4_0`` (latent attention, four residual streams, a routed layer
by sigmoid score) take the same gradients with the names kept as with
nothing kept, and the kept grad runs the router's top-k once a routed layer
where it ran it twice, and the kept products' matmuls once; an unfenced
checkpoint lowers to the text it lowered to before the names.  At the
cell's own widths, sequence and batch, for a described (not attached) v5e,
remat's recompute in ``laguna-s.train-16k``'s step, cut to the layers that
hold each of its pairs of attention and MLP type once, sorts and multiplies
less than with nothing kept (the whole step's bytes and kernels are
``test_suite_laguna_v5e.py``'s).
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src import core as jax_core

from covalent_tpu_plugin.models import layers, moe
from covalent_tpu_plugin.models import transformer as tf
from covalent_tpu_plugin.models.latent import LatentAttentionConfig
from covalent_tpu_plugin.models.layers import YarnConfig
from covalent_tpu_plugin.models.moe import RoutedExpertsConfig
from covalent_tpu_plugin.models.streams import ResidualStreamsConfig
from covalent_tpu_plugin.models.train import lm_loss
from covalent_tpu_plugin.ops import attention
from tests.benchsuite.test_suite_laguna_v5e import _cell
from tests.benchsuite.test_suite_xing4_v5e import (  # noqa: F401 - fixtures
    no_cache,
    topo,
)

#: 1,024 pairs over a 512-row buffer: the routed layers take the bounded
#: branch of their conditional, as the cells do.
SEQ = 256


def _laguna_like(**changes) -> tf.TransformerConfig:
    types = (
        tf.AttentionType("attn_full", 4, rope_base=1e4, rope_share=0.5,
                         yarn=YarnConfig(4, 64, attention_factor=1.1),
                         gate=True),
        tf.AttentionType("attn_sliding", 6, sliding_window=64,
                         rope_base=100.0, gate=True),
    )
    return tf.TransformerConfig(**{
        **dict(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=1,
            head_dim=16, d_ff=128, max_seq=SEQ, dtype=jnp.float32,
            attention="flash", remat=True, remat_prevent_cse=True,
            scan_layers=False, mlp_gated=True, mlp_activation="silu",
            layer_kinds=("dense", "moe"), attention_types=types,
            attention_kinds=("attn_full", "attn_sliding"),
            routed=RoutedExpertsConfig(
                16, 4, 32, routed_scaling=2.5, score="softmax",
                correction_bias=False, held=(0, 2))),
        **changes})


def _xing4_like() -> tf.TransformerConfig:
    return tf.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=2, d_ff=128,
        max_seq=SEQ, dtype=jnp.float32, attention="flash", remat=True,
        remat_prevent_cse=True, scan_layers=False, mlp_gated=True,
        mlp_activation="silu", layer_kinds=("dense", "moe"),
        latent=LatentAttentionConfig(32, 32, 16, 8, 16),
        streams=ResidualStreamsConfig(),
        routed=RoutedExpertsConfig(16, 4, 32, routed_scaling=2.0,
                                   held=(0, 2)))


STACKS = {"laguna_like": _laguna_like, "xing4_like": _xing4_like}


def _grad_of(config: tf.TransformerConfig):
    """The loss's gradient and the parameters it is taken at."""
    lm = tf.TransformerLM(config)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, SEQ + 1), 0, config.vocab_size)
    params = lm.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]

    def loss(p):
        out = lm_loss(p, lm.apply, {"tokens": tokens})
        return out[0] if isinstance(out, tuple) else out

    return jax.grad(loss), params


def _calls(jaxpr, name: str) -> int:
    """Equations of the primitive ``name``, or of the kernel ``name``,
    nested ones included."""
    calls = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name or (
                eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            calls += 1
        for inner in jax_core.jaxprs_in_params(eqn.params):
            calls += _calls(inner, name)
    return calls


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_kept_products_give_the_gradients_of_a_full_recompute(
        stack, monkeypatch):
    # A function of its own each time: jax caches a trace by function.
    grad, params = _grad_of(STACKS[stack]())
    kept = jax.jit(grad)(params)
    monkeypatch.setattr(tf, "FENCE_KEEPS", ())
    grad, _ = _grad_of(STACKS[stack]())
    recomputed = jax.jit(grad)(params)
    flat = jax.tree_util.tree_leaves_with_path(kept)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(recomputed)):
        assert jnp.array_equal(a, b), jax.tree_util.keystr(path)
    assert any(jnp.any(a != 0) for _, a in flat)


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_the_kept_grad_routes_once_a_layer(stack, monkeypatch):
    config = STACKS[stack]()
    routed = config.layer_kinds.count("moe")
    grad, params = _grad_of(config)
    kept = jax.make_jaxpr(grad)(params).jaxpr
    monkeypatch.setattr(tf, "FENCE_KEEPS", ())
    grad, _ = _grad_of(config)
    recomputed = jax.make_jaxpr(grad)(params).jaxpr
    assert _calls(kept, "top_k") == routed
    assert _calls(recomputed, "top_k") == 2 * routed
    # The grouped order's sort, a routed layer.
    assert _calls(recomputed, "sort") - _calls(kept, "sort") == routed
    # A dense layer's two products, a routed layer's shared expert's two.
    dense = config.layer_kinds.count("dense")
    assert (_calls(recomputed, "dot_general")
            - _calls(kept, "dot_general")) == 2 * dense + 2 * routed
    # Attention's output is not kept: the recompute runs its kernel again.
    for grad_jaxpr in (kept, recomputed):
        assert _calls(grad_jaxpr, "flash_fwd") == 2 * config.n_layers
        assert _calls(grad_jaxpr, "flash_bwd_dkdv") == config.n_layers


def _sc2_like() -> tf.TransformerConfig:
    return tf.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=1,
        d_ff=128, max_seq=SEQ, dtype=jnp.float32, attention="flash",
        remat=True, scan_layers=False, sliding_window=64)


UNFENCED = {"sc2_like": _sc2_like,
            "xing4_like": lambda: dataclasses.replace(
                _xing4_like(), remat_prevent_cse=False)}


@pytest.mark.parametrize("stack", sorted(UNFENCED))
def test_an_unfenced_checkpoint_lowers_as_it_did_without_the_names(
        stack, monkeypatch):
    """An unfenced checkpoint (``sc2-3b.train-16k``'s) keeps nothing: the
    names lower to nothing.  Private functions are numbered in the order a
    process first traces them: the numbers go."""
    def lowered():
        grad, params = _grad_of(UNFENCED[stack]())
        text = jax.jit(grad).lower(params).as_text()
        return re.sub(r"(@[A-Za-z_]+?)_\d+\b", r"\1", text)

    named = lowered()
    unnamed = lambda x, name: x  # noqa: E731 - the parent's: no name
    for module in (layers, moe):
        monkeypatch.setattr(module, "checkpoint_name", unnamed)
    assert lowered() == named


def _one_of_each_pair(config: dict) -> dict:
    """``config`` cut to the first layer of each of its pairs of
    (attention type, MLP type): laguna's layers 0 (full, dense), 1
    (sliding, sparse) and 4 (full, sparse)."""
    pairs = list(zip(config["layer_types"], config["mlp_layer_types"]))
    keep = [i for i, pair in enumerate(pairs) if pair not in pairs[:i]]
    cut = {**config, "num_hidden_layers": len(keep)}
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        cut[key] = [config[key][i] for i in keep]
    cut["mlp_only_layers"] = [
        i for i, kind in enumerate(cut["mlp_layer_types"]) if kind == "dense"]
    return cut


def test_train_16k_step_recomputes_attention_and_the_routers_gate_alone(
        topo, no_cache, monkeypatch):
    import optax
    from flax import linen as nn
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.suite import archs
    from covalent_tpu_plugin.models.train import TrainState, make_train_step
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh
    from covalent_tpu_plugin.parallel.sharding import DEFAULT_RULES

    # The kernels ask the default backend (the CPU, here) whether to run
    # interpreted; the chip this compiles for runs them through Mosaic.
    monkeypatch.setattr(attention, "default_interpret", lambda: False)
    cell = _cell()
    config, job = _one_of_each_pair(cell["config"]), cell["traffic"]
    mesh = make_mesh(MeshPlan(**job["mesh"]), [topo.devices[0]])
    lm, loss_fn = archs.load(config).program(config, job, mesh)
    tokens = jax.ShapeDtypeStruct(
        (job["batch"], job["sequence"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec()))

    def init(rng):
        variables = lm.init(
            rng, jnp.zeros((job["batch"], job["sequence"]), jnp.int32))
        return TrainState.create(
            apply_fn=lm.apply, params=variables["params"],
            tx=optax.adamw(job["learning_rate"]))

    abstract = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abstract), mesh, list(DEFAULT_RULES))
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    state = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        for x, s in zip(leaves, jax.tree_util.tree_leaves(shardings))])
    step = make_train_step(loss_fn, mesh, shardings)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    text = compiled.as_text().splitlines()
    recompute = [line for line in text if "rematted_computation" in line]
    # The matmuls the recompute runs again are attention's q, k, v, gate
    # and out projections, a layer, and a routed layer's router gate (its
    # scores are not kept): the MLPs' products are.
    products = [re.search(r'op_name="([^"]*)"', line).group(1)
                for line in recompute if re.search(r"= \S+ convolution\(", line)]
    assert all(re.search(r"/(attention/.*_proj|moe/router/gate)/dot_general$",
                         name) for name in products), products
    routed = config["mlp_layer_types"].count("sparse")
    assert sum("/router/" in name for name in products) == routed
    assert len(products) == 5 * config["num_hidden_layers"] + routed
    # The router's choice and the held experts' order are kept: no sort.
    assert not any(" sort(" in line.split(" metadata=")[0]
                   for line in recompute)
