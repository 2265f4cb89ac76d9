"""Fused row-tiled cross-entropy (ops/xent.py) vs the dense path.

Exactness contract: at f32 inputs the fused loss and BOTH gradients match
a dense logits + stable log-softmax reference to float tolerance (the
row tiles are the same math, the sums over rows reassociated); through
the model at bf16 the comparison is against the standard `lm_loss` path
within bf16-matmul tolerance (the fused path intentionally runs the
lm_head matmul with bf16 inputs on the MXU-native path, where the
logits_dtype=f32 default upcasts first).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from covalent_tpu_plugin.ops.xent import fused_cross_entropy


def _ref(x, w, labels):
    logits = jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
    return jnp.mean(lse - lab)


def _inputs(T, d, V):
    x = jax.random.normal(jax.random.PRNGKey(0), (T, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)
    return x, w, labels


def _head_matmuls(jaxpr, vocab):
    """dot_generals with a vocabulary-sized operand, sub-jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general" and any(
            vocab in v.aval.shape for v in eqn.invars
        )
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _head_matmuls(sub, vocab)
    return n


# 256 is the whole vocabulary (one tile), 1 its smallest divisor (a row a tile).
@pytest.mark.parametrize("chunk", [32, 64, 256, 1])
def test_fused_xent_matches_dense(chunk):
    T, d, V = 48, 32, 256
    x, w, labels = _inputs(T, d, V)
    lf = fused_cross_entropy(x, w, labels, chunk)
    lr = _ref(x, w, labels)
    assert abs(float(lf) - float(lr)) < 1e-5


@pytest.mark.parametrize("chunk", [64, 256, 1])
def test_fused_xent_grads_match_dense(chunk):
    T, d, V = 48, 32, 256
    x, w, labels = _inputs(T, d, V)
    dxf, dwf = jax.grad(
        lambda x, w: fused_cross_entropy(x, w, labels, chunk), argnums=(0, 1)
    )(x, w)
    dxr, dwr = jax.grad(
        lambda x, w: _ref(x, w, labels), argnums=(0, 1)
    )(x, w)
    assert float(jnp.abs(dxf - dxr).max()) < 1e-6
    assert float(jnp.abs(dwf - dwr).max()) < 1e-6


@pytest.mark.parametrize("differentiated, matmuls", [(True, 3), (False, 1)])
def test_fused_xent_makes_each_score_once(differentiated, matmuls):
    """The loss and both gradients take three head-sized matmuls (scores,
    dx, dW), the loss alone one: no score is recomputed."""
    T, d, V, chunk = 48, 32, 256, 64
    x = jnp.zeros((T, d))
    w = jnp.zeros((d, V))
    labels = jnp.zeros((T,), jnp.int32)

    def loss(x, w):
        return fused_cross_entropy(x, w, labels, chunk)

    fn = jax.value_and_grad(loss, argnums=(0, 1)) if differentiated else loss
    assert _head_matmuls(jax.make_jaxpr(fn)(x, w).jaxpr, V) == matmuls


def test_fused_xent_cotangent_scales_gradients():
    """The gradients are made in the forward rule for a cotangent of 1;
    any other has to scale both, exactly."""
    T, d, V, chunk = 48, 32, 256, 64
    x, w, labels = _inputs(T, d, V)

    def grads(scale):
        return jax.grad(
            lambda x, w: scale * fused_cross_entropy(x, w, labels, chunk),
            argnums=(0, 1),
        )(x, w)

    (dx, dw), (dx_s, dw_s) = grads(1.0), grads(2.5)
    assert float(jnp.abs(dx).max()) > 0 and float(jnp.abs(dw).max()) > 0
    assert bool(jnp.array_equal(dx_s, 2.5 * dx))
    assert bool(jnp.array_equal(dw_s, 2.5 * dw))


# Tiles of 8 rows at chunk 64 of 256: neither T is a multiple of 8.
@pytest.mark.parametrize("T", [50, 53])
def test_fused_xent_padded_rows_carry_no_weight(T):
    d, V, chunk = 32, 256, 64
    x, w, labels = _inputs(T, d, V)
    lf, (dxf, dwf) = jax.value_and_grad(
        lambda x, w: fused_cross_entropy(x, w, labels, chunk), argnums=(0, 1)
    )(x, w)
    lr, (dxr, dwr) = jax.value_and_grad(
        lambda x, w: _ref(x, w, labels), argnums=(0, 1)
    )(x, w)
    assert abs(float(lf) - float(lr)) < 1e-5
    assert abs(float(fused_cross_entropy(x, w, labels, chunk)) - float(lr)) < 1e-5
    assert dxf.shape == x.shape
    assert float(jnp.abs(dxf - dxr).max()) < 1e-6
    assert float(jnp.abs(dwf - dwr).max()) < 1e-6


def test_fused_xent_bf16_inputs_match_dense_bf16():
    """bfloat16 features against a float32 head, as a train step has them:
    the tolerances of test_lm_loss_fused_path_matches_standard."""
    T, d, V, chunk = 48, 32, 256, 64
    x, w, labels = _inputs(T, d, V)
    x = x.astype(jnp.bfloat16)
    lf, gf = jax.value_and_grad(
        lambda x, w: fused_cross_entropy(x, w, labels, chunk), argnums=(0, 1)
    )(x, w)
    lr, gr = jax.value_and_grad(
        lambda x, w: _ref(x, w, labels), argnums=(0, 1)
    )(x, w)
    assert abs(float(lf) - float(lr)) < 2e-3
    for f, r in zip(gf, gr):
        assert f.dtype == r.dtype
        f, r = f.astype(jnp.float32), r.astype(jnp.float32)
        assert float(jnp.abs(f - r).max() / jnp.abs(r).max()) < 0.05


def test_fused_xent_takes_a_chunk_that_does_not_divide_the_vocabulary():
    """The chunk bounds the live scores and nothing else: an eighth of a
    100,352-word vocabulary is 49 x 256 under a chunk of 8,192."""
    from covalent_tpu_plugin.ops.xent import _row_tiles

    assert _row_tiles(16384, 12544, 8192) == (8192, 2)
    x, w, labels = _inputs(48, 32, 100)
    lf, gf = jax.value_and_grad(
        lambda x, w: fused_cross_entropy(x, w, labels, 64), argnums=(0, 1)
    )(x, w)
    lr, gr = jax.value_and_grad(
        lambda x, w: _ref(x, w, labels), argnums=(0, 1)
    )(x, w)
    assert abs(float(lf) - float(lr)) < 1e-5
    for f, r in zip(gf, gr):
        assert float(jnp.abs(f - r).max()) < 1e-5


def test_lm_loss_fused_path_matches_standard():
    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
    from covalent_tpu_plugin.models.train import lm_loss

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=2, d_ff=128,
        max_seq=32, scan_layers=False,
    )
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 17), 0, 128)
    params = model.init(jax.random.PRNGKey(4), tokens[:, :-1])["params"]
    batch = {"tokens": tokens}
    l_std = float(lm_loss(params, model.apply, batch))
    l_fused = float(lm_loss(params, model.apply, batch, vocab_chunk=32))
    assert abs(l_std - l_fused) < 2e-3
    g_std = jax.grad(lambda p: lm_loss(p, model.apply, batch))(params)
    g_fused = jax.grad(
        lambda p: lm_loss(p, model.apply, batch, vocab_chunk=32)
    )(params)
    rel = jax.tree_util.tree_map(
        lambda a, b: float(
            jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9)
        ),
        g_std, g_fused,
    )
    assert max(jax.tree_util.tree_leaves(rel)) < 0.05


def test_fused_xent_trains():
    """A few adamw steps through the fused path actually reduce loss."""
    import optax

    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
    from covalent_tpu_plugin.models.data import synthetic_lm_batch
    from covalent_tpu_plugin.models.train import TrainState, lm_loss

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq=33, scan_layers=False,
    )
    model = TransformerLM(cfg)
    tokens0 = jnp.asarray(
        synthetic_lm_batch(8, 33, 64, seed=0)["tokens"]
    )
    params = model.init(jax.random.PRNGKey(0), tokens0[:, :-1])["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adamw(1e-2)
    )

    @jax.jit
    def step(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(
                p, state.apply_fn, {"tokens": tokens}, vocab_chunk=32
            )
        )(state.params)
        return state.apply_gradients(grads=grads), loss

    losses = []
    for i in range(30):
        tokens = jnp.asarray(
            synthetic_lm_batch(8, 33, 64, seed=1 + i)["tokens"]
        )
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


def test_fused_xent_under_sharded_train_step():
    """The fused loss composes with the mesh story: a dp x tp sharded
    train step (lm_head vocab-sharded over tensor) produces the same
    loss and gradient norm as the standard logits path."""
    import optax

    from covalent_tpu_plugin.models import TransformerConfig, TransformerLM
    from covalent_tpu_plugin.models.data import synthetic_lm_batch
    from covalent_tpu_plugin.models.train import (
        lm_loss,
        make_sharded_train_state,
        make_train_step,
    )
    from covalent_tpu_plugin.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(data=2, tensor=4))
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq=33, scan_layers=False,
    )
    model = TransformerLM(cfg)
    tokens = jnp.asarray(synthetic_lm_batch(8, 33, 128, seed=0)["tokens"])
    state, shardings = make_sharded_train_state(
        model, optax.adamw(1e-2), jax.random.PRNGKey(0), tokens[:, :-1],
        mesh,
    )

    def loss_fused(params, apply_fn, batch):
        return lm_loss(params, apply_fn, batch, vocab_chunk=32)

    step_std = make_train_step(lm_loss, mesh, shardings, donate_state=False)
    step_fused = make_train_step(
        loss_fused, mesh, shardings, donate_state=False
    )
    # No `with mesh:` around the jitted steps: an ambient mesh makes flax
    # apply the params' *logical* axis names as sharding constraints during
    # tracing (Partitioned.unbox), which physical meshes reject; the steps
    # carry explicit in/out shardings and need no ambient mesh.
    _, m_std = step_std(state, {"tokens": tokens})
    _, m_fused = step_fused(state, {"tokens": tokens})
    assert abs(float(m_std["loss"]) - float(m_fused["loss"])) < 5e-3
    gs, gf = float(m_std["grad_norm"]), float(m_fused["grad_norm"])
    assert abs(gs - gf) / gs < 0.02
