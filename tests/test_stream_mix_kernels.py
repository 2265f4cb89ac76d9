"""The stream mixing's Pallas kernels (``ops/stream_mix.py``) against the
jnp passes they replace (``models/streams.py``), forward and through
``jax.vjp``, interpreted on the CPU at a toy shape that tiles: four streams,
a width of 256, two token tiles."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from covalent_tpu_plugin.models import TransformerConfig, streams
from covalent_tpu_plugin.models.streams import (
    ResidualStreamsConfig,
    StreamMix,
)
from covalent_tpu_plugin.ops import stream_mix

N, WIDTH = 4, 256
K = N * (N + 2)
DTYPES = [jnp.bfloat16, jnp.float32]
#: What a bfloat16 output's rounding allows; float32 runs hold 2e-5.
TOLERANCE = {jnp.bfloat16: 2e-2, jnp.float32: 2e-5}


def _normal(seed, shape, dtype=jnp.float32, scale=1.0):
    return (scale * jax.random.normal(
        jax.random.PRNGKey(seed), shape)).astype(dtype)


def _streams(dtype, batch=2):
    """Two token tiles a row."""
    tile = stream_mix.token_tile(
        jax.ShapeDtypeStruct((batch, N, 4096, WIDTH), dtype))
    x = _normal(1, (batch, N, 2 * tile, WIDTH), dtype)
    assert stream_mix.token_tile(x) == tile
    return x


def _close(got, want, dtype, scale=1.0):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOLERANCE[dtype] * scale, rtol=TOLERANCE[dtype])


def _pre_oracle(x, phi, alpha0, b_pre):
    raw, mean_square = streams._project(x, phi)
    h = raw * jax.lax.rsqrt(mean_square + stream_mix.NORM_EPS)[:, None, :]
    pre = jax.nn.sigmoid(alpha0 * h[:, :N] + b_pre[None, :, None])
    return raw, mean_square, streams._pre_mix(pre, x), x


def _pre_operands(dtype):
    x = _streams(dtype)
    phi = _normal(2, (N, WIDTH, K), scale=0.05)
    return x, phi, jnp.float32(0.7), jnp.asarray([0.3, -0.2, 0.1, 0.0])


def _res_operands(dtype):
    x = _streams(dtype)
    batch, _, seq, _ = x.shape
    res = jax.nn.softmax(_normal(3, (batch, N, N, seq)), axis=2)
    post = 2.0 * jax.nn.sigmoid(_normal(4, (batch, N, seq)))
    return res, post, x, _normal(5, (batch, seq, WIDTH), dtype)


@pytest.mark.parametrize("dtype", DTYPES)
def test_hc_pre_fwd_matches_the_project_and_pre_mix_passes(dtype):
    operands = _pre_operands(dtype)
    got = stream_mix.pre(*operands)
    want = _pre_oracle(*operands)
    # The products sum 4 x 256 terms of size 0.05.
    for g, w, scale in zip(got, want, (1.0, 1.0, 1.0, 0.0)):
        _close(g, w, jnp.float32 if w.dtype == jnp.float32 else dtype, scale)
    assert got[2].dtype == jnp.float32  # u leaves in float32


@pytest.mark.parametrize("dtype", DTYPES)
def test_hc_pre_bwd_matches_the_written_out_transposes(dtype):
    """Every cotangent at once: the products', the mean square's, ``du`` and
    the streams' own that ``res_mix`` sends back, whose sum with the three
    terms of ``dX`` is rounded once."""
    operands = _pre_operands(dtype)
    x = operands[0]
    batch, _, seq, _ = x.shape
    cotangents = (
        _normal(6, (batch, K, seq)), _normal(7, (batch, seq)),
        _normal(8, (batch, seq, WIDTH), scale=0.1), _normal(9, x.shape, dtype))
    got = jax.vjp(stream_mix.pre, *operands)[1](cotangents)
    want = jax.vjp(_pre_oracle, *operands)[1](cotangents)
    dx, d_phi, d_alpha0, d_b_pre = got
    # dX: the oracle rounds each of its four terms, the kernel their sum.
    _close(dx, want[0], dtype, scale=4.0)
    # d_phi sums over both token tiles and both rows of the batch.
    _close(d_phi, want[1], dtype, scale=float(jnp.abs(want[1]).max()))
    _close(d_alpha0, want[2], dtype, scale=float(jnp.abs(want[2])))
    _close(d_b_pre, want[3], dtype, scale=float(jnp.abs(want[3]).max()))
    assert float(jnp.abs(want[2])) > 1.0  # the gate's gradient is read


def test_d_phi_is_accumulated_over_more_than_one_token_tile():
    """``d_phi`` is linear in the tokens where only the products' cotangent
    arrives: the whole row's is the sum of its two tiles' own."""
    x, phi, alpha0, b_pre = _pre_operands(jnp.float32)
    tile = stream_mix.token_tile(x)
    assert x.shape[2] == 2 * tile
    d_raw = _normal(6, (x.shape[0], K, x.shape[2]))

    def d_phi(tokens):
        part = x[:, :, tokens]
        cotangents = (
            d_raw[:, :, tokens], jnp.zeros(part.shape[::2]),
            jnp.zeros(part.shape[:1] + part.shape[2:]), jnp.zeros_like(part))
        assert stream_mix.token_tile(part) in (tile, tile // 2)
        return jax.vjp(stream_mix.pre, part, phi, alpha0, b_pre)[1](
            cotangents)[1]

    whole = d_phi(slice(None))
    halves = d_phi(slice(0, tile)), d_phi(slice(tile, None))
    assert float(jnp.abs(halves[1]).max()) > 1.0
    np.testing.assert_allclose(
        whole, halves[0] + halves[1], atol=2e-4, rtol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
def test_hc_res_fwd_matches_the_res_mix_pass(dtype):
    operands = _res_operands(dtype)
    _close(stream_mix.res_mix(*operands), streams._res_mix(*operands), dtype,
           scale=4.0)


@pytest.mark.parametrize("dtype", DTYPES)
def test_hc_res_bwd_matches_the_written_out_transpose(dtype):
    operands = _res_operands(dtype)
    d_out = _normal(10, operands[2].shape, dtype)
    got = jax.vjp(stream_mix.res_mix, *operands)[1](d_out)
    want = jax.vjp(streams._res_mix, *operands)[1](d_out)
    for g, w in zip(got, want):
        # The row sums run over 256 products of size 1; float32 in both.
        scale = 16.0 if w.dtype == jnp.float32 else 4.0
        _close(g, w, jnp.float32 if w.dtype == jnp.float32 else dtype, scale)


def _module(width, seq, dtype):
    config = TransformerConfig(
        vocab_size=32, d_model=width, n_layers=1, n_heads=2, d_ff=64,
        max_seq=seq, dtype=dtype, streams=ResidualStreamsConfig())
    module = StreamMix(config)
    x = _normal(11, (1, N, seq, width), dtype)
    params = meta.unbox(module.init(
        jax.random.PRNGKey(0), x, method=StreamMix.before)["params"])
    # Away from the start values, so that every coefficient matters.
    params["alpha"] = jnp.asarray([0.7, -0.4, 0.6])
    params["b_pre"] = jnp.asarray([0.3, -0.2, 0.1, 0.0])
    return module, params, x


def _sublayer(u):
    return (jnp.tanh(u) * 0.5 + u[..., ::-1] * 0.25)


def _mixing(module, x_dtype):
    def run(params, x):
        u, through, coefficients = module.apply(
            {"params": params}, x, method=StreamMix.before)
        y = _sublayer(u).astype(x_dtype)
        return module.apply(
            {"params": params}, through, y, coefficients,
            method=StreamMix.mix), u
    return run


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_sublayers_mixing_runs_the_kernels_and_matches_the_jnp_path(
        dtype, monkeypatch):
    """The module end to end where the shape tiles: the four kernels are in
    the program, ``u`` leaves float32, and outputs and every leaf's gradient
    read as the jnp passes' do (``token_tile`` patched away for those)."""
    module, params, x = _module(WIDTH, 64, dtype)
    assert stream_mix.token_tile(x) == 64
    weight = _normal(12, x.shape)
    run = _mixing(module, dtype)

    def loss(params, x):
        out, u = run(params, x)
        return jnp.sum(out.astype(jnp.float32) * weight)

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x))
    for kernel in ("hc_pre_fwd", "hc_res_fwd", "hc_res_bwd", "hc_pre_bwd"):
        assert kernel in text, kernel
    out, u = run(params, x)
    grads = jax.grad(loss, (0, 1))(params, x)
    monkeypatch.setattr(stream_mix, "token_tile", lambda x: None)
    assert "hc_pre_fwd" not in str(jax.make_jaxpr(run)(params, x))
    want_out, want_u = run(params, x)
    want = jax.grad(loss, (0, 1))(params, x)
    assert u.dtype == jnp.float32 and out.dtype == dtype
    _close(u, want_u, jnp.float32)
    _close(out, want_out, dtype, scale=4.0)
    _close(grads[1], want[1], dtype, scale=4.0)
    assert set(grads[0]) == {
        "phi", "phi_res", "alpha", "b_pre", "b_post", "b_res"}
    for name, g in grads[0].items():
        size = float(jnp.abs(want[0][name]).max())
        assert size > 0, name
        _close(g, want[0][name], dtype, scale=size)


@pytest.mark.parametrize("width,seq", [(192, 64), (256, 72), (256, 8)])
def test_a_shape_that_does_not_tile_takes_the_jnp_path(width, seq):
    """A width that is no multiple of 128, a sequence that no tile divides:
    no kernel in the program, and the numbers of the jnp passes."""
    module, params, x = _module(width, seq, jnp.float32)
    assert stream_mix.token_tile(x) is None
    run = _mixing(module, jnp.float32)
    assert "pallas_call" not in str(jax.make_jaxpr(run)(params, x))
    out, u = run(params, x)
    assert u.dtype == jnp.float32

    def by_hand(params, x):
        phi = jnp.concatenate([params["phi"], params["phi_res"]], axis=-1)
        raw, mean_square = streams._project(x, phi)
        h = raw * jax.lax.rsqrt(mean_square + 1e-6)[:, None, :]
        pre = jax.nn.sigmoid(
            params["alpha"][0] * h[:, :N] + params["b_pre"][None, :, None])
        return streams._pre_mix(pre, x)

    np.testing.assert_array_equal(u, by_hand(params, x))


def test_token_tile_by_shape_and_dtype():
    shape = jax.ShapeDtypeStruct
    # The cell's streams: the largest tile of all four that is under 4 MiB.
    assert stream_mix.token_tile(
        shape((1, 4, 8192, 3584), jnp.bfloat16)) == 128
    assert stream_mix.token_tile(shape((2, 4, 1024, 256), jnp.float32)) == 512
    assert stream_mix.token_tile(shape((2, 4, 96, 256), jnp.float32)) == 32
    assert stream_mix.token_tile(shape((2, 4, 1024, 256), jnp.float16)) is None
    assert stream_mix.token_tile(shape((2, 4, 1024, 64), jnp.float32)) is None
    assert stream_mix.token_tile(shape((2, 12, 1024, 256), jnp.float32)) is None
