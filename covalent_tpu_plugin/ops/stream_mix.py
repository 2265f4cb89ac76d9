"""The stream mixing's four passes over the residual streams
(``models/streams.py``) as Pallas kernels: one sweep over token tiles a
pass, every operand the size of the streams read from HBM once, the
arithmetic on a tile in VMEM in float32.

=============  =====================================  =======================
kernel         reads                                  writes
=============  =====================================  =======================
``hc_pre_fwd``  ``X``, ``phi``, the gate               ``u`` (float32), stats
``hc_res_fwd``  ``X``, ``y``, coefficients             ``X'``
``hc_res_bwd``  ``dX'``, ``X``, ``y``, coefficients    ``dX``, ``dy``, row sums
``hc_pre_bwd``  ``X``, ``du``, the ``dX`` that came    ``dX`` (summed in
                through ``res_mix``, stats and their   float32, rounded once),
                cotangents, ``phi``, the gate          ``d_phi``, row sums
=============  =====================================  =======================

``X`` is ``(B, n, S, C)``, a tile ``X[:, :, ts, :]`` of all n streams with
the width whole.  Everything a token's size rides token-major, ``(B, S,
128)`` float32 with one quantity a lane, so that a token's coefficient is a
column that broadcasts over the width: *stats* are ``vec(X) phi`` in lanes
``[0, k)`` and ``mean(vec(X)^2)`` in lane ``k``; *coefficients* ``H_res[i,
j]`` in lane ``i n + j`` and ``H_post[i]`` in lane ``n^2 + i``; the *gate*
is ``alpha[0]`` and ``b_pre`` over lanes ``[0, n)``.  ``phi (n, C, k)`` is
padded to 128 columns and stays whole in VMEM.  ``H_pre`` needs only a
token's own stats, so both ``hc_pre_*`` kernels make it themselves.

``pre`` and ``res_mix`` are the two ``custom_vjp`` ops on top.  ``pre``
also returns ``X`` itself: ``res_mix`` takes that output, so its cotangent
arrives in ``pre``'s backward rule as an argument and ``hc_pre_bwd`` adds it
in the same sweep; fed the input instead, autodiff would add the two ``dX``
in a pass of its own.

The kernels engage where ``token_tile`` finds a tile (the width a multiple
of 128, the sequence of the tile); ``models/streams.py`` keeps the jnp
passes for every other shape, and the tests hold each kernel to them.
Compiled on a TPU, interpreted on an explicit CPU platform
(``attention.default_interpret``, asked at call time).  A ``pallas_call`` is
opaque to sharding propagation: under a sharded ``jit`` its operands are
gathered whole (``attention.flash_attention_sharded`` says how that is
cured); one chip's share runs as it is.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention

#: ``x~ = vec(X) rsqrt(mean(vec(X)^2) + NORM_EPS)``.
NORM_EPS = 1e-6

_LANES = 128
#: A tile of all n streams is at most this large.
_TILE_BYTES = 4 << 20
#: Rows of a tile the elementwise work holds at a time.
_ROWS = 64
_F32 = jnp.float32


def token_tile(x) -> int | None:
    """Tokens in one kernel step for streams ``x (B, n, S, C)``, or None
    where the shape does not tile and the jnp passes run."""
    if x.ndim != 4 or x.dtype not in (jnp.bfloat16, jnp.float32):
        return None
    _, n, seq, width = x.shape
    if width % _LANES or n * (n + 2) >= _LANES:
        return None
    tile = 512
    while tile >= 16:
        if (seq % tile == 0
                and n * tile * width * x.dtype.itemsize <= _TILE_BYTES):
            return tile
        tile //= 2
    return None


# Token-major packing: small XLA work around the kernels.


def _lanes(a):
    """Pad the last axis to the 128 lanes."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, _LANES - a.shape[-1])])


def _gate(alpha0, b_pre):
    return _lanes(jnp.stack(
        [jnp.broadcast_to(alpha0, b_pre.shape), b_pre]).astype(_F32))


def _coefficients(res, post):
    batch, n, _, seq = res.shape
    return _lanes(jnp.swapaxes(jnp.concatenate(
        [res.reshape(batch, n * n, seq), post], axis=1), 1, 2))


def _row_chunks(tile: int, body):
    """``body(rows of the tile)`` for every run of ``_ROWS`` rows."""
    if _ROWS >= tile:
        body(slice(None))
        return

    def step(c, carry):
        body(pl.ds(pl.multiple_of(c * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, tile // _ROWS, step, None)


def _column(a, lane: int):
    return a[:, lane:lane + 1]


def _place(columns, like):
    """``(rows, 1)`` columns by lane -> one ``(rows, 128)`` array, zero in
    every other lane."""
    lane = jax.lax.broadcasted_iota(jnp.int32, like.shape, 1)
    out = jnp.zeros_like(like)
    for at, column in columns.items():
        out = jnp.where(lane == at, column, out)
    return out


def _row_sum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


def _params(semantics, blocks_bytes: int, scratch_bytes: int = 0):
    # Two buffers a block, and room for the compiler's own temporaries.
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=min(
            2 * blocks_bytes + scratch_bytes + (24 << 20), 100 << 20))


def _nbytes(*shaped) -> int:
    return sum(jnp.dtype(dtype).itemsize * math.prod(shape)
               for shape, dtype in shaped)


# Before the sublayer.


def _pre_fwd_kernel(x_ref, phi_ref, gate_ref, u_ref, stats_ref, *, k):
    n, tile, width = x_ref.shape[1:]
    stats_ref[0] = sum(
        jnp.dot(x_ref[0, j], phi_ref[j], preferred_element_type=_F32)
        for j in range(n))

    def chunk(at):
        xs = [x_ref[0, j, at, :].astype(_F32) for j in range(n)]
        mean_square = sum(_row_sum(x * x) for x in xs) / (n * width)
        raw = stats_ref[0, at, :]
        pre = jax.nn.sigmoid(
            gate_ref[0:1, :] * (raw * jax.lax.rsqrt(mean_square + NORM_EPS))
            + gate_ref[1:2, :])
        lane = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 1)
        stats_ref[0, at, :] = jnp.where(lane == k, mean_square, raw)
        u_ref[0, at, :] = sum(_column(pre, j) * xs[j] for j in range(n))

    _row_chunks(tile, chunk)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _pre_fwd_call(x, phi, gate, *, k, interpret):
    batch, n, seq, width = x.shape
    tile = token_tile(x)
    small = pl.BlockSpec((1, tile, _LANES), lambda b, t: (b, t, 0))
    blocks = _nbytes(((n, tile, width), x.dtype), (phi.shape, phi.dtype),
                     ((tile, width), _F32), ((tile, _LANES), _F32))
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, k=k),
        name="hc_pre_fwd",
        grid=(batch, seq // tile),
        in_specs=[
            pl.BlockSpec((1, n, tile, width), lambda b, t: (b, 0, t, 0)),
            pl.BlockSpec(phi.shape, lambda b, t: (0, 0, 0)),
            pl.BlockSpec(gate.shape, lambda b, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile, width), lambda b, t: (b, t, 0)), small],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, width), _F32),
            jax.ShapeDtypeStruct((batch, seq, _LANES), _F32),
        ],
        compiler_params=_params(("parallel", "parallel"), blocks),
        cost_estimate=pl.CostEstimate(
            flops=2 * x.size * (_LANES + 2), transcendentals=0,
            bytes_accessed=x.nbytes + 4 * x.size // n),
        interpret=interpret,
    )(x, phi, gate)


def _pre_bwd_kernel(x_ref, du_ref, dx_in_ref, stats_ref, d_stats_ref,
                    phi_ref, gate_ref, dx_ref, d_phi_ref, d_gate_ref,
                    d_raw_ref, token_ref, product_ref, *, k):
    n, tile, width = x_ref.shape[1:]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        d_phi_ref[...] = jnp.zeros_like(d_phi_ref)

    def coefficients(at):
        du = du_ref[0, at, :]
        stats = stats_ref[0, at, :]
        d_stats = d_stats_ref[0, at, :]
        d_pre = _place(
            {j: _row_sum(du * x_ref[0, j, at, :].astype(_F32))
             for j in range(n)}, stats)
        scale = jax.lax.rsqrt(_column(stats, k) + NORM_EPS)
        h = stats * scale
        pre = jax.nn.sigmoid(gate_ref[0:1, :] * h + gate_ref[1:2, :])
        dz = d_pre * pre * (1.0 - pre)  # zero past lane n, as d_pre is
        dh = gate_ref[0:1, :] * dz
        d_raw_ref[at, :] = d_stats + dh * scale
        d_mean_square = _column(d_stats, k) - 0.5 * _row_sum(
            dh * stats) * scale * scale * scale
        lane = jax.lax.broadcasted_iota(jnp.int32, stats.shape, 1)
        # Lane k: what alpha[0] is owed, and the norm's term a token.
        d_gate_ref[0, at, :] = jnp.where(lane == k, _row_sum(dz * h), dz)
        token_ref[at, :] = jnp.where(
            lane == k, (2.0 / (n * width)) * d_mean_square, pre)

    _row_chunks(tile, coefficients)
    d_raw = d_raw_ref[...]
    # (k rounded up, tile): the rows of d_phi, lane k's and the padding's
    # with them; the caller keeps the first k.
    d_raw_t = d_raw.T[:d_phi_ref.shape[1]].astype(x_ref.dtype)
    d_raw = d_raw.astype(x_ref.dtype)
    for j in range(n):
        d_phi_ref[j] += jnp.dot(
            d_raw_t, x_ref[0, j], preferred_element_type=_F32)
        product_ref[...] = jax.lax.dot_general(
            d_raw, phi_ref[j], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)

        def chunk(at, j=j):
            token = token_ref[at, :]
            dx_ref[0, j, at, :] = (
                product_ref[at, :]
                + _column(token, k) * x_ref[0, j, at, :].astype(_F32)
                + _column(token, j) * du_ref[0, at, :]
                + dx_in_ref[0, j, at, :].astype(_F32)
            ).astype(dx_ref.dtype)

        _row_chunks(tile, chunk)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _pre_bwd_call(x, du, dx_in, stats, d_stats, phi, gate, *, k, interpret):
    batch, n, seq, width = x.shape
    tile = token_tile(x)
    k_rows = -(-k // 16) * 16
    streams = pl.BlockSpec((1, n, tile, width), lambda b, t: (b, 0, t, 0))
    small = pl.BlockSpec((1, tile, _LANES), lambda b, t: (b, t, 0))
    blocks = _nbytes(
        ((3 * n, tile, width), x.dtype), (phi.shape, phi.dtype),
        ((tile, width), _F32), ((n, k_rows, width), _F32),
        ((3, tile, _LANES), _F32))
    scratch = _nbytes(((tile, width), _F32), ((2, tile, _LANES), _F32))
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, k=k),
        name="hc_pre_bwd",
        grid=(batch, seq // tile),
        in_specs=[
            streams,
            pl.BlockSpec((1, tile, width), lambda b, t: (b, t, 0)),
            streams, small, small,
            pl.BlockSpec(phi.shape, lambda b, t: (0, 0, 0)),
            pl.BlockSpec(gate.shape, lambda b, t: (0, 0)),
        ],
        out_specs=[
            streams,
            pl.BlockSpec((n, k_rows, width), lambda b, t: (0, 0, 0)),
            small,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((n, k_rows, width), _F32),
            jax.ShapeDtypeStruct((batch, seq, _LANES), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile, _LANES), _F32),  # d_raw, every term in
            pltpu.VMEM((tile, _LANES), _F32),  # H_pre; lane k the norm's
            pltpu.VMEM((tile, width), _F32),   # d_raw phi^T of one stream
        ],
        # The cotangent that came through res_mix is dead after this.
        input_output_aliases={2: 0},
        compiler_params=_params(("arbitrary", "arbitrary"), blocks, scratch),
        cost_estimate=pl.CostEstimate(
            flops=2 * x.size * (_LANES + k_rows + 4), transcendentals=0,
            bytes_accessed=3 * x.nbytes + du.nbytes),
        interpret=interpret,
    )(x, du, dx_in, stats, d_stats, phi, gate)


@jax.custom_vjp
def pre(x, phi, alpha0, b_pre):
    """``X (B, n, S, C)``, the joined ``phi (n, C, k)``, ``alpha[0]`` and
    ``b_pre (n)`` -> ``vec(X) phi`` as ``(B, k, S)`` and ``mean(vec(X)^2)``
    as ``(B, S)``, both float32; ``u = sum_j H_pre[j] X[j]`` as ``(B, S,
    C)`` float32, with ``H_pre = sigmoid(alpha[0] x~ phi_pre + b_pre)``;
    and ``X`` itself, for ``res_mix`` (the module's docstring says why).
    ``token_tile(x)`` must have found a tile."""
    return _pre_fwd(x, phi, alpha0, b_pre)[0]


def _pre_operands(x, phi, alpha0, b_pre):
    return dict(
        phi=_lanes(phi.astype(x.dtype)), gate=_gate(alpha0, b_pre),
        k=phi.shape[-1], interpret=attention.default_interpret())


def _pre_fwd(x, phi, alpha0, b_pre):
    k = phi.shape[-1]
    u, stats = _pre_fwd_call(x, **_pre_operands(x, phi, alpha0, b_pre))
    raw = jnp.swapaxes(stats[..., :k], 1, 2)
    return (raw, stats[..., k], u, x), (x, phi, alpha0, b_pre, stats)


def _pre_bwd(saved, cotangents):
    x, phi, alpha0, b_pre, stats = saved
    d_raw, d_mean_square, du, dx_in = cotangents
    n, _, k = phi.shape
    d_stats = _lanes(jnp.concatenate(
        [jnp.swapaxes(d_raw, 1, 2), d_mean_square[..., None]], axis=-1))
    dx, d_phi, d_gate = _pre_bwd_call(
        x, du, dx_in, stats, d_stats, **_pre_operands(x, phi, alpha0, b_pre))
    return (
        dx, jnp.swapaxes(d_phi[:, :k], 1, 2).astype(phi.dtype),
        jnp.sum(d_gate[..., k]).astype(alpha0.dtype),
        jnp.sum(d_gate[..., :n], axis=(0, 1)).astype(b_pre.dtype))


pre.defvjp(_pre_fwd, _pre_bwd)


# After the sublayer.


def _res_fwd_kernel(x_ref, y_ref, coefficients_ref, out_ref):
    n, tile, _ = x_ref.shape[1:]

    def chunk(at):
        c = coefficients_ref[0, at, :]
        xs = [x_ref[0, j, at, :].astype(_F32) for j in range(n)]
        y = y_ref[0, at, :].astype(_F32)
        for i in range(n):
            out_ref[0, i, at, :] = (
                sum(_column(c, i * n + j) * xs[j] for j in range(n))
                + _column(c, n * n + i) * y).astype(out_ref.dtype)

    _row_chunks(tile, chunk)


def _res_bwd_kernel(d_ref, x_ref, y_ref, coefficients_ref, dx_ref, dy_ref,
                    d_coefficients_ref):
    n, tile, _ = x_ref.shape[1:]

    def chunk(at):
        c = coefficients_ref[0, at, :]
        ds = [d_ref[0, i, at, :].astype(_F32) for i in range(n)]
        xs = [x_ref[0, j, at, :].astype(_F32) for j in range(n)]
        y = y_ref[0, at, :].astype(_F32)
        sums = {i * n + j: _row_sum(ds[i] * xs[j])
                for i in range(n) for j in range(n)}
        sums.update({n * n + i: _row_sum(ds[i] * y) for i in range(n)})
        d_coefficients_ref[0, at, :] = _place(sums, c)
        for j in range(n):
            dx_ref[0, j, at, :] = sum(
                _column(c, i * n + j) * ds[i] for i in range(n)
            ).astype(dx_ref.dtype)
        dy_ref[0, at, :] = sum(
            _column(c, n * n + i) * ds[i] for i in range(n)
        ).astype(dy_ref.dtype)

    _row_chunks(tile, chunk)


def _res_specs(x, tile):
    _, n, _, width = x.shape
    return (
        pl.BlockSpec((1, n, tile, width), lambda b, t: (b, 0, t, 0)),
        pl.BlockSpec((1, tile, width), lambda b, t: (b, t, 0)),
        pl.BlockSpec((1, tile, _LANES), lambda b, t: (b, t, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _res_fwd_call(x, y, coefficients, *, interpret):
    batch, n, seq, width = x.shape
    tile = token_tile(x)
    streams, one, small = _res_specs(x, tile)
    blocks = _nbytes(((2 * n + 1, tile, width), x.dtype),
                     ((tile, _LANES), _F32))
    return pl.pallas_call(
        _res_fwd_kernel,
        name="hc_res_fwd",
        grid=(batch, seq // tile),
        in_specs=[streams, one, small],
        out_specs=streams,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(("parallel", "parallel"), blocks),
        cost_estimate=pl.CostEstimate(
            flops=2 * (n + 1) * x.size, transcendentals=0,
            bytes_accessed=2 * x.nbytes + y.nbytes),
        interpret=interpret,
    )(x, y, coefficients)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _res_bwd_call(d_out, x, y, coefficients, *, interpret):
    batch, n, seq, width = x.shape
    tile = token_tile(x)
    streams, one, small = _res_specs(x, tile)
    blocks = _nbytes(((3 * n + 2, tile, width), x.dtype),
                     ((2, tile, _LANES), _F32))
    return pl.pallas_call(
        _res_bwd_kernel,
        name="hc_res_bwd",
        grid=(batch, seq // tile),
        in_specs=[streams, streams, one, small],
        out_specs=[streams, one, small],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct((batch, seq, _LANES), _F32),
        ],
        # The streams' cotangent is dead after this.
        input_output_aliases={0: 0},
        compiler_params=_params(("parallel", "parallel"), blocks),
        cost_estimate=pl.CostEstimate(
            flops=4 * (n + 1) * x.size, transcendentals=0,
            bytes_accessed=3 * x.nbytes + 2 * y.nbytes),
        interpret=interpret,
    )(d_out, x, y, coefficients)


@jax.custom_vjp
def res_mix(res, post, x, y):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: ``res (B, n, n,
    S)``, ``post (B, n, S)``, ``X (B, n, S, C)``, ``y (B, S, C)``.
    ``token_tile(x)`` must have found a tile."""
    return _res_mix_fwd(res, post, x, y)[0]


def _res_mix_fwd(res, post, x, y):
    coefficients = _coefficients(res, post)
    out = _res_fwd_call(x, y.astype(x.dtype), coefficients,
                        interpret=attention.default_interpret())
    return out, (coefficients, x, y)


def _res_mix_bwd(saved, d_out):
    coefficients, x, y = saved
    batch, n, seq, _ = x.shape
    dx, dy, d_coefficients = _res_bwd_call(
        d_out, x, y.astype(x.dtype), coefficients,
        interpret=attention.default_interpret())
    d_coefficients = jnp.swapaxes(d_coefficients[..., :n * (n + 1)], 1, 2)
    return (d_coefficients[:, :n * n].reshape(batch, n, n, seq),
            d_coefficients[:, n * n:], dx, dy.astype(y.dtype))


res_mix.defvjp(_res_mix_fwd, _res_mix_bwd)
