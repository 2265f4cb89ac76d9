"""A residual path of ``n`` streams mixed around every sublayer (manifold-
constrained hyper-connections, arXiv:2512.24880) in place of
``x + f(norm(x))``.

The state is ``X`` in R^{n x C} a token, held ``(B, n, S, C)`` so that each
stream is a whole ``(S, C)`` slab.  Around a sublayer ``F`` (attention or
MLP, its own pre-norm inside)::

    x~     = RMSNorm(vec(X))                     over n C, no learned scale
    H~pre  = a_pre  (x~ phi_pre)  + b_pre         (n)
    H~post = a_post (x~ phi_post) + b_post        (n)
    H~res  = a_res  mat(x~ phi_res) + b_res       (n x n)
    H_pre = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
    H_res = SinkhornKnopp(exp(clamp(H~res)))      doubly stochastic
    u  = sum_j H_pre[j] X[j];   y = F(u)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

The three ``phi`` are two leaves, ``phi`` (pre and post) and ``phi_res``
(its gradient is zero while the streams are equal: a doubly stochastic mix
of equal streams changes nothing), joined into one ``(n, C, n (n + 2))``
kernel (columns: pre, post, res row by row), so the coefficients cost one
pass over ``X``; the norm scales that product (``x~ phi = rsqrt(mean X^2)
(vec(X) phi)``) and ``x~`` is never written.  Coefficients live ``(B, ...,
S)`` with the tokens minor: a token's 4 x 4 matrix as the minor dims would
fill a sixty-fourth of a tile.  Coefficients, Sinkhorn and the mixes run in
float32; ``X`` is held in the activations' dtype, and ``u`` leaves in
float32: ``F`` norms its input, so ``dL/du`` is orthogonal to ``u`` and
``H_pre``'s gradient ``du . X[j]`` is what is left of a cancellation, which
a ``u`` or a ``du`` rounded to bfloat16 buries in noise while the streams
are nearly equal.

**What runs where.**  A sublayer's mixing passes over ``X`` four times:
``u`` and the coefficients' products before the sublayer, ``X'`` after it,
and the transposes of both.  Where the shape tiles
(``ops.stream_mix.token_tile``: the width a multiple of 128, the sequence
of a tile of 16 to 512 tokens, ``X`` in bfloat16 or float32) each pass is
one Pallas kernel of ``ops/stream_mix.py``, ``hc_pre_fwd``, ``hc_res_fwd``,
``hc_res_bwd``, ``hc_pre_bwd``: every operand the size of ``X`` read once,
the arithmetic in float32 on a tile in VMEM, ``H_pre`` made inside the
``hc_pre_*`` kernels, and all four terms of ``dX`` (the products', the
norm's, ``H_pre du`` and what came back through ``H_res``) summed in
float32 and rounded once.  Any other shape takes the jnp passes below, each
with its transpose written out; they are the kernels' oracle in the tests.
What is the coefficients' size, ``(B, 24, S)``, stays here under autodiff
on either path: the scaling by ``rsqrt``, ``H_post``'s sigmoid and
Sinkhorn's scan.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import stream_mix


@dataclasses.dataclass(frozen=True)
class ResidualStreamsConfig:
    n: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)
    #: start values: the dynamic part's gates, and the diagonal of ``b_res``
    #: (``b_pre``, ``b_post`` and the rest of ``b_res`` start at 0).
    alpha_init: float = 0.01
    res_diagonal_init: float = 2.0


def sinkhorn_knopp(m, iters: int, eps: float):
    """``iters`` rounds of column then row normalisation of positive
    ``(B, n, n, S)`` matrices (rows: axis 1, columns: axis 2), ``eps`` in
    each denominator.  A scan, so that the compiled step holds one round
    and not ``iters`` of them, forward and transposed, a sublayer."""

    def one_round(m, _):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=2, keepdims=True) + eps), None

    return jax.lax.scan(one_round, m, None, length=iters)[0]


# The three passes over the streams, each with its transpose written out:
# the path of a shape that does not tile (``StreamMix.before`` asks), and
# what ``ops/stream_mix.py``'s kernels are held to.  Left to autodiff, every
# product of a coefficient and a stream leaves a float32 cotangent the size
# of a stream behind (twenty of them for one sublayer's mix at n = 4);
# written out, a pass owes what it writes in the activations' dtype.  XLA
# still reads ``X`` once a fusion, about 29 times a sublayer over forward,
# remat and backward, where the kernels move it 14.5 times.


@jax.custom_vjp
def _project(x, phi):
    """``X (B, n, S, C)``, ``phi (n, C, k)`` -> ``vec(X) phi`` as ``(B, k,
    S)`` and ``mean(vec(X)^2)`` as ``(B, S)``, both float32."""
    raw = jnp.einsum("bnsc,nck->bks", x, phi.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    x32 = x.astype(jnp.float32)
    return raw, jnp.mean(x32 * x32, axis=(1, 3))


def _project_fwd(x, phi):
    return _project(x, phi), (x, phi)


def _project_bwd(saved, cotangents):
    x, phi = saved
    d_raw, d_ms = cotangents
    d_raw = d_raw.astype(x.dtype)
    # Each term leaves in the activations' dtype: a float32 product here is
    # twice a stream's size, and the scheduler keeps every layer's.
    dx = jnp.einsum("bks,nck->bnsc", d_raw, phi.astype(x.dtype),
                    preferred_element_type=x.dtype)
    per_token = (2.0 / (x.shape[1] * x.shape[3])) * d_ms
    dx = dx + (per_token[:, None, :, None] * x.astype(jnp.float32)).astype(
        x.dtype)
    d_phi = jnp.einsum("bnsc,bks->nck", x, d_raw,
                       preferred_element_type=jnp.float32)
    return dx, d_phi.astype(phi.dtype)


_project.defvjp(_project_fwd, _project_bwd)


@jax.custom_vjp
def _pre_mix(pre, x):
    """``u = sum_j H_pre[j] X[j]``: ``pre (B, n, S)``, ``X (B, n, S, C)``.
    Written out over the n streams: elementwise work that fuses into one
    pass over X, where an einsum would be a batched matmul with a
    contraction of n.  Float32 out (the module's docstring says why)."""
    return sum(
        pre[:, j, :, None] * x[:, j].astype(jnp.float32)
        for j in range(x.shape[1]))


def _pre_mix_fwd(pre, x):
    return _pre_mix(pre, x), (pre, x)


def _pre_mix_bwd(saved, du):
    pre, x = saved
    du = du.astype(jnp.float32)
    streams = range(x.shape[1])
    d_pre = jnp.stack([
        jnp.sum(du * x[:, j].astype(jnp.float32), axis=-1) for j in streams
    ], axis=1)
    dx = jnp.stack([
        (pre[:, j, :, None] * du).astype(x.dtype) for j in streams], axis=1)
    return d_pre, dx


_pre_mix.defvjp(_pre_mix_fwd, _pre_mix_bwd)


@jax.custom_vjp
def _res_mix(res, post, x, y):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: ``res (B, n, n,
    S)``, ``post (B, n, S)``, ``X (B, n, S, C)``, ``y (B, S, C)``."""
    n = x.shape[1]
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([
        sum(res[:, i, j, :, None] * x32[:, j] for j in range(n))
        + post[:, i, :, None] * y32
        for i in range(n)
    ], axis=1).astype(x.dtype)


def _res_mix_fwd(res, post, x, y):
    return _res_mix(res, post, x, y), (res, post, x, y)


def _res_mix_bwd(saved, d_out):
    res, post, x, y = saved
    n = x.shape[1]
    d32, x32, y32 = (a.astype(jnp.float32) for a in (d_out, x, y))
    d_res = jnp.stack([
        jnp.stack([jnp.sum(d32[:, i] * x32[:, j], axis=-1) for j in range(n)],
                  axis=1)
        for i in range(n)], axis=1)
    d_post = jnp.stack(
        [jnp.sum(d32[:, i] * y32, axis=-1) for i in range(n)], axis=1)
    dx = jnp.stack([
        sum(res[:, i, j, :, None] * d32[:, i] for i in range(n))
        for j in range(n)], axis=1).astype(x.dtype)
    dy = sum(post[:, i, :, None] * d32[:, i] for i in range(n)).astype(y.dtype)
    return d_res, d_post, dx, dy


_res_mix.defvjp(_res_mix_fwd, _res_mix_bwd)


class StreamMix(nn.Module):
    """One sublayer's mixing: ``coefficients`` before it, ``mix`` after."""

    config: object  # TransformerConfig

    def setup(self):
        cfg, hc = self.config, self.config.streams
        n = hc.n

        def kernel(name, columns):
            return self.param(
                name,
                nn.with_partitioning(
                    nn.initializers.normal(0.02), (None, "embed", None)),
                (n, cfg.d_model, columns), cfg.param_dtype)

        self.phi = kernel("phi", 2 * n)
        self.phi_res = kernel("phi_res", n * n)
        const = nn.initializers.constant
        self.alpha = self.param(
            "alpha", const(hc.alpha_init), (3,), jnp.float32)
        self.b_pre = self.param("b_pre", const(0.0), (n,), jnp.float32)
        self.b_post = self.param("b_post", const(0.0), (n,), jnp.float32)
        self.b_res = self.param(
            "b_res",
            lambda key, shape, dtype: hc.res_diagonal_init * jnp.eye(
                n, dtype=dtype),
            (n, n), jnp.float32)

    def before(self, x):
        """``X (B, n, S, C)`` -> ``u``, the ``X`` that ``mix`` should take
        (the kernels' op hands the streams through, so that both of their
        cotangents meet in its one backward pass) and ``(H_post, H_res)``."""
        hc = self.config.streams
        n = hc.n
        batch, _, seq, _ = x.shape
        phi = jnp.concatenate([self.phi, self.phi_res], axis=-1)
        alpha = self.alpha
        kernels = stream_mix.token_tile(x) is not None
        with jax.named_scope("hc"):
            if kernels:
                raw, mean_square, u, x = stream_mix.pre(
                    x, phi, alpha[0], self.b_pre)
            else:
                raw, mean_square = _project(x, phi)
            h = raw * jax.lax.rsqrt(
                mean_square + stream_mix.NORM_EPS)[:, None, :]  # (B, k, S)
            if not kernels:
                u = _pre_mix(jax.nn.sigmoid(
                    alpha[0] * h[:, :n] + self.b_pre[None, :, None]), x)
            post = 2.0 * jax.nn.sigmoid(
                alpha[1] * h[:, n:2 * n] + self.b_post[None, :, None])
            res = alpha[2] * h[:, 2 * n:].reshape(
                batch, n, n, seq) + self.b_res[None, :, :, None]
            res = sinkhorn_knopp(
                jnp.exp(jnp.clip(res, *hc.clamp)), hc.sinkhorn_iters, hc.eps)
        return u, x, (post, res)

    def coefficients(self, x):
        """``X (B, n, S, C)`` -> the sublayer's input ``u (B, S, C)``
        (float32) and ``(H_post (B, n, S), H_res (B, n, n, S))`` for
        ``mix``."""
        u, _, coefficients = self.before(x)
        return u, coefficients

    def mix(self, x, y, coefficients):
        """``X' = H_res X + H_post y``."""
        post, res = coefficients
        with jax.named_scope("hc"):
            if stream_mix.token_tile(x) is None:
                out = _res_mix(res, post, x, y)
            else:
                out = stream_mix.res_mix(res, post, x, y)
        return nn.with_logical_constraint(
            out, ("batch", None, "seq", "embed"))
