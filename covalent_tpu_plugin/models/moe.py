"""Mixture-of-experts MLP: Switch-style top-1 routing, einsum dispatch.

The TPU-native MoE formulation (Mesh-TensorFlow lineage): routing becomes
dense one-hot dispatch/combine einsums over a capacity-bounded buffer —
no gathers, no dynamic shapes, so XLA tiles everything onto the MXU and,
with the ``expert`` logical axis mapped to a mesh axis, inserts the
expert-parallel all-to-alls automatically from the shardings (the
scaling-book recipe; nothing here hand-writes a collective).

Semantics (Switch Transformer):
  * top-1 routing with softmax gate scaling;
  * per-call capacity ``C = ceil(capacity_factor * N / E)`` over the
    flattened token set; tokens over capacity are *dropped* — they
    contribute zero from the expert layer and ride the residual;
  * the standard load-balance auxiliary loss is sown into the
    ``"intermediates"`` collection (``moe_aux``) for the loss function to
    collect (:func:`lm_loss_with_moe_aux`).
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import routed_rows
from .layers import MlpBlock


class MoEMlp(nn.Module):
    """Drop-in MLP replacement: route each token to one of ``n_experts``."""

    config: object  # TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        n_experts = cfg.moe_experts
        batch, seq_len, d_model = x.shape
        n_tokens = batch * seq_len
        capacity = int(
            -(-cfg.moe_capacity_factor * n_tokens // n_experts)  # ceil
        )
        capacity = max(1, min(capacity, n_tokens))

        router = nn.DenseGeneral(
            features=n_experts,
            use_bias=False,
            dtype=jnp.float32,  # routing decisions in f32, always
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_partitioning(
                nn.initializers.normal(0.02), ("embed", None)
            ),
            name="router",
        )
        tokens = x.reshape(n_tokens, d_model)
        gates = jax.nn.softmax(router(tokens.astype(jnp.float32)), axis=-1)
        expert_index = jnp.argmax(gates, axis=-1)                 # (N,)
        expert_gate = jnp.max(gates, axis=-1)                     # (N,)
        expert_onehot = jax.nn.one_hot(expert_index, n_experts)   # (N, E)

        # Load-balance aux (Switch eq. 4): E * sum_e f_e * P_e, minimised
        # at uniform routing where it equals 1.
        fraction = expert_onehot.mean(axis=0)
        prob_mass = gates.mean(axis=0)
        self.sow(
            "intermediates", "moe_aux",
            n_experts * jnp.sum(fraction * prob_mass),
        )

        # Position of each token within its expert's capacity buffer; the
        # cumsum is over the flat token order (deterministic priority).
        position = jnp.cumsum(expert_onehot, axis=0) * expert_onehot - 1.0
        kept = (position >= 0) & (position < capacity)
        position = jnp.clip(position, 0, capacity - 1).astype(jnp.int32)
        # Dispatch tensor (N, E, C): one-hot in both expert and slot.
        dispatch = (
            expert_onehot[:, :, None]
            * jax.nn.one_hot(position, capacity)
            * kept[:, :, None]
        ).astype(cfg.dtype)

        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch, tokens.astype(cfg.dtype)
        )
        wi = self.param(
            "wi",
            nn.with_partitioning(
                nn.initializers.normal(0.02), ("expert", "embed", "expert_mlp")
            ),
            (n_experts, d_model, cfg.d_ff),
            cfg.param_dtype,
        )
        wo = self.param(
            "wo",
            nn.with_partitioning(
                nn.initializers.normal(0.02 / (2 * cfg.n_layers) ** 0.5),
                ("expert", "expert_mlp", "embed"),
            ),
            (n_experts, cfg.d_ff, d_model),
            cfg.param_dtype,
        )
        h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(cfg.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo.astype(cfg.dtype))

        # Combine: gate-scaled return trip; dropped tokens get zero (their
        # dispatch row is all-zero) and survive through the residual.
        combine = dispatch * expert_gate[:, None, None].astype(cfg.dtype)
        out = jnp.einsum("nec,ecd->nd", combine, expert_out)
        out = out.reshape(batch, seq_len, d_model)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


@dataclasses.dataclass(frozen=True)
class RoutedExpertsConfig:
    """Top-k of ``n_experts`` by score, gated experts of ``d_ff``,
    ``n_shared`` shared ones, and the share of the experts held here.  The
    defaults are DeepSeek-V3's router (arXiv:2412.19437) without its group
    limit: sigmoid scores and a correction bias on the choice."""

    n_experts: int
    top_k: int
    d_ff: int
    n_shared: int = 1
    routed_scaling: float = 1.0
    norm_topk: bool = True
    #: "sigmoid": each expert's own; "softmax": over all ``n_experts``.
    score: str = "sigmoid"
    #: a bias leaf added to the scores for the choice alone, which no
    #: gradient reaches (corrected outside it); False = no such leaf.
    correction_bias: bool = True
    #: ``(first, count)``: the experts this layer holds and computes (expert
    #: parallelism's share; the others' part of the result is left out, as
    #: the chips that hold them would add it).  None = all of them.
    held: tuple | None = None

    def __post_init__(self):
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(
                f"score must be 'sigmoid' or 'softmax', got {self.score!r}")

    @property
    def held_range(self) -> tuple[int, int]:
        return (0, self.n_experts) if self.held is None else tuple(self.held)


class Router(nn.Module):
    """Scores in float32 over ALL the experts, whichever are held:
    ``s = sigmoid(h W_r)``, or ``softmax(h W_r)`` over them all; the
    ``top_k`` of ``s + b`` are chosen (``b``: the correction bias, which no
    gradient reaches, where the configuration has one), weighted
    ``s[chosen] / sum * routed_scaling``."""

    config: object  # TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg, ex = self.config, self.config.routed
        logits = nn.DenseGeneral(
            features=ex.n_experts, use_bias=False, dtype=jnp.float32,
            # A choice flips on the scores' last bits: no bf16 pass here.
            precision=jax.lax.Precision.HIGHEST,
            param_dtype=cfg.param_dtype, name="gate",
            kernel_init=nn.with_partitioning(
                nn.initializers.normal(0.02), ("embed", None)),
        )(tokens.astype(jnp.float32))
        if ex.score == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            scores = jax.nn.sigmoid(logits)
        biased = scores
        if ex.correction_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (ex.n_experts,),
                jnp.float32)
            biased = scores + jax.lax.stop_gradient(bias)
        _, chosen = jax.lax.top_k(biased, ex.top_k)             # (T, k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if ex.norm_topk:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return chosen, weights * ex.routed_scaling


# (token, choice) pairs are numbered choice-major (pair ``c T + t``), so
# that the pairs' own order is ``(k, T, C)``: k whole slabs, summed a token
# by adding slabs, where token-major would put k on the tiles' second-minor
# dimension and every reshape would copy.

#: The row buffer's room, in even shares: the rows the held experts get
#: when every expert is chosen alike.  Only the held experts' outputs reach
#: this chip's loss, so the router drifts toward them: over a window the
#: mean reads 1.84 even shares and one expert peaks at 2.33 times its own
#: (PERF.md §5, §6 PR 32).  Four leaves the late steps room; a step that
#: routes more than that here takes the whole buffer, so no row is dropped.
BUFFER_EVEN_SHARES = 4
#: The buffer's rows are a multiple of this, whatever the shares come to.
BUFFER_ROW_TILE = 512


def buffer_rows(pairs: int, held: int, n_experts: int) -> int:
    """Rows of the held experts' buffer: ``BUFFER_EVEN_SHARES`` times the
    even share of the ``pairs``, and never more than all of them."""
    rows = -(-BUFFER_EVEN_SHARES * pairs * held // n_experts)
    return min(pairs, -(-rows // BUFFER_ROW_TILE) * BUFFER_ROW_TILE)


def _rows_of_pairs(tokens, order, n_rows: int):
    """``(T, C)`` -> ``(n_rows, C)``: row ``r`` is the token of pair
    ``order[r]``, for the first ``n_rows`` places of the grouped order."""
    return tokens[order[:n_rows] % tokens.shape[0]]


def _sum_of_pairs(rows, inverse, n_tokens: int, n_live):
    """``(n_rows, C)`` -> ``(T, C)``: a token's sum, in float32, over its
    pairs' rows (pair ``p`` stands at place ``inverse[p]``).  Only the
    first ``n_live`` rows are read: a pair whose place lies past them, or
    past the rows, reads a zero.  The transpose of :func:`_rows_of_pairs`,
    as a gather."""
    n_rows = rows.shape[0]
    picked = jnp.where(
        (inverse < jnp.minimum(n_live, n_rows))[:, None],
        rows[jnp.minimum(inverse, n_rows - 1)], 0)
    return picked.reshape(-1, n_tokens, rows.shape[-1]).sum(
        axis=0, dtype=jnp.float32).astype(rows.dtype)


def _places(order, n_rows: int, group_sizes, tokens):
    """What the read-back of an ``n_rows`` buffer needs of the grouped
    order.  Where the buffer has a row for every pair, one row a pair is
    what there is, and where the shape does not tile
    (``routed_rows.token_tile``) no kernel runs: ``inverse``, which undoes
    ``order``, for :func:`_sum_of_pairs`.  Else what the kernel
    ``moe_readback`` takes (its docstring has each): the walk, which is the
    live rows sorted by token, a token's in its pairs' own order (so its
    float32 sum adds the same numbers in the same order either way); and
    the streams, one for every held expert and choice: inside an expert's
    group the rows stand in the pairs' order, which is choice-major, so
    the tokens of one choice's rows ascend.  A sort of ``n_rows`` keys,
    where ``inverse`` is one of all the pairs."""
    n_tokens, width = tokens.shape
    pairs = order.shape[0]
    k = pairs // n_tokens
    streams = group_sizes.shape[0] * k
    tile = routed_rows.token_tile(
        jax.ShapeDtypeStruct((n_rows, width), tokens.dtype), n_tokens,
        streams)
    if n_rows == pairs or tile is None:
        return jnp.argsort(order)
    row = jnp.arange(n_rows, dtype=jnp.int32)
    live = row < jnp.sum(group_sizes)
    pair = order[:n_rows]
    choice = pair // n_tokens
    expert = jnp.sum(
        row[:, None] >= jnp.cumsum(group_sizes)[None, :], axis=1,
        dtype=jnp.int32)
    stream = jnp.where(live, expert * k + choice, streams)
    key, source = jax.lax.sort(
        (jnp.where(live, pair % n_tokens * k + choice, pairs),
         stream << routed_rows.row_bits(n_rows) | row), num_keys=1)
    token = key // k                              # a dead row's: n_tokens
    starts = jnp.sum(
        token[:, None] < jnp.arange(0, n_tokens + 1, tile)[None, :],
        axis=0, dtype=jnp.int32)
    bounds = jnp.sum(
        stream[:, None] < jnp.arange(streams + 1)[None, :],
        axis=0, dtype=jnp.int32)
    return starts, source, token, bounds


def _sum_by_token(rows, places, n_tokens: int, n_live):
    """``(n_rows, C)`` -> ``(T, C)``: a token's sum, in float32 and rounded
    once, over its live rows, in the form ``places`` was made for
    (:func:`_places`)."""
    if isinstance(places, tuple):
        return routed_rows.moe_readback(rows, *places, n_tokens=n_tokens)
    return _sum_of_pairs(rows, places, n_tokens, n_live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _group_rows(n_rows, tokens, order, places, n_live):
    """:func:`_rows_of_pairs`, whose transpose is :func:`_sum_by_token`,
    where a plain gather's would scatter-add, and reads the live rows'
    cotangents alone."""
    return _rows_of_pairs(tokens, order, n_rows)


def _group_rows_fwd(n_rows, tokens, order, places, n_live):
    return (_rows_of_pairs(tokens, order, n_rows),
            (places, tokens.shape[0], n_live))


def _group_rows_bwd(n_rows, res, g):
    places, n_tokens, n_live = res
    return _sum_by_token(g, places, n_tokens, n_live), None, None, None


_group_rows.defvjp(_group_rows_fwd, _group_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ungroup_rows(n_tokens, rows, order, places, n_live):
    """Rows in grouped order -> each token's sum over its live rows
    (:func:`_sum_by_token`): no array of one row a pair is left for the
    transpose to broadcast into."""
    return _sum_by_token(rows, places, n_tokens, n_live)


def _ungroup_rows_fwd(n_tokens, rows, order, places, n_live):
    return (_sum_by_token(rows, places, n_tokens, n_live),
            (order, rows.shape[0]))


def _ungroup_rows_bwd(n_tokens, res, g):
    order, n_rows = res
    return _rows_of_pairs(g, order, n_rows), None, None, None


_ungroup_rows.defvjp(_ungroup_rows_fwd, _ungroup_rows_bwd)


def _held_result(n_rows, tokens, pair_weight, order, group_sizes,
                 wg, wu, wd):
    """The held experts' part of every token's result, over a buffer of
    the first ``n_rows`` places of the grouped order: right where no more
    pairs than that chose a held expert.

    Rows past the held ones belong to no group: a grouped product reads
    none of them and leaves whatever it finds in their place (PR 28: a NaN
    there reached every gradient).  The hidden rows (1024 wide) are masked
    where they leave a product and where they enter the last one, so that
    nothing of them reaches the weights' gradient on the way back; the
    model-wide rows (3584 wide) are never masked: gathered, they hold some
    token's numbers, and what a product leaves there is not read
    (``n_live``), forward or back."""
    n_live = jnp.sum(group_sizes)
    live = (jnp.arange(n_rows) < n_live)[:, None]
    places = _places(order, n_rows, group_sizes, tokens)
    rows = _group_rows(n_rows, tokens, order, places, n_live)
    gate = jnp.where(live, jax.lax.ragged_dot(rows, wg, group_sizes), 0)
    up = jnp.where(live, jax.lax.ragged_dot(rows, wu, group_sizes), 0)
    # A row's weight goes onto its hidden row (1024 wide) and not onto its
    # output (3584 wide): scaling rows commutes with the product.
    row_weight = pair_weight[order[:n_rows]][:, None].astype(rows.dtype)
    hidden = jnp.where(live, nn.silu(gate) * up * row_weight, 0)
    out = jax.lax.ragged_dot(hidden, wd, group_sizes)
    return _ungroup_rows(tokens.shape[0], out, order, places, n_live)


# The bounded buffer where the step's held pairs fit it, else the whole
# one.  A plain ``cond`` under autodiff returns the union of its branches'
# residuals, each branch filling the other's with zeros (jax 0.9.0,
# ``_join_cond_outputs``): the bounded branch would write zeros the size of
# everything the whole buffer keeps.  So the pair has a rule of its own,
# which keeps its inputs and nothing else (what a conditional hands to a
# later one is held apart for every layer at once: 0.27 GB of the compiled
# step for the two hidden factors, PERF.md §6 PR 32), and whose transpose
# is a ``cond`` over the two branches' own.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bounded_or_whole(buffers, fits, *operands):
    """:func:`_held_result` over the first of the two ``buffers`` (their
    rows: the bounded one's, the whole one's) where ``fits``, else over
    the second."""
    bounded, whole = (functools.partial(_held_result, n) for n in buffers)
    return jax.lax.cond(fits, bounded, whole, *operands)


def _bounded_or_whole_fwd(buffers, fits, *operands):
    return _bounded_or_whole(buffers, fits, *operands), (fits, operands)


def _bounded_or_whole_bwd(buffers, res, g):
    fits, operands = res
    tokens, pair_weight, order, group_sizes, wg, wu, wd = operands

    def transposed(n_rows, g):
        _, transpose = jax.vjp(
            lambda tokens, pair_weight, wg, wu, wd: _held_result(
                n_rows, tokens, pair_weight, order, group_sizes,
                wg, wu, wd), tokens, pair_weight, wg, wu, wd)
        return transpose(g)

    bounded, whole = (functools.partial(transposed, n) for n in buffers)
    # Fenced: the compiler would move the kernels' gradients' way to
    # float32 into both branches, which then hold both forms of all three.
    d_tokens, d_weight, d_wg, d_wu, d_wd = jax.lax.optimization_barrier(
        jax.lax.cond(fits, bounded, whole, g))
    return None, d_tokens, d_weight, None, None, d_wg, d_wu, d_wd


_bounded_or_whole.defvjp(_bounded_or_whole_fwd, _bounded_or_whole_bwd)


class HeldExperts(nn.Module):
    """The held experts' part of the routed result.  Every (token, choice)
    pair whose expert is held becomes a row; rows are grouped by expert (a
    stable sort, the others' pairs last) and each of the three matmuls is
    one grouped product over the held experts (``jax.lax.ragged_dot``).
    The row buffer has room for :func:`buffer_rows` of them; a step that
    sends more here takes a buffer with room for every pair instead, so no
    row is ever dropped, whatever the imbalance.  Rows past the held ones
    are not computed on, and what stands there is never read."""

    config: object  # TransformerConfig

    @nn.compact
    def __call__(self, tokens, chosen, weights):
        cfg, ex = self.config, self.config.routed
        first, held = ex.held_range
        n_tokens, d_model = tokens.shape

        def kernel(name, shape, axes, std):
            return self.param(
                name,
                nn.with_partitioning(nn.initializers.normal(std), axes),
                shape, cfg.param_dtype).astype(cfg.dtype)

        wg = kernel("wg", (held, d_model, ex.d_ff),
                    ("expert", "embed", "expert_mlp"), 0.02)
        wu = kernel("wu", (held, d_model, ex.d_ff),
                    ("expert", "embed", "expert_mlp"), 0.02)
        wd = kernel("wd", (held, ex.d_ff, d_model),
                    ("expert", "expert_mlp", "embed"),
                    0.02 / (2 * cfg.n_layers) ** 0.5)

        local = chosen.T.reshape(-1) - first          # (k T,), choice-major
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held)                   # others last
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
        n_held = jnp.sum(group_sizes)
        pairs = order.shape[0]
        bound = buffer_rows(pairs, held, ex.n_experts)
        fits = n_held <= bound
        operands = (tokens, weights.T.reshape(-1), order, group_sizes,
                    wg, wu, wd)
        if bound == pairs:  # always fits: one path, no conditional
            routed = _held_result(pairs, *operands)
        else:
            routed = _bounded_or_whole((bound, pairs), fits, *operands)
        # What a trace cannot see: the rows this step sent through the held
        # experts, the most loaded one's over the mean, the held pairs that
        # got no row (none, by construction: counted, not assumed), and
        # whether the step took the whole buffer here (1) or the bounded.
        placed = jnp.minimum(n_held, jnp.where(fits, bound, pairs))
        self.sow("intermediates", "moe_stats", jnp.stack([
            n_held.astype(jnp.float32),
            jnp.max(group_sizes) * held / jnp.maximum(n_held, 1),
            (jnp.sum(is_held) - placed).astype(jnp.float32),
            (~fits).astype(jnp.float32),
        ]))
        return routed


class RoutedExperts(nn.Module):
    """Drop-in MLP replacement: ``sum over chosen and held g_e E_e(h) +
    shared(h)``, ``E`` and ``shared`` gated MLPs.  No balance term: where
    there is a correction bias it is corrected outside the gradient
    (``noaux_tc``)."""

    config: object  # TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, ex = self.config, self.config.routed
        batch, seq_len, d_model = x.shape
        tokens = x.reshape(batch * seq_len, d_model)
        chosen, weights = Router(cfg, name="router")(tokens)
        out = HeldExperts(cfg, name="experts")(tokens, chosen, weights)
        out = out.reshape(batch, seq_len, d_model)
        if ex.n_shared:
            out = out + MlpBlock(
                cfg, d_ff=ex.n_shared * ex.d_ff, name="shared_expert")(x)
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


def collect_moe_stats(intermediates):
    """Every sown ``moe_stats`` record, stacked ``(layers, 4)``: held rows,
    peak load over mean, dropped rows, whether the layer took the whole
    buffer; None where no layer sowed one."""
    found = [
        jnp.reshape(leaf, (-1, 4))
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            intermediates)[0]
        if any(getattr(entry, "key", None) == "moe_stats" for entry in path)
    ]
    return jnp.concatenate(found) if found else None


def collect_moe_aux(intermediates) -> jax.Array:
    """Sum every sown ``moe_aux`` scalar in an intermediates collection.

    Filters by key so unrelated sown diagnostics can never leak into the
    training loss.
    """
    total = jnp.zeros((), jnp.float32)
    flat = jax.tree_util.tree_flatten_with_path(intermediates)[0]
    for path, leaf in flat:
        if any(getattr(entry, "key", None) == "moe_aux" for entry in path):
            total = total + jnp.sum(leaf)
    return total


def lm_loss_with_moe_aux(params, apply_fn, batch, aux_weight: float = 0.01):
    """Next-token loss + weighted MoE load-balance loss.

    Use in place of :func:`..train.lm_loss` for MoE configs; works with
    ``make_train_step`` unchanged.
    """
    from .train import cross_entropy_loss

    tokens = batch["tokens"]
    logits, variables = apply_fn(
        {"params": params}, tokens[:, :-1], mutable=["intermediates"]
    )
    loss = cross_entropy_loss(logits, tokens[:, 1:])
    aux = collect_moe_aux(variables.get("intermediates", {}))
    return loss + aux_weight * aux
