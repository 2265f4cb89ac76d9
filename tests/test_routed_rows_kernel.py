"""The read-back's kernel (``ops/routed_rows.py`` ``moe_readback``),
interpreted on the CPU, against the gather form it replaces
(``models/moe.py`` ``_sum_of_pairs``): the same float32 sums rounded once,
bit for bit, whatever the live rows' count; rows at or past the live ones
never read; the gather form where a shape does not tile; and the gradients
of ``_group_rows`` / ``_ungroup_rows`` and of the layer through it against
plain autodiff of the gathers."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.archs import xing4_0 as arch
from covalent_tpu_plugin.models import moe
from covalent_tpu_plugin.models.moe import HeldExperts
from covalent_tpu_plugin.ops import routed_rows
from tests.benchsuite import xing4_toy

TOKENS, K, EXPERTS, HELD = 256, 4, 16, 3


def _grouped(chosen, held=HELD):
    """``HeldExperts``' own index work for ``chosen (T, k)``: the grouped
    order and the held experts' rows."""
    local = np.asarray(chosen).T.reshape(-1)
    key = np.where(local < held, local, held)
    order = np.argsort(key, kind="stable").astype(np.int32)
    return jnp.asarray(order), jnp.asarray(
        np.bincount(key, minlength=held + 1)[:held].astype(np.int32))


def _chosen(n_held=None):
    """Every token's ``K`` of the experts; with ``n_held``, that many of
    the pairs on a held expert (the surplus sent to the last one)."""
    rng = np.random.default_rng(0)
    chosen = np.stack(
        [rng.permutation(EXPERTS)[:K] for _ in range(TOKENS)])
    if n_held is not None:
        held = np.argwhere(chosen < HELD)
        surplus = held[rng.permutation(len(held))[n_held:]]
        chosen[surplus[:, 0], surplus[:, 1]] = EXPERTS - 1
    return chosen


def _one_token_holds_all():
    """Token 77 chose every held expert (``min(k, held)`` rows), no other
    token any."""
    chosen = np.tile(np.arange(HELD, HELD + K), (TOKENS, 1))
    chosen[77] = np.r_[np.arange(HELD), EXPERTS - 1][:K]
    return chosen


def _rows(n_rows, width, dtype, n_live, seed=1):
    """A buffer whose rows at or past the live ones are NaN, as a grouped
    product may leave them."""
    rows = np.random.default_rng(seed).standard_normal((n_rows, width))
    rows[min(n_live, n_rows):] = np.nan
    return jnp.asarray(rows, dtype)


CASES = {
    # name: (chosen, rows of the buffer, width)
    "no-live-row": (_chosen(0), 512, 128),
    "one-token-holds-every-held-expert": (_one_token_holds_all(), 512, 128),
    "the-held-pairs": (_chosen(), 512, 256),
    "exactly-the-buffer": (_chosen(184), 184, 128),
    "past-the-buffer": (_chosen(), 128, 128),
    "a-width-that-does-not-tile": (_chosen(), 512, 96),
    "a-buffer-that-does-not-tile": (_chosen(), 500, 128),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_gives_the_gather_forms_sums_bit_for_bit(case, dtype):
    chosen, n_rows, width = CASES[case]
    order, group_sizes = _grouped(chosen)
    n_live = int(group_sizes.sum())
    if case == "no-live-row":
        assert n_live == 0
    if case == "exactly-the-buffer":
        assert n_live == n_rows
    if case == "past-the-buffer":
        assert n_live > n_rows
    if case == "one-token-holds-every-held-expert":
        assert n_live == min(K, HELD)
    rows = _rows(n_rows, width, dtype, n_live)
    tokens = jax.ShapeDtypeStruct((TOKENS, width), dtype)
    places = moe._places(order, n_rows, group_sizes, tokens)
    takes_the_kernel = "does-not-tile" not in case
    assert isinstance(places, tuple) == takes_the_kernel
    if takes_the_kernel:
        starts, source, token, bounds = places
        # Only the first ``n_rows`` places, and of them the live ones.
        assert int(starts[-1]) == int(bounds[-1]) == min(n_live, n_rows)
        assert int(starts[0]) == 0 and starts.shape == (TOKENS // 128 + 1,)
        assert bool(jnp.all(jnp.diff(token) >= 0))
        # A stream is one held expert's rows for one choice, in the
        # buffer's order.
        assert bounds.shape == (HELD * K + 1,) and int(bounds[0]) == 0
        assert bool(jnp.all(jnp.diff(bounds) >= 0))
    got = moe._sum_by_token(rows, places, TOKENS, jnp.int32(n_live))
    assert got.shape == (TOKENS, width) and got.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    # The gather form on the same buffer with its dead rows zeroed: it
    # masks them, but a NaN times its mask would tell nothing.
    want = moe._sum_of_pairs(
        jnp.nan_to_num(rows), jnp.argsort(order), TOKENS, jnp.int32(n_live))
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)))
    assert bool(jnp.any(got != 0)) == bool(n_live)
    if case == "one-token-holds-every-held-expert":
        assert not bool(jnp.any(jnp.delete(got, 77, axis=0)))


def test_token_tile_says_which_shapes_take_the_kernel():
    def tile(n_rows, width, dtype=jnp.bfloat16, n_tokens=256, streams=32):
        return routed_rows.token_tile(
            jax.ShapeDtypeStruct((n_rows, width), dtype), n_tokens, streams)

    # Both cells: eight held experts, ten and four choices.
    assert tile(20480, 3072, streams=80) == tile(16384, 3584) == 128
    assert tile(20480, 3072, streams=2048) is None  # their tiles' VMEM
    assert tile(131072, 3072, streams=80) is None   # the walk's SMEM
    assert tile(512, 128, jnp.float32) == 128
    assert tile(512, 96) is None and tile(500, 128) is None
    assert tile(512, 128, n_tokens=200) is None
    assert tile(512, 128, jnp.float16) is None


def _plain(tokens, order, n_rows, n_live, scale):
    """Gather, a function of each row, gather back and sum: plain jnp, for
    autodiff to transpose as it will."""
    n_tokens = tokens.shape[0]
    live = (jnp.arange(n_rows) < n_live)[:, None]
    rows = jnp.where(
        live, jnp.tanh(tokens[order[:n_rows] % n_tokens]) * scale, 0)
    inverse = jnp.argsort(order)
    picked = jnp.where(
        (inverse < jnp.minimum(n_live, n_rows))[:, None],
        rows[jnp.minimum(inverse, n_rows - 1)], 0)
    return picked.reshape(-1, n_tokens, tokens.shape[1]).sum(axis=0)


def _through_the_kernel(tokens, order, n_rows, group_sizes, scale):
    places = moe._places(order, n_rows, group_sizes, tokens)
    assert isinstance(places, tuple)
    n_live = jnp.sum(group_sizes)
    live = (jnp.arange(n_rows) < n_live)[:, None]
    rows = moe._group_rows(n_rows, tokens, order, places, n_live)
    # What a grouped product leaves past the live rows: not to be read,
    # forward or back.
    rows = jnp.where(live, jnp.tanh(rows) * scale, jnp.nan)
    return moe._ungroup_rows(tokens.shape[0], rows, order, places, n_live)


@pytest.mark.parametrize("n_rows", [512, 184, 128],
                         ids=["room-to-spare", "exactly-the-buffer",
                              "past-the-buffer"])
def test_the_gradients_through_the_kernel_are_plain_autodiffs(n_rows):
    order, group_sizes = _grouped(_chosen(184))
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    tokens = jax.random.normal(keys[0], (TOKENS, 128))
    scale = jax.random.uniform(keys[1], (n_rows, 1), minval=0.5)
    weight = jax.random.normal(keys[2], (TOKENS, 128))

    def loss(form, live):
        return lambda tokens, scale: jnp.sum(
            weight * form(tokens, order, n_rows, live, scale))

    got = jax.jit(jax.value_and_grad(
        loss(_through_the_kernel, group_sizes), (0, 1)))(tokens, scale)
    want = jax.jit(jax.value_and_grad(
        loss(_plain, jnp.sum(group_sizes)), (0, 1)))(tokens, scale)
    for mine, theirs in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(mine)))
        np.testing.assert_allclose(
            mine, theirs, rtol=0, atol=2e-6 * float(jnp.abs(theirs).max()))


def _held_layer(n_held_pairs, dtype):
    """``HeldExperts`` of two of sixteen experts, 128 wide, over 512
    tokens' two choices: the bounded buffer has 512 rows of the 1,024
    pairs, and the shapes tile."""
    config = xing4_toy.with_sizes(
        router_width=16, hidden_size=128, activation_dtype=dtype)
    module = HeldExperts(arch.model_config(config, max_seq=512))
    rng = np.random.default_rng(n_held_pairs)
    chosen = rng.choice(np.r_[0:2, 4:16], size=1024)
    chosen[rng.permutation(1024)[:n_held_pairs]] = rng.choice(
        [2, 3], size=n_held_pairs)
    chosen = jnp.asarray(chosen.reshape(2, 512).T)
    keys = jax.random.split(jax.random.PRNGKey(n_held_pairs), 3)
    tokens = jax.random.normal(keys[0], (512, 128), jnp.dtype(dtype))
    weights = jax.random.uniform(keys[1], (512, 2), minval=0.5)
    params = jax.tree.map(
        lambda leaf: 0.3 * jax.random.normal(keys[2], leaf.shape),
        module.init(keys[2], tokens, chosen, weights)["params"])
    weight = jax.random.normal(jax.random.PRNGKey(13), (512, 128))

    def loss(params, tokens, weights):
        out, sown = module.apply(
            {"params": params}, tokens, chosen, weights,
            mutable=["intermediates"])
        return (jnp.sum(out.astype(jnp.float32) * weight),
                (out, sown["intermediates"]["moe_stats"][0]))

    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True), (
        params, tokens, weights)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("n_held_pairs, takes_whole", [
    (300, 0), (512, 0), (513, 1), (0, 0)],
    ids=["well-under", "exactly-the-bound", "one-over", "none-held"])
def test_the_layer_through_the_kernel_is_the_layer_through_the_gathers(
        n_held_pairs, takes_whole, dtype, monkeypatch):
    step, operands = _held_layer(n_held_pairs, dtype)
    calls = []
    kernel = routed_rows.moe_readback
    monkeypatch.setattr(routed_rows, "moe_readback", lambda *a, **k: (
        calls.append(a[0].shape), kernel(*a, **k))[1])
    (_, (out, stats)), grads = jax.jit(step)(*operands)
    # Traced: forward; the backward rule's own forward, which the compiler
    # drops; and the rows' transpose.  All in the bounded branch.
    assert calls == [(512, 128)] * 3
    assert [float(v) for v in stats][::3] == [n_held_pairs, takes_whole]
    monkeypatch.setattr(routed_rows, "token_tile", lambda *shape: None)
    calls.clear()
    (_, (want, _)), want_grads = jax.jit(step)(*operands)
    assert not calls
    for got, wanted in zip(jax.tree.leaves((out, grads)),
                           jax.tree.leaves((want, want_grads))):
        assert got.dtype == wanted.dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(wanted.astype(jnp.float32)))
    assert bool(jnp.any(out != 0)) == bool(n_held_pairs)
