"""Ring attention: sequence/context parallelism over the mesh ``seq`` axis.

Long-context scaling the TPU-native way: Q, K, V are sharded along the
sequence dimension across devices; each device keeps its query shard
resident while K/V shards rotate around the ring via ``lax.ppermute`` (one
ICI hop per step).  Partial attention results merge with the same online
softmax used by the flash kernel, so the full S×S score matrix never exists
on any one chip and per-device memory is O(S/n · S/n) per step.

Run inside ``shard_map`` over a mesh with a ``seq`` axis — see
``sequence_parallel_attention`` for the wrapped entry point.  The loop is a
``lax.scan`` (not fori) so reverse-mode autodiff works for training.

The reference has no model/sequence scaling at all (SURVEY §5 "long-context
— ABSENT"); this module is a new capability mandated by the TPU north star.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.collectives import all_to_all, ring_permute
from .attention import (
    _flash_backward,
    _flash_forward,
    default_interpret,
    flash_attention,
    on_tpu,
)

_NEG_INF = -1e30


def _shard_indices(
    shard: jax.Array, n: int, seq_local: int, zigzag: bool
) -> jax.Array:
    """Global positions of ``shard``'s local rows ((seq_local,) int32)."""
    if zigzag:
        # Device i holds stripes i and 2n-1-i (each seq_local//2 long):
        # the mirror pairing balances causal work across the ring.
        stripe = seq_local // 2
        low = shard * stripe + jnp.arange(stripe, dtype=jnp.int32)
        high = (2 * n - 1 - shard) * stripe + jnp.arange(stripe, dtype=jnp.int32)
        return jnp.concatenate([low, high])
    return shard * seq_local + jnp.arange(seq_local, dtype=jnp.int32)


def _block_attend(q, k, v, q_idx, k_idx, scale, causal, window=None):
    """Score one (local-q, rotating-k) block pair; return (m, l, o) partials.

    Shapes: q (B,H,Sq,D), k/v (B,H,Sk,D); ``q_idx``/``k_idx`` are the
    GLOBAL sequence positions of each local row ((Sq,)/(Sk,) int32) — index
    vectors rather than offsets so non-contiguous (zigzag-striped) layouts
    mask correctly.  ``window`` adds the sliding-band upper edge (row sees
    column iff ``0 <= q - k < window``).  Matmul inputs stay in the input
    dtype (bf16 on TPU — the MXU's native path; casting to f32 first costs
    3-4x, same lesson as the flash kernel) with f32 accumulation; softmax
    statistics are f32.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_idx[:, None] >= k_idx[None, :]
        if window is not None:
            mask &= q_idx[:, None] - k_idx[None, :] < window
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (B,H,Sq,1)
    p = jnp.exp(s - m)
    if causal:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m, l, o


def _hop_needed(q_idx, k_idx, window):
    """Whether a (q-shard, k-shard) hop intersects the visible band.

    ``min(k) <= max(q)`` kills hops wholly in the future; with a window,
    ``max(k) > min(q) - window`` kills hops wholly behind the band — the
    shard-level analog of the kernels' ``_band_tile_needed``, exact for
    contiguous layouts and conservative-but-correct for striped ones.
    """
    needed = jnp.min(k_idx) <= jnp.max(q_idx)
    if window is not None:
        needed = jnp.logical_and(
            needed, jnp.max(k_idx) > jnp.min(q_idx) - window
        )
    return needed


def _ring_steps(n: int, seq_local: int, window, zigzag: bool) -> int:
    """Number of ring hops that can carry in-band work.

    Contiguous (non-zigzag) layout with a sliding window: device ``i``'s
    queries span ``[i*L, (i+1)*L)`` and their band reaches back at most
    ``window - 1`` keys, so only the own shard plus the previous
    ``ceil((window-1)/L)`` shards matter — the scan runs
    ``min(n, (window-2)//L + 2)`` steps instead of ``n``.  Striped
    (zigzag) shards interleave early and late stripes, so every hop may
    carry band work: full ``n`` steps.
    """
    if window is None or zigzag:
        return n
    return max(1, min(n, (window - 2) // seq_local + 2))


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    scale: float | None = None,
    zigzag: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Per-shard body: call under ``shard_map`` with seq-sharded (B,H,S/n,D).

    Step ``t`` holds the K/V shard that originated on device
    ``(my_index - t) mod n``; after scoring, the shard is passed to the next
    device in the ring.  With ``zigzag=True`` the local shard is assumed to
    be the striped layout produced by :func:`stripe_sequence` (device i owns
    stripes i and 2n-1-i), which load-balances causal masking across the
    ring — without it, early-ring devices idle while late ones attend.

    ``window`` masks to the sliding causal band; on the contiguous layout
    the ring then runs only the ``_ring_steps`` hops that can carry band
    work (the banded ring), and hops wholly outside any local band skip
    their matmuls.
    """
    n = lax.axis_size(axis_name)
    my_index = lax.axis_index(axis_name)
    seq_local = q.shape[2]
    head_dim = q.shape[3]
    scale = head_dim**-0.5 if scale is None else scale

    def shard_indices(shard: jax.Array) -> jax.Array:
        return _shard_indices(shard, n, seq_local, zigzag)

    q_idx = shard_indices(my_index)

    def step(carry, t):
        m_prev, l_prev, acc_prev, k_cur, v_cur = carry
        src = jnp.mod(my_index - t, n)
        k_idx = shard_indices(src)

        def attend(_):
            return _block_attend(
                q, k_cur, v_cur, q_idx, k_idx, scale, causal, window
            )

        if causal and (not zigzag or window is not None):
            # A fully-masked K/V shard (strictly future, or — windowed —
            # wholly behind the band): skip its matmuls.  The ring is
            # lockstep (every step ends at a ppermute), so this saves
            # FLOPs/energy on the skipping devices, not wall-clock —
            # latency stays bound by the device still attending.  Zigzag
            # striping is the wall-clock fix for unwindowed causal: every
            # (q-shard, k-shard) pair then carries ~equal causal work, so
            # no step has an idle device (and no pair is fully masked, so
            # no skip applies).  The windowed wall-clock fix is the
            # truncated scan below.
            def skip(_):
                stat_shape = q.shape[:3] + (1,)
                return (
                    jnp.full(stat_shape, _NEG_INF, jnp.float32),
                    jnp.zeros(stat_shape, jnp.float32),
                    jnp.zeros(q.shape, jnp.float32),
                )

            needed = _hop_needed(q_idx, k_idx, window)
            m_blk, l_blk, o_blk = lax.cond(needed, attend, skip, None)
        else:
            m_blk, l_blk, o_blk = attend(None)
        m_new = jnp.maximum(m_prev, m_blk)
        alpha_prev = jnp.exp(m_prev - m_new)
        alpha_blk = jnp.exp(m_blk - m_new)
        l_new = l_prev * alpha_prev + l_blk * alpha_blk
        acc_new = acc_prev * alpha_prev + o_blk * alpha_blk
        # Rotate K/V one hop around the ring (skipped result unused on the
        # last step but keeps the scan body uniform; XLA overlaps the
        # ppermute with the next step's einsum).
        k_next = ring_permute(k_cur, axis_name, shift=1)
        v_next = ring_permute(v_cur, axis_name, shift=1)
        return (m_new, l_new, acc_new, k_next, v_next), ()

    steps = _ring_steps(n, seq_local, window if causal else None, zigzag)
    shape = q.shape[:3] + (1,)
    m0 = jnp.full(shape, _NEG_INF, jnp.float32)
    l0 = jnp.zeros(shape, jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    (m, l, acc, _, _), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(steps)
    )
    return (acc / jnp.maximum(l, 1e-37)).astype(q.dtype)


# --------------------------------------------------------------------- #
# Ring flash: the Pallas kernels do each (q-shard, k-shard) block pair
# --------------------------------------------------------------------- #
#
# The einsum ring above materialises an (S/n x S/n) f32 score block per
# step — fine at moderate lengths, but the per-device memory still grows
# quadratically in the local shard.  The flash ring keeps the kernels'
# O(S/n * D) footprint: the forward merges per-block flash outputs with a
# log-sum-exp running merge, and the backward makes a second ring pass
# calling the FlashAttention-2 kernels per block with the GLOBAL softmax
# statistics (lse, delta) — dk/dv partials rotate around the ring with
# their k/v shards.  Position vectors (attention.py) make the causal mask
# correct for striped/rotated layouts where block offsets mean nothing.


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, zigzag, interpret,
                         window=None):
    n = lax.axis_size(axis_name)
    my_index = lax.axis_index(axis_name)
    seq_local = q.shape[2]
    q_idx = _shard_indices(my_index, n, seq_local, zigzag)
    stat_shape = q.shape[:3] + (1,)

    def step(carry, t):
        o_run, lse_run, k_cur, v_cur = carry
        src = jnp.mod(my_index - t, n)
        k_idx = _shard_indices(src, n, seq_local, zigzag)

        def attend(_):
            # f32 block outputs: the merge below sums one partial per hop
            # and must not pay a bf16 rounding at each one.
            return _flash_forward(
                q, k_cur, v_cur, q_idx, k_idx, causal, None, None, interpret,
                out_dtype=jnp.float32, window=window,
            )

        if causal and (not zigzag or window is not None):
            # A fully-masked K/V shard (strictly future, or wholly behind
            # the band): skip its kernels (the lockstep ring still waits
            # on the ppermute either way).
            def skip(_):
                return (
                    jnp.zeros(q.shape, jnp.float32),
                    jnp.full(stat_shape, _NEG_INF, jnp.float32),
                )

            needed = _hop_needed(q_idx, k_idx, window)
            o_blk, lse_blk = lax.cond(needed, attend, skip, None)
        else:
            o_blk, lse_blk = attend(None)

        # Merge the normalised block output into the running output:
        # out = sum_blk exp(lse_blk - lse_global) * o_blk.  All statistics
        # are finite (_NEG_INF, not -inf), so no NaN guards are needed.
        lse_new = jnp.logaddexp(lse_run, lse_blk)
        w_run = jnp.exp(lse_run - lse_new)
        w_blk = jnp.exp(lse_blk - lse_new)
        o_new = o_run * w_run + o_blk * w_blk
        k_next = ring_permute(k_cur, axis_name, shift=1)
        v_next = ring_permute(v_cur, axis_name, shift=1)
        return (o_new, lse_new, k_next, v_next), ()

    steps = _ring_steps(n, seq_local, window if causal else None, zigzag)
    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full(stat_shape, _NEG_INF, jnp.float32)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(steps))
    return o.astype(q.dtype), lse


def _ring_flash_bwd_pass(q, k, v, out, lse, g, axis_name, causal, zigzag,
                         interpret, window=None):
    n = lax.axis_size(axis_name)
    my_index = lax.axis_index(axis_name)
    seq_local = q.shape[2]
    q_idx = _shard_indices(my_index, n, seq_local, zigzag)
    # delta = rowsum(dO * O) is loop-invariant: compute once, not per hop.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )

    def step(carry, t):
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        src = jnp.mod(my_index - t, n)
        k_idx = _shard_indices(src, n, seq_local, zigzag)

        def attend(_):
            # f32 per-hop gradient partials (grad_dtype): n bf16 roundings
            # per accumulator would otherwise stack up around the ring.
            return _flash_backward(
                q, k_cur, v_cur, out, lse, g, q_idx, k_idx, causal, interpret,
                delta=delta, grad_dtype=jnp.float32, window=window,
            )

        if causal and (not zigzag or window is not None):
            def skip(_):
                return (
                    jnp.zeros(q.shape, jnp.float32),
                    jnp.zeros(k.shape, jnp.float32),
                    jnp.zeros(v.shape, jnp.float32),
                )

            needed = _hop_needed(q_idx, k_idx, window)
            dq_blk, dk_blk, dv_blk = lax.cond(needed, attend, skip, None)
        else:
            dq_blk, dk_blk, dv_blk = attend(None)

        dq_acc = dq_acc + dq_blk
        dk_cur = dk_cur + dk_blk
        dv_cur = dv_cur + dv_blk
        # dk/dv partials ride the ring WITH their k/v shards; after n
        # rotations each shard (and its accumulated gradient) is home.
        k_next = ring_permute(k_cur, axis_name, shift=1)
        v_next = ring_permute(v_cur, axis_name, shift=1)
        dk_next = ring_permute(dk_cur, axis_name, shift=1)
        dv_next = ring_permute(dv_cur, axis_name, shift=1)
        return (dq_acc, k_next, v_next, dk_next, dv_next), ()

    steps = _ring_steps(n, seq_local, window if causal else None, zigzag)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(steps)
    )
    if steps < n:
        # The truncated scan leaves each dk/dv partial ``steps`` hops past
        # its home device; one ppermute (a single collective, whatever the
        # shift) re-homes them — still far cheaper than the n - steps
        # skipped kernel hops.
        dk = ring_permute(dk, axis_name, shift=n - steps)
        dv = ring_permute(dv, axis_name, shift=n - steps)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, zigzag, interpret, window):
    out, _ = _ring_flash_fwd_pass(
        q, k, v, axis_name, causal, zigzag, interpret, window
    )
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, zigzag, interpret, window):
    out, lse = _ring_flash_fwd_pass(
        q, k, v, axis_name, causal, zigzag, interpret, window
    )
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, zigzag, interpret, window,
                        residuals, g):
    q, k, v, out, lse = residuals
    return _ring_flash_bwd_pass(
        q, k, v, out, lse, g, axis_name, causal, zigzag, interpret, window
    )


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    zigzag: bool = False,
    interpret: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Per-shard ring attention through the Pallas flash kernels.

    Same contract as :func:`ring_attention` (call under ``shard_map`` with
    seq-sharded (B, H, S/n, D)), but each (q-shard, k-shard) pair runs the
    flash kernel instead of a dense einsum, so per-device memory stays
    O(S/n · D) at any length, forward AND backward (a second ring pass
    recomputes per-block gradients from the global softmax statistics).
    ``window`` masks to the sliding band and (contiguous layout) truncates
    the ring to the hops that can carry band work.
    """
    if interpret is None:
        interpret = default_interpret()
    return _ring_flash(q, k, v, axis_name, causal, zigzag, interpret, window)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    window: int | None = None,
    sinks: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-shard Ulysses (all-to-all) sequence parallelism.

    Call under ``shard_map`` with seq-sharded (B, H, S/n, D).  Two
    all-to-alls swap shard ownership sequence<->heads: each device runs
    the flash kernel over the FULL sequence for H/n of the heads, then
    swaps back.  Communication is 2 all-to-alls of O(B·H·S·D/n) per
    device (vs the ring's n ppermute hops); because the local attention
    sees the whole sequence with contiguous positions, the banded
    windowed grids AND attention sinks compose unchanged — this is the
    sinks × sequence-parallelism path the rotating ring cannot offer.

    Head divisibility: local heads (H after any tensor sharding) must be
    divisible by the axis size.  GQA kv tensors with fewer heads are
    repeated up to H first — acceptable at Ulysses' communication scale,
    where kv bytes already cross the interconnect.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return flash_attention(
            q, k, v, causal=causal, window=window, sinks=sinks,
            interpret=interpret,
        )
    h_q, h_kv = q.shape[1], k.shape[1]
    if h_q % n:
        raise ValueError(
            f"ulysses needs local heads ({h_q}) divisible by the "
            f"'{axis_name}' axis ({n})"
        )
    if h_kv != h_q:
        group = h_q // h_kv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    # (B, H, S/n, D) -> (B, H/n, S, D): heads scatter, sequence gathers.
    q = all_to_all(q, axis_name, split_axis=1, concat_axis=2)
    k = all_to_all(k, axis_name, split_axis=1, concat_axis=2)
    v = all_to_all(v, axis_name, split_axis=1, concat_axis=2)
    out = flash_attention(
        q, k, v, causal=causal, window=window, sinks=sinks,
        interpret=interpret,
    )
    return all_to_all(out, axis_name, split_axis=2, concat_axis=1)


def _stripe_permutation(seq_len: int, n: int) -> jax.Array:
    """Index vector mapping natural order -> zigzag-striped order.

    The sequence splits into 2n stripes; device i's contiguous shard under
    ``P(..., axis_name, ...)`` becomes [stripe i ; stripe 2n-1-i], pairing
    a cheap (early) stripe with an expensive (late) one on every device.
    """
    import numpy as np

    if seq_len % (2 * n):
        raise ValueError(
            f"zigzag striping needs seq_len divisible by 2*n ({2 * n}); "
            f"got {seq_len} — pad the sequence or pass zigzag=False"
        )
    stripe = seq_len // (2 * n)
    order = []
    for device in range(n):
        order.extend(range(device * stripe, (device + 1) * stripe))
        order.extend(range((2 * n - 1 - device) * stripe, (2 * n - device) * stripe))
    return jnp.asarray(np.asarray(order, dtype=np.int32))


def stripe_sequence(x: jax.Array, n: int, axis: int = 2) -> jax.Array:
    """Permute ``axis`` into the zigzag layout for an ``n``-device ring."""
    return jnp.take(x, _stripe_permutation(x.shape[axis], n), axis=axis)


def unstripe_sequence(x: jax.Array, n: int, axis: int = 2) -> jax.Array:
    """Inverse of :func:`stripe_sequence`."""
    perm = _stripe_permutation(x.shape[axis], n)
    return jnp.take(x, jnp.argsort(perm), axis=axis)


def sequence_parallel_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = "seq",
    batch_axes: tuple[str, ...] = ("data", "fsdp"),
    head_axis: str | None = "tensor",
    zigzag: bool | None = None,
    impl: str | None = None,
    window: int | None = None,
    sinks: int = 0,
) -> jax.Array:
    """Global entry: (B, H, S, D) arrays -> ring attention over ``mesh``.

    Batch shards over the data axes, heads over tensor, sequence around the
    ring — composing context parallelism with DP/TP in one shard_map.

    ``zigzag`` (default: on for unwindowed causal) permutes the sequence
    into the striped layout before sharding and back after, so causal work
    balances across the ring instead of serialising on the last device; XLA
    lowers the permutes to collective data movement alongside the resharding
    it already performs for ``P(..., seq, ...)``.

    ``window`` masks to the sliding causal band (long-context × sequence
    parallelism — the banded ring).  The default layout is then contiguous,
    NOT zigzag: a band of width ``w`` gives every query the same work
    regardless of position (no causal imbalance to stripe away), and the
    contiguous layout lets the ring truncate to
    ``min(n, ceil((w-1)/(S/n)) + 1)`` hops instead of ``n``
    (``_ring_steps``).  Explicit ``zigzag=True`` still composes with the
    window (full ``n`` hops, positions mask exactly).

    ``impl``: ``"flash"`` runs each (q-shard, k-shard) block pair through
    the Pallas kernels (O(S/n·D) per-device memory, fwd and bwd),
    ``"einsum"`` uses the fused dense block path, ``"ulysses"`` swaps
    shard ownership sequence<->heads with two all-to-alls and runs the
    full-sequence flash kernel on H/n local heads (needs head
    divisibility; the only impl that composes with ``sinks``); default
    auto-selects flash on TPU.
    """
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires causal")
    n = mesh.shape[axis_name]
    if impl is None:
        impl = "flash" if on_tpu() else "einsum"
    if impl not in ("flash", "einsum", "ulysses"):
        raise ValueError(
            f"impl must be 'flash', 'einsum', or 'ulysses', got {impl!r}"
        )
    if sinks and impl != "ulysses":
        raise ValueError(
            "sinks require impl='ulysses' (the rotating ring would need "
            "shard 0's sink slab resident on every hop)"
        )
    spec = P(batch_axes, head_axis, axis_name, None)
    if impl == "ulysses":
        # Ulysses keeps the contiguous layout (full sequence local after
        # the swap): zigzag striping has nothing to balance.
        body = functools.partial(
            ulysses_attention, axis_name=axis_name, causal=causal,
            window=window, sinks=sinks,
        )
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    if zigzag is None:
        zigzag = (
            causal and n > 1 and q.shape[2] % (2 * n) == 0 and window is None
        )
    if zigzag:
        q = stripe_sequence(q, n)
        k = stripe_sequence(k, n)
        v = stripe_sequence(v, n)
    body = ring_flash_attention if impl == "flash" else ring_attention
    ring = functools.partial(
        body, axis_name=axis_name, causal=causal, zigzag=zigzag, window=window
    )
    out = jax.shard_map(
        ring,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
    if zigzag:
        out = unstripe_sequence(out, n)
    return out
