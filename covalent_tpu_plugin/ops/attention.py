"""Attention: XLA reference path + Pallas TPU flash-attention kernel.

``mha_reference`` is the semantics oracle — plain einsum attention with a
float32 softmax, fully fused by XLA, O(S^2) memory.  ``flash_attention`` is
the memory-efficient Pallas kernel: query blocks stream over key/value
blocks with an online softmax, so the S×S score matrix never materialises
in HBM (activations stay in VMEM, scores live only as a (BQ, BK) tile).

Kernel layout (per pallas_guide.md):
  grid = (batch, heads, S // BQ); each program owns one query tile and
  fori-loops over key tiles, carrying (running max, running sum, output
  accumulator) in f32.  Causal masking prunes the loop bound so the kernel
  does ~half the work of the dense path.

The backward pass is likewise Pallas (FlashAttention-2 style): the forward
saves only the per-row log-sum-exp (B, H, S, 1) — not the S×S probabilities
— and two backward kernels recompute each probability tile from (q, k, lse)
on the fly: one accumulates dk/dv sweeping query tiles, one accumulates dq
sweeping key tiles.  Backward HBM stays O(S·D), the same as forward, where
the dense path's backward would materialise O(S²) probabilities.

On an explicit CPU platform the same kernels run in interpreter mode, which
is what the CPU test tier exercises; a backend that fails to initialise
raises instead of sliding there (see ``on_tpu``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Finite stand-in for -inf in masked scores (finite so downstream
#: exp/logaddexp arithmetic can never produce NaN).  Public: the model's
#: decode path masks with the same constant.
NEG_INF = -1e30
_NEG_INF = NEG_INF

# Forward tile winners at S=4096 from an earlier v5e sweep; not re-measured
# on this installation.
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 1024


def _pick_windowed_blocks(seq_len_q: int, seq_len_k: int,
                          window: int) -> tuple[int, int]:
    """Forward-tile choice for the BANDED (windowed) grids.

    The band run is quantised to whole key tiles, so tile choice trades
    band tightness (smaller BK wastes fewer out-of-band columns) against
    MXU/overhead efficiency (larger tiles amortise better).  The
    constants came from an earlier v5e sweep over S = 4k..16k — (512, 512)
    for w <= 512, (1024, 1024) for wider bands — and have not been
    re-measured on this installation.  Explicit ``block_q``/``block_k``
    args always override.
    """
    if window <= 512:
        return 512, 512
    return 1024, 1024


def _gqa_group(q: jax.Array, k: jax.Array) -> int:
    """Query-heads-per-kv-head ratio; validates the GQA head contract."""
    h_q, h_kv = q.shape[1], k.shape[1]
    if h_kv == 0 or h_q % h_kv:
        raise ValueError(
            f"GQA needs query heads ({h_q}) divisible by kv heads ({h_kv})"
        )
    return h_q // h_kv


def on_tpu() -> bool:
    """Whether the default backend is a TPU.

    A backend that fails to initialise raises out of here: answering
    ``False`` would quietly send every caller down the interpreter or the
    dense-reference road on a machine that was meant to have a chip.
    """
    return jax.devices()[0].platform == "tpu"


def default_interpret() -> bool:
    """Pallas mode for a call that did not pick one: compiled (Mosaic) on
    a TPU, interpreter on an explicit CPU platform, an error anywhere else
    — the kernels use TPU memory spaces no other compiler lowers."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"flash attention kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {platform!r}"
    )


def _band_visible(qpos, kpos, window: int | None, sinks: int = 0):
    """Causal(-band) visibility on broadcastable position grids: row sees
    column iff ``q >= k`` and (windowed) ``q - k < window``, OR — with
    ``sinks`` (StreamingLLM attention sinks) — ``k < sinks`` and
    ``q >= k``.  The ONE definition of the band, shared by every kernel
    and the dense oracle."""
    causal_ok = qpos >= kpos
    if window is None:
        return causal_ok
    in_band = qpos - kpos < window
    if sinks:
        in_band = jnp.logical_or(in_band, kpos < sinks)
    return jnp.logical_and(causal_ok, in_band)


def _band_tile_needed(qpos_tile, kpos_tile, causal: bool, window: int | None,
                      sinks: int = 0):
    """Whether a (query tile, key tile) pair intersects the visible band.

    ``min(k) <= max(q)`` kills tiles wholly in the future; with a window,
    ``max(k) > min(q) - window`` kills tiles wholly behind the band —
    unless the tile holds sink columns (``min(k) < sinks``), which stay
    visible at any distance.  The same bounds serve all three sweeps (for
    dk/dv the roles read swapped but the inequalities are algebraically
    identical).
    """
    needed = True if not causal else (
        jnp.min(kpos_tile) <= jnp.max(qpos_tile)
    )
    if window is not None:
        behind_ok = jnp.max(kpos_tile) > jnp.min(qpos_tile) - window
        if sinks:
            behind_ok = jnp.logical_or(behind_ok, jnp.min(kpos_tile) < sinks)
        needed = jnp.logical_and(needed, behind_ok)
    return needed


def diffusion_visible(qn, kn, seq: int, block: int):
    """Block-diffusion visibility (BD3-LM, arXiv:2503.09573) over the
    ``[noisy ; clean]`` concatenation of ``2 * seq`` positions, on
    broadcastable index grids: position ``n`` lies in block
    ``(n % seq) // block`` of its half.  A noisy query sees the noisy keys
    of its own block and the clean keys of earlier blocks; a clean query
    sees the clean keys of its block and earlier ones, and no noisy key."""
    q_noisy, k_noisy = qn < seq, kn < seq
    qb, kb = (qn % seq) // block, (kn % seq) // block
    return jnp.where(
        q_noisy, jnp.where(k_noisy, qb == kb, kb < qb),
        jnp.logical_and(jnp.logical_not(k_noisy), kb <= qb))


def _check_window(window, causal, sinks: int = 0) -> None:
    if sinks:
        if sinks < 0:
            raise ValueError(f"sinks must be >= 0, got {sinks}")
        if window is None:
            raise ValueError("sinks (attention sinks) require a window")
    if window is None:
        return
    if not causal:
        raise ValueError("window (sliding-window attention) requires causal")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


# --- Band-only grid (windowed attention, contiguous positions) -----------
#
# With a sliding window the visible band covers only ~S·w of the S² score
# matrix.  The `@pl.when` tile-skip alone saves the MXU work but the grid
# still *visits* (and DMAs) every K/V tile — at S=16k/w=1k that is ~8× of
# wasted HBM traffic.  When positions are the default contiguous
# arange, the tiles a query tile needs are statically a contiguous run of
# ~⌈(BQ+w)/BK⌉+1 key tiles, so the sweep dimension can be shrunk to that
# run with a q-tile-relative index_map.  The index_map clamps to the last
# real tile; the kernel decides liveness from grid ids + static block
# sizes (NOT the clamped DMA index), so a clamped duplicate tile is never
# double-counted.  Striped/ring position vectors fall back to the full
# grid with the @pl.when skip.


def _sink_tiles(sinks: int, block_k: int) -> int:
    """Number of leading key tiles holding sink columns."""
    return -(-sinks // block_k) if sinks else 0


def _banded_n_inner_kt(seq_q: int, seq_k: int, block_q: int, block_k: int,
                       window: int, sinks: int = 0) -> int | None:
    """Static length of the inner key-tile sweep for the banded forward/dq
    grids: a leading run of sink tiles plus the max number of key tiles
    any query tile's band touches (the band run starts after the sink
    run — overlapping tiles are visited once, by the sink run).
    Returns None when that covers the full sweep anyway (no gain)."""
    kt_full = seq_k // block_k
    nst = _sink_tiles(sinks, block_k)
    worst = 0
    for i in range(seq_q // block_q):
        lo = max(nst, (i * block_q - (window - 1)) // block_k)
        hi = min(kt_full - 1, ((i + 1) * block_q - 1) // block_k)
        if hi >= lo:
            worst = max(worst, hi - lo + 1)
    n_inner = nst + worst
    return n_inner if 0 < n_inner < kt_full else None


def _banded_n_inner_qt(seq_q: int, seq_k: int, block_q: int, block_k: int,
                       window: int) -> int | None:
    """Static length of the inner query-tile sweep for the banded dk/dv
    grid: the max number of query tiles any key tile's band touches."""
    qt_full = seq_q // block_q
    worst = 0
    for jk in range(seq_k // block_k):
        lo = (jk * block_k) // block_q
        hi = min(qt_full - 1, ((jk + 1) * block_k - 1 + window - 1) // block_q)
        if hi >= lo:
            worst = max(worst, hi - lo + 1)
    return worst if 0 < worst < qt_full else None


def _band_kt_lo(i, block_q: int, block_k: int, window: int, sinks: int = 0):
    """Traced first key tile of query tile ``i``'s band RUN (contiguous
    pos): with sinks the run starts after the sink tiles, which the
    leading sweep steps already visit."""
    lo = jnp.maximum(i * block_q - (window - 1), 0) // block_k
    nst = _sink_tiles(sinks, block_k)
    return jnp.maximum(lo, nst) if nst else lo


def _band_kt_live(i, jj, block_q: int, block_k: int, window: int,
                  kt_full: int, sinks: int = 0):
    """Whether inner step ``jj`` of query tile ``i`` is a live tile (vs.
    a clamped duplicate past the causal edge).  Steps below the sink-run
    length always map to their own (unclamped) tile, so the position
    check alone is exact for them."""
    nst = _sink_tiles(sinks, block_k)
    hi = jnp.minimum(((i + 1) * block_q - 1) // block_k, kt_full - 1)
    in_band = _band_kt_lo(i, block_q, block_k, window, sinks) + (jj - nst) <= hi
    if not nst:
        return in_band
    return jnp.where(jj < nst, True, in_band)


def _band_qt_lo(jk, block_q: int, block_k: int):
    """Traced first query tile of key tile ``jk``'s band (causal bound)."""
    return (jk * block_k) // block_q


def _band_kt_global(i, jj, block_q: int, block_k: int, window: int,
                    kt_full: int, sinks: int = 0):
    """Global key-tile index of inner step ``jj`` of query tile ``i`` —
    THE geometry both the sweep's index map and the interior test use, so
    a clamp/sink-run change cannot desync them."""
    nst = _sink_tiles(sinks, block_k)
    band_j = jnp.minimum(
        _band_kt_lo(i, block_q, block_k, window, sinks) + (jj - nst),
        kt_full - 1,
    )
    return jnp.where(jj < nst, jj, band_j) if nst else band_j


def _band_qt_global(jk, qq, block_q: int, block_k: int, qt_full: int):
    """Global query-tile index of inner step ``qq`` of key tile ``jk``."""
    return jnp.minimum(_band_qt_lo(jk, block_q, block_k) + qq, qt_full - 1)


def _kt_interior(i, jj, block_q: int, block_k: int, window: int,
                 kt_full: int, sinks: int = 0):
    """Inner step ``jj`` of query tile ``i`` is an INTERIOR tile: every
    (q, k) pair it holds is visible, so the kernel may skip the band mask
    entirely (interior tiles are the dominant per-tile VPU cost once DMA
    is banded; the gain has not been measured on this installation).
    Exact only for
    contiguous positions, which is the precondition of the banded grid
    this is used with.  A tile is interior iff it is fully causal
    (``max_k <= min_q``) and fully inside the band (``min_k > max_q -
    window``) or fully inside the sink columns (``max_k < sinks``)."""
    kt_g = _band_kt_global(i, jj, block_q, block_k, window, kt_full, sinks)
    causal_full = (kt_g + 1) * block_k - 1 <= i * block_q
    window_full = kt_g * block_k > (i + 1) * block_q - 1 - window
    if sinks:
        window_full = jnp.logical_or(
            window_full, (kt_g + 1) * block_k <= sinks
        )
    return jnp.logical_and(causal_full, window_full)


def _qt_interior(jk, qq, block_q: int, block_k: int, window: int,
                 qt_full: int):
    """Interior test for the dk/dv sweep (roles swapped: key tile ``jk``
    fixed, inner step ``qq`` walks query tiles).  The banded dk/dv call
    never covers sink columns (the sinks split handles those in a
    separate full sweep), so only the causal and band bounds apply."""
    qt_g = _band_qt_global(jk, qq, block_q, block_k, qt_full)
    causal_full = qt_g * block_q >= (jk + 1) * block_k - 1
    window_full = (qt_g + 1) * block_q - 1 - jk * block_k < window
    return jnp.logical_and(causal_full, window_full)


def _banded_sweep_kt(seq_q: int, seq_k: int, block_q: int, block_k: int,
                     window, enabled: bool, sinks: int = 0):
    """(steps, tile_index_fn, band) for a key-tile inner sweep.

    Banded (a sink-tile run + the band's q-tile-relative clamped run)
    when it helps; otherwise the full sweep with identity indexing and
    ``band=None``.  The ONE constructor for the forward and dq grids, so
    clamp-bound or geometry changes happen in a single place.
    """
    kt_full = seq_k // block_k
    n_inner = (
        _banded_n_inner_kt(seq_q, seq_k, block_q, block_k, window, sinks)
        if enabled else None
    )
    if n_inner is None:
        return kt_full, (lambda i, jj: jj), None
    def tile(i, jj):
        return _band_kt_global(i, jj, block_q, block_k, window, kt_full,
                               sinks)

    return n_inner, tile, (block_q, block_k, kt_full)


def _banded_sweep_qt(seq_q: int, seq_k: int, block_q: int, block_k: int,
                     window, enabled: bool):
    """(steps, tile_index_fn, band) for the dk/dv query-tile inner sweep."""
    qt_full = seq_q // block_q
    n_inner = (
        _banded_n_inner_qt(seq_q, seq_k, block_q, block_k, window)
        if enabled else None
    )
    if n_inner is None:
        return qt_full, (lambda jk, qq: qq), None

    def tile(jk, qq):
        return _band_qt_global(jk, qq, block_q, block_k, qt_full)

    return n_inner, tile, (block_q, block_k, qt_full)


def _band_qt_live(jk, qq, block_q: int, block_k: int, window: int,
                  qt_full: int):
    hi = jnp.minimum(
        ((jk + 1) * block_k - 1 + window - 1) // block_q, qt_full - 1
    )
    return _band_qt_lo(jk, block_q, block_k) + qq <= hi


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    sinks: int = 0,
    diffusion_block: int | None = None,
) -> jax.Array:
    """Dense multi-head attention oracle.  Shapes: (B, H, S, D).

    Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``
    (``H_q % H_kv == 0``); each kv head serves a contiguous group of query
    heads, matching the flash kernel's convention.  ``window=w`` masks to
    the sliding causal band: row ``i`` sees columns ``(i-w, i]``;
    ``sinks=k`` (StreamingLLM) keeps the first ``k`` columns visible to
    every row alongside the band.  ``diffusion_block=B`` masks by
    :func:`diffusion_visible` in place of the causal band (``causal`` is
    then not read): the S positions are ``[noisy ; clean]``, S / 2 each.
    """
    _check_window(window, causal, sinks)
    if k.shape[1] != q.shape[1]:
        group = _gqa_group(q, k)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    d = q.shape[-1]
    scale = d**-0.5 if scale is None else scale
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if diffusion_block is not None:
        s_q = q.shape[2]
        qi = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_q), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_q), 1)
        scores = jnp.where(
            diffusion_visible(qi, ki, s_q // 2, diffusion_block), scores,
            _NEG_INF)
    elif causal:
        s_q, s_k = q.shape[2], k.shape[2]
        qi = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        scores = jnp.where(_band_visible(qi, ki, window, sinks), scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def _fwd_tile(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale, visible):
    """One key tile into a query tile's online softmax (running max ``m``,
    normaliser ``l``, accumulator ``acc``).  ``visible``: None (every pair
    of the tile is seen) or a thunk giving the (BQ, BK) mask, made after
    the scores."""
    # Matmul inputs stay in the INPUT dtype (bf16 on TPU) with f32
    # accumulation — casting to f32 first would push the hot matmuls
    # off the MXU's native bf16 path (measured 3-4x slower end to end).
    q = q_ref[0, 0, :, :]
    k_tile = k_ref[0, 0, :, :]
    v_tile = v_ref[0, 0, :, :]

    s = jax.lax.dot_general(
        q, k_tile,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (BQ, BK) f32

    if visible is not None:
        # Masking reads GLOBAL positions — (BQ,1) against (1,BK) —
        # so striped/rotated layouts (ring attention) mask correctly;
        # contiguous arange positions reproduce the classic diagonal.
        mask = visible()
        s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:]
    l_prev = l_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if visible is not None:
        p = jnp.where(mask, p, 0.0)
    m_ref[:] = m_new
    l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_tile.dtype), v_tile,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[:] = acc_ref[:] * alpha + pv


def _fwd_init(m_ref, l_ref, acc_ref):
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _fwd_finalise(o_ref, lse_ref, m_ref, l_ref, acc_ref):
    o_ref[0, 0, :, :] = (
        acc_ref[:] / jnp.maximum(l_ref[:], 1e-37)
    ).astype(o_ref.dtype)
    # Per-row log-sum-exp — the only softmax statistic the backward
    # kernels need to recompute any probability tile.  Kept in the
    # (BQ, 1) sublane layout the scratch already uses.
    lse_ref[0, 0, :, :] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-37))


def _dkdv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc,
               dv_acc, scale, visible):
    """One query tile's share of a key tile's dK and dV, the probability
    tile recomputed from (q, k, lse); ``visible`` as in ``_fwd_tile``."""
    q = q_ref[0, 0, :, :]
    k_tile = k_ref[0, 0, :, :]
    v_tile = v_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :]      # (BQ, 1) f32
    delta = delta_ref[0, 0, :, :]  # (BQ, 1) f32

    s = jax.lax.dot_general(
        q, k_tile,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (BQ, BK) f32
    p = jnp.exp(s - lse)  # exactly the forward's normalised probabilities
    if visible is not None:
        p = jnp.where(visible(), p, 0.0)

    # dV += P^T dO ; dP = dO V^T ; dS = P*(dP - delta)*scale ; dK += dS^T Q
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v_tile,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * scale
    dk_acc[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc, scale,
             visible):
    """One key tile's share of a query tile's dQ; ``visible`` as in
    ``_fwd_tile``."""
    q = q_ref[0, 0, :, :]
    k_tile = k_ref[0, 0, :, :]
    v_tile = v_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :]
    delta = delta_ref[0, 0, :, :]

    s = jax.lax.dot_general(
        q, k_tile,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.exp(s - lse)
    if visible is not None:
        p = jnp.where(visible(), p, 0.0)

    dp = jax.lax.dot_general(
        do, v_tile,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * scale
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k_tile.dtype), k_tile,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _flash_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref, lse_ref,
                  m_ref, l_ref, acc_ref,
                  *, causal: bool, scale: float, window: int | None = None,
                  sinks: int = 0, band: tuple[int, int, int] | None = None):
    """One (query tile, key tile) grid cell.

    The key-tile index is the *innermost* grid dimension, so for a fixed
    query tile the kernel runs over key tiles sequentially while the online
    softmax state (running max ``m``, normaliser ``l``, accumulator ``acc``)
    persists in VMEM scratch — only one (BQ, BK) score tile and one K/V tile
    are ever resident, which is what lets sequence length scale far past
    VMEM.  Pallas double-buffers the K/V tile DMAs across grid steps.
    """
    kt = pl.program_id(3)
    num_k_tiles = pl.num_programs(3)

    pl.when(kt == 0)(lambda: _fwd_init(m_ref, l_ref, acc_ref))

    # A tile whose every key position is in the future of every query
    # position contributes nothing under causal masking: skip its MXU work.
    # The bound check reads the POSITION tiles, so it is exact for the
    # default contiguous layout (reproducing the classic above-diagonal
    # skip, ~2x fewer ops) and conservative-but-correct for arbitrary
    # ring/striped position vectors.
    needed = _band_tile_needed(
        qpos_ref[:, :], kpos_ref[:, :], causal, window, sinks
    )
    if band is not None:
        # Banded grid: the inner sweep visits only the band's tile run; a
        # step past the causal edge DMA'd a clamped duplicate whose
        # position tile would wrongly read "needed" — liveness must come
        # from grid ids + static geometry, never the DMA'd positions.
        block_q, block_k, kt_full = band
        needed = jnp.logical_and(
            needed,
            _band_kt_live(pl.program_id(2), kt, block_q, block_k, window,
                          kt_full, sinks),
        )

    def _tile_body(masked: bool):
        _fwd_tile(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale, (
            lambda: _band_visible(qpos_ref[:, :], kpos_ref[:, :], window,
                                  sinks)) if causal and masked else None)

    if band is None:
        pl.when(needed)(lambda: _tile_body(True))
    else:
        # Two-branch banded cell: INTERIOR tiles (statically fully
        # visible — exact for the contiguous positions the banded grid
        # requires) skip the mask compute and both (BQ, BK) selects; only
        # band-edge tiles pay the masked path.
        interior = jnp.logical_and(needed, _kt_interior(
            pl.program_id(2), kt, block_q, block_k, window, kt_full, sinks
        ))
        pl.when(interior)(lambda: _tile_body(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))(
            lambda: _tile_body(True)
        )

    pl.when(kt == num_k_tiles - 1)(
        lambda: _fwd_finalise(o_ref, lse_ref, m_ref, l_ref, acc_ref))


def _fit_block(requested: int, seq_len: int) -> int:
    """Largest power-of-two shrink of ``requested`` that divides seq_len.

    Floors at 128 (or the whole sequence when shorter): a length with a
    large odd factor (4098 = 2·3·683) would otherwise silently degrade to
    2-wide tiles — pathologically slow or rejected by Mosaic — where the
    pre-adaptive behavior was a clear "pad the sequence" error.
    """
    block = min(requested, seq_len)
    while block > 1 and seq_len % block:
        block //= 2
    floor = min(128, seq_len)
    if block < floor:
        raise ValueError(
            f"seq_len {seq_len} has no usable tile size (>= {floor}); "
            "pad the sequence to a multiple of 128"
        )
    return block


def _positions_2d(q_positions, k_positions, seq_len_q: int, seq_len_k: int):
    """Normalise optional (S,) position vectors to the kernels' layouts:
    query positions (S,1) — sublanes; key positions (1,S) — lanes."""
    if q_positions is None:
        q_positions = jnp.arange(seq_len_q, dtype=jnp.int32)
    if k_positions is None:
        k_positions = jnp.arange(seq_len_k, dtype=jnp.int32)
    qpos = jnp.asarray(q_positions, jnp.int32).reshape(seq_len_q, 1)
    kpos = jnp.asarray(k_positions, jnp.int32).reshape(1, seq_len_k)
    return qpos, kpos


def _flash_forward(
    q, k, v, q_positions, k_positions, causal: bool,
    block_q: int | None, block_k: int | None, interpret: bool,
    out_dtype=None, window: int | None = None, sinks: int = 0,
    scale: float | None = None,
):
    # Q and K share one width (the scores' contraction), V and the output
    # another: latent attention scores with 192 dims and mixes values of 128.
    batch, heads, seq_len, head_dim = q.shape
    value_dim = v.shape[3]
    seq_len_k = k.shape[2]
    scale = head_dim**-0.5 if scale is None else scale
    # Default (None) blocks adapt to the sequence: the tuned sweep winners
    # shrink by halving until they divide seq_len, so any even-ish length
    # works out of the box.  EXPLICIT blocks stay strict — a user-chosen
    # tile that doesn't divide is an error, not a silent re-tile.
    # Windowed (banded-grid) calls get their own per-shape winners: the
    # full-attention tiles are measurably wrong for a band (see
    # _pick_windowed_blocks).
    if window is not None and causal:
        win_bq, win_bk = _pick_windowed_blocks(seq_len, seq_len_k, window)
    else:
        win_bq, win_bk = _DEFAULT_BLOCK_Q, _DEFAULT_BLOCK_K
    if block_q is None:
        block_q = _fit_block(win_bq, seq_len)
    else:
        block_q = min(block_q, seq_len)
    if block_k is None:
        block_k = _fit_block(win_bk, seq_len_k)
    else:
        block_k = min(block_k, seq_len_k)
    if seq_len % block_q or seq_len_k % block_k:
        raise ValueError(
            f"seq lengths ({seq_len}, {seq_len_k}) must be divisible by "
            f"block sizes ({block_q}, {block_k}); pad the sequence"
        )

    group = _gqa_group(q, k)
    qpos, kpos = _positions_2d(q_positions, k_positions, seq_len, seq_len_k)
    contiguous = q_positions is None and k_positions is None
    # With sinks the inner sweep is a sink-tile run + the band run (two
    # contiguous runs, visited once each — overlaps fold into the sink run).
    steps, _kj, band = _banded_sweep_kt(
        seq_len, seq_len_k, block_q, block_k, window,
        window is not None and causal and contiguous, sinks,
    )
    grid = (batch, heads, seq_len // block_q, steps)

    def q_side(width):
        return pl.BlockSpec(
            (1, 1, block_q, width), lambda b, h, i, j: (b, h, i, 0))

    def k_side(width):
        # GQA: each query head reads its group's shared kv head (h // group).
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, h, i, j: (b, h // group, _kj(i, j), 0))

    qpos_spec = pl.BlockSpec((block_q, 1), lambda b, h, i, j: (i, 0))
    lse_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    kpos_spec = pl.BlockSpec((1, block_k), lambda b, h, i, j: (0, _kj(i, j)))
    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale, window=window,
        sinks=sinks, band=band,
    )
    flops_factor = 0.5 if causal else 1.0
    if window is not None:
        # The band covers ~S*(w+sinks) of the S^2 score matrix; feeding
        # the causal half-estimate to the compiler's cost model would
        # overstate a w<<S kernel by ~S/(2w) and skew latency-hiding.
        flops_factor = min(
            flops_factor, (window + sinks) / max(seq_len_k, 1)
        )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[q_side(head_dim), k_side(head_dim), k_side(value_dim),
                  qpos_spec, kpos_spec],
        out_specs=[q_side(value_dim), lse_spec],
        out_shape=[
            # out_dtype=f32 lets ring callers merge unrounded block partials
            jax.ShapeDtypeStruct(
                (batch, heads, seq_len, value_dim), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),        # running max
            pltpu.VMEM((block_q, 1), jnp.float32),        # running sum
            pltpu.VMEM((block_q, value_dim), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(2 * batch * heads * seq_len * seq_len_k
                      * (head_dim + value_dim) * flops_factor),
            bytes_accessed=int(2 * batch * heads * seq_len
                               * (head_dim + value_dim) * q.dtype.itemsize),
            transcendentals=int(batch * heads * seq_len * seq_len_k * flops_factor),
        ),
    )(q, k, v, qpos, kpos)
    return out, lse


# Backward tile edge: 1024 beat 512/256 at S=2048/4096/8192, d=64/128 in
# an earlier v5e sweep; not re-measured on this installation.
# _fit_block halves it to divide shorter or odd sequences.
_DEFAULT_BWD_BLOCK = 1024


def _flash_bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qpos_ref, kpos_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, causal: bool, scale: float, window: int | None = None,
    sinks: int = 0, band: tuple[int, int, int] | None = None,
    kt_offset: int = 0
):
    """One (kv head, key tile, group member, query tile) cell of the dk/dv
    sweep, grid (B, H_kv, KT, G, QT).

    The two innermost grid dimensions — query-head-group member and query
    tile — share one (kv head, key tile) output block, so the accumulators
    persist in VMEM scratch across the whole sweep and dk/dv sum over the
    query heads a GQA kv head serves (G = 1 degenerates to plain MHA).  The
    probability tile is recomputed from (q, k, lse) — never read from HBM.
    """
    gi = pl.program_id(3)
    qt = pl.program_id(4)
    num_q_tiles = pl.num_programs(4)
    last_group = pl.num_programs(3) - 1

    @pl.when(jnp.logical_and(gi == 0, qt == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # A query tile entirely in the past of this key tile contributes no
    # gradient under causal masking; the position-tile bound check is exact
    # for contiguous layouts and conservative for striped ones.
    needed = _band_tile_needed(
        qpos_ref[:, :], kpos_ref[:, :], causal, window, sinks
    )
    if band is not None:
        # Banded grid: liveness from grid ids + static geometry (clamped
        # duplicate tiles must not double-count) — see forward kernel.
        # kt_offset maps this call's local key-tile ids to global ones
        # (the sinks split runs the banded call on the post-sink tiles).
        block_q, block_k, qt_full = band
        needed = jnp.logical_and(
            needed,
            _band_qt_live(pl.program_id(2) + kt_offset, qt, block_q,
                          block_k, window, qt_full),
        )

    def _tile_body(masked: bool):
        _dkdv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc,
                   dv_acc, scale, (
                       lambda: _band_visible(qpos_ref[:, :], kpos_ref[:, :],
                                             window, sinks))
                   if causal and masked else None)

    if band is None:
        pl.when(needed)(lambda: _tile_body(True))
    else:
        # Interior tiles skip the band mask (see forward kernel); the
        # banded dk/dv call never covers sink columns (the sinks split
        # runs those separately), so _qt_interior needs no sinks case.
        interior = jnp.logical_and(needed, _qt_interior(
            pl.program_id(2) + kt_offset, qt, block_q, block_k, window,
            qt_full,
        ))
        pl.when(interior)(lambda: _tile_body(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))(
            lambda: _tile_body(True)
        )

    @pl.when(jnp.logical_and(gi == last_group, qt == num_q_tiles - 1))
    def _finalise():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qpos_ref, kpos_ref,
    dq_ref, dq_acc,
    *, causal: bool, scale: float, window: int | None = None,
    sinks: int = 0, band: tuple[int, int, int] | None = None
):
    """One (query tile, key tile) cell of the dq sweep (key tiles innermost)."""
    kt = pl.program_id(3)
    num_k_tiles = pl.num_programs(3)

    @pl.when(kt == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = _band_tile_needed(
        qpos_ref[:, :], kpos_ref[:, :], causal, window, sinks
    )
    if band is not None:
        block_q, block_k, kt_full = band
        needed = jnp.logical_and(
            needed,
            _band_kt_live(pl.program_id(2), kt, block_q, block_k, window,
                          kt_full, sinks),
        )

    def _tile_body(masked: bool):
        _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc,
                 scale, (
                     lambda: _band_visible(qpos_ref[:, :], kpos_ref[:, :],
                                           window, sinks))
                 if causal and masked else None)

    if band is None:
        pl.when(needed)(lambda: _tile_body(True))
    else:
        # Interior tiles skip the band mask (see forward kernel).
        interior = jnp.logical_and(needed, _kt_interior(
            pl.program_id(2), kt, block_q, block_k, window, kt_full, sinks
        ))
        pl.when(interior)(lambda: _tile_body(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))(
            lambda: _tile_body(True)
        )

    @pl.when(kt == num_k_tiles - 1)
    def _finalise():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, g, q_positions, k_positions, causal: bool,
    interpret: bool, delta=None, grad_dtype=None, window: int | None = None,
    sinks: int = 0, scale: float | None = None,
):
    """FlashAttention-2 backward: two Pallas sweeps, O(S·D) HBM."""
    batch, heads, seq_len, head_dim = q.shape
    value_dim = v.shape[3]  # V's, O's and dO's width; Q and K share head_dim
    kv_heads = k.shape[1]
    seq_len_k = k.shape[2]
    group = _gqa_group(q, k)
    scale = head_dim**-0.5 if scale is None else scale
    block_q = _fit_block(_DEFAULT_BWD_BLOCK, seq_len)
    block_k = _fit_block(_DEFAULT_BWD_BLOCK, seq_len_k)
    qpos, kpos = _positions_2d(q_positions, k_positions, seq_len, seq_len_k)

    # delta_i = rowsum(dO_i * O_i) — a cheap elementwise reduce XLA fuses;
    # kept (B, H, S, 1) to match the kernels' sublane layout.  Ring callers
    # precompute it once per training step (it is loop-invariant there).
    if delta is None:
        delta = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
            keepdims=True,
        )

    flops_factor = 0.5 if causal else 1.0
    if window is not None:
        # The band covers ~S*(w+sinks) of the S^2 score matrix; feeding
        # the causal half-estimate to the compiler's cost model would
        # overstate a w<<S kernel by ~S/(2w) and skew latency-hiding.
        flops_factor = min(flops_factor, (window + sinks) / max(seq_len_k, 1))
    cost = pl.CostEstimate(
        flops=int(batch * heads * seq_len * seq_len_k
                  * (6 * head_dim + 4 * value_dim) * flops_factor),
        bytes_accessed=int(4 * batch * heads * seq_len
                           * (head_dim + value_dim) * q.dtype.itemsize),
        transcendentals=int(batch * heads * seq_len * seq_len_k * flops_factor),
    )

    contiguous = q_positions is None and k_positions is None
    banded = window is not None and causal and contiguous
    qt_full = seq_len // block_q
    kt_full = seq_len_k // block_k

    # dk/dv sweep — grid (B, H_kv, KT, G, QT): group member + query tile are
    # innermost so one (kv head, key tile) output block accumulates across
    # every query head in its group (see kernel docstring).  With a window
    # the QT sweep shrinks to the band's query-tile run (see forward).
    # With sinks the sweep SPLITS: a sink KEY tile is read by every later
    # query tile (no band run exists for it), so the leading sink tiles
    # get their own full-QT-sweep call and the remaining tiles run the
    # banded grid with a key-tile offset — both calls write disjoint
    # dk/dv slabs that concatenate back to (B, H_kv, S, D).
    def run_dkdv(kt_offset, kt_n, qi, band, n_inner):
        """One dk/dv pallas_call over key tiles [kt_offset, kt_offset+kt_n)."""
        def q_side(width):
            return pl.BlockSpec(
                (1, 1, block_q, width),
                lambda b, h, i, gi, j: (b, h * group + gi, qi(i, j), 0))

        def k_in(width):
            return pl.BlockSpec(
                (1, 1, block_k, width),
                lambda b, h, i, gi, j: (b, h, i + kt_offset, 0))

        def k_out(width):
            return pl.BlockSpec(
                (1, 1, block_k, width), lambda b, h, i, gi, j: (b, h, i, 0))

        stat_spec_q = pl.BlockSpec(
            (1, 1, block_q, 1),
            lambda b, h, i, gi, j: (b, h * group + gi, qi(i, j), 0),
        )
        qpos_spec_q = pl.BlockSpec(
            (block_q, 1), lambda b, h, i, gi, j: (qi(i, j), 0)
        )
        kpos_spec_k = pl.BlockSpec(
            (1, block_k), lambda b, h, i, gi, j: (0, i + kt_offset)
        )
        return pl.pallas_call(
            functools.partial(
                _flash_bwd_dkdv_kernel, causal=causal, scale=scale,
                window=window, sinks=sinks, band=band, kt_offset=kt_offset,
            ),
            name="flash_bwd_dkdv",
            grid=(batch, kv_heads, kt_n, group, n_inner),
            in_specs=[q_side(head_dim), k_in(head_dim), k_in(value_dim),
                      q_side(value_dim), stat_spec_q, stat_spec_q,
                      qpos_spec_q, kpos_spec_k],
            out_specs=[k_out(head_dim), k_out(value_dim)],
            out_shape=[
                # grad_dtype=f32: ring callers sum one partial per hop and
                # must not pay a bf16 rounding at every hop
                jax.ShapeDtypeStruct(
                    (batch, kv_heads, kt_n * block_k, head_dim),
                    grad_dtype or k.dtype,
                ),
                jax.ShapeDtypeStruct(
                    (batch, kv_heads, kt_n * block_k, value_dim),
                    grad_dtype or v.dtype,
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, head_dim), jnp.float32),  # dk acc
                pltpu.VMEM((block_k, value_dim), jnp.float32),  # dv acc
            ],
            interpret=interpret,
            cost_estimate=cost,
        )(q, k, v, g, lse, delta, qpos, kpos)

    nst_bwd = _sink_tiles(sinks, block_k) if (banded and sinks) else 0
    n_inner_rem = (
        _banded_n_inner_qt(seq_len, seq_len_k, block_q, block_k, window)
        if 0 < nst_bwd < kt_full else None
    )
    if n_inner_rem is not None:
        # Sinks split: full sweep over the few sink tiles, banded sweep
        # (global geometry via kt_offset) over everything after them.
        def qi_rem(i, j):
            return jnp.minimum(
                _band_qt_lo(i + nst_bwd, block_q, block_k) + j, qt_full - 1
            )

        dk_s, dv_s = run_dkdv(0, nst_bwd, lambda i, j: j, None, qt_full)
        dk_r, dv_r = run_dkdv(
            nst_bwd, kt_full - nst_bwd, qi_rem,
            (block_q, block_k, qt_full), n_inner_rem,
        )
        dk = jnp.concatenate([dk_s, dk_r], axis=2)
        dv = jnp.concatenate([dv_s, dv_r], axis=2)
    else:
        n_inner_qt, _qi, band_kv = _banded_sweep_qt(
            seq_len, seq_len_k, block_q, block_k, window,
            banded and not sinks,
        )
        dk, dv = run_dkdv(0, kt_full, _qi, band_kv, n_inner_qt)

    # dq sweep — banded exactly like the forward (key tiles innermost).
    n_inner_kt, _kj, band_q = _banded_sweep_kt(
        seq_len, seq_len_k, block_q, block_k, window, banded, sinks
    )

    def q_side_i(width):
        return pl.BlockSpec(
            (1, 1, block_q, width), lambda b, h, i, j: (b, h, i, 0))

    def k_side_j(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, h, i, j: (b, h // group, _kj(i, j), 0))

    stat_spec_i = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    qpos_spec_i = pl.BlockSpec((block_q, 1), lambda b, h, i, j: (i, 0))
    kpos_spec_j = pl.BlockSpec((1, block_k), lambda b, h, i, j: (0, _kj(i, j)))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal, scale=scale, window=window,
            sinks=sinks, band=band_q,
        ),
        name="flash_bwd_dq",
        grid=(batch, heads, qt_full, n_inner_kt),
        in_specs=[q_side_i(head_dim), k_side_j(head_dim), k_side_j(value_dim),
                  q_side_i(value_dim), stat_spec_i, stat_spec_i, qpos_spec_i,
                  kpos_spec_j],
        out_specs=q_side_i(head_dim),
        out_shape=jax.ShapeDtypeStruct(q.shape, grad_dtype or q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # dq accumulator
        ],
        interpret=interpret,
        cost_estimate=cost,
    )(q, k, v, g, lse, delta, qpos, kpos)
    return dq, dk, dv


def _pos_zero(positions):
    """float0 cotangent for an (integer) position argument, or None."""
    if positions is None:
        return None
    return jnp.zeros(jnp.shape(positions), dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, q_positions, k_positions, causal, block_q, block_k,
           interpret, window, sinks, scale):
    out, _ = _flash_forward(
        q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret,
        window=window, sinks=sinks, scale=scale,
    )
    return out


def _flash_fwd(q, k, v, q_positions, k_positions, causal, block_q, block_k,
               interpret, window, sinks, scale):
    out, lse = _flash_forward(
        q, k, v, q_positions, k_positions, causal, block_q, block_k, interpret,
        window=window, sinks=sinks, scale=scale,
    )
    return out, (q, k, v, out, lse, q_positions, k_positions)


def _flash_bwd(causal, block_q, block_k, interpret, window, sinks, scale,
               residuals, g):
    q, k, v, out, lse, q_positions, k_positions = residuals
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, g, q_positions, k_positions, causal, interpret,
        window=window, sinks=sinks, scale=scale,
    )
    return dq, dk, dv, _pos_zero(q_positions), _pos_zero(k_positions)


_flash.defvjp(_flash_fwd, _flash_bwd)


# --- Block-diffusion grid ------------------------------------------------
#
# The block-diffusion mask (``diffusion_visible``) is no band over one
# position line: noisy queries see their own noisy block and the clean
# keys of earlier blocks, clean queries the clean keys up to their block.
# About a quarter of the (2S)^2 square is visible, and a causal grid over
# the concatenation would visit twice that.  So each sweep walks a static
# list of the live (query tile, key tile) pairs, prefetched into SMEM:
# grid (batch, heads, live pairs), the index maps read the pair's tiles,
# and the list's flags say where a query (dq, forward) or key (dK/dV)
# tile's run starts and ends and whether the tile is only partly visible
# (masked) or interior (every pair seen, no mask).  Tiles never straddle
# the two halves, so a tile's half is its index's and the mask is a
# comparison of block indices, fed in like the band's positions.

_FIRST, _LAST, _MASKED = 1, 2, 4


def diffusion_tiles(seq: int, block: int, block_q: int,
                    block_k: int) -> list[tuple[int, int, bool]]:
    """``(query tile, key tile, masked)`` for every tile of the block-
    diffusion mask over ``2 * seq`` positions that holds a visible pair,
    query tile by query tile; ``masked`` where not every pair of it is
    visible.  ``seq`` is a multiple of both tile sizes."""

    def span(tile: int, size: int):
        first = tile * size % seq
        return tile * size < seq, first // block, (first + size - 1) // block

    out = []
    for i in range(2 * seq // block_q):
        q_noisy, q_lo, q_hi = span(i, block_q)
        for j in range(2 * seq // block_k):
            k_noisy, k_lo, k_hi = span(j, block_k)
            if q_noisy and k_noisy:      # the same block
                live = k_lo <= q_hi and q_lo <= k_hi
                whole = q_lo == q_hi == k_lo == k_hi
            elif q_noisy:                # clean keys of earlier blocks
                live, whole = k_lo < q_hi, k_hi < q_lo
            elif not k_noisy:            # clean keys up to its own block
                live, whole = k_lo <= q_hi, k_hi <= q_lo
            else:                        # no clean query sees a noisy key
                continue
            if live:
                out.append((i, j, not whole))
    return out


def _diffusion_table(runs: list[tuple]) -> tuple:
    """Prefetched SMEM columns of a sweep's steps: each step's leading
    fields, then its flags (``_FIRST`` / ``_LAST`` of the run of steps
    that share the first field, ``_MASKED``)."""
    table = np.asarray([step[:-1] for step in runs], np.int32)
    flags = np.asarray([_MASKED if step[-1] else 0 for step in runs],
                       np.int32)
    lead = table[:, 0]
    flags[np.r_[True, lead[1:] != lead[:-1]]] |= _FIRST
    flags[np.r_[lead[1:] != lead[:-1], True]] |= _LAST
    return tuple(jnp.asarray(column) for column in table.T) + (
        jnp.asarray(flags),)


def _diffusion_mask(qblk_ref, kblk_ref, q_tile, k_tile, q_noisy_tiles: int,
                    k_noisy_tiles: int):
    """The (BQ, BK) mask of a partly visible tile: ``lo <= qb - kb <= hi``
    on the rows' and columns' block indices, ``(lo, hi)`` by the tiles'
    halves: (0, 0) noisy to noisy, (1, .) noisy to clean, (0, .) clean to
    clean."""
    k_noisy = k_tile < k_noisy_tiles
    lo = jnp.logical_and(q_tile < q_noisy_tiles,
                         jnp.logical_not(k_noisy)).astype(jnp.int32)
    hi = jnp.where(k_noisy, 0, jnp.iinfo(jnp.int32).max)
    behind = qblk_ref[:, :] - kblk_ref[:, :]
    return jnp.logical_and(behind >= lo, behind <= hi)


def _diffusion_fwd_kernel(qt_ref, kt_ref, flag_ref, q_ref, k_ref, v_ref,
                          qblk_ref, kblk_ref, o_ref, lse_ref, m_ref, l_ref,
                          acc_ref, *, scale: float, noisy: tuple[int, int]):
    """One live (query tile, key tile) step of the forward."""
    step = pl.program_id(2)
    flag = flag_ref[step]
    pl.when(flag & _FIRST != 0)(lambda: _fwd_init(m_ref, l_ref, acc_ref))

    def visible():
        return _diffusion_mask(qblk_ref, kblk_ref, qt_ref[step],
                               kt_ref[step], *noisy)

    for masked in (False, True):
        pl.when((flag & _MASKED != 0) == masked)(functools.partial(
            _fwd_tile, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale,
            visible if masked else None))
    pl.when(flag & _LAST != 0)(
        lambda: _fwd_finalise(o_ref, lse_ref, m_ref, l_ref, acc_ref))


def _diffusion_dq_kernel(qt_ref, kt_ref, flag_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, delta_ref, qblk_ref, kblk_ref,
                         dq_ref, dq_acc, *, scale: float,
                         noisy: tuple[int, int]):
    """One live (query tile, key tile) step of the dq sweep."""
    step = pl.program_id(2)
    flag = flag_ref[step]

    @pl.when(flag & _FIRST != 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def visible():
        return _diffusion_mask(qblk_ref, kblk_ref, qt_ref[step],
                               kt_ref[step], *noisy)

    for masked in (False, True):
        pl.when((flag & _MASKED != 0) == masked)(functools.partial(
            _dq_tile, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            dq_acc, scale, visible if masked else None))

    @pl.when(flag & _LAST != 0)
    def _finalise():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _diffusion_dkdv_kernel(kt_ref, gi_ref, qt_ref, flag_ref, q_ref, k_ref,
                           v_ref, do_ref, lse_ref, delta_ref, qblk_ref,
                           kblk_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                           scale: float, noisy: tuple[int, int]):
    """One live (key tile, group member, query tile) step of the dK/dV
    sweep: a key tile's run covers every query head its kv head serves."""
    step = pl.program_id(2)
    flag = flag_ref[step]

    @pl.when(flag & _FIRST != 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def visible():
        return _diffusion_mask(qblk_ref, kblk_ref, qt_ref[step],
                               kt_ref[step], *noisy)

    for masked in (False, True):
        pl.when((flag & _MASKED != 0) == masked)(functools.partial(
            _dkdv_tile, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            dk_acc, dv_acc, scale, visible if masked else None))

    @pl.when(flag & _LAST != 0)
    def _finalise():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _diffusion_blocks(q, block: int):
    """Each position's block index within its half, as the kernels read
    it: rows (2S, 1) and columns (1, 2S)."""
    n = q.shape[2]
    if n % 2:
        raise ValueError(f"the [noisy ; clean] rows must be even, got {n}")
    blocks = jnp.arange(n, dtype=jnp.int32) % (n // 2) // block
    return blocks.reshape(n, 1), blocks.reshape(1, n)


def _diffusion_fit(requested: int | None, default: int, half: int) -> int:
    """A tile size that divides each half (no tile straddles the two)."""
    size = _fit_block(default, half) if requested is None else requested
    if half % size:
        raise ValueError(
            f"tile {size} does not divide the half of {half} positions")
    return size


def _diffusion_cost(q, v, pairs: int, factor: int):
    """The compiler's cost model of a sweep over ``pairs`` visited score
    entries a head: ``factor`` FLOPs a pair for each of the two widths."""
    batch, heads, _, head_dim = q.shape
    return pl.CostEstimate(
        flops=int(batch * heads * pairs * factor * (head_dim + v.shape[3])),
        bytes_accessed=int(2 * batch * heads * q.shape[2]
                           * (head_dim + v.shape[3]) * q.dtype.itemsize),
        transcendentals=int(batch * heads * pairs),
    )


def _diffusion_forward(q, k, v, block: int, block_q, block_k,
                       interpret: bool, scale):
    batch, heads, n, head_dim = q.shape
    value_dim = v.shape[3]
    half = n // 2
    block_q = _diffusion_fit(block_q, _DEFAULT_BLOCK_Q, half)
    block_k = _diffusion_fit(block_k, _DEFAULT_BLOCK_K, half)
    scale = head_dim**-0.5 if scale is None else scale
    group = _gqa_group(q, k)
    live = diffusion_tiles(half, block, block_q, block_k)
    qt, kt, flags = _diffusion_table(live)
    qblk, kblk = _diffusion_blocks(q, block)

    def q_side(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, s, qt, kt, f: (b, h, qt[s], 0))

    def k_side(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, h, s, qt, kt, f: (b, h // group, kt[s], 0))

    out, lse = pl.pallas_call(
        functools.partial(_diffusion_fwd_kernel, scale=scale,
                          noisy=(half // block_q, half // block_k)),
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch, heads, len(live)),
            in_specs=[
                q_side(head_dim), k_side(head_dim), k_side(value_dim),
                pl.BlockSpec((block_q, 1),
                             lambda b, h, s, qt, kt, f: (qt[s], 0)),
                pl.BlockSpec((1, block_k),
                             lambda b, h, s, qt, kt, f: (0, kt[s])),
            ],
            out_specs=[
                q_side(value_dim),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, s, qt, kt, f: (b, h, qt[s], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, value_dim), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, n, value_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, n, 1), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=_diffusion_cost(
            q, v, len(live) * block_q * block_k, 2),
    )(qt, kt, flags, q, k, v, qblk, kblk)
    return out, lse


def _diffusion_backward(q, k, v, out, lse, g, block: int, interpret: bool,
                        scale):
    batch, heads, n, head_dim = q.shape
    value_dim = v.shape[3]
    kv_heads = k.shape[1]
    group = _gqa_group(q, k)
    half = n // 2
    block_q = _diffusion_fit(None, _DEFAULT_BWD_BLOCK, half)
    block_k = _diffusion_fit(None, _DEFAULT_BWD_BLOCK, half)
    scale = head_dim**-0.5 if scale is None else scale
    noisy = (half // block_q, half // block_k)
    qblk, kblk = _diffusion_blocks(q, block)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    live = diffusion_tiles(half, block, block_q, block_k)
    cost = _diffusion_cost(q, v, len(live) * block_q * block_k, 5)

    # dK/dV: key tile by key tile, and inside a key tile's run every
    # member of its kv head's group over the live query tiles.
    runs = sorted((j, gi, i, masked) for i, j, masked in live
                  for gi in range(group))
    kt, gi, qt, flags = _diffusion_table(runs)

    def q_in(width):
        return pl.BlockSpec(
            (1, 1, block_q, width),
            lambda b, h, s, kt, gi, qt, f: (b, h * group + gi[s], qt[s], 0))

    def k_in(width):
        return pl.BlockSpec((1, 1, block_k, width),
                            lambda b, h, s, kt, gi, qt, f: (b, h, kt[s], 0))

    dk, dv = pl.pallas_call(
        functools.partial(_diffusion_dkdv_kernel, scale=scale, noisy=noisy),
        name="flash_bwd_dkdv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(batch, kv_heads, len(runs)),
            in_specs=[
                q_in(head_dim), k_in(head_dim), k_in(value_dim),
                q_in(value_dim), q_in(1), q_in(1),
                pl.BlockSpec((block_q, 1),
                             lambda b, h, s, kt, gi, qt, f: (qt[s], 0)),
                pl.BlockSpec((1, block_k),
                             lambda b, h, s, kt, gi, qt, f: (0, kt[s])),
            ],
            out_specs=[k_in(head_dim), k_in(value_dim)],
            scratch_shapes=[
                pltpu.VMEM((block_k, head_dim), jnp.float32),
                pltpu.VMEM((block_k, value_dim), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        cost_estimate=cost,
    )(kt, gi, qt, flags, q, k, v, g, lse, delta, qblk, kblk)

    # dQ: query tile by query tile, as the forward walks them.
    qt, kt, flags = _diffusion_table(live)

    def q_side(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, s, qt, kt, f: (b, h, qt[s], 0))

    def k_side(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, h, s, qt, kt, f: (b, h // group, kt[s], 0))

    dq = pl.pallas_call(
        functools.partial(_diffusion_dq_kernel, scale=scale, noisy=noisy),
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch, heads, len(live)),
            in_specs=[
                q_side(head_dim), k_side(head_dim), k_side(value_dim),
                q_side(value_dim), q_side(1), q_side(1),
                pl.BlockSpec((block_q, 1),
                             lambda b, h, s, qt, kt, f: (qt[s], 0)),
                pl.BlockSpec((1, block_k),
                             lambda b, h, s, qt, kt, f: (0, kt[s])),
            ],
            out_specs=q_side(head_dim),
            scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        cost_estimate=cost,
    )(qt, kt, flags, q, k, v, g, lse, delta, qblk, kblk)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diffusion(q, k, v, block, block_q, block_k, interpret, scale):
    out, _ = _diffusion_forward(q, k, v, block, block_q, block_k, interpret,
                                scale)
    return out


def _flash_diffusion_fwd(q, k, v, block, block_q, block_k, interpret, scale):
    out, lse = _diffusion_forward(q, k, v, block, block_q, block_k,
                                  interpret, scale)
    return out, (q, k, v, out, lse)


def _flash_diffusion_bwd(block, block_q, block_k, interpret, scale,
                         residuals, g):
    q, k, v, out, lse = residuals
    return _diffusion_backward(q, k, v, out, lse, g, block, interpret, scale)


_flash_diffusion.defvjp(_flash_diffusion_fwd, _flash_diffusion_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    *,
    q_positions: jax.Array | None = None,
    k_positions: jax.Array | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
    sinks: int = 0,
    scale: float | None = None,
    diffusion_block: int | None = None,
) -> jax.Array:
    """Flash attention over (B, H, S, D) inputs.

    ``q`` and ``k`` share one width and ``v`` may have another (latent
    attention scores with 192 dims a head and mixes values of 128): the
    output and ``dv`` take ``v``'s width, ``dq`` and ``dk`` the scores'.
    ``scale`` multiplies the scores (default: the query width's inverse
    square root); a model whose scale carries more (YaRN's factor) passes
    its own.

    Grouped-query attention: ``k``/``v`` may have fewer heads than ``q``
    (``H_q % H_kv == 0``); kv head ``i`` serves query heads
    ``[i*G, (i+1)*G)``.  Gradients flow to the true kv shapes (dk/dv sum
    over each group) — no materialised ``repeat``.

    ``q_positions``/``k_positions`` ((S,) int32) override the causal mask's
    notion of position: row ``i`` attends column ``j`` iff
    ``q_positions[i] >= k_positions[j]``.  This is what lets ring attention
    run striped (zigzag) sequence layouts through the same kernels; the
    static above-diagonal tile skip applies only to the default contiguous
    positions.  ``k`` may also have a different sequence length than ``q``
    (ring K/V shards).

    ``interpret=None`` auto-selects: compiled Mosaic kernel on TPU,
    interpreter on an explicit CPU platform (the CPU-mesh test tier); a
    backend that fails to initialise raises.  Default (None) blocks (fwd
    512×1024, bwd 1024²) came from an earlier v5e sweep and have not been
    re-measured on this installation; they auto-shrink by halving to
    divide any sequence length, while explicitly passed blocks must
    divide the sequence exactly.

    ``window=w`` (sliding-window / Mistral-style local attention,
    requires ``causal``) restricts each query to the ``w`` most recent
    positions; with default contiguous positions the grids visit ONLY the
    band's tiles (compute and DMA scale O(S·w) instead of O(S²)).
    ``sinks=k`` (StreamingLLM attention sinks) keeps columns ``< k``
    visible to every row alongside the band; the forward and dq sweeps
    band as a sink-tile run + band run, and the dk/dv sweep splits into
    a full-sweep call over the sink key tiles plus a banded call over
    the rest, so all three sweeps stay O(S·w) with sinks on.

    ``diffusion_block=B`` masks by :func:`diffusion_visible` in place of
    ``causal``: the S rows of ``q``, ``k`` and ``v`` are a noisy and a
    clean copy of S / 2 positions, ``[noisy ; clean]``.  The three sweeps
    visit only the live tiles (:func:`diffusion_tiles`); ``block_q`` and
    ``block_k`` must divide each half.  No window, sinks or positions.
    """
    _check_window(window, causal, sinks)
    if interpret is None:
        interpret = default_interpret()
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"q and k must share the scores' width, got {q.shape[-1]} and "
            f"{k.shape[-1]}"
        )
    if diffusion_block is not None:
        if (window is not None or q_positions is not None
                or k_positions is not None or q.shape[2] != k.shape[2]):
            raise ValueError(
                "diffusion_block takes no window or positions, and keys "
                "as long as the queries")
        return _flash_diffusion(q, k, v, diffusion_block, block_q, block_k,
                                interpret, scale)
    return _flash(
        q, k, v, q_positions, k_positions, causal, block_q, block_k,
        interpret, window, sinks, scale,
    )


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = True,
    *,
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
    **kwargs,
) -> jax.Array:
    """Flash attention as a shard_map over (batch, heads) mesh axes.

    A ``pallas_call`` is opaque to XLA's sharding propagation: under a
    sharded ``jit`` the bare kernel forces all-gathers of Q/K/V to every
    device (measured 27 gathers in the compiled HLO of one call on a
    2×4 mesh).  Attention is embarrassingly parallel over batch and query
    heads, so this wrapper runs the kernel on each shard's local block
    instead — zero collectives in the forward pass.

    GQA under tensor parallelism: when the head axis divides ``H_kv`` the
    kv tensors shard right along with q (contiguous groups keep the
    q↔kv correspondence); when the head axis is *larger* than ``H_kv``
    (``tp % H_kv == 0``) kv arrives replicated and each shard slices the
    single kv head its query slab attends to.  The shard_map transpose
    rule psums the sliced-kv cotangents automatically in the backward.
    """
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    if head_axis not in mesh.shape:
        # No head axis on this mesh (e.g. a hand-built data-only Mesh):
        # shard over batch only, heads stay whole on every shard.
        head_axis = None
    tp = mesh.shape[head_axis] if head_axis else 1
    h_q, h_kv = q.shape[1], k.shape[1]
    group = _gqa_group(q, k)
    if h_q % tp:
        raise ValueError(f"query heads {h_q} not divisible by {head_axis}={tp}")
    local_q_heads = h_q // tp

    q_spec = P(batch_axes, head_axis, None, None)
    if h_kv % tp == 0:
        kv_spec = P(batch_axes, head_axis, None, None)
        slice_kv = False
    elif tp % h_kv == 0:
        # More shards than kv heads: replicate kv, slice per shard.
        kv_spec = P(batch_axes, None, None, None)
        slice_kv = True
    else:
        raise ValueError(
            f"kv heads {h_kv} and {head_axis} axis {tp} must divide one way"
        )

    def local_fn(q_l, k_l, v_l):
        if slice_kv:
            shard = jax.lax.axis_index(head_axis)
            kv_head = (shard * local_q_heads) // group
            k_l = jax.lax.dynamic_slice_in_dim(k_l, kv_head, 1, axis=1)
            v_l = jax.lax.dynamic_slice_in_dim(v_l, kv_head, 1, axis=1)
        return flash_attention(q_l, k_l, v_l, causal=causal, **kwargs)

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,  # pallas_call defeats the replication checker
    )(q, k, v)
