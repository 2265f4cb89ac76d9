"""The routed layer's read-back (``models/moe.py`` ``HeldExperts``) as one
Pallas kernel, ``moe_readback``: each token's sum over the live rows of
the held experts' buffer that belong to it, in float32, rounded once.

One sweep over token tiles, a tile's sums in VMEM in float32.  The caller
has sorted the live rows by token, and the kernel walks them in that
order, adding each to its token's sum; a tile is written once, zeros where
a token has no row.  Rows at or past the live ones are not in the sorted
run: none is added to anything.

The buffer stays in HBM, and a DMA out of it moves whole tiles of its
layout: eight rows, as 32-bit words (a bfloat16 row and its neighbour lie
in one word's two halves).  Fetching a row's tile for every row would move
the live rows eight times.  But the buffer is a sequence of *streams*, runs
of rows whose tokens ascend (one expert's rows for one of the ``k``
choices), and the walk meets a stream's rows in the buffer's own order: so
each stream keeps a cursor, two tiles of VMEM of its own, and fetches its
next tile while the walk is busy with the current one.  Every live tile
comes in once (twice where two streams share it).  The row wanted is picked
from its tile by its place in it and, with integer shifts, by the half its
parity says: what stands in the others never meets a float.

The kernel engages where ``token_tile`` finds a tile; ``models/moe.py``
keeps the gather form for every other shape, and the tests hold the kernel
to it.  Compiled on a TPU, interpreted on an explicit CPU platform
(``attention.default_interpret``, asked at call time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention

_LANES = 128
#: Tokens in one kernel step.
_TILE = 128
#: Rows in one tile of the buffer's layout, which is what a DMA moves:
#: eight, as four rows of 32-bit words where a word holds two.
_TILE_ROWS = 8
#: The streams' tiles in VMEM are at most this large, all together.
_LANDED_BYTES = 32 << 20
#: The walk's two arrays stand in SMEM (1 MiB) and are at most this large.
_WALK_BYTES = 512 << 10
_F32 = jnp.float32


def row_bits(n_rows: int) -> int:
    """The bits of a buffer row's number in a place's ``source``: its
    stream's number stands above them."""
    return (n_rows - 1).bit_length()


def _landed_bytes(rows, streams: int) -> int:
    """Two tiles of the buffer's layout a stream."""
    return 2 * streams * _TILE_ROWS * rows.shape[1] * rows.dtype.itemsize


def token_tile(rows, n_tokens: int, streams: int) -> int | None:
    """Tokens in one kernel step for a buffer ``rows (R, C)`` of
    ``streams`` streams read back to ``n_tokens`` tokens, or None where
    the shape does not tile and the gather form runs."""
    if rows.ndim != 2 or rows.dtype not in (jnp.bfloat16, jnp.float32):
        return None
    n_rows, width = rows.shape
    if (width % _LANES or n_rows % _TILE_ROWS or n_tokens % _TILE
            or 2 * n_rows * 4 > _WALK_BYTES
            or streams << row_bits(n_rows) >= 1 << 31
            or _landed_bytes(rows, streams) > _LANDED_BYTES):
        return None
    return _TILE


def _readback_kernel(starts_ref, source_ref, token_ref, bounds_ref, rows_ref,
                     out_ref, sums_ref, landed_ref, at_ref, sem_ref, *,
                     per_word, bits):
    tile, width = out_ref.shape
    step = pl.program_id(0)
    streams = at_ref.shape[0]
    word_rows = _TILE_ROWS // per_word
    words = rows_ref.bitcast(jnp.uint32) if per_word == 2 else rows_ref

    def tile_copy(stream, index):
        """The DMA of tile ``index`` of the buffer into ``stream``'s slot
        for it (a stream's tiles alternate between its two)."""
        slot = 2 * stream + index % 2
        first = pl.multiple_of(index * word_rows, word_rows)
        return pltpu.make_async_copy(
            words.at[pl.ds(first, word_rows)], landed_ref.at[slot],
            sem_ref.at[slot])

    @pl.when(step == 0)
    def _():
        def open_stream(stream, carry):
            first = bounds_ref[stream] // _TILE_ROWS
            at_ref[stream] = first - 1  # the tile it holds: none yet

            @pl.when(bounds_ref[stream] < bounds_ref[stream + 1])
            def _():
                tile_copy(stream, first).start()

            return carry

        jax.lax.fori_loop(0, streams, open_stream, None)

    sums_ref[...] = jnp.zeros_like(sums_ref)

    def add(at, carry):
        source = source_ref[at]
        row, stream = source & ((1 << bits) - 1), source >> bits
        index = row // _TILE_ROWS

        @pl.when(at_ref[stream] != index)
        def _():
            # The stream moves on to its next tile: sent for when it came
            # to the last one.  Every row of that one has been added, so
            # its slot takes the tile after this.
            tile_copy(stream, index).wait()
            at_ref[stream] = index

            @pl.when(index < (bounds_ref[stream + 1] - 1) // _TILE_ROWS)
            def _():
                tile_copy(stream, index + 1).start()

        held = landed_ref[
            2 * stream + index % 2, pl.ds(row // per_word % word_rows, 1), :]
        if per_word == 2:
            # The odd row is the word's high half, the even one its low:
            # either, moved to where a float32 keeps a bfloat16.
            held = jax.lax.bitcast_convert_type(
                (held >> (16 * (row % 2)).astype(jnp.uint32)) << 16, _F32)
        mine = pl.ds(token_ref[at] - step * tile, 1)
        sums_ref[mine, :] = sums_ref[mine, :] + held
        return carry

    jax.lax.fori_loop(starts_ref[step], starts_ref[step + 1], add, None)
    out_ref[...] = sums_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_tokens", "interpret"))
def _readback_call(rows, starts, source, token, bounds, *, n_tokens,
                   interpret):
    n_rows, width = rows.shape
    streams = bounds.shape[0] - 1
    tile = token_tile(rows, n_tokens, streams)
    per_word = 4 // rows.dtype.itemsize
    landed = (2 * streams, _TILE_ROWS // per_word, width)
    return pl.pallas_call(
        functools.partial(
            _readback_kernel, per_word=per_word, bits=row_bits(n_rows)),
        name="moe_readback",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tokens // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda t, *_: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile, width), _F32),  # the tile's sums
                pltpu.VMEM(landed,
                           jnp.uint32 if per_word == 2 else rows.dtype),
                pltpu.SMEM((streams,), jnp.int32),  # the tile each holds
                pltpu.SemaphoreType.DMA((2 * streams,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((n_tokens, width), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            # The streams' cursors cross the tiles' ends: in order.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_landed_bytes(rows, streams)
            + 6 * tile * width * 4 + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=n_rows * width, transcendentals=0,
            bytes_accessed=rows.nbytes
            + n_tokens * width * rows.dtype.itemsize),
        interpret=interpret,
    )(starts, source, token, bounds, rows)


def moe_readback(rows, starts, source, token, bounds, *, n_tokens: int):
    """``rows (R, C)`` -> ``(n_tokens, C)``: each token's sum, in float32
    and rounded once to the rows' dtype, over the rows that are its own.

    The walk: ``source[p]`` is the buffer row at sorted place ``p`` (with
    its stream's number above the row's bits) and ``token[p]`` its token,
    ascending, a token's rows in the order they are to be added;
    ``starts[i]`` is the first place whose token lies in tile ``i`` of
    ``token_tile`` (which must have found one), ``starts[-1]`` the end of
    the live places: no place from there on is looked at, and no row but a
    place's source is added to anything.  The streams: stream ``s`` is the
    walked rows ``[bounds[s], bounds[s + 1])`` of the buffer, and the walk
    meets them in that order."""
    return _readback_call(
        rows, starts, source, token, bounds, n_tokens=n_tokens,
        interpret=attention.default_interpret())
