"""Pallas TPU kernels that move whole rows between token order and
expert-sorted slot order, and that sum a token's held slots: the dispatch
and the combine of the sparse-expert trunk (``models/trunk.py``).

A row of ``hidden`` bfloat16 in a ``[rows, hidden]`` array is not
contiguous on the TPU: sixteen rows share each ``(16, 128)`` tile, so
XLA's row gather moves ``hidden / 128`` pieces of 256 bytes a row. Seen
as ``[rows, hidden // 128, 128]`` one row is whole tiles where ``hidden
// 128`` is a multiple of 8 (16 x 128 bfloat16 at hidden 2048: one 4 KiB
tile; in float32 two), and a DMA moves it in one piece. A hidden whose
128-lane pieces are not a multiple of 8 (2,688 = 21 x 128) is not whole
tiles and Mosaic refuses to slice it a row at a time: the caller pads
such rows to the next multiple before they get here (``models/trunk.py
_whole_rows``: 24 x 128 = 3,072, 6 KiB in bfloat16), so every statement
below about ``hidden`` is one about the padded row. The kernels address
single rows only in that view, on the token side; the slot side, which
the grouped products read, stays ``[slots, hidden]`` and is read or
written in contiguous blocks of ``tm`` rows. The change of view happens
in VMEM.

``rows_out``   ``out[i] = src[index[i]] (* scale[i])``: one DMA a row
               into a VMEM buffer, reshape, one block out;
               ``rows_out_dot`` gives beside it ``<src[index[i]],
               dot[i]>`` a row, in float32.
``rows_back``  ``out[index[i]] = rows[i]`` (``index`` a permutation):
               one block in, reshape, one DMA a row out.
``rows_sum``   ``out[t] = sum_j (weight[t, j] *) src[t * k + j]`` over a
               token's HELD slots alone: one DMA a held row, summed in
               float32 at its token in VMEM, one block of tokens out.

The first two are each other's transpose. The next block's DMAs are in
flight while this block is reshaped (two buffers, one DMA semaphore each,
one wait for a buffer's bytes). Off the TPU all run under the Pallas
interpreter, as the FT gather (``ops/ft_gather.py``) does.

The extent of a move. Both moves take an optional ``extent``, an int32 scalar
on the device: only rows ``[0, extent)`` of the slot side matter to the
caller (a share of the experts: the rows of the experts it holds, which
its sort puts first). The grid then has ``cdiv(extent, tm)`` (+ 1)
steps, a traced bound as megablox ``gmm``'s, and the blocks past them
are neither fetched, reshaped nor written back. Shapes do not change,
so what lies past the last moved block is UNINITIALISED, not zero:
the tail rows of ``rows_out``'s results, and in ``rows_back``'s result
every place ``index[i]`` of a row ``i`` that was not moved (there, "every
row is written exactly once" holds only without an extent). The
grouped products leave the same tails: given the held groups' sizes
alone they write no row past the extent, of a result or of a cotangent
(``models/trunk.py grouped_matmul``). Who may
read them: the grouped products, which visit the held groups' rows
alone and mask a straddling tile by ``select``; the gated activation
between them (``ops/expert_gate.py``), whose grid stops at the same
block as a move's (``rows_covered``); ``rows_out_dot`` in the
combine's gradient, which reads ``dot`` block by block to the extent's
block and whose sums past the extent are selected away by the slot's
mask after a sort has brought them to token order, never multiplied;
and nobody else. The token-order view that ``rows_back`` writes under an
extent is read by ``rows_sum`` alone, which fetches the held places one
row a DMA (``held_places`` lists them from the slots' mask) and passes
over nothing: on a share no XLA operation reads the view, and what its
other places hold (NaN under the interpreter, which is what the tests
lean on) reaches no result. The block that straddles ``extent`` moves
whole. Without ``extent`` the grid is the static one and the kernels are
what they were; the sums over a token's slots are then XLA's, over the
whole view, every place of which was written (``models/trunk.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["row_view", "rows_out", "rows_out_dot", "rows_back", "rows_covered", "held_places", "rows_sum"]

#: Rows a grid step, and DMA starts unrolled in one loop body: the fastest
#: of 128-1024 rows and 1-64 starts on a v5e at 262,144 rows of 4 KiB
#: (hidden 2048, bfloat16; PERF.md section 5). The scaled move reads
#: float32 rows (8 KiB there, ``hidden // 128`` half-KiB pieces anywhere) and
#: is fastest at 128.
_TM = 512
_TM_SCALED = 128
_UNROLL = 32


def row_view(x: jax.Array) -> jax.Array:
    """``[rows, hidden]`` as ``[rows, hidden // 128, 128]``: every row
    whole tiles where ``hidden // 128`` is a multiple of 8 (the caller
    sees to that: module docstring). A hidden under 128 is one short row
    (the tests' tiny nets under the interpreter; Mosaic would pad it)."""
    rows, hidden = x.shape
    if hidden < 128:
        return x.reshape(rows, 1, hidden)
    if hidden % 128:
        raise ValueError(f"hidden {hidden} is neither under 128 nor a multiple of it")
    return x.reshape(rows, hidden // 128, 128)


def _unroll(interpret: bool, most: int = _UNROLL) -> int:
    """Unrolling is for Mosaic's scheduler; the interpreter pays for every
    emitted operation and gains nothing."""
    return 1 if interpret else most


def _tile(rows: int, most: int = _TM) -> int:
    """The most rows a grid step, dividing ``rows`` as the grouped
    product's tile does."""
    return math.gcd(rows, most)


def _blocks(rows: int, tm: int, extent: Optional[jax.Array]):
    """The blocks of ``tm`` rows a move covers: all of them, or, as a
    traced grid bound, those that hold rows ``[0, extent)``."""
    return rows // tm if extent is None else pl.cdiv(jnp.asarray(extent, jnp.int32), tm)


def rows_covered(rows: int, extent: Optional[jax.Array]):
    """The rows a move of ``rows`` rows really covers under ``extent``:
    whole blocks of the row tile (the scaled ``rows_out`` moves in
    smaller blocks and covers up to ``_TM - _TM_SCALED`` rows fewer)."""
    tm = _tile(rows)
    return _blocks(rows, tm, extent) * tm


#: A one-dimensional int32 operand is tiled by 1024 on the TPU, so the
#: indices reach SMEM in blocks of that many, whatever the row tile.
_INDEX_BLOCK = 1024


def _index_blocks(index: jax.Array, tm: int):
    """The operand and the SMEM BlockSpec that hold the indices of grid
    step ``i`` (clamped to the last block of rows), and the function of
    the step that gives their offset inside the block."""
    steps = index.shape[0] // tm
    per = _INDEX_BLOCK // tm
    padded = jnp.pad(index.astype(jnp.int32), (0, -index.shape[0] % _INDEX_BLOCK))
    step = lambda i: jnp.minimum(i, steps - 1)
    spec = pl.BlockSpec((_INDEX_BLOCK,), lambda i: (step(i) // per,), memory_space=pltpu.SMEM)
    return padded, spec, lambda i: jax.lax.rem(step(i), per) * tm


def _each_row(tm: int, start, unroll: int) -> None:
    """``start(r)`` for every row of a block, ``unroll`` to a loop body
    (Mosaic unrolls a ``fori_loop`` wholly or not at all). Never call it
    under ``pl.when``: a loop inside a conditional copies its buffers
    once a row under the interpreter (5 times the time of a step of the
    tests' tiny trunk)."""
    unroll = math.gcd(tm, unroll)

    def body(j, carry):
        for u in range(unroll):
            start(j * unroll + u)
        return carry

    jax.lax.fori_loop(0, tm // unroll, body, 0)


def _wait(buf, sem, which) -> None:
    """One wait for all of buffer ``which``: its semaphore counts the
    bytes of its tm row DMAs, in or out."""
    pltpu.make_async_copy(buf.at[which], buf.at[which], sem.at[which]).wait()


def _two_buffers(tm: int, view, dtype):
    return [pltpu.VMEM((2, tm) + tuple(view), dtype), pltpu.SemaphoreType.DMA((2,))]


_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20)


def _rows_out_kernel(idx_ref, src_ref, *rest, scaled: bool, dotted: bool, at, unroll: int):
    """Grid step ``i`` of ``blocks + 1`` starts the DMAs of block ``i``
    and turns block ``i - 1``, which arrived meanwhile, into its output
    block. Both ends run the same code: step 0 turns a buffer nothing
    filled into output block 0, which step 1 then overwrites before it
    is written back, and the last step fetches the last block a second
    time and only waits for it."""
    if dotted:  # ``rows_out_dot``: always under a scale
        scale_ref, dot_ref, out_ref, sums_ref, buf, sem = rest
    elif scaled:
        scale_ref, out_ref, buf, sem = rest
    else:
        out_ref, buf, sem = rest
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    tm = buf.shape[1]
    slot = jax.lax.rem(i, 2)
    base = at(i)
    _each_row(tm, lambda r: pltpu.make_async_copy(src_ref.at[idx_ref[base + r]], buf.at[slot, r], sem.at[slot]).start(), unroll)

    @pl.when(i > 0)
    def _():
        _wait(buf, sem, 1 - slot)

    rows = buf[1 - slot].reshape(out_ref.shape)
    if dotted:
        # Of the row as it arrived, before the scale. The block's sums are a column, one a sublane, and leave as one row of
        # lanes (a ``[slots, 1]`` float32 result would be written 128 lanes wide): Mosaic turns a column into a row only as
        # a square's transpose, 64 KiB of VMEM at the 128 rows of a scaled block.
        sums = jnp.sum(rows.astype(jnp.float32) * dot_ref[...].astype(jnp.float32), axis=-1, keepdims=True)
        sums_ref[0] = jnp.broadcast_to(sums, (tm, tm)).T[:1]
    if scaled:
        rows = rows.astype(jnp.float32) * scale_ref[...]
    out_ref[...] = rows.astype(out_ref.dtype)

    @pl.when(i == last)
    def _():
        _wait(buf, sem, slot)


def _rows_out(src, index, scale, dot, extent, dtype, interpret):
    """``rows_out`` and ``rows_out_dot``: one ``pallas_call``, whose results
    are one array or a pair as ``dot`` is None or not."""
    n, sub, lanes = src.shape
    m = index.shape[0]
    tm = _tile(m, _TM if scale is None else _TM_SCALED)
    index, spec, at = _index_blocks(index, tm)
    before = lambda i: (jnp.maximum(i - 1, 0), 0)  # the block a step turns out is the one before the block it fetches
    in_specs = [spec, pl.BlockSpec(memory_space=pl.ANY)]
    operands = [index, src]
    out_specs = pl.BlockSpec((tm, sub * lanes), before)
    out_shape = jax.ShapeDtypeStruct((m, sub * lanes), dtype or src.dtype)
    aliases = {}
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), before))
        operands.append(scale.astype(jnp.float32).reshape(m, 1))
    if dot is not None:  # read block by block as the result is written, and written over; a block's sums are a row of lanes
        in_specs.append(pl.BlockSpec((tm, sub * lanes), before))
        operands.append(dot)
        aliases = {len(operands) - 1: 0}
        out_specs = [out_specs, pl.BlockSpec((1, 1, tm), lambda i: (*before(i), 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((m // tm, 1, tm), jnp.float32)]
    return pl.pallas_call(
        lambda *refs: _rows_out_kernel(*refs, scaled=scale is not None, dotted=dot is not None, at=at, unroll=_unroll(interpret)),
        grid=(_blocks(m, tm, extent) + 1,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_two_buffers(tm, (sub, lanes), src.dtype),
        input_output_aliases=aliases,
        compiler_params=_PARAMS,
        name="moe_rows_out",
        interpret=interpret,
    )(*operands)


def rows_out(src: jax.Array, index: jax.Array, scale: Optional[jax.Array] = None, *,
             extent: Optional[jax.Array] = None, dtype=None, interpret: bool = False) -> jax.Array:
    """``src[index]`` for ``src`` in the row view ``[n, sub, lanes]``,
    as ``[len(index), sub * lanes]`` of ``dtype`` (default: ``src``'s).
    With ``scale`` (float32, one a row of the result) each row is
    multiplied in float32 before it is rounded to ``dtype``. With
    ``extent`` rows ``[0, extent)`` of the result alone are promised:
    the blocks past them are never written and hold whatever the buffer
    held."""
    return _rows_out(src, index, scale, None, extent, dtype, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rows_out_dot(src: jax.Array, index: jax.Array, scale: jax.Array, dot: jax.Array, *, extent: Optional[jax.Array] = None,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """``rows_out`` under ``scale`` as ``[len(index), sub * lanes]`` of
    ``dot``'s dtype, and beside it ``[len(index)]`` float32: each gathered
    row's product with the same row of ``dot`` (``[len(index), sub *
    lanes]``, read in contiguous blocks as the first result is written),
    summed in float32, the row taken before its scale. The first result
    is written over ``dot``'s own buffer (a block is read before it is
    written), which the caller gives up. With ``extent`` rows ``[0,
    extent)`` of both results alone are promised. Traced once for all the
    layers of a shape (``jit``), as ``rows_sum``."""
    rows, sums = _rows_out(src, index, scale, dot, extent, dot.dtype, interpret)
    return rows, sums.reshape(index.shape[0])


def _rows_back_kernel(idx_ref, rows_ref, out_ref, buf, sem, *, at, unroll: int):
    i, n = pl.program_id(0), pl.num_programs(0)
    tm = buf.shape[1]
    slot = jax.lax.rem(i, 2)

    @pl.when(i >= 2)
    def _():
        _wait(buf, sem, slot)  # the rows this buffer held two steps ago have left

    buf[slot] = rows_ref[...].reshape(buf.shape[1:])
    base = at(i)
    _each_row(tm, lambda r: pltpu.make_async_copy(buf.at[slot, r], out_ref.at[idx_ref[base + r]], sem.at[slot]).start(), unroll)

    @pl.when(i == n - 1)
    def _():
        _wait(buf, sem, slot)

        @pl.when(n > 1)
        def _():
            _wait(buf, sem, 1 - slot)


def rows_back(rows: jax.Array, index: jax.Array, *, extent: Optional[jax.Array] = None,
              interpret: bool = False) -> jax.Array:
    """``out[index[i]] = rows[i]`` for a permutation ``index``: the
    result in the row view ``[len(index), sub, lanes]``. Without
    ``extent`` every row of the result is written exactly once, so
    nothing is added and nothing needs zeroing. With it only rows ``[0,
    extent)`` of ``rows`` are promised to arrive (whole blocks do): every
    other place of the result, ``index[i]`` for ``i`` past the last
    moved block, is never written and holds whatever the buffer held.
    The reader has to know which places those are and select, never
    multiply: what is there may be NaN."""
    m = rows.shape[0]
    shape = row_view(rows).shape
    tm = _tile(m)
    index, spec, at = _index_blocks(index, tm)
    return pl.pallas_call(
        lambda *refs: _rows_back_kernel(*refs, at=at, unroll=_unroll(interpret)),
        grid=(_blocks(m, tm, extent),),
        in_specs=[spec, pl.BlockSpec((tm, rows.shape[1]), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, rows.dtype),
        scratch_shapes=_two_buffers(tm, shape[1:], rows.dtype),
        compiler_params=_PARAMS,
        name="moe_rows_back",
        interpret=interpret,
    )(index, rows)


#: Tokens a grid step of the sum over a token's slots, and rows to a loop body.
_TOKENS = 128
_SUM_UNROLL = 8


def held_places(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """What ``rows_sum`` reads of a share's routing, from ``mask`` [N, k]
    (which of a token's slots are held): for every tile of tokens the
    places ``token * k + slot`` of its held slots in ascending order (one
    sort along a tile's ``tile * k`` slots; what follows them in a tile's
    run is never read), each tile's run padded to whole index blocks,
    int32 ``[tiles * run]``; and how many they are, int32 ``[tiles]``."""
    n, k = mask.shape
    tile = _tile(n, _TOKENS)
    held = mask.reshape(n // tile, tile * k)
    places = jnp.where(held, jnp.arange(n * k, dtype=jnp.int32).reshape(held.shape), n * k)
    places = jnp.pad(jax.lax.sort(places, dimension=1), ((0, 0), (0, -(tile * k) % _INDEX_BLOCK)))
    return places.reshape(-1), jnp.sum(held, axis=1, dtype=jnp.int32)


def _rows_sum_kernel(count_ref, next_ref, this_ref, src_ref, *rest, weighted: bool, k: int, unroll: int):
    """Grid step ``i`` of ``tiles + 1`` starts one DMA for every held
    slot of tile ``i`` and sums the rows of tile ``i - 1``, which arrived
    meanwhile, at their tokens. The loops run over a tile's count, a
    scalar from SMEM, ``unroll`` rows to a body: a tile with no held slot
    starts nothing and writes zeros, and the rows that fill its last
    body are the tile's first held row again, fetched and then selected
    away (never another place: it may hold NaN). Both ends run the same
    code with a count of 0."""
    if weighted:
        weight_ref, out_ref, acc, buf, sem = rest
    else:
        out_ref, acc, buf, sem = rest
    i, tiles = pl.program_id(0), pl.num_programs(0) - 1
    slot = jax.lax.rem(i, 2)
    coming = jnp.where(i < tiles, count_ref[jnp.minimum(i, tiles - 1)], 0)
    here = jnp.where(i > 0, count_ref[jnp.maximum(i - 1, 0)], 0)
    first = (i - 1) * acc.shape[0] * k  # the place of this tile's first slot

    def start(j, carry):
        for e in (j * unroll + u for u in range(unroll)):
            pltpu.make_async_copy(src_ref.at[next_ref[jnp.where(e < coming, e, 0)]], buf.at[slot, e], sem.at[slot]).start()
        return carry

    def wait(j, carry):  # a semaphore counts bytes: a body's rows a wait, whichever rows they were
        pltpu.make_async_copy(src_ref.at[pl.ds(0, unroll)], buf.at[1 - slot, pl.ds(0, unroll)], sem.at[1 - slot]).wait()
        return carry

    def add(j, carry):
        for e in (j * unroll + u for u in range(unroll)):
            at = this_ref[jnp.where(e < here, e, 0)] - first
            row = buf[1 - slot, e].astype(jnp.float32)
            token = jax.lax.div(at, k)
            acc[token] = acc[token] + jnp.where(e < here, row * weight_ref[at] if weighted else row, 0.0)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(coming, unroll), start, 0)
    jax.lax.fori_loop(0, pl.cdiv(here, unroll), wait, 0)
    acc[...] = jnp.zeros(acc.shape, acc.dtype)
    jax.lax.fori_loop(0, pl.cdiv(here, unroll), add, 0)
    out_ref[...] = acc[...].reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "dtype", "interpret"))
def rows_sum(src: jax.Array, places: jax.Array, counts: jax.Array, weight: Optional[jax.Array] = None, *, k: int, dtype,
             interpret: bool = False) -> jax.Array:
    """``sum_j (weight[t, j] *) src[t * k + j]`` over a token's HELD slots
    ``j``, for ``src`` in the row view ``[N * k, sub, lanes]`` (what
    ``rows_back`` under an extent wrote: the held places alone are
    initialised) and the held places as ``held_places`` lists them:
    ``[N, sub * lanes]`` of ``dtype``, float32 weights, products and sum,
    a token's slots in ascending order, rounded once. One DMA a held row
    and nothing else is read of ``src``: a token with no held slot is
    zero. Every other place of ``src`` may hold anything, NaN too. With
    8 or 6 slots a token this is a sum of up to that many rows; at ONE
    slot a token it would be a copy of the row or a zero, and
    ``trunk._held_slots_sum`` does not call it then (a select on the
    slot's mask, fused into its reader; no lists of places are made). A
    ``jit`` of its own: the layers of one shape share one trace and one
    lowering of the kernel (a start-up pays for each in Python)."""
    slots, sub, lanes = src.shape
    n = slots // k
    tile = _tile(n, _TOKENS)
    tiles = n // tile
    run = places.shape[0] // tiles
    unroll = _unroll(interpret, _SUM_UNROLL)
    before = lambda i: (jnp.maximum(i - 1, 0),)
    smem = lambda index_map: pl.BlockSpec((run,), index_map, memory_space=pltpu.SMEM)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), smem(lambda i: (jnp.minimum(i, tiles - 1),)), smem(before),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [counts, places, places, src]
    if weight is not None:
        in_specs.append(smem(before))
        operands.append(jnp.pad(weight.astype(jnp.float32).reshape(tiles, tile * k), ((0, 0), (0, run - tile * k))).reshape(-1))
    return pl.pallas_call(
        lambda *refs: _rows_sum_kernel(*refs, weighted=weight is not None, k=k, unroll=unroll),
        grid=(tiles + 1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile, sub * lanes), lambda i: (*before(i), 0)),
        out_shape=jax.ShapeDtypeStruct((n, sub * lanes), dtype),
        scratch_shapes=[pltpu.VMEM((tile, sub, lanes), jnp.float32), pltpu.VMEM((2, -(-tile * k // unroll) * unroll, sub, lanes), src.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=_PARAMS,
        name="moe_rows_sum",
        interpret=interpret,
    )(*operands)
