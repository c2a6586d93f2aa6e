"""Pallas TPU kernels that move whole rows between token order and
expert-sorted slot order: the dispatch and the combine of the
sparse-expert trunk (``models/trunk.py``).

A row of ``hidden`` bfloat16 in a ``[rows, hidden]`` array is not
contiguous on the TPU: sixteen rows share each ``(16, 128)`` tile, so
XLA's row gather moves ``hidden / 128`` pieces of 256 bytes a row. Seen
as ``[rows, hidden // 128, 128]`` one row is whole tiles (one 4 KiB tile
at hidden 2048), and a DMA moves it in one piece. Both kernels address
single rows only in that view, on the token side; the slot side, which
the grouped products read, stays ``[slots, hidden]`` and is read or
written in contiguous blocks of ``tm`` rows. The change of view happens
in VMEM.

``rows_out``   ``out[i] = src[index[i]] (* scale[i])``: one DMA a row
               into a VMEM buffer, reshape, one block out.
``rows_back``  ``out[index[i]] = rows[i]`` (``index`` a permutation):
               one block in, reshape, one DMA a row out.

Each is the other's transpose. The next block's DMAs are in flight while
this block is reshaped (two buffers, one DMA semaphore each, one wait
for a buffer's bytes). Off the TPU both run under the Pallas
interpreter, as the FT gather (``ops/ft_gather.py``) does.

The extent of a move. Both take an optional ``extent``, an int32 scalar
on the device: only rows ``[0, extent)`` of the slot side matter to the
caller (a share of the experts: the rows of the experts it holds, which
its sort puts first). The grid then has ``cdiv(extent, tm)`` (+ 1)
steps, a traced bound as megablox ``gmm``'s, and the blocks past them
are neither fetched, reshaped nor written back. Shapes do not change,
so what lies past the last moved block is UNINITIALISED, not zero:
the tail rows of ``rows_out``'s result, and in ``rows_back``'s result
every place ``index[i]`` of a row ``i`` that was not moved (there, "every
row is written exactly once" holds only without an extent). The
grouped products leave the same tails: given the held groups' sizes
alone they write no row past the extent, of a result or of a cotangent
(``models/trunk.py grouped_matmul``). Who may
read them: the grouped products, which visit the held groups' rows
alone and mask a straddling tile by ``select``; the gated activation
between them (``ops/expert_gate.py``), whose grid stops at the same
block as a move's (``rows_covered``); and the sums over a
token's slots in ``models/trunk.py``, which select by the slot's mask
and never multiply, because what is there may be NaN (the interpreter
fills it with NaN, which is what the tests lean on). The block that
straddles ``extent`` moves whole. Without ``extent`` the grid is the
static one and the kernels are what they were.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["row_view", "rows_out", "rows_back", "rows_covered"]

#: Rows a grid step, and DMA starts unrolled in one loop body: the fastest
#: of 128-1024 rows and 1-64 starts on a v5e at 262,144 rows of 4 KiB
#: (PERF.md section 5). The scaled move reads float32 rows (8 KiB) and
#: is fastest at 128.
_TM = 512
_TM_SCALED = 128
_UNROLL = 32


def row_view(x: jax.Array) -> jax.Array:
    """``[rows, hidden]`` as ``[rows, hidden // 128, 128]``: every row
    whole tiles. A hidden under 128 is one short row (the tests' tiny
    nets under the interpreter; Mosaic would pad it)."""
    rows, hidden = x.shape
    if hidden < 128:
        return x.reshape(rows, 1, hidden)
    if hidden % 128:
        raise ValueError(f"hidden {hidden} is neither under 128 nor a multiple of it")
    return x.reshape(rows, hidden // 128, 128)


def _unroll(interpret: bool) -> int:
    """Unrolling is for Mosaic's scheduler; the interpreter pays for every
    emitted operation and gains nothing."""
    return 1 if interpret else _UNROLL


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


def _rows_out_kernel(idx_ref, src_ref, *rest, scaled: bool, at, unroll: int):
    """Grid step ``i`` of ``blocks + 1`` starts the DMAs of block ``i``
    and turns block ``i - 1``, which arrived meanwhile, into its output
    block. Both ends run the same code: step 0 turns a buffer nothing
    filled into output block 0, which step 1 then overwrites before it
    is written back, and the last step fetches the last block a second
    time and only waits for it."""
    if scaled:
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
    if scaled:
        rows = rows.astype(jnp.float32) * scale_ref[...]
    out_ref[...] = rows.astype(out_ref.dtype)

    @pl.when(i == last)
    def _():
        _wait(buf, sem, slot)


def rows_out(src: jax.Array, index: jax.Array, scale: Optional[jax.Array] = None, *,
             extent: Optional[jax.Array] = None, dtype=None, interpret: bool = False) -> jax.Array:
    """``src[index]`` for ``src`` in the row view ``[n, sub, lanes]``,
    as ``[len(index), sub * lanes]`` of ``dtype`` (default: ``src``'s).
    With ``scale`` (float32, one a row of the result) each row is
    multiplied in float32 before it is rounded to ``dtype``. With
    ``extent`` rows ``[0, extent)`` of the result alone are promised:
    the blocks past them are never written and hold whatever the buffer
    held."""
    n, sub, lanes = src.shape
    m = index.shape[0]
    tm = _tile(m, _TM if scale is None else _TM_SCALED)
    dtype = dtype or src.dtype
    index, spec, at = _index_blocks(index, tm)
    before = lambda i: (jnp.maximum(i - 1, 0), 0)  # the block a step turns out is the one before the block it fetches
    in_specs = [spec, pl.BlockSpec(memory_space=pl.ANY)]
    operands = [index, src]
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), before))
        operands.append(scale.astype(jnp.float32).reshape(m, 1))
    return pl.pallas_call(
        lambda *refs: _rows_out_kernel(*refs, scaled=scale is not None, at=at, unroll=_unroll(interpret)),
        grid=(_blocks(m, tm, extent) + 1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, sub * lanes), before),
        out_shape=jax.ShapeDtypeStruct((m, sub * lanes), dtype),
        scratch_shapes=_two_buffers(tm, (sub, lanes), src.dtype),
        compiler_params=_PARAMS,
        name="moe_rows_out",
        interpret=interpret,
    )(*operands)


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
