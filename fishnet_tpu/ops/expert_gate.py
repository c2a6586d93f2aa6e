"""Pallas TPU kernels for a gated activation, ``silu(gate) * up``, and
its gradient, between the products of a gated feed-forward of the
sparse-expert trunk (``models/trunk.py``): on the sorted rows between the
routed experts' grouped products, and since PR 42 on every token between
the wide dense layer's plain products.

The gate and the up product are ONE product there, so a row of the
operand ``gu`` ``[slots, 2 * width]`` holds its gate in the first
``width`` columns and its up in the rest (bfloat16 from a grouped
product, float32 from the dense layer's):

``expert_gate``       ``h = silu(gu[:, :width]) * gu[:, width:]``, ``[slots, width]``.
``expert_gate_grad``  ``(gu, d_h) -> d_gu`` ``[slots, 2 * width]``: the gate's
                      and the up's cotangents side by side, as the
                      transposed product reads them; and, asked, ``h``
                      again as a second result.

A second form is the ungated expert's, whose up product stands alone
(``[slots, width]``): ``squared_relu`` is ``relu(u)^2`` and its gradient
``2 relu(u) d_h``, the same two kernels' names, blocks and extent.

All work on whole rows in blocks, in float32, and round once to
bfloat16, as XLA's fusion of the same expression does. They are
memory-bound passes, and a kernel only for two things XLA cannot be
told. **The extent.** With ``extent`` (``ops/row_move.py``, "The extent
of a move": an int32 scalar on the device, a share's held rows) the grid
covers ``rows_covered(slots, extent)`` rows, the blocks the moves cover,
and the blocks past them are neither fetched nor written: the tail of
either result is UNINITIALISED, as the tails of the moves' and of the
grouped products' results are. Without it the grid is the static one.
**To make the array at all.** Left to itself XLA makes no array of the
activation's gradient: it fuses the whole chain (``exp``, ``divide``,
eight multiplies over float32 operands) as a producer into the operand
of every product that reads it, and makes it again in each; a kernel's
result is an array, made once, and the products round it read plain
bfloat16 (``trunk._gated_products``; PERF.md section 6, PR 42). Off the
TPU both run under the Pallas interpreter.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fishnet_tpu.ops.row_move import rows_covered

__all__ = ["expert_gate", "expert_gate_grad", "gated_activation", "squared_relu"]

#: Rows a grid step: the moves' row tile, so that its blocks are theirs.
#: On a v5e 128, 256 and 512 read within 0.05 ms of each other under an
#: extent and 512 the least at the static grid (PERF.md section 6, PR 36).
_TM = 512
#: The most a grid step's operand blocks hold together: the experts' (bfloat16 rows of up to 2,048 + 1,024 columns: 3 MiB at
#: ``_TM``) are under it; the dense layer's float32 ``[tokens, 2 x 6144]`` with a bfloat16 cotangent takes 128 rows (7.5 MiB),
#: where 512 (30 MiB, twice buffered with their results) pass the 64 MiB the kernels ask for and Mosaic refuses the call.
_BLOCK_BYTES = 8 << 20

_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20)


def _halves(gu_ref, width: int):
    gate, up = gu_ref[:, :width].astype(jnp.float32), gu_ref[:, width:].astype(jnp.float32)
    return gate, up, jax.nn.sigmoid(gate)


def _gate_kernel(gu_ref, out_ref):
    gate, up, s = _halves(gu_ref, out_ref.shape[1])
    out_ref[...] = (gate * s * up).astype(out_ref.dtype)


def _gate_grad_kernel(gu_ref, d_ref, out_ref, h_ref=None):
    width = d_ref.shape[1]
    gate, up, s = _halves(gu_ref, width)
    d = d_ref[...].astype(jnp.float32)
    silu = gate * s
    out_ref[:, :width] = (d * up * (s + silu * (1.0 - s))).astype(out_ref.dtype)
    out_ref[:, width:] = (d * silu).astype(out_ref.dtype)
    if h_ref is not None:  # ``_gate_kernel``'s result again, bit for bit
        h_ref[...] = (silu * up).astype(h_ref.dtype)


def _relu2_kernel(u_ref, out_ref):
    r = jnp.maximum(u_ref[...].astype(jnp.float32), 0.0)
    out_ref[...] = (r * r).astype(out_ref.dtype)


def _relu2_grad_kernel(u_ref, d_ref, out_ref):
    out_ref[...] = (2.0 * jnp.maximum(u_ref[...].astype(jnp.float32), 0.0) * d_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _row_tile(slots: int, row_bytes: int) -> int:
    """Rows a grid step: ``_TM``, halved until the operands' blocks are
    within ``_BLOCK_BYTES`` together (float32 rows of 12,288 columns and
    a bfloat16 cotangent of 6,144 take 128), and a divisor of ``slots``."""
    tile = _TM
    while tile > 8 and tile * row_bytes > _BLOCK_BYTES:
        tile //= 2
    return math.gcd(slots, tile)


def _call(kernel, name: str, out_width, extent: Optional[jax.Array], interpret: bool, *operands: jax.Array, in_place: bool = False):
    """``kernel`` over row blocks of ``operands`` ``[slots, .]``, all of
    them or those the moves cover under ``extent``, into bfloat16
    ``[slots, out_width]`` (a tuple of widths: as many results).
    ``in_place``: the result takes the first operand's buffer (a block
    is read before it is written back)."""
    slots = operands[0].shape[0]
    tm = _row_tile(slots, sum(x.shape[1] * x.dtype.itemsize for x in operands))
    block = lambda width: pl.BlockSpec((tm, width), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows_covered(slots, extent) // tm,),
        in_specs=[block(x.shape[1]) for x in operands],
        out_specs=jax.tree.map(block, out_width),
        out_shape=jax.tree.map(lambda width: jax.ShapeDtypeStruct((slots, width), jnp.bfloat16), out_width),
        input_output_aliases={0: 0} if in_place else {},
        compiler_params=_PARAMS,
        name=name,
        interpret=interpret,
    )(*operands)


def expert_gate(gu: jax.Array, extent: Optional[jax.Array] = None, interpret: bool = False) -> jax.Array:
    """The kernel ``expert_gate`` alone, no gradient rule: bfloat16 ``h``
    ``[slots, width]`` from ``gu`` ``[slots, 2 * width]``, bfloat16 or
    float32."""
    return _call(_gate_kernel, "expert_gate", gu.shape[1] // 2, extent, interpret, gu)


def expert_gate_grad(gu: jax.Array, d_h: jax.Array, extent: Optional[jax.Array] = None, interpret: bool = False, with_h: bool = False):
    """The kernel ``expert_gate_grad`` alone: bfloat16 ``d_gu`` ``[slots,
    2 * width]`` from ``gu`` and ``h``'s cotangent ``d_h`` ``[slots,
    width]``. A bfloat16 ``gu`` gives the result its buffer: it is dead
    after this. ``with_h``: also ``expert_gate``'s own result again,
    ``(d_gu, h)``: the kernel has the halves and the sigmoid in
    registers, so a caller need not keep ``h`` from the forward pass to
    the backward (a write of ``[slots, width]`` against that many bytes
    held through a step)."""
    width = d_h.shape[1]
    return _call(_gate_grad_kernel, "expert_gate_grad", (2 * width, width) if with_h else 2 * width, extent, interpret, gu, d_h,
                 in_place=gu.dtype == jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_activation(gu: jax.Array, extent: Optional[jax.Array] = None, interpret: bool = False) -> jax.Array:
    """``silu(gu[:, :width]) * gu[:, width:]`` for a bfloat16 ``gu``
    ``[slots, 2 * width]``: ``[slots, width]`` bfloat16, float32 arithmetic.
    With ``extent`` rows ``[0, extent)`` alone are promised (whole blocks
    are computed), of the result and of the gradient to ``gu``: the rest
    is never written and holds whatever the buffer held."""
    return expert_gate(gu, extent, interpret)


def _gated_fwd(gu, extent, interpret):
    return gated_activation(gu, extent, interpret), (gu, extent)


def _gated_bwd(interpret, res, d_h):
    gu, extent = res
    # ``gu`` is this rule's alone and dead after it: its cotangent, the largest array of the experts' backward pass, takes its place.
    return expert_gate_grad(gu, d_h.astype(gu.dtype), extent, interpret), None


gated_activation.defvjp(_gated_fwd, _gated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def squared_relu(u: jax.Array, extent: Optional[jax.Array] = None, interpret: bool = False) -> jax.Array:
    """``relu(u)^2`` for ``u`` ``[slots, width]``, the ungated expert's
    activation: ``u``'s shape, bfloat16 as ``u``, float32 arithmetic, and
    ``gated_activation``'s contract under ``extent``."""
    return _call(_relu2_kernel, "expert_gate", u.shape[1], extent, interpret, u)


def _relu2_fwd(u, extent, interpret):
    return squared_relu(u, extent, interpret), (u, extent)


def _relu2_bwd(interpret, res, d_h):
    u, extent = res
    return _call(_relu2_grad_kernel, "expert_gate_grad", u.shape[1], extent, interpret, u, d_h.astype(u.dtype), in_place=True), None


squared_relu.defvjp(_relu2_fwd, _relu2_bwd)
