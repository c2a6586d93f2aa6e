"""Pallas TPU kernels for the core of a Mamba-2 state-space mixer over
the 64 squares of a board (``models/trunk.py _mamba``): the selective
scan itself, between the convolution and the gated norm, without leaving
VMEM.

Per board, head h (``P`` columns of x) and its group g = h // (heads //
groups) (``N`` columns of B and of C), with ``D_t > 0`` the step
(softplus of dt, made outside), ``a < 0`` the head's decay rate and
``skip`` its direct term, the recurrence over the squares t = 0..63 is::

    S_t = exp(D_t a) S_{t-1} + D_t x_t B_t^T          S [P, N], zero before square 0
    y_t = S_t C_t + skip x_t

A board is 64 tokens and the published chunk is 128, so a board is ONE
chunk and the recurrence is exactly its dual (quadratic) form, which is
what the kernels compute::

    c     = cumsum(D a) along the board                        float32, made in the kernel
    L_ij  = exp(c_i - c_j) for i >= j, else 0                  every exponent <= 0
    y_i   = sum_j (C_i . B_j) L_ij D_j x_j + skip x_i

No state is handed from chunk to chunk (there is no second chunk), and
none is kept: a scan longer than one chunk is not computed here.

``board_scan(x, b, c, step, a, skip)`` takes the operands as the
convolution writes them, x ``[boards, 64, heads * P]``, b and c
``[boards, 64, groups * N]`` (bfloat16), ``step`` ``[boards, 64, heads]``
float32, ``a`` and ``skip`` ``[heads]`` float32, and gives y in x's
shape, bfloat16. A grid step is one B/C group and its heads of a few
boards, as ``board_attention``'s is one key-value head and its group:
``G = C B^T`` ``[64, 64]`` once a group, a head's decay ``L`` from a
cumulative sum made in the kernel (two small float32 products with a
triangle of ones, at ``highest``: the sums are exponents), ``W = G * L *
D`` rounded to bfloat16, ``Y = W X + skip X`` with float32 accumulation.
Heads narrower than a 128-lane tile are worked a tile at a time: ``W_h``
times the whole tile, each head's lanes selected from its own product,
so nothing narrower than a vreg is sliced or stored. No ``[.., heads,
P]`` view and no ``[64, 64]`` table reaches HBM.

``board_scan_grad`` recomputes ``G`` and ``L`` from the same inputs
(the residuals are the inputs) and returns dx, db, dc (summed over a
group's heads in VMEM), d(step), and d(a) and d(skip) as one partial sum
a grid step, summed outside. It rounds where JAX's own transposes of the
formula above round: cotangents of bfloat16 values are bfloat16, the
products take bfloat16 operands and accumulate in float32; everything
of the decay is float32.

Off the TPU both kernels run under the Pallas interpreter.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fishnet_tpu.ops.board_attention import SQUARES

__all__ = ["board_scan"]

#: Boards a grid step. A step's blocks (x, y and their cotangents at 64
#: KiB a board and group of 8 x 64 columns, B and C at 16) stay under 3
#: MiB double-buffered in the gradient.
_BOARDS = 8
_LANES = 128

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _exact(a: jax.Array, b: jax.Array, dims=(((1,), (0,)), ((), ()))) -> jax.Array:
    """A small float32 product whose result is an exponent or a sum of
    exponents' cotangents: no operand is rounded."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _triangles() -> Tuple[jax.Array, jax.Array]:
    """``lower[i, j] = 1`` for ``i >= j`` (float32) and the same as a mask."""
    i = jax.lax.broadcasted_iota(jnp.int32, (SQUARES, SQUARES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (SQUARES, SQUARES), 1)
    return (i >= j).astype(jnp.float32), i >= j


def _sizes(x_ref, step_ref) -> Tuple[int, int, int]:
    """Heads a group, a head's columns, and the heads of one lane tile of x."""
    heads = step_ref.shape[1]
    p = x_ref.shape[-1] // heads
    tile = min(heads * p, _LANES)
    return heads, p, tile // p


def _own_lanes(per: int, p: int):
    """For each head of a tile the mask of its own lanes ``[1, per * p]`` (None where a tile is one head)."""
    if per == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, per * p), 1)
    return [(lane >= k * p) & (lane < (k + 1) * p) for k in range(per)]


def _decays(step_t: jax.Array, a: jax.Array, lower: jax.Array):
    """``step_t`` [heads, 64] and ``a`` [heads, 1] -> the cumulative sums
    of ``step * a`` with the squares along the lanes [heads, 64] and down
    the sublanes [64, heads]: a head's ``c_i - c_j`` is a column of the
    second less a row of the first."""
    rate = step_t * a
    return _exact(rate, lower, _NT), _exact(lower, rate, _NT)


def _decay(rows: jax.Array, cols: jax.Array, h: int, mask: jax.Array) -> jax.Array:
    """Head ``h``'s ``L`` [64 i, 64 j]; the exponent is masked before the exponential."""
    return jnp.exp(jnp.where(mask, cols[:, h:h + 1] - rows[h:h + 1, :], -1e30))


def _forward_kernel(x_ref, b_ref, c_ref, step_ref, a_ref, skip_ref, y_ref):
    f32, bf16 = jnp.float32, jnp.bfloat16
    heads, p, per = _sizes(x_ref, step_ref)
    lower, mask = _triangles()
    own = _own_lanes(per, p)
    a = a_ref[0]

    def board(i, carry):
        g = jax.lax.dot_general(c_ref[i], b_ref[i], _NT, preferred_element_type=f32)  # C B^T [64 i, 64 j]
        step_t = step_ref[i]
        rows, cols = _decays(step_t, a, lower)
        for t in range(heads // per):
            lanes = slice(t * per * p, (t + 1) * per * p)
            xt = x_ref[i, :, lanes]
            y = skip_ref[:, lanes] * xt.astype(f32)
            for k in range(per):
                h = t * per + k
                w = (g * _decay(rows, cols, h, mask) * step_t[h:h + 1, :]).astype(bf16)
                mixed = jnp.dot(w, xt, preferred_element_type=f32)
                y = y + (mixed if own[k] is None else jnp.where(own[k], mixed, 0.0))
            y_ref[i, :, lanes] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], board, 0)


def _backward_kernel(x_ref, b_ref, c_ref, step_ref, a_ref, skip_ref, dy_ref,
                     dx_ref, db_ref, dc_ref, dstep_ref, da_ref, dskip_ref, direct_ref, decay_ref):
    f32, bf16 = jnp.float32, jnp.bfloat16
    heads, p, per = _sizes(x_ref, step_ref)
    lower, mask = _triangles()
    own = _own_lanes(per, p)
    a = a_ref[0]
    ones = jnp.ones((8, SQUARES), f32)

    def board(i, da):
        bm, cm = b_ref[i], c_ref[i]
        g = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=f32)
        step_t = step_ref[i]
        rows, cols = _decays(step_t, a, lower)
        dg = jnp.zeros((SQUARES, SQUARES), f32)
        for t in range(heads // per):
            lanes = slice(t * per * p, (t + 1) * per * p)
            xt, dyt = x_ref[i, :, lanes], dy_ref[i, :, lanes]
            x32, dy32 = xt.astype(f32), dyt.astype(f32)
            dxt = skip_ref[:, lanes] * dy32
            dskip_ref[0, :, lanes] = dskip_ref[0, :, lanes] + jnp.sum(dy32 * x32, axis=0, keepdims=True)
            for k in range(per):
                h = t * per + k
                decay = _decay(rows, cols, h, mask)
                d_row = step_t[h:h + 1, :]
                w = (g * decay * d_row).astype(bf16)
                mixed = jax.lax.dot_general(w, dyt, _TN, preferred_element_type=f32)  # W^T dY [64 j, lanes]
                dxt = dxt + (mixed if own[k] is None else jnp.where(own[k], mixed, 0.0))
                xh = xt if own[k] is None else jnp.where(own[k], x32, 0.0).astype(bf16)
                dwl = jax.lax.dot_general(dyt, xh, _NT, preferred_element_type=f32) * decay  # (dY X_h^T) * L [64 i, 64 j]
                dg = dg + dwl * d_row
                direct = dwl * g  # the cotangent of D_j's own factor, before its sum down the column
                through = direct * d_row  # e_ij = dW_ij W_ij: d c_i = sum_j e_ij, d c_j = -sum_i e_ij
                direct_ref[h:h + 1, :] = jnp.sum(direct, axis=0, keepdims=True)
                # The row sums are wanted as a row (squares along the lanes, as the step): a product with ones, not a transpose.
                decay_ref[h:h + 1, :] = _exact(ones, through, _NT)[0:1, :] - jnp.sum(through, axis=0, keepdims=True)
            dx_ref[i, :, lanes] = dxt.astype(dx_ref.dtype)
        dg = dg.astype(bf16)
        dc_ref[i] = jnp.dot(dg, bm, preferred_element_type=f32).astype(dc_ref.dtype)
        db_ref[i] = jax.lax.dot_general(dg, cm, _TN, preferred_element_type=f32).astype(db_ref.dtype)
        d_rate = _exact(decay_ref[...], lower)  # d(step a)_j = the sum of d c_i over i >= j
        dstep_ref[i] = d_rate * a + direct_ref[...]
        return da + jnp.sum(d_rate * step_t, axis=1, keepdims=True)

    dskip_ref[...] = jnp.zeros(dskip_ref.shape, f32)
    da_ref[0, 0] = jax.lax.fori_loop(0, x_ref.shape[0], board, jnp.zeros((heads, 1), f32))


def _blocks(x: jax.Array, b: jax.Array, heads: int, groups: int):
    """The grid (blocks of boards, groups) and the BlockSpecs of a
    group's columns of x, of b or c, of the transposed step ``[boards,
    heads, 64]``, of a scalar a head ``[groups, heads a group, 1]``, of
    the direct term a lane ``[1, heads * P]``, and of a grid step's
    partial sums of the last two."""
    boards, squares, inner = x.shape
    if squares != SQUARES or heads % groups or inner % heads or b.shape[-1] % groups:
        raise ValueError(f"board_scan: x {x.shape} and b {b.shape} are not [boards, {SQUARES}, heads x P] and [boards, {SQUARES}, groups x N] "
                         f"for {heads} heads in {groups} groups")
    per_group, p = heads // groups, inner // heads
    if (per_group * p) % min(per_group * p, _LANES) or min(per_group * p, _LANES) % p:
        raise ValueError(f"board_scan: a group's {per_group} heads of {p} columns do not fill whole {_LANES}-lane tiles")
    tb = math.gcd(boards, _BOARDS)
    spec = lambda lanes: pl.BlockSpec((tb, SQUARES, lanes), lambda i, g: (i, 0, g))
    return (boards // tb, groups), dict(
        x=spec(per_group * p), state=spec(b.shape[-1] // groups),
        step=pl.BlockSpec((tb, per_group, SQUARES), lambda i, g: (i, g, 0)),
        head=pl.BlockSpec((1, per_group, 1), lambda i, g: (g, 0, 0)),
        lane=pl.BlockSpec((1, per_group * p), lambda i, g: (0, g)),
        head_sum=pl.BlockSpec((1, 1, per_group, 1), lambda i, g: (i, g, 0, 0)),
        lane_sum=pl.BlockSpec((1, 1, per_group * p), lambda i, g: (i, 0, g)))


def _operands(step: jax.Array, a: jax.Array, skip: jax.Array, groups: int, p: int):
    """The step with the squares along the lanes, the decay rate a head
    of a group, and the direct term a lane of x."""
    heads = step.shape[-1]
    return (step.astype(jnp.float32).swapaxes(1, 2), a.astype(jnp.float32).reshape(groups, heads // groups, 1),
            jnp.repeat(skip.astype(jnp.float32), p)[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def board_scan(x: jax.Array, b: jax.Array, c: jax.Array, step: jax.Array, a: jax.Array, skip: jax.Array,
               groups: int, interpret: bool = False) -> jax.Array:
    """The scan's core (module docstring): x ``[boards, 64, heads * P]``,
    b and c ``[boards, 64, groups * N]`` bfloat16, ``step`` ``[boards,
    64, heads]`` float32 (positive), ``a`` (negative) and ``skip``
    ``[heads]`` float32 -> y in x's shape, bfloat16."""
    heads = step.shape[-1]
    grid, specs = _blocks(x, b, heads, groups)
    return pl.pallas_call(
        _forward_kernel,
        grid=grid,
        in_specs=[specs["x"], specs["state"], specs["state"], specs["step"], specs["head"], specs["lane"]],
        out_specs=specs["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="board_scan",
        interpret=interpret,
    )(x.astype(jnp.bfloat16), b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), *_operands(step, a, skip, groups, x.shape[-1] // heads))


def _board_scan_fwd(x, b, c, step, a, skip, groups, interpret):
    return board_scan(x, b, c, step, a, skip, groups, interpret), (x, b, c, step, a, skip)


def _board_scan_bwd(groups, interpret, residuals, dy):
    x, b, c, step, a, skip = residuals
    (boards, _, inner), heads = x.shape, step.shape[-1]
    grid, specs = _blocks(x, b, heads, groups)
    bf16, f32 = jnp.bfloat16, jnp.float32
    like = lambda y: jax.ShapeDtypeStruct(y.shape, bf16)
    dx, db, dc, dstep, da, dskip = pl.pallas_call(
        _backward_kernel,
        grid=grid,
        in_specs=[specs["x"], specs["state"], specs["state"], specs["step"], specs["head"], specs["lane"], specs["x"]],
        out_specs=[specs["x"], specs["state"], specs["state"], specs["step"], specs["head_sum"], specs["lane_sum"]],
        out_shape=[like(x), like(b), like(c), jax.ShapeDtypeStruct((boards, heads, SQUARES), f32),
                   jax.ShapeDtypeStruct((grid[0], groups, heads // groups, 1), f32), jax.ShapeDtypeStruct((grid[0], 1, inner), f32)],
        scratch_shapes=[pltpu.VMEM((heads // groups, SQUARES), f32)] * 2,
        compiler_params=_PARAMS,
        name="board_scan_grad",
        interpret=interpret,
    )(x.astype(bf16), b.astype(bf16), c.astype(bf16), *_operands(step, a, skip, groups, inner // heads), dy.astype(bf16))
    return (dx.astype(x.dtype), db.astype(b.dtype), dc.astype(c.dtype), dstep.swapaxes(1, 2).astype(step.dtype),
            da.sum(axis=0).reshape(heads).astype(a.dtype), dskip.sum(axis=(0, 1)).reshape(heads, -1).sum(axis=1).astype(skip.dtype))


board_scan.defvjp(_board_scan_fwd, _board_scan_bwd)
