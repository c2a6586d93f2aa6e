"""Pallas TPU kernels for the mix of compressed convolutional attention
(the fifth block of ``models/trunk.py``): everything between the joined
query-key projection and the attention core, for the 64 squares of a
few boards at a time, without leaving VMEM.

``cca_mix(x, conv0_w, conv0_b, conv1_w, conv1_b, heads, kv_heads)`` with
``x`` float32 ``[boards, 64, (heads + kv_heads) * head_dim]``, the
projection's result ``[q~ | k~]`` as it is written, gives q ``[boards,
64, heads * head_dim]`` and k ``[boards, 64, kv_heads * head_dim]``,
float32, as ``ops/board_attention.py`` takes them, and two sums for the
step's counters. ``t - s`` is an earlier square of the same board,
nothing before square 0; a column belongs to head ``column //
head_dim``, the query heads first::

    a[t]    = b0 + sum_k w0[:, k] * x[t - (T0 - 1) + k]                 depthwise, ``conv0_w`` [columns, T0], float32
    c[t, g] = b1[g] + sum_k a[t - (T1 - 1) + k, g] @ W1[g, k]           a head at a time, ``conv1_w`` [heads + kv_heads, T1, head_dim,
                                                                        head_dim]: bfloat16 operands, float32 accumulation
    q[h]    = c[h] + (x[h] + x[heads + h // group]) / 2                 group = heads // kv_heads
    k[g]    = c[heads + g] + (mean over the group's heads of x[h] + x[heads + g]) / 2
    sums    = (sum (c - x)^2, sum x^2)                                  no gradient: what the convolutions changed, over what they were given

A grid step holds a few boards' ``[64, columns]`` as ``[boards * 64,
head_dim]`` a head: a shift along the squares is a rotation of the
sublanes and a select on the square's index (what the rotation brings in
from the board before is selected away), conv0 is ``T0`` multiply-adds,
conv1 ``T1`` products of ``[boards * 64, head_dim] x [head_dim,
head_dim]`` a head, and q and k are written once. x is read once.

The gradient is a second kernel, ``cca_mix_grad``, that reads x and the
cotangents of q and k once, makes ``a`` again (``T0`` multiply-adds: the
one thing recomputed) and writes the cotangent of x once; the gradients
of the two convolutions' weights and biases are summed over the grid's
steps in VMEM (their blocks stay where they are from step to step) and
leave once, float32. Cotangents round where JAX's own transposes of the
formula above round: the operands of conv1's three products are
bfloat16.

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

__all__ = ["cca_mix"]

SQUARES = 64
#: Boards a grid step: with x, q and k (forward) or x, both cotangents and x's (gradient) double-buffered beside conv1's
#: weights and their float32 sums, 6 and 12 MiB of VMEM at 1,280 columns.
_BOARDS = 4
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=32 << 20)


def _squares(rows: int, lanes: int) -> jax.Array:
    """Each row's square of its board, ``[rows, lanes]`` (rows are boards x 64)."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) % SQUARES


def _earlier(u: jax.Array, by: int, square: jax.Array) -> jax.Array:
    """``u[t - by]`` along the squares of every board of ``[boards * 64, lanes]``, zero before square 0."""
    return u if by == 0 else jnp.where(square >= by, pltpu.roll(u, by, axis=0), 0.0)


def _later(u: jax.Array, by: int, square: jax.Array) -> jax.Array:
    """The transpose of ``_earlier``: ``u[t + by]``, zero past square 63."""
    return u if by == 0 else jnp.where(square < SQUARES - by, pltpu.roll(u, u.shape[0] - by, axis=0), 0.0)


def _head(ref, g: int, hd: int) -> jax.Array:
    """Head ``g``'s columns of a block ``[boards, 64, heads * head_dim]`` as ``[boards * 64, head_dim]``."""
    return ref[:, :, g * hd:(g + 1) * hd].reshape(-1, hd)


def _conv0(xg: jax.Array, w0_ref, b0_ref, g: int, hd: int, square: jax.Array) -> jax.Array:
    lanes, taps = slice(g * hd, (g + 1) * hd), w0_ref.shape[0]
    return b0_ref[:, lanes] + sum(w0_ref[k:k + 1, lanes] * _earlier(xg, taps - 1 - k, square) for k in range(taps))


def _forward_kernel(x_ref, w0_ref, b0_ref, w1_ref, b1_ref, q_ref, k_ref, sums_ref, *, heads: int, kv_heads: int):
    hd, group, taps = w1_ref.shape[-1], heads // kv_heads, w1_ref.shape[1]
    rows = x_ref.shape[0] * SQUARES
    square = _squares(rows, hd)
    changed = given = jnp.zeros((1, hd), jnp.float32)
    for g in range(heads + kv_heads):
        xg = _head(x_ref, g, hd)
        a = _conv0(xg, w0_ref, b0_ref, g, hd, square)
        c = b1_ref[:, g * hd:(g + 1) * hd] + sum(
            jnp.dot(_earlier(a, taps - 1 - k, square).astype(jnp.bfloat16), w1_ref[g, k], preferred_element_type=jnp.float32) for k in range(taps))
        changed = changed + jnp.sum(jnp.square(c - xg), axis=0, keepdims=True)
        given = given + jnp.sum(jnp.square(xg), axis=0, keepdims=True)
        if g < heads:
            out = c + 0.5 * (xg + _head(x_ref, heads + g // group, hd))
            q_ref[:, :, g * hd:(g + 1) * hd] = out.reshape(-1, SQUARES, hd)
        else:
            kv = g - heads
            mean = sum(_head(x_ref, kv * group + j, hd) for j in range(group)) * (1.0 / group)
            k_ref[:, :, kv * hd:(kv + 1) * hd] = (c + 0.5 * (mean + xg)).reshape(-1, SQUARES, hd)
    sums_ref[0, 0:1, :] = changed
    sums_ref[0, 1:2, :] = given


def _backward_kernel(x_ref, dq_ref, dk_ref, w0_ref, b0_ref, w1_ref, dx_ref, dw0_ref, db0_ref, dw1_ref, db1_ref, *, heads: int, kv_heads: int):
    bf16, f32 = jnp.bfloat16, jnp.float32
    hd, group, taps0, taps1 = w1_ref.shape[-1], heads // kv_heads, w0_ref.shape[0], w1_ref.shape[1]
    rows = x_ref.shape[0] * SQUARES
    square = _squares(rows, hd)

    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref in (dw0_ref, db0_ref, dw1_ref, db1_ref):
            ref[...] = jnp.zeros(ref.shape, f32)

    for g in range(heads + kv_heads):
        lanes = slice(g * hd, (g + 1) * hd)
        xg = _head(x_ref, g, hd)
        a = _conv0(xg, w0_ref, b0_ref, g, hd, square)
        dc = _head(dq_ref, g, hd) if g < heads else _head(dk_ref, g - heads, hd)
        dcb = dc.astype(bf16)
        da = jnp.zeros((rows, hd), f32)
        for k in range(taps1):
            by = taps1 - 1 - k
            da = da + _later(jax.lax.dot_general(dcb, w1_ref[g, k], (((1,), (1,)), ((), ())), preferred_element_type=f32), by, square)
            dw1_ref[g, k] = dw1_ref[g, k] + jax.lax.dot_general(_earlier(a, by, square).astype(bf16), dcb, (((0,), (0,)), ((), ())),
                                                                preferred_element_type=f32)
        db1_ref[:, lanes] = db1_ref[:, lanes] + jnp.sum(dc, axis=0, keepdims=True)
        db0_ref[:, lanes] = db0_ref[:, lanes] + jnp.sum(da, axis=0, keepdims=True)
        dxg = jnp.zeros((rows, hd), f32)
        for k in range(taps0):
            by = taps0 - 1 - k
            dxg = dxg + w0_ref[k:k + 1, lanes] * _later(da, by, square)
            dw0_ref[k:k + 1, lanes] = dw0_ref[k:k + 1, lanes] + jnp.sum(_earlier(xg, by, square) * da, axis=0, keepdims=True)
        if g < heads:  # the q-k mean: its own half, and its share of its key head's mean over the group
            dxg = dxg + 0.5 * dc + (0.5 / group) * _head(dk_ref, g // group, hd)
        else:
            kv = g - heads
            dxg = dxg + 0.5 * dc + 0.5 * sum(_head(dq_ref, kv * group + j, hd) for j in range(group))
        dx_ref[:, :, lanes] = dxg.reshape(-1, SQUARES, hd)


def _specs(x: jax.Array, heads: int, kv_heads: int, taps0: int, taps1: int):
    boards, _, columns = x.shape
    hd = columns // (heads + kv_heads)
    if columns != (heads + kv_heads) * hd or heads % kv_heads:
        raise ValueError(f"cca_mix: {columns} columns are not {heads} + {kv_heads} heads of one width, or the query heads do not divide over the key heads")
    tb = math.gcd(boards, _BOARDS)
    by_board = lambda lanes: pl.BlockSpec((tb, SQUARES, lanes), lambda i: (i, 0, 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return (boards // tb, hd, by_board(columns), by_board(heads * hd), by_board(kv_heads * hd), whole(taps0, columns), whole(1, columns),
            whole(heads + kv_heads, taps1, hd, hd))


def _weights(conv0_w, conv0_b, conv1_w, conv1_b):
    """The operands as the kernels read them: conv0's taps down the sublanes, conv1's matrices in bfloat16."""
    row = lambda b: b.astype(jnp.float32).reshape(1, -1)
    return conv0_w.astype(jnp.float32).T, row(conv0_b), conv1_w.astype(jnp.bfloat16), row(conv1_b)


def cca_mix(x: jax.Array, conv0_w: jax.Array, conv0_b: jax.Array, conv1_w: jax.Array, conv1_b: jax.Array, heads: int, kv_heads: int,
            interpret: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q, k and ``[sum (c - x)^2, sum x^2]`` (module docstring); the sums have no gradient."""
    q, k, sums = _cca_mix(x, conv0_w, conv0_b, conv1_w, conv1_b, heads, kv_heads, interpret)
    return q, k, jax.lax.stop_gradient(sums)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _cca_mix(x, conv0_w, conv0_b, conv1_w, conv1_b, heads: int, kv_heads: int, interpret: bool):
    steps, hd, whole_x, q_spec, k_spec, w0_spec, b_spec, w1_spec = _specs(x, heads, kv_heads, conv0_w.shape[-1], conv1_w.shape[1])
    boards = x.shape[0]
    q, k, sums = pl.pallas_call(
        functools.partial(_forward_kernel, heads=heads, kv_heads=kv_heads),
        grid=(steps,),
        in_specs=[whole_x, w0_spec, b_spec, w1_spec, b_spec],
        out_specs=[q_spec, k_spec, pl.BlockSpec((1, 2, hd), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((boards, SQUARES, heads * hd), jnp.float32), jax.ShapeDtypeStruct((boards, SQUARES, kv_heads * hd), jnp.float32),
                   jax.ShapeDtypeStruct((steps, 2, hd), jnp.float32)],
        compiler_params=_PARAMS,
        name="cca_mix",
        interpret=interpret,
    )(x, *_weights(conv0_w, conv0_b, conv1_w, conv1_b))
    return q, k, jnp.sum(sums, axis=(0, 2))


def _cca_mix_fwd(x, conv0_w, conv0_b, conv1_w, conv1_b, heads, kv_heads, interpret):
    return _cca_mix(x, conv0_w, conv0_b, conv1_w, conv1_b, heads, kv_heads, interpret), (x, conv0_w, conv0_b, conv1_w, conv1_b)


def _cca_mix_bwd(heads, kv_heads, interpret, residuals, cotangents):
    x, conv0_w, conv0_b, conv1_w, conv1_b = residuals
    dq, dk, _ = cotangents
    taps0, taps1 = conv0_w.shape[-1], conv1_w.shape[1]
    steps, hd, whole_x, q_spec, k_spec, w0_spec, b_spec, w1_spec = _specs(x, heads, kv_heads, taps0, taps1)
    columns = x.shape[-1]
    w0, b0, w1, _ = _weights(conv0_w, conv0_b, conv1_w, conv1_b)
    dx, dw0, db0, dw1, db1 = pl.pallas_call(
        functools.partial(_backward_kernel, heads=heads, kv_heads=kv_heads),
        grid=(steps,),
        in_specs=[whole_x, q_spec, k_spec, w0_spec, b_spec, w1_spec],
        out_specs=[whole_x, w0_spec, b_spec, w1_spec, b_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((taps0, columns), jnp.float32), jax.ShapeDtypeStruct((1, columns), jnp.float32),
                   jax.ShapeDtypeStruct(conv1_w.shape, jnp.float32), jax.ShapeDtypeStruct((1, columns), jnp.float32)],
        compiler_params=_PARAMS,
        name="cca_mix_grad",
        interpret=interpret,
    )(x, dq, dk, w0, b0, w1)
    return dx, dw0.T.astype(conv0_w.dtype), db0.reshape(-1).astype(conv0_b.dtype), dw1.astype(conv1_w.dtype), db1.reshape(-1).astype(conv1_b.dtype)


_cca_mix.defvjp(_cca_mix_fwd, _cca_mix_bwd)
