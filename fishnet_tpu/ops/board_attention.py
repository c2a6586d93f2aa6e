"""Pallas TPU kernels for the attention core of the square-token trunk
(``models/trunk.py``): everything between the q, k, v projections and
the output projection, for the 64 squares of a board and one head at a
time, without leaving VMEM.

``board_attention(q, k, v, g_q, g_k)`` with q, k (float32) and v
(bfloat16) as the projections write them, ``[boards, 64, heads *
head_dim]``, gives ``mixed`` in the same shape, bfloat16. Per board and
head::

    q, k <- RMSNorm over head_dim (float32 statistics, gains g_q, g_k),
            then RoPE (rotate-half, position = square index), rounded
            to bfloat16
    s     = q k^T / sqrt(head_dim)        float32 accumulation
    p     = softmax(s)                    float32
    mixed = p (bfloat16) @ v              float32 accumulation

A grid step's block is ``(boards, 64, head_dim)`` at lane offset ``head
* head_dim`` of the arrays as they are: no ``[.., heads, head_dim]``
view ever exists outside VMEM, and the 64 x 64 scores never reach HBM.
Inside, the scores stand ``[key, query]``, the keys down the sublanes,
so that the softmax's sums run over whole vregs: both kernels are bound
by the unit that moves data across lanes (the norms' sums, RoPE's
rotation), not by bytes or products (PERF.md section 5).

The gradient is a second kernel that recomputes the normed and rotated
q, k, the scores and the softmax from the same inputs (the residuals
are the inputs; nothing ``[boards, heads, 64, 64]`` is kept) and rounds
where JAX's own transposes of the formula above round: the cotangents of
bfloat16 values are bfloat16, the four products take bfloat16 operands
and accumulate in float32. The gains' gradients leave it as one partial
sum a grid step, summed outside over ``[steps, heads, head_dim]``.

Off the TPU both kernels run under the Pallas interpreter.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SQUARES", "board_attention", "rope_tables"]

SQUARES = 64

#: Boards a grid step, and boards unrolled in one loop body of the forward
#: and of the gradient kernel: the fastest of 4-64 boards and 1-16 unrolled
#: on a v5e at [512, 64, 16 x 128] (PERF.md section 5). The blocks of a
#: step, double-buffered, take 3 MiB (forward) and 5.5 MiB (gradient) of
#: VMEM; 64 boards would pass the 16 MiB a kernel gets by default.
_BOARDS = 16
_UNROLL = 4
_UNROLL_GRAD = 8

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def rope_tables(theta: float, head_dim: int) -> Tuple[np.ndarray, np.ndarray]:
    """cos and sin ``[64, head_dim]`` float32 of rotate-half RoPE over
    all of ``head_dim``, position = square index. The sine carries
    rotate-half's sign, so that ``rope(x) = x * cos + turned(x) * sin``
    with ``turned`` the plain rotation of the lanes by half of them."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    angle = np.arange(SQUARES, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = np.concatenate([np.cos(angle)] * 2, axis=-1)
    sin = np.concatenate([-np.sin(angle), np.sin(angle)], axis=-1)
    return cos.astype(np.float32), sin.astype(np.float32)


def _turned(x: jax.Array) -> jax.Array:
    """The lanes rotated by half their number (its own inverse)."""
    return pltpu.roll(x, x.shape[-1] // 2, axis=x.ndim - 1)


def _unit(x: jax.Array, eps: float) -> Tuple[jax.Array, jax.Array]:
    """``x / rms(x)`` over the lanes and the factor itself."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    return x * cos + _turned(x) * sin


def _scores(kb: jax.Array, qb: jax.Array) -> jax.Array:
    """``[key, query]``: every sum of the softmax and of its gradient
    then runs down the sublanes, and one product in each kernel pays for
    it with a left side that has to be transposed."""
    s = jax.lax.dot_general(kb, qb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return s * np.float32(1.0 / math.sqrt(qb.shape[-1]))


def _softmax(s: jax.Array) -> jax.Array:
    """Over the keys of ``[key, query]`` scores."""
    e = jnp.exp(s - jnp.max(s, axis=0, keepdims=True))
    return e / jnp.sum(e, axis=0, keepdims=True)


def _each_board(boards: int, body, carry, unroll: int):
    """``carry = body(b, carry)`` for every board of a block, ``unroll`` to
    a loop body (Mosaic unrolls a ``fori_loop`` wholly or not at all)."""
    unroll = math.gcd(boards, unroll)

    def step(j, carry):
        for u in range(unroll):
            carry = body(j * unroll + u, carry)
        return carry

    return jax.lax.fori_loop(0, boards // unroll, step, carry)


def _forward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, out_ref, *, eps: float, unroll: int):
    cos, sin = cos_ref[...], sin_ref[...]
    gq, gk = gq_ref[...], gk_ref[...]

    def board(b, carry):
        qb = _rope(_unit(q_ref[b], eps)[0] * gq, cos, sin).astype(jnp.bfloat16)
        kb = _rope(_unit(k_ref[b], eps)[0] * gk, cos, sin).astype(jnp.bfloat16)
        p = _softmax(_scores(kb, qb)).astype(jnp.bfloat16)
        mixed = jax.lax.dot_general(p, v_ref[b], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        out_ref[b] = mixed.astype(out_ref.dtype)
        return carry

    _each_board(q_ref.shape[0], board, 0, unroll)


def _rounded(x: jax.Array) -> jax.Array:
    """What reaches float32 code as the cotangent of a bfloat16 value."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _unrope_unnorm(d_rot: jax.Array, unit: jax.Array, r: jax.Array, gain: jax.Array, cos: jax.Array, sin: jax.Array):
    """The cotangent of a normed and rotated ``[64, head_dim]`` back to
    its raw input, and the summand of the gain's gradient."""
    d_normed = d_rot * cos + _turned(d_rot * sin)
    d_unit = d_normed * gain
    d_x = r * (d_unit - unit * jnp.mean(d_unit * unit, axis=-1, keepdims=True))
    return d_x, d_normed * unit


def _backward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dgq_ref, dgk_ref, *, eps: float, unroll: int):
    cos, sin = cos_ref[...], sin_ref[...]
    gq, gk = gq_ref[...], gk_ref[...]
    bf16, f32 = jnp.bfloat16, jnp.float32
    scale = np.float32(1.0 / math.sqrt(q_ref.shape[-1]))

    def board(b, carry):
        uq, rq = _unit(q_ref[b], eps)
        uk, rk = _unit(k_ref[b], eps)
        qb = _rope(uq * gq, cos, sin).astype(bf16)
        kb = _rope(uk * gk, cos, sin).astype(bf16)
        vb, do = v_ref[b], do_ref[b]
        p = _softmax(_scores(kb, qb))
        dv_ref[b] = jnp.dot(p.astype(bf16), do, preferred_element_type=f32).astype(dv_ref.dtype)
        dp = _rounded(jax.lax.dot_general(vb, do, (((1,), (1,)), ((), ())), preferred_element_type=f32))
        ds = (p * (dp - jnp.sum(dp * p, axis=0, keepdims=True)) * scale).astype(bf16)
        dq_rot = _rounded(jax.lax.dot_general(ds, kb, (((0,), (0,)), ((), ())), preferred_element_type=f32))
        dk_rot = _rounded(jnp.dot(ds, qb, preferred_element_type=f32))
        dq, dgq = _unrope_unnorm(dq_rot, uq, rq, gq, cos, sin)
        dk, dgk = _unrope_unnorm(dk_rot, uk, rk, gk, cos, sin)
        dq_ref[b], dk_ref[b] = dq, dk
        return carry[0] + dgq, carry[1] + dgk

    zero = jnp.zeros(cos.shape, f32)
    dgq, dgk = _each_board(q_ref.shape[0], board, (zero, zero), unroll)
    dgq_ref[0] = jnp.sum(dgq, axis=0, keepdims=True)
    dgk_ref[0] = jnp.sum(dgk, axis=0, keepdims=True)


def _blocks(boards: int, heads: int, head_dim: int):
    """The grid (blocks of boards, heads) and the BlockSpecs of a ``[boards,
    64, heads * head_dim]`` operand, a gain or table ``[.., head_dim]``
    and a step's partial sum in ``[steps, 1, heads * head_dim]``."""
    tb = math.gcd(boards, _BOARDS)
    per_head = pl.BlockSpec((tb, SQUARES, head_dim), lambda i, h: (i, 0, h))
    whole = lambda rows: pl.BlockSpec((rows, head_dim), lambda i, h: (0, 0))
    partial = pl.BlockSpec((1, 1, head_dim), lambda i, h: (i, 0, h))
    return (boards // tb, heads), per_head, whole, partial


def _operands(g_q, g_k, theta: float):
    head_dim = g_q.shape[-1]
    cos, sin = rope_tables(theta, head_dim)
    gain = lambda g: g.astype(jnp.float32).reshape(1, head_dim)
    return gain(g_q), gain(g_k), jnp.asarray(cos), jnp.asarray(sin)


def _unroll(interpret: bool, unroll: int) -> int:
    """Unrolling is for Mosaic's scheduler; the interpreter pays for every
    emitted operation and gains nothing."""
    return 1 if interpret else unroll


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def board_attention(q: jax.Array, k: jax.Array, v: jax.Array, g_q: jax.Array, g_k: jax.Array,
                    theta: float, eps: float, interpret: bool = False) -> jax.Array:
    """The attention core (module docstring): q, k float32 and v bfloat16
    ``[boards, 64, heads * head_dim]``, gains ``[head_dim]`` -> bfloat16
    of the same shape. ``heads`` follows from the shapes."""
    boards, _, inner = q.shape
    head_dim = g_q.shape[-1]
    grid, per_head, whole, _ = _blocks(boards, inner // head_dim, head_dim)
    return pl.pallas_call(
        functools.partial(_forward_kernel, eps=eps, unroll=_unroll(interpret, _UNROLL)),
        grid=grid,
        in_specs=[per_head, per_head, per_head, whole(1), whole(1), whole(SQUARES), whole(SQUARES)],
        out_specs=per_head,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="board_attention",
        interpret=interpret,
    )(q, k, v, *_operands(g_q, g_k, theta))


def _board_attention_fwd(q, k, v, g_q, g_k, theta, eps, interpret):
    return board_attention(q, k, v, g_q, g_k, theta, eps, interpret), (q, k, v, g_q, g_k)


def _board_attention_bwd(theta, eps, interpret, residuals, d_mixed):
    q, k, v, g_q, g_k = residuals
    boards, _, inner = q.shape
    head_dim = g_q.shape[-1]
    heads = inner // head_dim
    grid, per_head, whole, partial = _blocks(boards, heads, head_dim)
    sums = jax.ShapeDtypeStruct((grid[0], 1, inner), jnp.float32)
    dq, dk, dv, dgq, dgk = pl.pallas_call(
        functools.partial(_backward_kernel, eps=eps, unroll=_unroll(interpret, _UNROLL_GRAD)),
        grid=grid,
        in_specs=[per_head, per_head, per_head, whole(1), whole(1), whole(SQUARES), whole(SQUARES), per_head],
        out_specs=[per_head, per_head, per_head, partial, partial],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), sums, sums],
        compiler_params=_PARAMS,
        name="board_attention_grad",
        interpret=interpret,
    )(q, k, v, *_operands(g_q, g_k, theta), d_mixed)
    total = lambda s, g: s.reshape(-1, heads, head_dim).sum(axis=(0, 1)).astype(g.dtype)
    return dq, dk, dv, total(dgq, g_q), total(dgk, g_k)


board_attention.defvjp(_board_attention_fwd, _board_attention_bwd)
