"""Pallas TPU kernels for the core of a Kimi Delta Attention mixer over the
64 squares of a board (``models/trunk.py _kda``): the gated delta rule with
a decay a CHANNEL, between the convolution and the gated head norm, without
leaving VMEM.

Per board and head (``d`` columns of q, k, v and of the log-decay ``g <=
0``, one ``beta`` in (0, 1) a square), with ``q^ = q / |q| * d^-1/2`` and
``k^ = k / |k|`` (the l2 norm a head, eps 1e-6 under the root, made here),
the recurrence over the squares t = 0..63 is::

    S_t = (I - beta_t k^_t k^_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k^_t v_t^T        S [d, d], zero before square 0
    o_t = S_t^T q^_t

A board is ONE chunk from a zero state, so the recurrence is exactly its
chunk form, which is what the kernels compute (``c = cumsum(g)`` a channel,
float32, made in the kernel)::

    Mk[t, j] = sum_c k^_t[c] k^_j[c] exp(c_t[c] - c_j[c])      j <  t, else 0
    Mq[t, j] = sum_c q^_t[c] k^_j[c] exp(c_t[c] - c_j[c])      j <= t, else 0
    U        = (I + Diag(beta) Mk)^-1 (beta * V)                unit lower triangular, 64 x 64
    O        = Mq U

**No exponent is ever positive.** ``exp(c_t - c_j)`` is a decay a channel,
so ``Mk`` and ``Mq`` are not one product of two scaled operands: ``k^_j
exp(-c_j)`` overflows float32 where a channel forgets fast (64 squares at
1.6 a square is e^102). The strict lower triangle is cut by the HIGHEST BIT
in which t and j differ, ``p`` = 0..5: the pairs of level ``p`` are those
with t in the upper and j in the lower half of one block of ``2^(p+1)``
squares, and for them ``exp(c_t - c_j) = exp(c_t - r) exp(r - c_j)`` with
``r = c`` at the block's middle square, both exponents <= 0. A level is one
product of ``[64, d]`` operands (the rows of the other half zeroed, by a
select BEFORE the exponential) under a static mask; six levels and the
diagonal make a matrix. ``r`` comes from a product with a 0/1 selection
matrix; any ``r`` between the two serves, as long as both sides read the
same, so its precision is nobody's concern. The solve is the same cut
upside down: ``T = (I + A)^-1`` is built from 1 x 1 blocks (1) by ``T <- T
- T A_p T``, ``A_p`` the level's part of ``A``: block forward substitution
as six pairs of 64 x 64 float32 products, no loop over rows. The decays,
the cumulative sum and the solve are float32 (products at ``highest``); the
level products, ``Mq U`` and their transposes take bfloat16 operands and
accumulate in float32.

``board_delta(q, k, v, g, beta)`` takes q, k, v ``[boards, 64, heads * d]``
bfloat16 as the convolution writes them, ``g`` float32 in the same shape and
``beta`` ``[boards, 64, heads]`` float32, and gives o in q's shape,
bfloat16. A grid step is one head of a few boards; no ``[.., heads, d]``
view, no ``[64, 64]`` table and no state reaches HBM. ``board_delta_grad``
recomputes the chunk form from the same inputs (the residuals are the
inputs) and returns dq, dk, dv (bfloat16: cotangents of bfloat16 values),
dg and dbeta (float32; a board's ``[64, heads]`` block of dbeta stays in
VMEM over the heads' steps). With ``dM`` the cotangents of the two
matrices, a level's operands get theirs by three products, and ``dc = QL *
dQL + KL * dKL - KR * dKR`` summed over the levels (``r`` has none: the
product does not depend on it); ``dg`` is ``dc`` summed over the later
squares. A scan longer than one chunk (a state handed on) is not computed
here. Off the TPU both kernels run under the Pallas interpreter.
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

__all__ = ["board_delta"]

#: Boards a grid step: a step's blocks (four bfloat16 and two float32 ``[boards, 64, 128]`` in, five out in the gradient) stay
#: under 3 MiB double-buffered.
_BOARDS = 8
_LANES = 128
#: Under the root of the l2 norm of a head's q and of its k.
L2_EPS = 1e-6
#: The levels of the cut: the bits of a square's index.
_LEVELS = SQUARES.bit_length() - 1
_NEVER = -1e30  # an exponent that is selected away: exp gives 0

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _exact(a: jax.Array, b: jax.Array, dims=_NN) -> jax.Array:
    """A float32 product of the decays or of the solve: no operand is rounded."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _dot(a: jax.Array, b: jax.Array, dims=_NN) -> jax.Array:
    """bfloat16 operands, float32 accumulation and result."""
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims, preferred_element_type=jnp.float32)


def _squares():
    """The square of a row and of a column of a ``[64, 64]`` table, and of a row of a ``[64, 1]`` column."""
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return iota((SQUARES, SQUARES), 0), iota((SQUARES, SQUARES), 1), iota((SQUARES, 1), 0)


def _level(p: int, t: jax.Array, j: jax.Array, row: jax.Array):
    """Level ``p``'s pairs ``[64, 64]`` (t in the upper, j in the lower half of one block of ``2^(p+1)`` squares), which rows
    are an upper half's ``[64, 1]``, and the 0/1 matrix that gives every row its block's middle row."""
    pairs = (jnp.right_shift(jnp.bitwise_xor(t, j), p) == 1) & (t > j)
    middle = jnp.left_shift(jnp.right_shift(t, p + 1), p + 1) + (1 << p)
    return pairs, (jnp.right_shift(row, p) & 1) == 1, (j == middle).astype(jnp.float32)


def _level_decays(p: int, c: jax.Array, t: jax.Array, j: jax.Array, row: jax.Array):
    """Level ``p``'s pairs and its two decays ``[64, d]``: ``exp(c - r)`` on the rows of the upper halves and ``exp(r - c)``
    on those of the lower, 0 on the others (selected before the exponential: nothing overflows)."""
    pairs, upper, select = _level(p, t, j, row)
    r = jnp.dot(select, c, preferred_element_type=jnp.float32)
    return pairs, jnp.exp(jnp.where(upper, c - r, _NEVER)), jnp.exp(jnp.where(upper, _NEVER, r - c))


def _unit(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A head's rows over their l2 norm, and the reciprocal norm ``[64, 1]``."""
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return x * r, r


def _chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array):
    """One head of one board, float32 ``[64, d]`` and beta ``[64, 1]`` -> everything of the chunk form that both kernels
    read: the normed q and k with their reciprocal norms, c, Mq, Mk, T and U."""
    f32 = jnp.float32
    t, j, row = _squares()
    scale = 1.0 / math.sqrt(q.shape[-1])
    (qn, rq), (kn, rk) = _unit(q), _unit(k)
    qn = qn * scale
    c = _exact((t >= j).astype(f32), g)
    mq = jnp.where(t == j, jnp.sum(qn * kn, axis=-1, keepdims=True), 0.0)
    mk = jnp.zeros((SQUARES, SQUARES), f32)
    for p in range(_LEVELS):
        pairs, upper, lower = _level_decays(p, c, t, j, row)
        kr = kn * lower
        mq = mq + jnp.where(pairs, _dot(qn * upper, kr, _NT), 0.0)
        mk = mk + jnp.where(pairs, _dot(kn * upper, kr, _NT), 0.0)
    a = beta * mk
    tm = (t == j).astype(f32)
    for p in range(_LEVELS):  # blocks of 1, 2, .. 32 joined two by two: [[T1, 0], [-T2 A21 T1, T2]]
        ap = jnp.where(_level(p, t, j, row)[0], a, 0.0)
        tm = tm - _exact(_exact(tm, ap), tm)
    u = _exact(tm, beta * v)
    return dict(qn=qn, kn=kn, rq=rq, rk=rk, c=c, mq=mq, mk=mk, tm=tm, u=u, scale=scale)


def _own_beta(beta_ref, i, h) -> Tuple[jax.Array, jax.Array]:
    """Head ``h``'s beta of board ``i`` as a column ``[64, 1]``, and the mask of its lane in the ``[64, heads]`` block."""
    block = beta_ref[i]
    own = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1) == h
    return jnp.sum(jnp.where(own, block, 0.0), axis=-1, keepdims=True), own


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref):
    f32, h = jnp.float32, pl.program_id(1)

    def board(i, carry):
        beta, _ = _own_beta(beta_ref, i, h)
        parts = _chunk(q_ref[i].astype(f32), k_ref[i].astype(f32), v_ref[i].astype(f32), g_ref[i], beta)
        o_ref[i] = _dot(parts["mq"], parts["u"]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], board, 0)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    f32, h = jnp.float32, pl.program_id(1)

    def board(i, carry):
        beta, own = _own_beta(beta_ref, i, h)
        v, do = v_ref[i].astype(f32), do_ref[i]
        parts = _chunk(q_ref[i].astype(f32), k_ref[i].astype(f32), v, g_ref[i], beta)
        qn, kn, c, mq, mk, tm, u = (parts[name] for name in ("qn", "kn", "c", "mq", "mk", "tm", "u"))
        t, j, row = _squares()
        dmq = jnp.where(t >= j, _dot(do, u, _NT), 0.0)
        w = _exact(tm, _dot(mq, do, _TN), _TN)  # T^T dU: the cotangent of beta * V
        da = -jnp.where(t > j, _exact(w, u, _NT), 0.0)
        dv_ref[i] = (beta * w).astype(dv_ref.dtype)
        dbeta = jnp.sum(w * v, axis=-1, keepdims=True) + jnp.sum(da * mk, axis=-1, keepdims=True)
        dbeta_ref[i] = jnp.where(own, dbeta, dbeta_ref[i])
        dmk = beta * da
        on_diagonal = jnp.sum(jnp.where(t == j, dmq, 0.0), axis=-1, keepdims=True)
        dqn, dkn, dc = on_diagonal * kn, on_diagonal * qn, jnp.zeros_like(c)
        for p in range(_LEVELS):
            pairs, upper, lower = _level_decays(p, c, t, j, row)
            ql, kl, kr = qn * upper, kn * upper, kn * lower
            dq_pairs, dk_pairs = jnp.where(pairs, dmq, 0.0), jnp.where(pairs, dmk, 0.0)
            dql, dkl = _dot(dq_pairs, kr), _dot(dk_pairs, kr)
            dkr = _dot(dq_pairs, ql, _TN) + _dot(dk_pairs, kl, _TN)
            dqn, dkn = dqn + dql * upper, dkn + dkl * upper + dkr * lower
            dc = dc + ql * dql + kl * dkl - kr * dkr
        dg_ref[i] = _exact((t >= j).astype(f32), dc, _TN)  # dg_s = the sum of dc_t over t >= s
        # through the l2 norms: y = x r, dx = r (dy - y sum(y dy)); q's y is qn / scale
        dqn = dqn * parts["scale"]
        qy = qn * (1.0 / parts["scale"])
        dq_ref[i] = (parts["rq"] * (dqn - qy * jnp.sum(qy * dqn, axis=-1, keepdims=True))).astype(dq_ref.dtype)
        dk_ref[i] = (parts["rk"] * (dkn - kn * jnp.sum(kn * dkn, axis=-1, keepdims=True))).astype(dk_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], board, 0)


def _blocks(q: jax.Array, beta: jax.Array, interpret: bool):
    """The grid (blocks of boards, heads) and the BlockSpecs of a head's columns and of a block of boards' beta."""
    boards, squares, inner = q.shape
    heads = beta.shape[-1]
    if squares != SQUARES or inner % heads or beta.shape[:2] != (boards, SQUARES):
        raise ValueError(f"board_delta: q {q.shape} and beta {beta.shape} are not [boards, {SQUARES}, heads x d] and [boards, {SQUARES}, heads]")
    d = inner // heads
    if d % _LANES and not interpret:
        raise ValueError(f"board_delta: a head of {d} columns is not whole {_LANES}-lane tiles")
    tb = math.gcd(boards, _BOARDS)
    return (boards // tb, heads), pl.BlockSpec((tb, SQUARES, d), lambda i, h: (i, 0, h)), pl.BlockSpec((tb, SQUARES, heads), lambda i, h: (i, 0, 0))


def _operands(q, k, v, g, beta):
    bf16, f32 = jnp.bfloat16, jnp.float32
    return q.astype(bf16), k.astype(bf16), v.astype(bf16), g.astype(f32), beta.astype(f32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def board_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, interpret: bool = False) -> jax.Array:
    """The delta rule's core (module docstring): q, k, v ``[boards, 64,
    heads * d]`` bfloat16 (before their l2 norms), ``g`` float32 in that
    shape (a log-decay a channel, <= 0), ``beta`` ``[boards, 64, heads]``
    float32 -> o in q's shape, bfloat16."""
    grid, head, betas = _blocks(q, beta, interpret)
    return pl.pallas_call(
        _forward_kernel,
        grid=grid,
        in_specs=[head, head, head, head, betas],
        out_specs=head,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="board_delta",
        interpret=interpret,
    )(*_operands(q, k, v, g, beta))


def _board_delta_fwd(q, k, v, g, beta, interpret):
    return board_delta(q, k, v, g, beta, interpret), (q, k, v, g, beta)


def _board_delta_bwd(interpret, residuals, do):
    q, k, v, g, beta = residuals
    grid, head, betas = _blocks(q, beta, interpret)
    like = lambda x, dtype: jax.ShapeDtypeStruct(x.shape, dtype)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        _backward_kernel,
        grid=grid,
        in_specs=[head, head, head, head, betas, head],
        out_specs=[head, head, head, head, betas],
        out_shape=[like(q, jnp.bfloat16), like(k, jnp.bfloat16), like(v, jnp.bfloat16), like(g, jnp.float32), like(beta, jnp.float32)],
        compiler_params=_PARAMS,
        name="board_delta_grad",
        interpret=interpret,
    )(*_operands(q, k, v, g, beta), do.astype(jnp.bfloat16))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dg.astype(g.dtype), dbeta.astype(beta.dtype)


board_delta.defvjp(_board_delta_fwd, _board_delta_bwd)
