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
as five pairs of float32 products after a level 0 that needs none (``I -
A_0``), no loop over rows. The decays,
the cumulative sum and the solve are float32 (products at ``highest``); the
level products, ``Mq U`` and their transposes take bfloat16 operands and
accumulate in float32.

``board_delta(q, k, v, g, beta)`` takes q, k, v ``[boards, 64, heads * d]``
bfloat16 as the convolution writes them, ``g`` float32 in the same shape and
``beta`` ``[boards, 64, heads]`` float32, and gives o in q's shape,
bfloat16. A grid step is one head of a few boards; no ``[.., heads, d]``
view and no state reaches HBM, and called without a gradient the kernel
writes o alone.

**The solve is made once, two chains a product.** The chunk form is three
parts: the norms and ``c`` (``_normed``: cheap, no chain), the two score
tables (``_tables``) and the solve with ``U`` (``_solve``: a chain of
dependent float32 products at ``highest``, each waiting for the one before,
which is what the kernel's time is made of, not its bytes; a round trip
through the array costs the same for ``[64, 128] x [128, 128]`` as for ``[64,
64] x [64, 64]``, a quarter of it, and Mosaic overlaps no two chains by
itself). So ``_solve`` takes ONE chain or a PACKED PAIR of independent chains
``a``, ``b`` side by side, ``P = [T_a | T_b]`` float32 ``[64, 128]`` (8 whole
vregs), and a level is ``P <- P - (P bd(A_p)) bd(P)`` with ``bd(X) = [[X_a,
0], [0, X_b]]`` ``[128, 128]`` (the packed value laid twice along the rows
under a select), ``U`` of both ``bd(P) [beta V_a ; beta V_b]``. **Level 0 has
no product**: before it ``T`` is the identity, so its ``T - (T A_0) T`` is ``I
- A_0`` and is written so; the loop of products starts at level 1. Ten
dependent round trips and ``U``'s for two chains where the six-level single
chain made twenty-four and two, at the same precision; the zero blocks add
exact zeros and ``I A_0 I`` at ``highest`` is ``A_0``, so every ``T``, ``U``
and ``o`` is the six-level single chain's bit for bit (on the chip and under
XLA:CPU; PERF.md section 6, PR 53). Which two chains is told by shapes alone:
the first form pairs boards ``2 t``, ``2 t + 1`` of a grid step's block (a
loop turn is a pair; an odd block, ``gcd(boards, 8)`` 1, runs one board a
turn), the second form a key head's value heads two by two (an odd one left
over runs the single chain). The forward walks all three parts. When
``board_delta`` is differentiated its forward rule calls the SAME kernel
body told to write, beside o, what the gradient needs of the form, and
hands it on as residuals with the five inputs (a board and head, at the
padded size HBM holds: 80 KB, 160 MiB at 128 boards x 16 heads)::

    [T | Mk]   float32  [boards, 64, heads * 128]   T in a head's lower 64 lanes, Mk in its upper: one whole tile
    U          float32  [boards, 64, heads * d]     in q's columns
    Mq         bfloat16 [boards, 64, heads * 128]   in a head's lower 64 lanes (the upper 64 are never written or read);
                                                    its only reader rounds it to bfloat16 anyway: the same bits

Every minor dimension is whole 128-lane tiles and every block is indexed by
(block of boards, head) like the operands'. ``board_delta_grad`` reads the
three, makes ``_normed`` again for itself and starts on its own work: no
table, no solve, and every value it reads is the value it would have
computed, bit for bit (the other two kernel pairs of the trunk recompute
from their inputs because their tables are a product and a softmax; here
the table is the chain). It returns dq, dk, dv (bfloat16: cotangents of
bfloat16 values), dg and dbeta (float32; a board's ``[64, heads]`` block of
dbeta stays in VMEM over the heads' steps). With ``dM`` the cotangents of the two
matrices, a level's operands get theirs by three products, and ``dc = QL *
dQL + KL * dKL - KR * dKR`` summed over the levels (``r`` has none: the
product does not depend on it); ``dg`` is ``dc`` summed over the later
squares. A scan longer than one chunk (a state handed on) is not computed
here. Off the TPU both kernels run under the Pallas interpreter.

**The gradient works two chains a product too**, told by the same shapes
as the forward and for its reason (its time was the same dependent round
trips: ``Mq^T dO``, ``w = T^T .``, ``dA = -w U^T``, then the levels or the
spans, each waiting for the one before). The first form's loop turn takes
boards ``2 t``, ``2 t + 1`` of the block STACKED ALONG THE ROWS: q^, k^,
``c``, v, ``U``, o's cotangent and every level's decays are ``[128, d]``
values, the tables ``T``, ``Mq``, ``dMq``, ``dMk`` ONE block diagonal ``[128,
128]`` each (``_squares(2)``: a column of the other board's table is square
64, after every square, so the triangle's, the diagonal's and every level's
masks cut the off-diagonal blocks by themselves and the lines are the single
board's), and each of a board's products is the pair's one: ``c`` and ``dg``
on ``bd`` of the triangle, ``w`` on ``bd(T)``, ``dMq`` and ``dA`` the
diagonal blocks of ``[128, d] x [d, 128]``, a level's ``dQL`` and ``dKL``
the halves of ONE product on ``[dMq_p ; dMk_p]`` and the two halves of
``dKR`` one each, the six levels' ``r`` ONE product of their six 0/1
matrices stacked (``_middles``): 25 products a pair of boards where one
board made 36. An odd block (``gcd(boards, 8)`` 1) runs one board a turn,
the same lines on ``[64, .]``. The second form takes a key head's value
heads two by two as the forward does: ``L``, ``Mq``, ``Mk``, ``dMq``, ``dMk``
``[64, 128]`` side by side, their spans ONE product, ``T^T dU`` of both ONE
product on ``bd`` of the kept tile as it stands, ``dMq`` and ``dA`` the
diagonal blocks of ONE ``[128, d] x [d, 128]`` each on v's, ``U``'s and
the cotangent's rows stacked, ``dD``'s sums ONE product; the key head's two
undecayed tables are the halves of one product on ``[q^ ; k^]`` and its four
closing products two (``[dqk ; dkk] k^`` and ``bd([dqk | dkk])^T [q^ ;
k^]``): 9 products a board and key head of two value heads where there were
18; an odd value head left over is one chain. No two products are joined
along a contraction, a chain's own sums (``dbeta``, ``dg``, ``dqk``,
``dkk``) are made on its own lanes in the single chain's order, a zero block
adds exact zeros and a dropped block changes no kept element: dq, dk, dv,
dg, dbeta are the single chain's bit for bit, on the chip and under XLA:CPU
(``tests/test_board_delta.py`` keeps the single-chain bodies as its oracle;
PERF.md section 6, PR 58).

**The pair is traced once a program.** The forward call (both forms) and the
gradient call each sit under one ``jax.jit`` (bare under the interpreter:
``mamba_mix._called``), so the four KDA layers of a step share one trace of
each kernel body and one Mosaic lowering, which every start of the program
pays (``setup_s``); a body works ONE turn (a board, or a pair of boards on
twice the rows) inside a ``fori_loop`` that is not unrolled, for the same
reason (``tests/test_board_delta.py`` holds the
loop rolled, ``tests/test_trunk_tpu_compile.py`` counts the entries into
both bodies while the cell's step is lowered).

**The second form: a decay a HEAD and token** (Gated DeltaNet, ``models/trunk.py
_gdn``). ``board_delta`` is told it by what it is handed and does not guess: ``g``
in ``beta``'s shape, ``[boards, 64, value heads]``, one log-decay a value head
and square, and v (and o) at ``value heads x d`` columns beside q and k at ``key
heads x d``, value head h reading key head ``h // (value heads // key heads)``.
A scalar decay needs no levels: ``D[t, j] = sum of g over the squares (j, t]`` is
ONE float32 product of a triangle of ones with ``g`` masked a column (every
entry a plain sum of the ``g`` themselves, none a difference of two cumulative
sums), ``L = exp(D)`` for ``t >= j`` has no exponent over 0, and::

    Mq = (q^ k^^T) * L   (j <= t)        Mk = (k^ k^^T) * L   (j < t)

are each ONE bfloat16 product under it, made once a KEY head for all of its
value heads. A grid step is one key head of a few boards and its value heads:
q and k are read once a key head, v, g and beta once a value head, nothing is
repeated or broadcast in HBM. The solve is the first form's (``_solve``), in
the forward a PAIR of value heads a chain: the two undecayed tables come twice
along the lanes from one product each (k's rows laid twice), the pair's spans
are ONE product (the triangle times ``[g_a masked | g_b masked]``), ``L``,
``Mk`` and ``Mq`` are ``[64, 128]`` values, ``Mq U`` one product on ``bd(Mq)``,
and the packed ``T`` IS the kept tile. The differentiated forward keeps ``T`` (a
key head's value heads side by side in one 128-lane tile) and ``U``; the gradient kernel makes the two
tables and ``L`` again (they are no chain) and, beside the first form's
gradients of the solve, ``dD = dMq * Mq + dMk * Mk``, ``dg`` from it by the
triangle's transpose, ``dq^``, ``dk^`` from ``sum over the value heads of dM * L``
by two products a key head.
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
from fishnet_tpu.ops.mamba_mix import _called  # a kernel's call under its jax.jit on the chip, bare under the interpreter

__all__ = ["board_delta"]

#: Boards a grid step: a step's blocks (in the gradient four bfloat16 and two float32 ``[boards, 64, 128]`` and the three kept
#: tiles in, five out: 2.5 MiB) are 5 MiB double-buffered.
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


def _squares(boards: int = 1):
    """The square of a row and of a column of a ``[64, 64]`` table, and of a row of a ``[64, 1]`` column. ``boards`` 2: of two
    boards' tables as ONE block diagonal ``[128, 128]`` (the boards stacked along the rows, ``[128, 1]``), each square within its
    own board; a column of the OTHER board's table is square 64, after every square: in no triangle, no level's pairs and on no
    diagonal, and nobody's middle, so every mask made of the three cuts the off-diagonal blocks by itself."""
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    if boards == 1:
        return iota((SQUARES, SQUARES), 0), iota((SQUARES, SQUARES), 1), iota((SQUARES, 1), 0)
    rows = boards * SQUARES
    t, j = iota((rows, rows), 0), iota((rows, rows), 1)
    own = jnp.right_shift(t, _LEVELS) == jnp.right_shift(j, _LEVELS)
    return t & (SQUARES - 1), jnp.where(own, j & (SQUARES - 1), SQUARES), iota((rows, 1), 0) & (SQUARES - 1)


def _pairs(p: int, t: jax.Array, j: jax.Array) -> jax.Array:
    """Level ``p``'s pairs of the squares ``t``, ``j``: t in the upper, j in the lower half of one block of ``2^(p+1)`` squares."""
    return (jnp.right_shift(jnp.bitwise_xor(t, j), p) == 1) & (t > j)


def _level(p: int, t: jax.Array, j: jax.Array, row: jax.Array):
    """Level ``p``'s pairs ``[64, 64]``, which rows are an upper half's ``[64, 1]``, and the 0/1 matrix that gives every row
    its block's middle row."""
    pairs = _pairs(p, t, j)
    middle = jnp.left_shift(jnp.right_shift(t, p + 1), p + 1) + (1 << p)
    return pairs, (jnp.right_shift(row, p) & 1) == 1, (j == middle).astype(jnp.float32)


def _level_decays(p: int, c: jax.Array, t: jax.Array, j: jax.Array, row: jax.Array, r=None):
    """Level ``p``'s pairs and its two decays ``[64, d]``: ``exp(c - r)`` on the rows of the upper halves and ``exp(r - c)``
    on those of the lower, 0 on the others (selected before the exponential: nothing overflows). ``r``: the level's rows of
    ``_middles``, where the caller made all six at once."""
    pairs, upper, select = _level(p, t, j, row)
    if r is None:
        r = jnp.dot(select, c, preferred_element_type=jnp.float32)
    return pairs, jnp.exp(jnp.where(upper, c - r, _NEVER)), jnp.exp(jnp.where(upper, _NEVER, r - c))


def _middles(c: jax.Array, t: jax.Array, j: jax.Array, row: jax.Array):
    """Every level's ``r`` (each row's block's middle row of ``c``) as ONE product: the six 0/1 matrices stacked along the rows
    share ``c``, and a row of the result is the row the level's own product gives."""
    stacked = jnp.dot(jnp.concatenate([_level(p, t, j, row)[2] for p in range(_LEVELS)], axis=0), c, preferred_element_type=jnp.float32)
    return [stacked[at:at + c.shape[0]] for at in range(0, stacked.shape[0], c.shape[0])]


def _unit(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A head's rows over their l2 norm, and the reciprocal norm ``[64, 1]``."""
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return x * r, r


def _normed(q: jax.Array, k: jax.Array, g: jax.Array):
    """One head of one board, float32 ``[64, d]`` (or of two boards stacked along the rows, ``[128, d]``: the gradient's pair)
    -> what is cheap and no chain, made by both kernels: the normed q (times ``d^-1/2``) and k, their reciprocal norms ``[64,
    1]``, ``c = cumsum(g)`` a board and the scale."""
    t, j, _ = _squares(q.shape[0] // SQUARES)
    scale = 1.0 / math.sqrt(q.shape[-1])
    (qn, rq), (kn, rk) = _unit(q), _unit(k)
    return qn * scale, kn, rq, rk, _exact((t >= j).astype(jnp.float32), g), scale


def _tables(qn: jax.Array, kn: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The two decayed score tables ``Mq`` (j <= t) and ``Mk`` (j < t), float32 ``[64, 64]``: the diagonal and six levels."""
    t, j, row = _squares()
    mq = jnp.where(t == j, jnp.sum(qn * kn, axis=-1, keepdims=True), 0.0)
    mk = jnp.zeros((SQUARES, SQUARES), jnp.float32)
    for p in range(_LEVELS):
        pairs, upper, lower = _level_decays(p, c, t, j, row)
        kr = kn * lower
        mq = mq + jnp.where(pairs, _dot(qn * upper, kr, _NT), 0.0)
        mk = mk + jnp.where(pairs, _dot(kn * upper, kr, _NT), 0.0)
    return mq, mk


def _chain_squares(chains: int, rows: int = SQUARES):
    """``_squares`` for ``chains`` tables side by side on the lanes (a PACKED PAIR: two on 128): the square of a row and of a
    lane WITHIN its own chain's table, of ``[rows, chains * 64]`` (``rows`` 64: the tables; 128: a pair's block diagonal), and
    which lanes are the first chain's."""
    shape = (rows, chains * SQUARES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) & (SQUARES - 1), lane & (SQUARES - 1), lane < SQUARES


def _block_diagonal(x: jax.Array) -> jax.Array:
    """A packed pair ``[X_a | X_b]`` ``[64, 128]`` -> ``[[X_a, 0], [0, X_b]]`` ``[128, 128]``: laid twice along the rows under a
    select. One chain's ``[64, 64]`` is its own."""
    if x.shape[-1] == SQUARES:
        return x
    twice = jnp.concatenate([x, x], axis=0)
    row, lane = (jax.lax.broadcasted_iota(jnp.int32, twice.shape, axis) for axis in (0, 1))
    return jnp.where((row < SQUARES) == (lane < SQUARES), twice, 0.0)


def _side_by_side(x: jax.Array) -> jax.Array:
    """A packed pair's rows ``[X_a ; X_b]`` ``[128, d]`` -> ``[X_a | X_b]`` ``[64, 2 d]``, whole 128-lane tiles moved (one chain's
    ``[64, d]``: itself)."""
    return jnp.concatenate([x[at:at + SQUARES] for at in range(0, x.shape[0], SQUARES)], axis=1)


def _diagonal_blocks(x: jax.Array, axis: int) -> jax.Array:
    """A pair's product ``[[X_a, .], [., X_b]]`` ``[128, 128]`` -> its diagonal blocks side by side ``[X_a | X_b]`` ``[64, 128]``
    (``axis`` 1) or stacked ``[X_a ; X_b]`` ``[128, 64]`` (``axis`` 0): one select between the two halves, the off-diagonal
    blocks dropped. One chain's ``[64, 64]`` is its own."""
    if x.shape[0] == SQUARES:
        return x
    halves = (x[:SQUARES], x[SQUARES:]) if axis == 1 else (x[:, :SQUARES], x[:, SQUARES:])
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, halves[0].shape, axis) < SQUARES, *halves)


def _solve(a: jax.Array, bv: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``a = Diag(beta) Mk`` and ``bv = beta V`` -> ``T = (I + a)^-1`` and ``U = T bv``, float32: the chain of ten dependent
    products and ``U``'s, walked by the forward kernels alone. Told by ``a``'s shape: ONE chain (``a`` ``[64, 64]``, ``bv`` ``[64, d]`` -> ``T``
    ``[64, 64]``, ``U`` ``[64, d]``) or a PACKED PAIR of independent chains (``a = [a_a | a_b]`` ``[64, 128]``, ``bv = [bv_a ; bv_b]``
    ``[128, d]`` -> ``[T_a | T_b]`` ``[64, 128]``, ``[U_a ; U_b]`` ``[128, d]``): every product of the pair is one ``[., 128] x [128, .]``
    with the right operand block diagonal, whose zero blocks add exact zeros to what each chain's own product sums."""
    chains = a.shape[-1] // SQUARES
    t, j, _ = _chain_squares(chains)
    tm = (t == j).astype(jnp.float32) - jnp.where(_pairs(0, t, j), a, 0.0)  # level 0 of I, or of [I | I]: I - (I A_0) I is I - A_0, no product
    a = _block_diagonal(a)
    t, j, _ = _chain_squares(chains, chains * SQUARES)
    for p in range(1, _LEVELS):  # blocks of 2, 4, .. 32 joined two by two: [[T1, 0], [-T2 A21 T1, T2]]
        ap = jnp.where(_pairs(p, t, j), a, 0.0)
        tm = tm - _exact(_exact(tm, ap), _block_diagonal(tm))
    return tm, _exact(_block_diagonal(tm), bv)


def _own_lane(ref, i, h) -> Tuple[jax.Array, jax.Array]:
    """Head ``h``'s lane of board ``i``'s ``[64, heads]`` block (a head's beta, or its log-decay) as a column ``[64, 1]``, and the lane's mask."""
    block = ref[i]
    own = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1) == h
    return jnp.sum(jnp.where(own, block, 0.0), axis=-1, keepdims=True), own


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *kept_refs):
    """``kept_refs`` is empty (the primal) or the differentiated form's three further outputs (``_kept``). A loop turn solves
    boards ``2 turn`` and ``2 turn + 1`` of the block as a packed pair (``_solve``), or ONE board where the block is odd."""
    f32, h = jnp.float32, pl.program_id(1)
    together = 2 - q_ref.shape[0] % 2  # boards a turn

    def made(i):  # board i's two tables, its ``Diag(beta) Mk`` and its ``beta V``
        beta, _ = _own_lane(beta_ref, i, h)
        qn, kn, _, _, c, _ = _normed(q_ref[i].astype(f32), k_ref[i].astype(f32), g_ref[i])
        mq, mk = _tables(qn, kn, c)
        return mq, mk, beta * mk, beta * v_ref[i].astype(f32)

    def turn(n, carry):
        boards = [n * together + e for e in range(together)]
        tables = [made(i) for i in boards]
        tm, u = _solve(jnp.concatenate([a for _, _, a, _ in tables], axis=1), jnp.concatenate([bv for _, _, _, bv in tables], axis=0))
        for i, (mq, mk, _, _), at in zip(boards, tables, range(0, together * SQUARES, SQUARES)):
            o_ref[i] = _dot(mq, u[at:at + SQUARES]).astype(o_ref.dtype)
            if kept_refs:
                solve_ref, u_ref, mq_ref = kept_refs
                solve_ref[i, :, :SQUARES], solve_ref[i, :, SQUARES:] = tm[:, at:at + SQUARES], mk
                u_ref[i] = u[at:at + SQUARES]
                mq_ref[i, :, :SQUARES] = mq.astype(mq_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // together, turn, 0)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref, u_ref, mq_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """A loop turn works boards ``2 turn`` and ``2 turn + 1`` of the block STACKED along the rows (``[128, d]`` values, the tables
    one block diagonal ``[128, 128]`` under ``_squares(2)``: every product of the pair is one, whose zero blocks add exact zeros
    to what each board's own product sums), or ONE board where the block is odd: the same lines on ``[64, .]``."""
    f32, h = jnp.float32, pl.program_id(1)
    together = 2 - q_ref.shape[0] % 2  # boards a turn

    def turn(n, carry):
        boards = [n * together + e for e in range(together)]
        stacked = lambda ref, lanes=slice(None): jnp.concatenate([ref[i, :, lanes] for i in boards], axis=0)
        diagonal = lambda ref: _block_diagonal(jnp.concatenate([ref[i, :, :SQUARES] for i in boards], axis=1))
        betas, own = zip(*(_own_lane(beta_ref, i, h) for i in boards))
        beta = jnp.concatenate(betas, axis=0)
        v, do, u, mk = stacked(v_ref).astype(f32), stacked(do_ref), stacked(u_ref), stacked(solve_ref, slice(SQUARES, None))
        qn, kn, rq, rk, c, scale = _normed(stacked(q_ref).astype(f32), stacked(k_ref).astype(f32), stacked(g_ref))
        tm, mq = diagonal(solve_ref), diagonal(mq_ref)
        t, j, row = _squares(together)
        dmq = jnp.where(t >= j, _dot(do, u, _NT), 0.0)
        w = _exact(tm, _dot(mq, do, _TN), _TN)  # T^T dU: the cotangent of beta * V
        da = -jnp.where(t > j, _exact(w, u, _NT), 0.0)
        dv = (beta * w).astype(dv_ref.dtype)
        dbeta = jnp.sum(w * v, axis=-1, keepdims=True) + jnp.sum(_diagonal_blocks(da, 0) * mk, axis=-1, keepdims=True)
        dmk = beta * da
        on_diagonal = jnp.sum(jnp.where(t == j, dmq, 0.0), axis=-1, keepdims=True)
        dqn, dkn, dc = on_diagonal * kn, on_diagonal * qn, jnp.zeros_like(c)
        for p, r in enumerate(_middles(c, t, j, row)):
            pairs, upper, lower = _level_decays(p, c, t, j, row, r)
            ql, kl, kr = qn * upper, kn * upper, kn * lower
            dq_pairs, dk_pairs = jnp.where(pairs, dmq, 0.0), jnp.where(pairs, dmk, 0.0)
            straight = _dot(jnp.concatenate([dq_pairs, dk_pairs], axis=0), kr)  # [dMq_p ; dMk_p] KR: ONE product
            dql, dkl = straight[:dq_pairs.shape[0]], straight[dq_pairs.shape[0]:]
            dkr = _dot(dq_pairs, ql, _TN) + _dot(dk_pairs, kl, _TN)
            dqn, dkn = dqn + dql * upper, dkn + dkl * upper + dkr * lower
            dc = dc + ql * dql + kl * dkl - kr * dkr
        dg = _exact((t >= j).astype(f32), dc, _TN)  # dg_s = the sum of dc_t over t >= s
        # through the l2 norms: y = x r, dx = r (dy - y sum(y dy)); q's y is qn / scale
        dqn = dqn * scale
        qy = qn * (1.0 / scale)
        dq = (rq * (dqn - qy * jnp.sum(qy * dqn, axis=-1, keepdims=True))).astype(dq_ref.dtype)
        dk = (rk * (dkn - kn * jnp.sum(kn * dkn, axis=-1, keepdims=True))).astype(dk_ref.dtype)
        for e, i in enumerate(boards):
            rows = slice(e * SQUARES, (e + 1) * SQUARES)
            dq_ref[i], dk_ref[i], dv_ref[i], dg_ref[i] = dq[rows], dk[rows], dv[rows], dg[rows]
            dbeta_ref[i] = jnp.where(own[e], dbeta[rows], dbeta_ref[i])
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // together, turn, 0)


# -- the second form: a decay a head and token, value heads in groups on a key head (module docstring) -------------------------


def _head_decay(g: jax.Array, t: jax.Array, j: jax.Array) -> jax.Array:
    """``g`` ``[64, 1]`` (<= 0) -> ``L[t, j] = exp(sum of g over (j, t])`` for ``t >= j``, else 0: float32 ``[64, 64]``. Of a packed
    pair of value heads (``g`` ``[g_a | g_b]`` a lane, ``t``, ``j`` ``_chain_squares(2)``'s): ``[L_a | L_b]`` ``[64, 128]``, the two
    decays' spans ONE product of the triangle."""
    rows, columns, _ = _squares()
    spans = _exact((rows >= columns).astype(jnp.float32), jnp.where(t > j, g, 0.0))
    return jnp.exp(jnp.where(t >= j, spans, _NEVER))


def _key_head(q_ref, k_ref, i, side_by_side: int = 1, stacked: bool = False):
    """What a key head's value heads share, of board ``i``: ``_unit``'s results, the scale, and the two undecayed tables
    (``side_by_side`` 2: each table twice along the lanes, ``[64, 128]``, for a packed pair of value heads: ONE product on k's
    rows laid twice; ``stacked``: the two tables the halves of ONE product on ``[q^ ; k^]``)."""
    scale = 1.0 / math.sqrt(q_ref.shape[-1])
    (qn, rq), (kn, rk) = _unit(q_ref[i].astype(jnp.float32)), _unit(k_ref[i].astype(jnp.float32))
    qn = qn * scale
    columns = kn if side_by_side == 1 else jnp.concatenate([kn] * side_by_side, axis=0)
    if stacked:
        both = _dot(jnp.concatenate([qn, kn], axis=0), columns, _NT)
        return qn, kn, rq, rk, scale, both[:SQUARES], both[SQUARES:]
    return qn, kn, rq, rk, scale, _dot(qn, columns, _NT), _dot(kn, columns, _NT)


def _head_forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *kept_refs):
    """``kept_refs`` is empty (the primal) or the differentiated form's two further outputs (``_head_kept``). A key head's value
    heads are solved two by two as packed pairs (``_solve``): their decays' spans are one product, their ``T`` is the kept tile
    as it stands; an odd value head left over is ONE chain, on the tables' first 64 lanes."""
    f32, key_head = jnp.float32, pl.program_id(1)
    d = q_ref.shape[-1]
    per = v_ref.shape[-1] // d  # value heads a key head

    def board(i, carry):
        t, j, _ = _squares()
        _, _, _, _, _, qk, kk = _key_head(q_ref, k_ref, i, min(per, 2))
        for s in range(0, per, 2):
            chains = min(2, per - s)  # value heads s and s + 1
            tp, jp, first = _chain_squares(chains)
            on_lanes = lambda ref: [_own_lane(ref, i, key_head * per + s + e)[0] for e in range(chains)]
            by_chain = lambda columns: jnp.where(first, *columns) if chains == 2 else columns[0]
            g, beta = on_lanes(g_ref), on_lanes(beta_ref)
            decay = _head_decay(by_chain(g), tp, jp)
            width, columns = chains * SQUARES, slice(s * d, (s + chains) * d)
            a = by_chain(beta) * jnp.where(tp > jp, kk[:, :width] * decay, 0.0)
            bv = [beta[e] * v_ref[i, :, (s + e) * d:(s + e + 1) * d].astype(f32) for e in range(chains)]
            tm, u = _solve(a, jnp.concatenate(bv, axis=0))
            o_ref[i, :, columns] = _side_by_side(_dot(_block_diagonal(qk[:, :width] * decay), u)).astype(o_ref.dtype)
            if kept_refs:
                solve_ref, u_ref = kept_refs
                solve_ref[i, :, s * SQUARES:s * SQUARES + width] = tm
                u_ref[i, :, columns] = _side_by_side(u)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], board, 0)


def _head_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref, u_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """A key head's value heads two by two as packed pairs, as the forward solves them: the tables ``[64, 128]`` side by side (the
    kept tile IS the packed ``T``), v, ``U`` and o's cotangent stacked along the rows ``[128, d]``; a product of the pair is one, on
    a block diagonal or with its off-diagonal blocks dropped. An odd value head left over is ONE chain: the same lines on ``[64, 64]``."""
    f32, key_head = jnp.float32, pl.program_id(1)
    d = q_ref.shape[-1]
    per = v_ref.shape[-1] // d

    def board(i, carry):
        t, j, _ = _squares()
        lower = (t >= j).astype(f32)
        qn, kn, rq, rk, scale, qk, kk = _key_head(q_ref, k_ref, i, min(per, 2), stacked=True)
        dqk, dkk = jnp.zeros((SQUARES, SQUARES), f32), jnp.zeros((SQUARES, SQUARES), f32)
        for s in range(0, per, 2):
            chains = min(2, per - s)  # value heads s and s + 1
            tp, jp, first = _chain_squares(chains)
            (g, own), (beta, _) = (zip(*(_own_lane(ref, i, key_head * per + s + e) for e in range(chains))) for ref in (g_ref, beta_ref))
            by_chain = lambda columns: jnp.where(first, *columns) if chains == 2 else columns[0]
            stacked = lambda ref: jnp.concatenate([ref[i, :, (s + e) * d:(s + e + 1) * d] for e in range(chains)], axis=0)
            width = chains * SQUARES
            decay = _head_decay(by_chain(g), tp, jp)
            mq, mk = qk[:, :width] * decay, jnp.where(tp > jp, kk[:, :width] * decay, 0.0)
            v, do, u = stacked(v_ref).astype(f32), stacked(do_ref), stacked(u_ref)
            tm = _block_diagonal(solve_ref[i, :, s * SQUARES:s * SQUARES + width])
            dmq = jnp.where(tp >= jp, _diagonal_blocks(_dot(do, u, _NT), 1), 0.0)
            w = _exact(tm, _dot(_block_diagonal(mq), do, _TN), _TN)  # T^T dU: the cotangent of beta * V
            da = -jnp.where(tp > jp, _diagonal_blocks(_exact(w, u, _NT), 1), 0.0)
            dmk = by_chain(beta) * da
            # dD = dL * L = dMq * Mq + dMk * Mk; D = lower (g masked a column): dg_m = the sum over j < m of (lower^T dD)[m, j]
            spans = _exact(lower, dmq * mq + dmk * mk, _TN)
            reads, keeps, dqk_pair, dkk_pair = jnp.sum(w * v, axis=-1, keepdims=True), da * mk, dmq * decay, dmk * decay
            for e in range(chains):  # each chain's own half of the pair's values (its rows of the stacked, its lanes of the side by side), summed in the single chain's order
                half = slice(e * SQUARES, (e + 1) * SQUARES)
                dv_ref[i, :, (s + e) * d:(s + e + 1) * d] = (beta[e] * w[half]).astype(dv_ref.dtype)
                dbeta = reads[half] + jnp.sum(keeps[:, half], axis=-1, keepdims=True)
                dbeta_ref[i] = jnp.where(own[e], dbeta, dbeta_ref[i])
                dg_ref[i] = jnp.where(own[e], jnp.sum(jnp.where(t > j, spans[:, half], 0.0), axis=-1, keepdims=True), dg_ref[i])
                dqk, dkk = dqk + dqk_pair[:, half], dkk + dkk_pair[:, half]
        # the four products of a key head as two: [dqk ; dkk] k^, and bd([dqk | dkk])^T [q^ ; k^]
        straight = _dot(jnp.concatenate([dqk, dkk], axis=0), kn)
        turned = _dot(_block_diagonal(jnp.concatenate([dqk, dkk], axis=1)), jnp.concatenate([qn, kn], axis=0), _TN)
        dqn = straight[:SQUARES] * scale
        dkn = turned[:SQUARES] + straight[SQUARES:] + turned[SQUARES:]
        # through the l2 norms: y = x r, dx = r (dy - y sum(y dy)); q's y is qn / scale
        qy = qn * (1.0 / scale)
        dq_ref[i] = (rq * (dqn - qy * jnp.sum(qy * dqn, axis=-1, keepdims=True))).astype(dq_ref.dtype)
        dk_ref[i] = (rk * (dkn - kn * jnp.sum(kn * dkn, axis=-1, keepdims=True))).astype(dk_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], board, 0)


def _head_blocks(q: jax.Array, v: jax.Array, beta: jax.Array, interpret: bool):
    """The second form's grid (blocks of boards, KEY heads) and the BlockSpecs of a key head's columns of q or k, of its value
    heads' columns of v, of a block of boards' g or beta, and of its value heads' ``T`` side by side in whole 128-lane tiles."""
    boards, squares, inner = q.shape
    heads = beta.shape[-1]
    d = v.shape[-1] // heads
    if squares != SQUARES or v.shape[-1] % heads or inner % d or heads % (inner // d) or beta.shape[:2] != (boards, SQUARES):
        raise ValueError(f"board_delta: q {q.shape}, v {v.shape} and a decay a head {beta.shape} are not [boards, {SQUARES}, key heads x d], "
                         f"[boards, {SQUARES}, value heads x d] and [boards, {SQUARES}, value heads], the value heads whole groups a key head")
    if d % _LANES and not interpret:
        raise ValueError(f"board_delta: a head of {d} columns is not whole {_LANES}-lane tiles")
    key_heads = inner // d
    per = heads // key_heads
    tb = math.gcd(boards, _BOARDS)
    by_key_head = lambda width: pl.BlockSpec((tb, SQUARES, width), lambda i, h: (i, 0, h))
    tile = -(-per * SQUARES // _LANES) * _LANES
    return (boards // tb, key_heads), by_key_head(d), by_key_head(per * d), pl.BlockSpec((tb, SQUARES, heads), lambda i, h: (i, 0, 0)), by_key_head(tile), tile


def _head_kept(v: jax.Array, key_heads: int, tile: int):
    """What the second form's differentiated forward writes beside o: ``T`` float32 (``_head_blocks``' tile a key head) and ``U`` in v's columns."""
    boards, squares, _ = v.shape
    return [jax.ShapeDtypeStruct((boards, squares, key_heads * tile), jnp.float32), jax.ShapeDtypeStruct(v.shape, jnp.float32)]


def _blocks(q: jax.Array, beta: jax.Array, interpret: bool):
    """The grid (blocks of boards, heads) and the BlockSpecs of a head's columns, of a block of boards' beta, and of a head's
    128-lane tile of a kept array."""
    boards, squares, inner = q.shape
    heads = beta.shape[-1]
    if squares != SQUARES or inner % heads or beta.shape[:2] != (boards, SQUARES):
        raise ValueError(f"board_delta: q {q.shape} and beta {beta.shape} are not [boards, {SQUARES}, heads x d] and [boards, {SQUARES}, heads]")
    d = inner // heads
    if d % _LANES and not interpret:
        raise ValueError(f"board_delta: a head of {d} columns is not whole {_LANES}-lane tiles")
    tb = math.gcd(boards, _BOARDS)
    by_head = lambda width: pl.BlockSpec((tb, SQUARES, width), lambda i, h: (i, 0, h))
    return (boards // tb, heads), by_head(d), pl.BlockSpec((tb, SQUARES, heads), lambda i, h: (i, 0, 0)), by_head(2 * SQUARES)


def _kept(q: jax.Array, heads: int):
    """What the differentiated forward writes beside o and the gradient reads, a board and head (module docstring): ``[T | Mk]`` side
    by side as one float32 tile of 128 lanes, ``U`` float32 in q's columns, ``Mq`` bfloat16 in the lower half of a tile."""
    boards, squares, _ = q.shape
    tile = (boards, squares, heads * 2 * SQUARES)
    return [jax.ShapeDtypeStruct(tile, jnp.float32), jax.ShapeDtypeStruct(q.shape, jnp.float32), jax.ShapeDtypeStruct(tile, jnp.bfloat16)]


def _operands(q, k, v, g, beta):
    bf16, f32 = jnp.bfloat16, jnp.float32
    return q.astype(bf16), k.astype(bf16), v.astype(bf16), g.astype(f32), beta.astype(f32)


#: Both kernels are called under ``jax.jit``, as ``ops/mamba_mix.py``'s are and for its reason: a step's four KDA layers then share
#: ONE trace of each kernel body and one Mosaic lowering a program, where a bare ``pallas_call`` is traced and lowered again at
#: every call site, at every start (ROADMAP S11; PERF.md section 6, PR 49). The call sites' scopes still name each call's operations.
@functools.partial(jax.jit, static_argnames=("interpret", "keep"))
def _forward_call(q, k, v, g, beta, *, interpret: bool, keep: bool):
    """The forward kernel of either form (module docstring), each in its two: o alone, or (``keep``) o and its kept arrays."""
    if g.shape == beta.shape:  # a decay a head: the second form
        grid, key_head, value_heads, betas, solve, tile = _head_blocks(q, v, beta, interpret)
        kernel, in_specs, results, kept = _head_forward_kernel, [key_head, key_head, value_heads, betas, betas], value_heads, ([solve, value_heads], _head_kept(v, grid[1], tile))
    else:
        grid, head, betas, tile = _blocks(q, beta, interpret)
        kernel, in_specs, results, kept = _forward_kernel, [head, head, head, head, betas], head, ([tile, head, tile], _kept(q, beta.shape[-1]))
    o = jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[results, *kept[0]] if keep else results,
        out_shape=[o, *kept[1]] if keep else o,
        compiler_params=_PARAMS,
        name="board_delta",
        interpret=interpret,
    )(*_operands(q, k, v, g, beta))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gradient_call(q, k, v, g, beta, kept, do, *, interpret: bool):
    if g.shape == beta.shape:  # a decay a head: the second form
        grid, key_head, value_heads, betas, solve, _ = _head_blocks(q, v, beta, interpret)
        kernel, in_specs, out_specs = _head_backward_kernel, [key_head, key_head, value_heads, betas, betas, solve, value_heads, value_heads], [key_head, key_head, value_heads, betas, betas]
    else:
        grid, head, betas, tile = _blocks(q, beta, interpret)
        kernel, in_specs, out_specs = _backward_kernel, [head, head, head, head, betas, tile, head, tile, head], [head, head, head, head, betas]
    like = lambda x, dtype: jax.ShapeDtypeStruct(x.shape, dtype)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[like(q, jnp.bfloat16), like(k, jnp.bfloat16), like(v, jnp.bfloat16), like(g, jnp.float32), like(beta, jnp.float32)],
        compiler_params=_PARAMS,
        name="board_delta_grad",
        interpret=interpret,
    )(*_operands(q, k, v, g, beta), *kept, do.astype(jnp.bfloat16))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dg.astype(g.dtype), dbeta.astype(beta.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def board_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, interpret: bool = False) -> jax.Array:
    """The delta rule's core (module docstring): q, k, v ``[boards, 64,
    heads * d]`` bfloat16 (before their l2 norms), ``g`` float32 in that
    shape (a log-decay a channel, <= 0), ``beta`` ``[boards, 64, heads]``
    float32 -> o in q's shape, bfloat16. Handed ``g`` in ``beta``'s shape
    (a log-decay a head) it is the second form: q and k ``[boards, 64, key
    heads * d]``, v ``[boards, 64, value heads * d]``, o in v's shape."""
    return _called(_forward_call, interpret)(q, k, v, g, beta, interpret=interpret, keep=False)


def _board_delta_fwd(q, k, v, g, beta, interpret):
    o, *kept = _called(_forward_call, interpret)(q, k, v, g, beta, interpret=interpret, keep=True)
    return o, (q, k, v, g, beta, tuple(kept))


def _board_delta_bwd(interpret, residuals, do):
    return _called(_gradient_call, interpret)(*residuals, do, interpret=interpret)


board_delta.defvjp(_board_delta_fwd, _board_delta_bwd)
