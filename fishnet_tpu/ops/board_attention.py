"""Pallas TPU kernels for the attention core of the square-token trunk
(``models/trunk.py``): everything between the q, k, v projections and
the output projection, for the 64 squares of a board and one head at a
time, without leaving VMEM.

``board_attention(q, k, v, g_q, g_k)`` with q, k (float32) and v
(bfloat16) as the projections write them, q ``[boards, 64, heads *
head_dim]`` and k, v ``[boards, 64, kv_heads * head_dim]``, gives
``mixed`` in q's shape, bfloat16. ``heads`` is a multiple of
``kv_heads``: query head h attends key-value head ``h // (heads //
kv_heads)`` (grouped-query attention; one to one where the two counts
are equal). Per board and query head::

    q, k <- RMSNorm over head_dim (float32 statistics, gains g_q, g_k),
            then RoPE (rotate-half, position = square index) unless
            ``theta`` is None, rounded to bfloat16
    s     = q k^T / sqrt(head_dim)        float32 accumulation
    p     = softmax(s)                    float32
    mixed = p (bfloat16) @ v              float32 accumulation

A grid step's block is one key-value head of a few boards, ``(boards,
64, head_dim)`` of k and v at lane offset ``kv_head * head_dim`` of the
arrays as they are, and the group of query heads that attend it,
``(boards, 64, group * head_dim)`` of q: k is normed and rotated once a
group, and the gradient's dk, dv sum over the group in VMEM. No ``[..,
heads, head_dim]`` view ever exists outside VMEM, and the scores never
reach HBM.
Inside, the scores stand ``[key, query]``, the keys down the sublanes,
so that the softmax's sums run down whole vregs, and a group's query
heads are taken TWO AT A TIME (since PR 61; PR 60 brought the same
change, the driver measured it and refused it on one pair of runs of
``train_pos_per_s``, and PR 61 asked again): the pair's normed, turned,
rounded queries and cotangents stacked along the rows, ``[128,
head_dim]``, so that the scores of both against the shared ``kb`` are
one ``[64 keys, 128 queries]`` tile that fills the 128 lanes, the
softmax and the score gradients pass over 8 full vregs a pair where
they passed over 16 half-filled ones, and the sums over a pair that
``dv`` and ``dk`` need are the products' own contraction over 128
queries. A pair costs the two forward and five gradient products one
head cost; an odd group's last head runs alone, and a group of 1 is the
one-head kernel PR 32 wrote, to the operation. What bounds the kernels,
as measured with parts taken out (PERF.md section 5): the PRODUCTS and
the DMA, 59% of the forward and 63% of the gradient before the pairing
at a group of 8 (86% at a group of 1, PR 32's table, whose closing
sentence said otherwise) and 61% / 62% of the shorter kernels after it;
the rest is the per-head norm and RoPE with their transposes (the unit
that moves data across lanes), which the pairing does not touch; the
softmax costs the gradient nothing measurable and the forward a tenth.

The gradient is a second kernel that recomputes the normed and rotated
q, k, the scores and the softmax from the same inputs (the residuals
are the inputs; nothing ``[boards, heads, 64, 64]`` is kept) and rounds
where JAX's own transposes of the formula above round: the cotangents of
bfloat16 values are bfloat16, the four products take bfloat16 operands
and accumulate in float32. The gains' gradients leave it as one partial
sum a grid step, summed outside over ``[steps, heads, head_dim]``.

The normed form is told three things more, each None or absent for the
blocks that do not have it (their programs are then what they were):
``g_k`` as ``[kv_heads, head_dim]`` is a gain a KEY-VALUE HEAD (a grid
step reads its own head's ``head_dim`` lanes of the gains laid side by
side, and its gradient comes back in that shape): the fifth block's key
temperature, the same number on a head's columns; ``g_q`` None beside a
``g_k`` norms both and gives the query no gain (an RMSNorm without one);
``rotary_dim`` rotates the FIRST ``rotary_dim`` columns of every head,
rotate-half inside them, and passes the rest: the tables are then
``head_dim`` wide, cos 1 and sin 0 on the passing lanes, and the sine is
split by which of two lane rotations brings a column's partner
(``part_rope_tables``), since a rotation of a whole 128-lane head by half
the ROTATED width wraps. A group of 4 query heads a key-value head takes
8 boards a grid step (32 board-head pairs, as every group of 2 or more
does; a group of 1 takes 16).

A layer that does not turn by the plain table of ``theta`` hands the
normed form its TABLES (``tables``: cos and signed sine ``[64, head_dim]``,
e.g. ``yarn_rope_tables``': YaRN's frequencies with the attention factor in
both). They are an operand like any other table: the kernels and their
programs are the plain layer's, and a row that is a scaled rotation is
un-turned in the gradient by the transpose of what turned it.

The same pair has a second, latent form (further down): no norm,
RoPE on a trailing part of the score width, one rotated key for all
heads. ``board_attention`` is told which, and does not guess.

A third form is a kernel pair of its own, ``board_attention_blocks`` and
``board_attention_blocks_grad`` (the end of this file): the normed form
under a BLOCK MASK, told ``block_length`` and how many ``streams`` of a
board ride side by side along the rows, a clean copy alone (64 rows,
block-causal) or a clean and a noised copy (128 rows: block-diffusion
training, ``block_mask``). The pair above is not touched by it. Since PR
63 it takes a group's query heads two at a time as the plain pair does
(the same ``_pairs``, ``_stacked``, ``_rows``).

Off the TPU both kernels run under the Pallas interpreter.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SQUARES", "block_mask", "board_attention", "latent_column_order", "paired_heads", "part_rope_tables", "rope_tables", "yarn_rope_tables"]

SQUARES = 64

#: (Board, query head) pairs a grid step, and pairs unrolled in one loop
#: body of the forward and of the gradient kernel: the fastest of 4-64 and
#: 1-16 on a v5e at [512, 64, 16 x 128] (PERF.md section 5), at a group of
#: 1. The latent and the block-masked forms divide them by their heads a
#: step, so that the blocks of a step, double-buffered, stay at 3 MiB
#: (forward) and 5.5 MiB (gradient) of VMEM; 64 would pass the 16 MiB a
#: kernel gets by default.
_BOARDS = 16
_UNROLL = 4
_UNROLL_GRAD = 8

#: The same two counts where a key-value head's query heads go two a product (a group of 2 or more, the
#: plain pair alone): the fastest of 1-8 boards a step x 1-4 a body at [256, 64, 32 x 128] over 4 key-value
#: heads, of 2-16 x 1-2 at 8 over 2 and of 8-16 x 1-8 at a group of 2 (PERF.md section 5; PR 60's builder, taken by PR 61 unswept). A loop body
#: of 4 pairs is the fastest in BOTH kernels (8 pairs cost the gradient +3%, 16 +33%); boards a step are
#: flat to 2%. Double-buffered, a step's blocks are 7 MiB (forward) and 11.5 MiB (gradient) at a head of 256, half that at 128.
_PAIRED_BOARDS = 32
_PAIRED_UNROLL = 8

#: The same two counts for the block-masked pair where a key-value head's query heads go two a product (since PR 63), in (board, head, copy)s:
#: at a group of 8 under both copies 4 boards a grid step AND a loop body (4 pairs x 2 copies a board), the fastest of 1-4 x 1-4 on a v5e at
#: [128, 128, 32 x 128] over 4 key-value heads (PERF.md section 5). Here a LONGER body is faster, unlike the plain pair's: 1 / 2 / 4 boards a body
#: read 1.29 / 1.19 / 1.13 ms forward and 1.83 / 1.71 / 1.68 gradient; boards a step are flat. Double-buffered, a step's blocks are 6.75 MiB
#: (forward) and 11.5 MiB (gradient) of VMEM at a head of 128: 8 boards would pass the 16 MiB a kernel gets by default.
_BLOCKS_PAIRED_BOARDS = 64
_BLOCKS_PAIRED_UNROLL = 64

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


def yarn_rope_tables(theta: float, head_dim: int, factor: float, original_max_position_embeddings: int, beta_fast: float, beta_slow: float,
                     attention_factor: float) -> Tuple[np.ndarray, np.ndarray]:
    """``rope_tables`` under YaRN (arXiv:2309.00071, as the published
    ``rope_parameters`` of ``rope_type`` "yarn" name its numbers): pair
    ``j`` of the ``head_dim // 2`` turns at its plain frequency
    ``theta^(-2j / head_dim)`` where that makes more than ``beta_fast``
    turns over the original context, at the frequency divided by
    ``factor`` where it makes fewer than ``beta_slow``, and on a linear
    ramp between (``lo`` and ``hi`` the pairs, ``dim`` = ``head_dim``)::

        c(b) = dim ln(original / (2 pi b)) / (2 ln theta);   lo = max(floor(c(beta_fast)), 0);   hi = min(ceil(c(beta_slow)), dim - 1)
        r_j  = clip((j - lo) / (hi - lo), 0, 1);             f_j = (1 - r_j) theta^(-2j / dim) + r_j theta^(-2j / dim) / factor

    and BOTH tables carry ``attention_factor``: a query and a key each
    turned by them give scores ``attention_factor^2`` times the plain
    ones'. Float64, then float32, the sine signed as ``rope_tables``'; a
    row is no unit rotation (cos^2 + sin^2 = ``attention_factor^2``), which
    the kernels never assume: the gradient's un-rotation is the
    transpose of ``x * cos + turned(x) * sin`` whatever the tables hold."""
    half = head_dim // 2
    at = lambda turns: head_dim * math.log(original_max_position_embeddings / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))
    lo, hi = max(math.floor(at(beta_fast)), 0), min(math.ceil(at(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
    plain = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    angle = np.arange(SQUARES, dtype=np.float64)[:, None] * ((1.0 - ramp) * plain + ramp * plain / factor)[None, :]
    cos = attention_factor * np.concatenate([np.cos(angle)] * 2, axis=-1)
    sin = attention_factor * np.concatenate([-np.sin(angle), np.sin(angle)], axis=-1)
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


def part_rope_tables(theta: float, head_dim: int, rotary_dim: int) -> Tuple[np.ndarray, np.ndarray]:
    """The tables of rotate-half RoPE on the FIRST ``rotary_dim`` columns
    of a head (the rest pass): cos ``[64, head_dim]`` (1 on the passing
    columns) and the signed sine ``[2 * 64, head_dim]`` split by which
    lane rotation brings a column's partner (``_part_rope``): rows ``[0,
    64)`` for the partner at ``lane - rotary_dim // 2`` (the second half
    of the rotated columns), rows ``[64, 128)`` for the partner at ``lane
    + rotary_dim // 2`` (their first half), zero elsewhere."""
    if rotary_dim % 2 or not 0 < rotary_dim <= head_dim:
        raise ValueError(f"rotary_dim {rotary_dim} is not an even part of a head of {head_dim}: RoPE turns pairs")
    cos, sin = rope_tables(theta, rotary_dim)
    passing = np.zeros((SQUARES, head_dim - rotary_dim), np.float32)
    first = np.arange(rotary_dim) < rotary_dim // 2
    whole = lambda part: np.concatenate([part, passing], axis=-1)
    return np.concatenate([cos, passing + 1.0], axis=-1), np.concatenate(
        [whole(np.where(first, 0.0, sin)), whole(np.where(first, sin, 0.0))]).astype(np.float32)


def _part_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, half: int) -> jax.Array:
    """Rotate-half RoPE inside the first ``2 * half`` lanes (``part_rope_tables``)."""
    lanes = x.shape[-1]
    return x * cos + pltpu.roll(x, half, axis=x.ndim - 1) * sin[:SQUARES] + pltpu.roll(x, lanes - half, axis=x.ndim - 1) * sin[SQUARES:]


def _part_unrope(d: jax.Array, cos: jax.Array, sin: jax.Array, half: int) -> jax.Array:
    """The transpose of ``_part_rope``."""
    lanes = d.shape[-1]
    return d * cos + pltpu.roll(d * sin[:SQUARES], lanes - half, axis=d.ndim - 1) + pltpu.roll(d * sin[SQUARES:], half, axis=d.ndim - 1)


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


def _head(b, g: int, head_dim: int, group: int):
    """The index of board ``b``, head ``g`` in a block ``[boards, 64, group * head_dim]``."""
    return b if group == 1 else (b, slice(None), slice(g * head_dim, (g + 1) * head_dim))


def _pairs(group: int):
    """A group's query heads as the plain pair's bodies take them: two at a time, an odd group's last head alone."""
    return [range(g, min(g + 2, group)) for g in range(0, group, 2)]


def paired_heads(heads: int, kv_heads: int) -> int:
    """Of ``heads`` query heads over ``kv_heads`` key-value heads, those whose scores the plain pair makes two a product: every head of an
    even group, all but the last of an odd one, none at a group of 1."""
    return kv_heads * sum(len(pair) for pair in _pairs(heads // kv_heads) if len(pair) == 2)


def _stacked(parts):
    """A pair's ``[64, head_dim]`` operands along the rows, ``[128, head_dim]`` (a placement at a tile boundary); a lone head's as it is. The
    block-masked gradient lays a board's two copies along the rows by it too."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _rows(x: jax.Array, i: int, parts: int) -> jax.Array:
    """Head ``i``'s 64 rows of what ``_stacked`` laid along the rows."""
    return x if parts == 1 else x[i * SQUARES:(i + 1) * SQUARES]


def _forward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, out_ref, *, eps: float, rope: bool, unroll: int, norm: bool = True,
                    half: Optional[int] = None, q_gain: bool = True):
    cos, sin = cos_ref[...], sin_ref[...]
    gq, gk = gq_ref[...] if q_gain else None, gk_ref[...]
    head_dim = k_ref.shape[-1]
    turn = (lambda x: _rope(x, cos, sin) if half is None else _part_rope(x, cos, sin, half)) if rope else (lambda x: x)
    normed = (lambda x, gain: _unit(x, eps)[0] if gain is None else _unit(x, eps)[0] * gain) if norm else (lambda x, gain: x)

    def board(b, carry):
        group = q_ref.shape[-1] // head_dim
        for heads in _pairs(group):
            g = heads[0]
            qb = _stacked([turn(normed(q_ref[_head(b, h, head_dim, group)], gq)).astype(jnp.bfloat16) for h in heads])
            if g == 0:  # after the first query head's, as PR 32's one-head kernel had it
                kb = turn(normed(k_ref[b], gk)).astype(jnp.bfloat16)
            p = _softmax(_scores(kb, qb)).astype(jnp.bfloat16)
            mixed = jax.lax.dot_general(p, v_ref[b], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for h in heads:
                out_ref[_head(b, h, head_dim, group)] = _rows(mixed, h - g, len(heads)).astype(out_ref.dtype)
        return carry

    _each_board(q_ref.shape[0], board, 0, unroll)


def _rounded(x: jax.Array) -> jax.Array:
    """What reaches float32 code as the cotangent of a bfloat16 value."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _score_gradients(p: jax.Array, kb: jax.Array, qb: jax.Array, vb: jax.Array, do: jax.Array, scale) -> Tuple[jax.Array, jax.Array]:
    """What both normed gradient kernels do with one query head's
    probabilities ``[key, query]`` (float32; exactly 0 where a mask
    forbade) and its output's cotangent: the turned queries' cotangent,
    rounded as float32 code reads a bfloat16 value's, and this head's part of
    the turned keys', float32 for the caller's sum over the heads."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    dp = _rounded(jax.lax.dot_general(vb, do, (((1,), (1,)), ((), ())), preferred_element_type=f32))
    ds = (p * (dp - jnp.sum(dp * p, axis=0, keepdims=True)) * scale).astype(bf16)
    dq_rot = _rounded(jax.lax.dot_general(ds, kb, (((0,), (0,)), ((), ())), preferred_element_type=f32))
    return dq_rot, jnp.dot(ds, qb, preferred_element_type=f32)


def _unrope_unnorm(d_rot: jax.Array, unit: jax.Array, r: jax.Array, gain: Optional[jax.Array], cos: jax.Array, sin: jax.Array, rope: bool,
                   half: Optional[int] = None):
    """The cotangent of a normed and rotated ``[64, head_dim]`` back to
    its raw input, and the summand of the gain's gradient (``r`` None:
    there was no norm, and the gain, which was not read, has none;
    ``gain`` None: a norm without a gain). ``half``: RoPE on the first
    ``2 * half`` columns alone."""
    if half is not None and rope:
        d_normed = _part_unrope(d_rot, cos, sin, half)
    else:
        d_normed = d_rot * cos + _turned(d_rot * sin) if rope else d_rot
    if r is None:
        return d_normed, jnp.zeros_like(d_normed)
    d_unit = d_normed if gain is None else d_normed * gain
    d_x = r * (d_unit - unit * jnp.mean(d_unit * unit, axis=-1, keepdims=True))
    return d_x, jnp.zeros_like(d_normed) if gain is None else d_normed * unit


def _backward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dgq_ref, dgk_ref, *, eps: float, rope: bool, unroll: int, norm: bool = True,
                     half: Optional[int] = None, q_gain: bool = True):
    cos, sin = cos_ref[...], sin_ref[...]
    gq, gk = gq_ref[...] if q_gain else None, gk_ref[...]
    bf16, f32 = jnp.bfloat16, jnp.float32
    head_dim = k_ref.shape[-1]
    scale = np.float32(1.0 / math.sqrt(head_dim))
    turn = (lambda x: _rope(x, cos, sin) if half is None else _part_rope(x, cos, sin, half)) if rope else (lambda x: x)
    unit = (lambda x: _unit(x, eps)) if norm else (lambda x: (x, None))
    gained = (lambda u, gain: u if gain is None else u * gain) if norm else (lambda u, gain: u)

    def board(b, carry):
        # In the order PR 32's one-head kernel emitted its operations (a group of one IS that kernel):
        # dv leaves as soon as the last head's part is in, dq and dk are taken back through RoPE and
        # the norm side by side. Mosaic schedules what it is given: the same work emitted with dv and
        # dk held to the end read 0.5 ms a step slower at [512, 64, 16 x 128] (PERF.md section 6, PR 33).
        dgq, dgk = carry
        group = q_ref.shape[-1] // head_dim
        for heads in _pairs(group):
            g = heads[0]
            uq, rq = zip(*(unit(q_ref[_head(b, h, head_dim, group)]) for h in heads))
            if g == 0:
                uk, rk = unit(k_ref[b])
            qb = _stacked([turn(gained(u, gq)).astype(bf16) for u in uq])
            if g == 0:
                kb = turn(gained(uk, gk)).astype(bf16)
                vb = v_ref[b]
            do = _stacked([do_ref[_head(b, h, head_dim, group)] for h in heads])
            p = _softmax(_scores(kb, qb))
            dv_g = jnp.dot(p.astype(bf16), do, preferred_element_type=f32)  # over a pair's 128 queries: the pair's sum is the product's own
            dv = dv_g if g == 0 else dv + dv_g  # float32 sums over the group's pairs, rounded once
            if heads[-1] == group - 1:
                dv_ref[b] = dv.astype(dv_ref.dtype)
            dq_rot, dk_g = _score_gradients(p, kb, qb, vb, do, scale)
            dk_rot = dk_g if g == 0 else dk_rot + dk_g
            for h in heads:
                dq, dgq_g = _unrope_unnorm(_rows(dq_rot, h - g, len(heads)), uq[h - g], rq[h - g], gq, cos, sin, rope, half)
                dgq = dgq + dgq_g
                if h == group - 1:
                    dk, dgk_b = _unrope_unnorm(_rounded(dk_rot), uk, rk, gk, cos, sin, rope, half)
                    dq_ref[_head(b, h, head_dim, group)], dk_ref[b] = dq, dk
                else:
                    dq_ref[_head(b, h, head_dim, group)] = dq
        return dgq, dgk + dgk_b

    zero = jnp.zeros((SQUARES, head_dim), f32)
    dgq, dgk = _each_board(q_ref.shape[0], board, (zero, zero), unroll)
    dgq_ref[0] = jnp.sum(dgq, axis=0, keepdims=True)
    dgk_ref[0] = jnp.sum(dgk, axis=0, keepdims=True)


def _blocks(boards: int, heads: int, kv_heads: int, head_dim: int):
    """The grid (blocks of boards, key-value heads) and the BlockSpecs of
    a key-value head of ``[boards, 64, kv_heads * head_dim]``, of its
    group of query heads in ``[boards, 64, heads * head_dim]``, of a gain
    or table ``[.., head_dim]`` and of a step's partial sum in ``[steps,
    1, kv_heads * head_dim]``."""
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads do not divide over {kv_heads} key-value heads")
    group = heads // kv_heads
    tb = math.gcd(boards, max(1, (_BOARDS if group == 1 else _PAIRED_BOARDS) // group))
    per_head = pl.BlockSpec((tb, SQUARES, head_dim), lambda i, h: (i, 0, h))
    per_group = pl.BlockSpec((tb, SQUARES, group * head_dim), lambda i, h: (i, 0, h))
    whole = lambda rows: pl.BlockSpec((rows, head_dim), lambda i, h: (0, 0))
    partial = pl.BlockSpec((1, 1, head_dim), lambda i, h: (i, 0, h))
    return (boards // tb, kv_heads), group, per_head, per_group, whole, partial


def _gain_spec(g_k: jax.Array, whole):
    """A gain's block: the one ``[1, head_dim]`` vector, or a key-value
    head's own ``head_dim`` lanes of ``[1, kv_heads * head_dim]``."""
    return whole(1) if g_k.ndim == 1 else pl.BlockSpec((1, g_k.shape[-1]), lambda i, h: (0, h))


def _tables(theta: Optional[float], head_dim: int, rotary_dim: Optional[int]) -> Tuple[np.ndarray, np.ndarray]:
    """The plain tables a caller that hands ``board_attention`` none is given: of ``theta`` over all of a head or its first ``rotary_dim`` columns."""
    if rotary_dim is None:
        return rope_tables(theta or 1.0, head_dim)  # not read without RoPE
    return part_rope_tables(theta or 1.0, head_dim, rotary_dim)


def _operands(g_q, g_k, tables: Tuple[np.ndarray, np.ndarray]):
    gain = lambda g: g.astype(jnp.float32).reshape(1, -1)  # a gain a key-value head: its heads side by side along the lanes
    return gain(g_q), gain(g_k), jnp.asarray(tables[0]), jnp.asarray(tables[1])


def _unroll(interpret: bool, unroll: int, group: int) -> int:
    """Boards to a loop body. Unrolling is for Mosaic's scheduler; the
    interpreter pays for every emitted operation and gains nothing."""
    return 1 if interpret else max(1, unroll // group)


def _plain_unroll(interpret: bool, unroll: int, group: int) -> int:
    """``_unroll`` for the plain pair: ``unroll`` (board, head)s at a group of 1, ``_PAIRED_UNROLL`` where the heads go two a product."""
    return _unroll(interpret, unroll if group == 1 else _PAIRED_UNROLL, group)


def board_attention(q: jax.Array, k: jax.Array, v: jax.Array, g_q: Optional[jax.Array], g_k: Optional[jax.Array],
                    theta: Optional[float], eps: float, interpret: bool = False,
                    q_pe: Optional[jax.Array] = None, k_pe: Optional[jax.Array] = None, head_dim: Optional[int] = None,
                    rotary_dim: Optional[int] = None, tables: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                    block_length: Optional[int] = None, streams: int = 1) -> jax.Array:
    """The attention core (module docstring). What it is told, and does
    not guess: the norm (the gains ``[head_dim]``, or None for none, and
    then ``head_dim`` itself unless the form is the latent one; ``g_q``
    None beside a ``g_k``: both are normed and the query takes no gain;
    ``g_k`` ``[kv_heads, head_dim]``: a gain a key-value head, whose
    gradient comes back in that shape), the
    extent of RoPE (``theta`` None: none of the score width; no ``k_pe``:
    all of it, or with ``rotary_dim`` the first ``rotary_dim`` columns of
    every head, rotate-half inside them; with ``q_pe`` and ``k_pe``: those
    trailing columns alone, or with ``rotary_dim`` 0 none: a latent NoPE
    layer, whose ``q_pe`` and ``k_pe`` are more score columns)
    and whether the rotated part of k is one a key-value head (it is part
    of ``k``) or one for all heads (``k_pe`` ``[boards, 64, rope]``), and,
    where a layer does not turn by the plain table of ``theta``, the
    TABLES themselves (``tables``: cos and signed sin ``[64, head_dim]``
    float32 as ``rope_tables`` lays them out, e.g. ``yarn_rope_tables``';
    the normed form over all of a head under a ``theta``, which then only
    says that the layer turns). The kernels read the tables as an operand
    and assume nothing of them: rows that are scaled rotations are turned
    and un-turned as they are. ``block_length`` (None: no mask, every form
    above) puts the normed form with both gains and RoPE over all of a head
    under the block mask of ``block_mask(block_length, streams)``: q, k, v
    are then ``[boards, 64 * streams, ..]``, a board's clean copy on rows
    ``[0, 64)`` and, at ``streams`` 2, its noised copy on rows ``[64,
    128)``, both turned by the square index (the end of this file).

    Normed form: q float32 ``[boards, 64, heads * head_dim]``, k float32
    and v bfloat16 ``[boards, 64, kv_heads * head_dim]`` -> bfloat16 of
    q's shape; both head counts follow from the shapes. Latent form: q
    and k float32 ``[boards, 64, heads * nope]``, ``q_pe`` float32
    ``[boards, 64, heads * rope]``, v bfloat16 ``[boards, 64, heads *
    value]`` -> bfloat16 of v's shape. The combinations the kernels do
    not compute are refused."""
    latent = (g_q is None, g_k is None, q_pe is not None, k_pe is not None)
    if block_length is not None:
        if any(latent) or head_dim is not None or theta is None or rotary_dim is not None or g_k.ndim != 1:
            raise ValueError("board_attention puts the normed form with both gains and RoPE over all of a head under a block mask, and no other")
        return _blocks_attention(q, k, v, g_q, g_k, eps, interpret, block_length, streams, tables or _tables(theta, g_q.shape[-1], None))
    if tables is not None and (theta is None or rotary_dim is not None or latent[2] or latent[3]):
        raise ValueError("board_attention takes tables for the normed form's RoPE over all of a head (a theta, no rotary_dim, no q_pe or k_pe)")
    if all(latent) and theta is not None and rotary_dim in (None, 0):  # 0: the same pair under tables that turn nothing (``_latent_tables``)
        return _latent_attention(q, q_pe, k, k_pe, v.astype(jnp.bfloat16), theta if rotary_dim is None else None, interpret)
    told = lambda width: tables or _tables(theta, width, rotary_dim)  # the caller's tables, or the plain ones of ``theta`` for a head of ``width``
    if latent == (True, True, False, False) and head_dim is not None:  # the grouped form without its norm: the gains are not read
        ones = jnp.ones((head_dim,), jnp.float32)
        return _normed_attention(q, k, v, ones, ones, theta, eps, interpret, False, rotary_dim, True, told(head_dim))
    if latent == (True, False, False, False) and head_dim is None:  # both normed, the query without a gain (which is not read)
        return _normed_attention(q, k, v, jnp.ones((g_k.shape[-1],), jnp.float32), g_k, theta, eps, interpret, True, rotary_dim, False, told(g_k.shape[-1]))
    if any(latent) or head_dim is not None:
        raise ValueError("board_attention computes qk-norm (or, told head_dim in the gains' place, no norm; or the query's norm without a "
                         "gain) with RoPE over all, none or the first rotary_dim columns of head_dim, or no norm with RoPE over trailing "
                         "columns q_pe and one k_pe for all heads; not a mixture of these")
    return _normed_attention(q, k, v, g_q, g_k, theta, eps, interpret, True, rotary_dim, True, told(g_q.shape[-1]))


def _form(theta: Optional[float], rotary_dim: Optional[int], q_gain: bool) -> dict:
    """What the kernels are told beside the norm: nothing for the forms
    they had before a head could be rotated in part or a query normed
    without a gain (their programs are what they were)."""
    part = {} if rotary_dim is None else {"half": rotary_dim // 2}
    return {"rope": theta is not None, **part, **({} if q_gain else {"q_gain": False})}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _normed_attention(q, k, v, g_q, g_k, theta: Optional[float], eps: float, interpret: bool, norm: bool, rotary_dim: Optional[int], q_gain: bool,
                      tables: Tuple[np.ndarray, np.ndarray]):
    """``tables``: what ``board_attention`` was told, or the plain ones of ``theta`` it made (``_tables``); ``theta`` itself only says whether a layer turns."""
    boards, _, inner = q.shape
    head_dim = g_q.shape[-1]
    grid, group, per_head, per_group, whole, _ = _blocks(boards, inner // head_dim, k.shape[-1] // head_dim, head_dim)
    return pl.pallas_call(
        functools.partial(_forward_kernel, eps=eps, unroll=_plain_unroll(interpret, _UNROLL, group), norm=norm, **_form(theta, rotary_dim, q_gain)),
        grid=grid,
        in_specs=[per_group, per_head, per_head, whole(1), _gain_spec(g_k, whole), whole(SQUARES), whole(tables[1].shape[0])],
        out_specs=per_group,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="board_attention",
        interpret=interpret,
    )(q, k, v, *_operands(g_q, g_k, tables))


def _board_attention_fwd(q, k, v, g_q, g_k, theta, eps, interpret, norm, rotary_dim, q_gain, tables):
    return _normed_attention(q, k, v, g_q, g_k, theta, eps, interpret, norm, rotary_dim, q_gain, tables), (q, k, v, g_q, g_k)


def _board_attention_bwd(theta, eps, interpret, norm, rotary_dim, q_gain, tables, residuals, d_mixed):
    q, k, v, g_q, g_k = residuals
    boards, _, inner = q.shape
    head_dim = g_q.shape[-1]
    kv_heads = k.shape[-1] // head_dim
    grid, group, per_head, per_group, whole, partial = _blocks(boards, inner // head_dim, kv_heads, head_dim)
    sums = jax.ShapeDtypeStruct((grid[0], 1, kv_heads * head_dim), jnp.float32)
    dq, dk, dv, dgq, dgk = pl.pallas_call(
        functools.partial(_backward_kernel, eps=eps, unroll=_plain_unroll(interpret, _UNROLL_GRAD, group), norm=norm, **_form(theta, rotary_dim, q_gain)),
        grid=grid,
        in_specs=[per_group, per_head, per_head, whole(1), _gain_spec(g_k, whole), whole(SQUARES), whole(tables[1].shape[0]), per_group],
        out_specs=[per_group, per_head, per_head, partial, partial],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), sums, sums],
        compiler_params=_PARAMS,
        name="board_attention_grad",
        interpret=interpret,
    )(q, k, v, *_operands(g_q, g_k, tables), d_mixed)
    total = lambda s, g: s.reshape(-1, kv_heads, head_dim).sum(axis=(0, 1) if g.ndim == 1 else 0).astype(g.dtype)
    return dq, dk, dv, total(dgq, g_q), total(dgk, g_k)


_normed_attention.defvjp(_board_attention_fwd, _board_attention_bwd)


# -- the latent form --------------------------------------------------------------------------------------------
#
# ``board_attention(q, k, v, None, None, theta, eps, interpret, k_pe)``: what the caller tells it is that there
# is no norm (the gains are None), that RoPE covers a trailing part of the score width (the width of ``k_pe``),
# and that the rotated part of k is one for all heads (``k_pe`` has no head axis). A head's score is then
#
#     s_h = (q_nope_h k_nope_h^T + rope(q_pe_h) rope(k_pe)^T) / sqrt(nope + rope)
#
# with q ``[boards, 64, heads * nope + heads * rope]`` laid out as every head's NoPE columns, then every head's
# RoPE columns (``latent_column_order``), k ``[boards, 64, heads * nope]`` and v ``[boards, 64, heads * value]``
# one a head, ``k_pe`` ``[boards, 64, rope]``. The RoPE columns of 128 // rope heads fill one 128-lane tile:
# the tile is rotated once (two lane rotations: rotate-half inside every ``rope`` lanes) and each head's part
# is the tile under a table that is zero off the head's lanes, so that the 128-wide product with ``k_pe``
# repeated across the tile contracts over that head's lanes alone. Nothing narrower than a vreg is ever
# sliced, and no copy of ``k_pe`` a head exists outside VMEM. In the gradient ``dk_pe`` sums over a grid
# step's heads in registers and over the steps of a board in a VMEM scratch, float32, rounded once.

#: Heads a grid step of the latent form; a step takes ``_BOARDS // _LATENT_HEADS`` boards.
_LATENT_HEADS = 8
_LANES = 128

_LATENT_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def latent_column_order(heads: int, nope: int, rope: int) -> np.ndarray:
    """Where the latent form wants each column of a published per-head
    ``[heads x (nope + rope)]`` query projection: ``w[:, order]`` has
    every head's NoPE columns first, then every head's RoPE columns,
    each head's interleaved pairs (2i, 2i + 1) taken apart into the two
    halves that rotate-half turns (a score is unchanged when q and k are
    permuted alike). With ``heads`` 1 and ``nope`` 0 it is the order of
    the RoPE key's columns."""
    per_head = np.arange(heads)[:, None] * (nope + rope)
    halves = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    return np.concatenate([(per_head + np.arange(nope)[None, :]).reshape(-1), (per_head + nope + halves[None, :]).reshape(-1)])


def _latent_tables(theta: Optional[float], rope: int) -> np.ndarray:
    """``[1 + per, 3, 64, 128]`` float32, ``per`` = 128 // rope heads a
    tile: cos, and the sine split by which lane rotation brings the
    partner (``_rotated``), for the whole tile (index 0: ``k_pe``
    repeated) and zero off each head's lanes (1 + j: head j of a tile).
    ``theta`` None is the form without a rotation (a latent NoPE layer:
    ``q_pe`` and ``k_pe`` are 64 more score columns, one key for all
    heads): cos 1 and sin 0, so the kernels, which read the tables as an
    operand, are the rotated form's to the last instruction, and the
    tables still cut each head's lanes out of its tile."""
    per, half = _LANES // rope, rope // 2
    # [64, rope], the sine signed: minus on the first half
    cos, sin = rope_tables(theta, rope) if theta is not None else (np.ones((SQUARES, rope), np.float32), np.zeros((SQUARES, rope), np.float32))
    cos, sin = np.tile(cos, per), np.tile(sin, per)
    first = (np.arange(_LANES) % rope) < half
    whole = np.stack([cos, np.where(first, 0.0, sin), np.where(first, sin, 0.0)])  # partner at lane - half, at lane + half
    own = [(np.arange(_LANES) // rope) == j for j in range(per)]
    return np.stack([whole] + [np.where(mask, whole, 0.0) for mask in own]).astype(np.float32)


def _rotated(x: jax.Array, table: jax.Array, half: int) -> jax.Array:
    """Rotate-half RoPE inside every ``2 * half`` lanes of a 128-lane tile."""
    return x * table[0] + pltpu.roll(x, half, axis=1) * table[1] + pltpu.roll(x, _LANES - half, axis=1) * table[2]


def _unrotated(d: jax.Array, table: jax.Array, half: int) -> jax.Array:
    """The transpose of ``_rotated``."""
    return d * table[0] + pltpu.roll(d * table[1], _LANES - half, axis=1) + pltpu.roll(d * table[2], half, axis=1)


def _lanes(h: int, width: int) -> slice:
    return slice(h * width, (h + 1) * width)


def _latent_sizes(qr_ref, kn_ref, v_ref, rope: int):
    """Heads a tile, half the RoPE width, tiles a step, a head's NoPE and value widths, the scores' scale."""
    per, tiles = _LANES // rope, qr_ref.shape[-1] // _LANES
    nope, width = kn_ref.shape[-1] // (tiles * per), v_ref.shape[-1] // (tiles * per)
    return per, rope // 2, tiles, nope, width, np.float32(1.0 / math.sqrt(nope + rope))


def _latent_scores(kn, qn, kr, qr, scale):
    both = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(kn, qn, both, preferred_element_type=jnp.float32)
    return (s + jax.lax.dot_general(kr, qr, both, preferred_element_type=jnp.float32)) * scale


def _latent_forward_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, tab_ref, out_ref, *, rope: int, unroll: int):
    bf16 = jnp.bfloat16
    per, half, tiles, nope, width, scale = _latent_sizes(qr_ref, kn_ref, v_ref, rope)

    def board(b, carry):
        kr = _rotated(jnp.concatenate([kr_ref[b]] * per, axis=-1), tab_ref[0], half).astype(bf16)
        for t in range(tiles):
            x = qr_ref[b, :, _lanes(t, _LANES)]
            back, ahead = pltpu.roll(x, half, axis=1), pltpu.roll(x, _LANES - half, axis=1)
            for j in range(per):
                h, table = t * per + j, tab_ref[1 + j]
                qr = (x * table[0] + back * table[1] + ahead * table[2]).astype(bf16)
                qn, kn = qn_ref[b, :, _lanes(h, nope)].astype(bf16), kn_ref[b, :, _lanes(h, nope)].astype(bf16)
                p = _softmax(_latent_scores(kn, qn, kr, qr, scale)).astype(bf16)
                mixed = jax.lax.dot_general(p, v_ref[b, :, _lanes(h, width)], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                out_ref[b, :, _lanes(h, width)] = mixed.astype(out_ref.dtype)
        return carry

    _each_board(qn_ref.shape[0], board, 0, unroll)


def _latent_backward_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, tab_ref, do_ref,
                            dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref, sum_ref, *, rope: int, unroll: int):
    bf16, f32 = jnp.bfloat16, jnp.float32
    per, half, tiles, nope, width, scale = _latent_sizes(qr_ref, kn_ref, v_ref, rope)
    step, last = pl.program_id(1), pl.num_programs(1) - 1

    def board(b, carry):
        kr = _rotated(jnp.concatenate([kr_ref[b]] * per, axis=-1), tab_ref[0], half).astype(bf16)
        dkr = jnp.zeros((SQUARES, _LANES), f32)
        for t in range(tiles):
            x = qr_ref[b, :, _lanes(t, _LANES)]
            back, ahead = pltpu.roll(x, half, axis=1), pltpu.roll(x, _LANES - half, axis=1)
            by_cos = by_back = by_ahead = jnp.zeros((SQUARES, _LANES), f32)
            for j in range(per):
                h, table = t * per + j, tab_ref[1 + j]
                qr = (x * table[0] + back * table[1] + ahead * table[2]).astype(bf16)
                qn, kn = qn_ref[b, :, _lanes(h, nope)].astype(bf16), kn_ref[b, :, _lanes(h, nope)].astype(bf16)
                vb, do = v_ref[b, :, _lanes(h, width)], do_ref[b, :, _lanes(h, width)]
                p = _softmax(_latent_scores(kn, qn, kr, qr, scale))
                dv_ref[b, :, _lanes(h, width)] = jnp.dot(p.astype(bf16), do, preferred_element_type=f32).astype(dv_ref.dtype)
                dp = _rounded(jax.lax.dot_general(vb, do, (((1,), (1,)), ((), ())), preferred_element_type=f32))
                ds = (p * (dp - jnp.sum(dp * p, axis=0, keepdims=True)) * scale).astype(bf16)
                by_query = (((0,), (0,)), ((), ()))
                dqn_ref[b, :, _lanes(h, nope)] = _rounded(jax.lax.dot_general(ds, kn, by_query, preferred_element_type=f32))
                dkn_ref[b, :, _lanes(h, nope)] = _rounded(jnp.dot(ds, qn, preferred_element_type=f32))
                dqr = _rounded(jax.lax.dot_general(ds, kr, by_query, preferred_element_type=f32))  # every head's lanes hold this head's
                by_cos, by_back, by_ahead = by_cos + dqr * table[0], by_back + dqr * table[1], by_ahead + dqr * table[2]
                dkr = dkr + jnp.dot(ds, qr, preferred_element_type=f32)  # this head's lanes alone: qr is zero off them
            dqr_ref[b, :, _lanes(t, _LANES)] = by_cos + pltpu.roll(by_back, _LANES - half, axis=1) + pltpu.roll(by_ahead, half, axis=1)

        @pl.when(step == 0)
        def _():
            sum_ref[b] = dkr

        @pl.when(step > 0)
        def _():
            sum_ref[b] = sum_ref[b] + dkr

        @pl.when(step == last)
        def _():
            total = sum_ref[b]
            for j in range(1, per):  # the tile's heads, folded: afterwards every ``rope`` lanes hold the sum over all heads
                total = total + pltpu.roll(sum_ref[b], j * rope, axis=1)
            dkr_ref[b] = _unrotated(_rounded(total), tab_ref[0], half)[:, :rope]

        return carry

    _each_board(qn_ref.shape[0], board, 0, unroll)


def _latent_blocks(q, q_pe, k, k_pe, v):
    """The grid (blocks of boards, blocks of heads) and the BlockSpecs of
    a step's NoPE columns of q or k, RoPE columns of q, values, ``k_pe``
    and the tables."""
    boards, rope = q.shape[0], k_pe.shape[-1]
    per = _LANES // rope if rope and rope % 2 == 0 and _LANES % rope == 0 else 0
    heads = q_pe.shape[-1] // rope if per else 0
    if not heads or heads % per or q_pe.shape[-1] != heads * rope or q.shape != k.shape or q.shape[-1] % heads or v.shape[-1] % heads:
        raise ValueError(f"latent attention: q {q.shape} + {q_pe.shape}, k {k.shape} + {k_pe.shape} and v {v.shape} are not heads x nope + "
                         "heads x rope, heads x nope + rope and heads x value with whole 128-lane tiles of RoPE columns")
    hg = math.gcd(heads, max(per, _LATENT_HEADS))
    tb = math.gcd(boards, max(1, _BOARDS // hg))
    spec = lambda lanes: pl.BlockSpec((tb, SQUARES, lanes), lambda i, h: (i, 0, h))
    return ((boards // tb, heads // hg), tb, hg,
            dict(nope=spec(hg * q.shape[-1] // heads), rope=spec(hg * rope), value=spec(hg * v.shape[-1] // heads),
                 key=pl.BlockSpec((tb, SQUARES, rope), lambda i, h: (i, 0, 0)),
                 tables=pl.BlockSpec((1 + per, 3, SQUARES, _LANES), lambda i, h: (0, 0, 0, 0))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _latent_attention(q, q_pe, k, k_pe, v, theta: Optional[float], interpret: bool):
    grid, _, hg, specs = _latent_blocks(q, q_pe, k, k_pe, v)
    rope = k_pe.shape[-1]
    return pl.pallas_call(
        functools.partial(_latent_forward_kernel, rope=rope, unroll=_unroll(interpret, _UNROLL, hg)),
        grid=grid,
        in_specs=[specs["nope"], specs["rope"], specs["nope"], specs["key"], specs["value"], specs["tables"]],
        out_specs=specs["value"],
        out_shape=jax.ShapeDtypeStruct(v.shape, jnp.bfloat16),
        compiler_params=_LATENT_PARAMS,
        name="board_attention",
        interpret=interpret,
    )(q, q_pe, k, k_pe, v, jnp.asarray(_latent_tables(theta, rope)))


def _latent_attention_fwd(q, q_pe, k, k_pe, v, theta, interpret):
    return _latent_attention(q, q_pe, k, k_pe, v, theta, interpret), (q, q_pe, k, k_pe, v)


def _latent_attention_bwd(theta, interpret, residuals, d_mixed):
    q, q_pe, k, k_pe, v = residuals
    grid, tb, hg, specs = _latent_blocks(q, q_pe, k, k_pe, v)
    rope = k_pe.shape[-1]
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return tuple(pl.pallas_call(
        functools.partial(_latent_backward_kernel, rope=rope, unroll=_unroll(interpret, _UNROLL_GRAD, hg)),
        grid=grid,
        in_specs=[specs["nope"], specs["rope"], specs["nope"], specs["key"], specs["value"], specs["tables"], specs["value"]],
        out_specs=[specs["nope"], specs["rope"], specs["nope"], specs["key"], specs["value"]],
        out_shape=[like(q), like(q_pe), like(k), like(k_pe), like(v)],
        scratch_shapes=[pltpu.VMEM((tb, SQUARES, _LANES), jnp.float32)],
        compiler_params=_LATENT_PARAMS,
        name="board_attention_grad",
        interpret=interpret,
    )(q, q_pe, k, k_pe, v, jnp.asarray(_latent_tables(theta, rope)), d_mixed))


_latent_attention.defvjp(_latent_attention_fwd, _latent_attention_bwd)


# -- the block-masked form ----------------------------------------------------------------------------------------
#
# ``board_attention(q, k, v, g_q, g_k, theta, eps, interpret, block_length=L, streams=S)``: block-diffusion training
# over a board (SDAR, arXiv:2510.06303, trained as BD3-LMs are, arXiv:2503.09573). The 64 squares are 64 / L blocks,
# ``blk(s) = s // L``. With S = 2 a board's rows are its CLEAN copy (rows 0-63) and its NOISED copy (rows 64-127) of
# the same squares under one set of weights, and (``block_mask``):
#
#     a clean query i sees the clean keys j with blk(j) <= blk(i), never a noised key;
#     a noised query i sees the noised keys j with blk(j) = blk(i) AND the clean keys j with blk(j) < blk(i), ONE softmax
#     over both; never a clean key of its own or a later block, never a noised key of another block.
#
# With S = 1 there is the clean copy alone under its first rule: what is served. Both copies are normed and turned as
# the plain form's rows are, position = SQUARE index in both (a noised square and its clean twin turn alike: the tables
# are laid twice along the rows). A grid step is one key-value head of a few boards and its group of query heads: the
# head's k (both copies) is normed, turned and read ONCE for the clean and the noised queries of the group; the group's
# query heads go TWO A PRODUCT (since PR 63, as the plain pair's since PR 61: an odd group's last head alone, a group of
# 1 one head at a time): the pair's raw queries stacked along the rows, ``[2 * rows, head_dim]``, are normed and turned in
# ONE pass (and taken back through RoPE and the norm in one in the gradient: the chip read the gradient 4-6% faster so and
# the forward no slower), and copy ``s``'s queries of both heads stand side by side on the lanes; the scores
# stand ``[key, query]``, 64 x 128 for a pair's clean queries and 128 x 128 for its noised ones (the clean queries x noised
# keys quarter, which is all masked, is never made), full 128-lane tiles, and never reach HBM; what is not allowed is taken
# out BEFORE the softmax's maximum and sums by a bias of -inf that is an operand like the tables (``_block_bias``, laid twice
# along a pair's queries; its probability is exactly 0, forward and in the gradient's recomputation, and so is its ``ds``);
# the gradient sums dk, dv of the clean copy over both copies' queries and over the group in VMEM, float32, rounded once: a
# pair's sum is its products' own contraction over 128 queries.


def block_mask(block_length: int, streams: int = 2) -> np.ndarray:
    """Who sees whom, bool ``[query, key]`` over a board's ``64 * streams``
    rows (the comment above): row and column ``s`` the clean square ``s``,
    ``64 + s`` the noised one."""
    if not 0 < block_length <= SQUARES or SQUARES % block_length or streams not in (1, 2):
        raise ValueError(f"a block of {block_length} squares does not divide the {SQUARES} of a board, or {streams} streams are neither the clean copy alone nor both")
    blk = np.arange(SQUARES) // block_length
    clean = blk[None, :] <= blk[:, None]
    if streams == 1:
        return clean
    none = np.zeros_like(clean)
    return np.block([[clean, none], [blk[None, :] < blk[:, None], blk[None, :] == blk[:, None]]])


def _block_bias(block_length: int, streams: int, heads: int = 1) -> np.ndarray:
    """The mask as the kernels add it to their ``[key, query]`` scores, 0
    where allowed and -inf where not, float32: rows ``[0, 64)`` the clean
    keys under the clean queries, then (two streams) rows ``[64, 192)``
    all 128 keys under the noised queries; laid ``heads`` times along the
    queries for the query heads of one product."""
    mask = block_mask(block_length, streams)
    parts = [mask[:SQUARES, :SQUARES].T] + ([mask[SQUARES:].T] if streams == 2 else [])
    return np.tile(np.where(np.concatenate(parts), 0.0, -np.inf).astype(np.float32), (1, heads))


def _bias(bias_ref, s: int, heads: int) -> jax.Array:
    """Copy ``s``'s rows of the bias (``_block_bias``) under the queries of ``heads`` heads side by side: an odd group's last head reads the first 64 lanes."""
    return bias_ref[:SQUARES, :heads * SQUARES] if s == 0 else bias_ref[SQUARES:, :heads * SQUARES]


def _stream(s: int) -> slice:
    """The rows of copy ``s`` of a board: 0 the clean one, 1 the noised one."""
    return slice(s * SQUARES, (s + 1) * SQUARES)


def _pair_tables(cos_ref, sin_ref, group: int) -> dict:
    """The tables by how many query heads an operand stacks along the rows: as the operand lays them for one head (and the keys), laid twice for a pair."""
    one = cos_ref[...], sin_ref[...]
    return {1: one} if group == 1 else {1: one, 2: tuple(jnp.concatenate([table, table]) for table in one)}


def _copy(i: int, s: int, rows: int) -> slice:
    """Copy ``s`` of head ``i`` in a pair's ``[2 * rows, head_dim]`` operand: each head's copies one under the other, the heads likewise."""
    return slice(i * rows + s * SQUARES, i * rows + (s + 1) * SQUARES)


def _blocks_forward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, bias_ref, out_ref, *, eps: float, unroll: int):
    gq, gk = gq_ref[...], gk_ref[...]
    rows, head_dim = k_ref.shape[1:]
    streams, group = rows // SQUARES, q_ref.shape[-1] // head_dim
    tables = _pair_tables(cos_ref, sin_ref, group)
    prepared = lambda x, gain: _rope(_unit(x, eps)[0] * gain, *tables[x.shape[0] // rows]).astype(jnp.bfloat16)

    def board(b, carry):
        kb, vb = prepared(k_ref[b], gk), v_ref[b]
        for heads in _pairs(group):
            n = len(heads)
            qb = prepared(_stacked([q_ref[b, :, _lanes(h, head_dim)] for h in heads]), gq)  # the pair's heads normed and turned in one pass
            for s in range(streams):  # copy s's queries, the pair's side by side on the lanes, over the keys of the copies up to it
                keys = SQUARES * (s + 1)
                p = _softmax(_scores(kb[:keys], _stacked([qb[_copy(i, s, rows)] for i in range(n)])) + _bias(bias_ref, s, n)).astype(jnp.bfloat16)
                mixed = jax.lax.dot_general(p, vb[:keys], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                for i, h in enumerate(heads):
                    out_ref[b, _stream(s), _lanes(h, head_dim)] = _rows(mixed, i, n).astype(out_ref.dtype)
        return carry

    _each_board(q_ref.shape[0], board, 0, unroll)


def _blocks_backward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, bias_ref, do_ref,
                            dq_ref, dk_ref, dv_ref, dgq_ref, dgk_ref, *, eps: float, unroll: int):
    gq, gk = gq_ref[...], gk_ref[...]
    bf16, f32 = jnp.bfloat16, jnp.float32
    rows, head_dim = k_ref.shape[1:]
    streams, group, scale = rows // SQUARES, q_ref.shape[-1] // head_dim, np.float32(1.0 / math.sqrt(head_dim))
    tables = _pair_tables(cos_ref, sin_ref, group)
    added = lambda total, part: part if total is None else total + part

    def board(b, carry):
        dgq, dgk = carry
        uk, rk = _unit(k_ref[b], eps)
        kb, vb = _rope(uk * gk, *tables[1]).astype(bf16), v_ref[b]
        dk_rot, dv = [None] * streams, [None] * streams  # float32 sums a copy's keys over the group's pairs and the copies that see them
        for heads in _pairs(group):
            n = len(heads)
            uq, rq = _unit(_stacked([q_ref[b, :, _lanes(h, head_dim)] for h in heads]), eps)
            qb = _rope(uq * gq, *tables[n]).astype(bf16)
            dq_rot = [None] * (n * streams)  # by head, then copy: the rows of ``qb``
            for s in range(streams):
                keys = SQUARES * (s + 1)
                qs, dos = _stacked([qb[_copy(i, s, rows)] for i in range(n)]), _stacked([do_ref[b, _stream(s), _lanes(h, head_dim)] for h in heads])
                ks, vs = kb[:keys], vb[:keys]
                p = _softmax(_scores(ks, qs) + _bias(bias_ref, s, n))  # exactly 0 where the mask forbids, and so is ds
                dv_s = jnp.dot(p.astype(bf16), dos, preferred_element_type=f32)  # over a pair's 128 queries: the pair's sum is the product's own
                dq_s, dk_s = _score_gradients(p, ks, qs, vs, dos, scale)
                for i in range(n):
                    dq_rot[i * streams + s] = _rows(dq_s, i, n)
                for t in range(s + 1):
                    dv[t], dk_rot[t] = added(dv[t], dv_s[_stream(t)]), added(dk_rot[t], dk_s[_stream(t)])
            dq, dgq_g = _unrope_unnorm(_stacked(dq_rot), uq, rq, gq, *tables[n], True)  # the pair's heads back through RoPE and the norm in one pass
            for i, h in enumerate(heads):
                own = slice(i * rows, (i + 1) * rows)  # head i's rows of the stacked operand
                dq_ref[b, :, _lanes(h, head_dim)], dgq = dq[own], dgq + dgq_g[own]
        dk_ref[b], dgk_b = _unrope_unnorm(_rounded(_stacked(dk_rot)), uk, rk, gk, *tables[1], True)
        dv_ref[b] = _stacked(dv).astype(dv_ref.dtype)
        return dgq, dgk + dgk_b

    zero = jnp.zeros((rows, head_dim), f32)
    dgq, dgk = _each_board(q_ref.shape[0], board, (zero, zero), unroll)
    dgq_ref[0] = jnp.sum(dgq, axis=0, keepdims=True)
    dgk_ref[0] = jnp.sum(dgk, axis=0, keepdims=True)


def _stream_blocks(q: jax.Array, k: jax.Array, head_dim: int, streams: int):
    """``_blocks`` for boards of ``64 * streams`` rows (a step takes 1 / streams of the boards, so that its blocks are
    the plain form's bytes), and the mask's block."""
    boards, rows, inner = q.shape
    heads, kv_heads = inner // head_dim, k.shape[-1] // head_dim
    if rows != SQUARES * streams or k.shape[1] != rows or heads % kv_heads:
        raise ValueError(f"q {q.shape} and k {k.shape} are not {streams} copies of a board's {SQUARES} squares side by side, or {heads} query heads "
                         f"do not divide over {kv_heads} key-value heads")
    group = heads // kv_heads
    tb = math.gcd(boards, max(1, (_BOARDS if group == 1 else _BLOCKS_PAIRED_BOARDS) // (group * streams)))
    per_head = pl.BlockSpec((tb, rows, head_dim), lambda i, h: (i, 0, h))
    per_group = pl.BlockSpec((tb, rows, group * head_dim), lambda i, h: (i, 0, h))
    whole = lambda size: pl.BlockSpec((size, head_dim), lambda i, h: (0, 0))
    mask = pl.BlockSpec(((2 * streams - 1) * SQUARES, min(group, 2) * SQUARES), lambda i, h: (0, 0))
    partial = pl.BlockSpec((1, 1, head_dim), lambda i, h: (i, 0, h))
    return (boards // tb, kv_heads), group, per_head, per_group, whole, mask, partial


def _blocks_operands(g_q, g_k, tables, block_length: int, streams: int, group: int):
    """The gains, the tables laid once a copy along the rows (a noised square turns as its clean twin) and the mask's bias, laid twice along
    the queries where a group's heads go two a product."""
    g_q, g_k, cos, sin = _operands(g_q, g_k, tables)
    return g_q, g_k, jnp.tile(cos, (streams, 1)), jnp.tile(sin, (streams, 1)), jnp.asarray(_block_bias(block_length, streams, min(group, 2)))


def _blocks_unroll(interpret: bool, unroll: int, group: int, streams: int) -> int:
    """``_unroll`` for the block-masked pair: ``unroll`` (board, head, copy)s at a group of 1, ``_BLOCKS_PAIRED_UNROLL`` where the heads go two a product."""
    return _unroll(interpret, unroll if group == 1 else _BLOCKS_PAIRED_UNROLL, group * streams)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _blocks_attention(q, k, v, g_q, g_k, eps: float, interpret: bool, block_length: int, streams: int, tables: Tuple[np.ndarray, np.ndarray]):
    head_dim = g_q.shape[-1]
    grid, group, per_head, per_group, whole, mask, _ = _stream_blocks(q, k, head_dim, streams)
    return pl.pallas_call(
        functools.partial(_blocks_forward_kernel, eps=eps, unroll=_blocks_unroll(interpret, _UNROLL, group, streams)),
        grid=grid,
        in_specs=[per_group, per_head, per_head, whole(1), whole(1), whole(q.shape[1]), whole(q.shape[1]), mask],
        out_specs=per_group,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.bfloat16),
        compiler_params=_PARAMS,
        name="board_attention_blocks",
        interpret=interpret,
    )(q, k, v, *_blocks_operands(g_q, g_k, tables, block_length, streams, group))


def _blocks_attention_fwd(q, k, v, g_q, g_k, eps, interpret, block_length, streams, tables):
    return _blocks_attention(q, k, v, g_q, g_k, eps, interpret, block_length, streams, tables), (q, k, v, g_q, g_k)


def _blocks_attention_bwd(eps, interpret, block_length, streams, tables, residuals, d_mixed):
    q, k, v, g_q, g_k = residuals
    head_dim = g_q.shape[-1]
    kv_heads = k.shape[-1] // head_dim
    grid, group, per_head, per_group, whole, mask, partial = _stream_blocks(q, k, head_dim, streams)
    sums = jax.ShapeDtypeStruct((grid[0], 1, kv_heads * head_dim), jnp.float32)
    dq, dk, dv, dgq, dgk = pl.pallas_call(
        functools.partial(_blocks_backward_kernel, eps=eps, unroll=_blocks_unroll(interpret, _UNROLL_GRAD, group, streams)),
        grid=grid,
        in_specs=[per_group, per_head, per_head, whole(1), whole(1), whole(q.shape[1]), whole(q.shape[1]), mask, per_group],
        out_specs=[per_group, per_head, per_head, partial, partial],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype), sums, sums],
        compiler_params=_PARAMS,
        name="board_attention_blocks_grad",
        interpret=interpret,
    )(q, k, v, *_blocks_operands(g_q, g_k, tables, block_length, streams, group), d_mixed)
    total = lambda s, g: s.reshape(-1, head_dim).sum(axis=0).astype(g.dtype)
    return dq, dk, dv, total(dgq, g_q), total(dgk, g_k)


_blocks_attention.defvjp(_blocks_attention_fwd, _blocks_attention_bwd)
