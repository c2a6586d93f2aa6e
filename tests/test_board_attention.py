"""The attention core's two Pallas kernels (ops/board_attention.py) alone,
under the Pallas interpreter, against the formula written out below in
float32.

Tolerances come from counting roundings, not from the readings. The
kernels round to bfloat16 (relative error at most 2^-8 an element: half
a unit in the last of 8 bits) where the configuration says products take
bfloat16 operands: on the way to ``mixed`` that is q, k, the
probabilities and the result, four roundings; on the way to a gradient
of q, k or a gain it is q, k, the probabilities' cotangent, the scores'
cotangent, the other operand of the product and the product's result,
six (v's gradient passes four). Scores here are O(1) (normed rows, gains
near 1), so the softmax does not amplify them, and independent roundings
add in quadrature, so the relative L2 error stays under roundings x 2^-8
with room (the readings, CPU: 0.0033-0.0037 forward, 0.0036-0.0061
gradients). Three wrong formulas show that the tolerances would catch a
missing piece: they miss by 7x to 57x (no scale 35-52x, no RoPE 31-57x,
no gain 7-10x).

Since PR 61 a key-value head's query heads go two a product inside both
kernels (an odd group's last head alone, a group of 1 as before). The
grouped cases hold every form at an even and an odd group to the plain
formula under the same tolerances; the parent's one-head-at-a-time
bodies are kept below as a test's helper (``PARENT_BODIES``) and the
packed bodies' six outputs are theirs to float32 rounding.
``tools/attention_alone.py`` (the pair timed alone) runs at a tiny shape.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.ops import board_attention as kernels
from fishnet_tpu.ops.board_attention import board_attention, rope_tables
from tools import attention_alone

THETA, EPS = 50000.0, 1e-5
ROUNDING = 2.0 ** -8
FORWARD_TOL, GRADIENT_TOL = 4 * ROUNDING, 6 * ROUNDING


def rope(x, head_dim, inverse=False):
    """Rotate-half RoPE of [.., 64, heads, head_dim], position = square."""
    half = head_dim // 2
    angle = np.arange(64)[:, None] / THETA ** (np.arange(half) / half)[None, :]
    cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, -1), jnp.float32)[:, None, :] for f in (np.cos, np.sin))
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * (-sin if inverse else sin)


def plain(q, k, v, g_q, g_k, wrong="", theta=THETA):
    """The core from the layer equations, float32 throughout. Fewer
    key-value heads than query heads: each is repeated for the query
    heads of its group (head h attends key-value head h // group)."""
    boards, head_dim = q.shape[0], g_q.shape[0]
    split = lambda y: y.astype(jnp.float32).reshape(boards, 64, -1, head_dim)
    norm = lambda x, g: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (1.0 if wrong == "no_gain" else g)
    turn = (lambda x: x) if wrong == "no_rope" or theta is None else (lambda x: rope(x, head_dim))
    q, k, v = turn(norm(split(q), g_q)), turn(norm(split(k), g_k)), split(v)
    k, v = (jnp.repeat(y, q.shape[2] // y.shape[2], axis=2) for y in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / (1.0 if wrong == "no_scale" else np.sqrt(head_dim))
    mixed = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision="highest")
    return mixed.reshape(boards, 64, -1)


def inputs(boards, heads, head_dim, seed=0, kv_heads=None):
    rng = np.random.default_rng(seed)
    shape, kv_shape = (boards, 64, heads * head_dim), (boards, 64, (kv_heads or heads) * head_dim)
    gain = lambda: jnp.asarray(1.0 + 0.1 * rng.standard_normal(head_dim), jnp.float32)
    return (jnp.asarray(1.5 * rng.standard_normal(shape), jnp.float32), jnp.asarray(1.5 * rng.standard_normal(kv_shape), jnp.float32),
            jnp.asarray(rng.standard_normal(kv_shape), jnp.bfloat16), gain(), gain(),
            jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))  # the last: the cotangent of ``mixed``


def kernel(q, k, v, g_q, g_k, theta=THETA):
    return board_attention(q, k, v, g_q, g_k, theta, EPS, True)


def value_and_gradients(f, q, k, v, g_q, g_k, cotangent):
    out, pull = jax.vjp(f, q, k, v, g_q, g_k)
    return (out, *pull(cotangent.astype(out.dtype)))


def rel(got, want):
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# (heads, head_dim, boards): 32 boards are two grid steps of the block's 16; 5 and 6 are batches
# the block does not divide (1 and 2 boards a step); head_dim 128 is the published lane block.
CASES = [(2, 16, 32), (2, 16, 5), (4, 16, 6), (2, 128, 4)]
OUTPUTS = ["mixed", "d_q", "d_k", "d_v", "d_q_norm", "d_k_norm"]


@functools.lru_cache(maxsize=None)
def both(heads, head_dim, boards):
    args = inputs(boards, heads, head_dim)
    return (jax.jit(functools.partial(value_and_gradients, kernel))(*args),
            jax.jit(functools.partial(value_and_gradients, plain))(*args))


@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("heads,head_dim,boards", CASES)
def test_kernels_match_the_plain_formula(heads, head_dim, boards, output):
    got, want = (side[OUTPUTS.index(output)] for side in both(heads, head_dim, boards))
    assert got.shape == want.shape
    assert got.dtype == (jnp.float32 if output in ("d_q", "d_k", "d_q_norm", "d_k_norm") else jnp.bfloat16)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL)


# (heads, key-value heads, head_dim, boards, theta): the second block's 8 query heads a key-value head at the
# published lane block (2 boards a grid step); groups of 2 and 4 with batches the block does and does not
# divide; None is a layer without RoPE, with and without a group. dk, dv sum over a group's query heads: the
# tolerance stays, because the sum is float32 and rounded once.
# Since PR 61 the heads of a group go two a product: 6 over 2 is an ODD group (a pair and a lone head in one grid step), 4 over 2 at the
# published lane block a group of one pair, 6 over 2 without RoPE the odd group on the other branch.
GROUPED_CASES = [(4, 2, 16, 12, THETA), (8, 2, 16, 5, THETA), (8, 1, 128, 2, THETA), (4, 2, 16, 6, None), (2, 2, 16, 8, None),
                 (6, 2, 16, 5, THETA), (4, 2, 128, 2, THETA), (6, 2, 16, 3, None)]


@functools.lru_cache(maxsize=None)
def both_grouped(heads, kv_heads, head_dim, boards, theta):
    args = inputs(boards, heads, head_dim, seed=5, kv_heads=kv_heads)
    return (jax.jit(functools.partial(value_and_gradients, functools.partial(kernel, theta=theta)))(*args),
            jax.jit(functools.partial(value_and_gradients, functools.partial(plain, theta=theta)))(*args))


@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("heads,kv_heads,head_dim,boards,theta", GROUPED_CASES)
def test_grouped_heads_and_layers_without_rope_match_the_plain_formula(heads, kv_heads, head_dim, boards, theta, output):
    got, want = (side[OUTPUTS.index(output)] for side in both_grouped(heads, kv_heads, head_dim, boards, theta))
    assert got.shape == want.shape
    if not output.endswith("_norm"):
        assert got.shape[-1] == (kv_heads if output in ("d_k", "d_v") else heads) * head_dim
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL)


def test_the_tolerances_catch_a_wrong_group_or_a_rotation_left_in():
    """Query head h attends key-value head h // group, not h % kv_heads;
    a layer without RoPE rotates nothing."""
    args = inputs(6, 4, 16, seed=6, kv_heads=2)
    got = jax.jit(functools.partial(value_and_gradients, kernel))(*args)
    interleaved = lambda q, k, v, g_q, g_k: plain(q, jnp.tile(k, 2), jnp.tile(v, 2), g_q, g_k)  # head h -> h % 2
    assert rel(got[0], jax.jit(interleaved)(*args[:5])) > 1.5 * FORWARD_TOL
    unrotated = jax.jit(functools.partial(value_and_gradients, functools.partial(kernel, theta=None)))(*args)
    assert rel(unrotated[0], got[0]) > 1.5 * FORWARD_TOL
    with pytest.raises(ValueError, match="do not divide"):
        board_attention(args[0], jnp.tile(args[1], 2)[..., :48], jnp.tile(args[2], 2)[..., :48], args[3], args[4], THETA, EPS, True)


@pytest.mark.parametrize("wrong", ["no_scale", "no_rope", "no_gain"])
def test_the_tolerances_catch_a_missing_piece(wrong):
    """Each wrong formula misses at least one forward or gradient
    tolerance by more than 1.5x."""
    args = inputs(6, 2, 16)
    got = jax.jit(functools.partial(value_and_gradients, kernel))(*args)
    want = jax.jit(functools.partial(value_and_gradients, functools.partial(plain, wrong=wrong)))(*args)
    misses = [rel(g, w) / (FORWARD_TOL if i == 0 else GRADIENT_TOL) for i, (g, w) in enumerate(zip(got, want))]
    assert max(misses) > 1.5, misses


def test_a_board_of_zero_queries_mixes_the_mean_of_the_values():
    """All scores 0: the softmax is uniform, 1/64 exactly, and ``mixed``
    is every head's mean value over the squares, rounded once."""
    q, k, v, g_q, g_k, cotangent = inputs(3, 2, 16, seed=1)
    q = q.at[1].set(0.0)
    out, d_q, *_ = jax.jit(functools.partial(value_and_gradients, kernel))(q, k, v, g_q, g_k, cotangent)
    mean = jnp.mean(v[1].astype(jnp.float32), axis=0, keepdims=True)
    assert np.allclose(out[1].astype(jnp.float32), jnp.broadcast_to(mean, (64, 32)), rtol=ROUNDING, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(d_q)))  # rsqrt(eps) is large, not infinite
    assert rel(out[0], plain(q, k, v, g_q, g_k)[0]) < FORWARD_TOL  # the boards beside it are untouched


def test_a_dominant_key_hands_every_query_its_value():
    """Queries and one key that all point the same way after RoPE (built
    by rotating a unit vector back to each square), gains 3: that key's
    score is 9 sqrt(head_dim) = 36 for every query, the others' are
    ~9 N(0, 1), so ``mixed`` is that key's value row on every square."""
    heads, head_dim, key_square = 2, 16, 37
    q, k, v, _, _, cotangent = inputs(2, heads, head_dim, seed=2)
    rng = np.random.default_rng(3)
    direction = jnp.asarray(rng.standard_normal((1, heads, head_dim)), jnp.float32)
    aligned = rope(jnp.broadcast_to(direction, (64, heads, head_dim)), head_dim, inverse=True).reshape(64, -1)
    q = q.at[0].set(aligned)
    k = k.at[0, key_square].set(aligned[key_square])
    gains = jnp.full((head_dim,), 3.0, jnp.float32)
    out = jax.jit(kernel)(q, k, v, gains, gains)
    want = jnp.broadcast_to(v[0, key_square].astype(jnp.float32), (64, heads * head_dim))
    assert np.allclose(out[0].astype(jnp.float32), want, rtol=ROUNDING, atol=1e-3)
    assert rel(out, plain(q, k, v, gains, gains)) < FORWARD_TOL


def test_rope_tables_fold_the_sign_of_rotate_half_into_the_sine():
    cos, sin = rope_tables(THETA, 16)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((64, 1, 16)), jnp.float32)
    want = rope(x, 16)[:, 0]
    assert cos.dtype == sin.dtype == np.float32 and cos.shape == sin.shape == (64, 16)
    assert np.allclose(x[:, 0] * cos + jnp.roll(x[:, 0], 8, axis=-1) * sin, want, atol=1e-6)


# -- the latent form: no norm, a NoPE and a RoPE part of every head's score, one RoPE key for all heads ---------------
#
# The plain formula is the published one, literally: per-head columns [nope | rope], RoPE on interleaved pairs
# (2i, 2i + 1). The kernels take every head's NoPE columns, then every head's RoPE columns with the pairs taken
# apart (``latent_column_order``): the tests permute the inputs in and the gradients back, so the map is held to the
# published order. Roundings as above (no norm: the same count or fewer), so the tolerances stay.

from fishnet_tpu.ops.board_attention import latent_column_order  # noqa: E402

LATENT_THETA = 1e6


def rope_pairs(x, theta=LATENT_THETA):
    """RoPE on interleaved pairs of [boards, 64, .., rope], position = square."""
    r = x.shape[-1]
    angle = np.arange(64)[:, None] / theta ** (np.arange(0, r, 2) / r)[None, :]
    shape = (64,) + (1,) * (x.ndim - 3) + (r // 2,)
    cos, sin = jnp.asarray(np.cos(angle), jnp.float32).reshape(shape), jnp.asarray(np.sin(angle), jnp.float32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def plain_latent(q, k, k_pe, v, heads, wrong=""):
    """q [boards, 64, heads x (nope + rope)] in the published per-head order, k [boards, 64, heads x nope], k_pe
    [boards, 64, rope] one for all heads, v [boards, 64, heads x value]; float32 throughout."""
    boards, rope_dim = q.shape[0], k_pe.shape[-1]
    split = lambda y: y.astype(jnp.float32).reshape(boards, 64, heads, -1)
    q, k, v = split(q), split(k), split(v)
    nope = k.shape[-1]
    q_nope, q_pe, k_pe = q[..., :nope], q[..., nope:], jnp.broadcast_to(k_pe[:, :, None, :], (boards, 64, heads, rope_dim))
    if wrong == "key_per_head":  # each head reads a RoPE key of its own (here: another square's)
        k_pe = jnp.stack([jnp.roll(k_pe[:, :, h], h, axis=1) for h in range(heads)], axis=2)
    apart = latent_column_order(1, 0, rope_dim)  # x[..., apart] has the pairs' first elements, then their second
    halves = lambda x: rope_pairs(x[..., np.argsort(apart)])[..., apart]  # column i turned with column i + rope / 2
    turn = halves if wrong == "rotate_half" else rope_pairs  # rotate-half where pairs were published, with no permutation
    if wrong == "rope_on_nope":
        q_nope, k = rope_pairs(q_nope, THETA), rope_pairs(k, THETA)
    q_pe, k_pe = turn(q_pe), turn(k_pe)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k, precision="highest") + jnp.einsum("bqhd,bkhd->bhqk", q_pe, k_pe, precision="highest")
    scores = scores / np.sqrt(nope if wrong == "scale_of_nope" else nope + rope_dim)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision="highest").reshape(boards, 64, -1)


def latent_kernel(q, k, k_pe, v, heads):
    """The kernel pair behind the published order: columns permuted in, so that ``jax.vjp`` permutes the gradients back."""
    rope_dim = k_pe.shape[-1]
    nope = k.shape[-1] // heads
    q = q[..., latent_column_order(heads, nope, rope_dim)]
    return board_attention(q[..., :heads * nope], k, v, None, None, LATENT_THETA, EPS, True,
                           q_pe=q[..., heads * nope:], k_pe=k_pe[..., latent_column_order(1, 0, rope_dim)])


def latent_inputs(heads, nope, rope_dim, value, boards, seed=7):
    rng = np.random.default_rng(seed)
    normal = lambda width, dtype=jnp.float32, scale=1.0: jnp.asarray(scale * rng.standard_normal((boards, 64, width)), dtype)
    return (normal(heads * (nope + rope_dim)), normal(heads * nope), normal(rope_dim), normal(heads * value, jnp.bfloat16),
            normal(heads * value, jnp.bfloat16))  # the last: the cotangent of ``mixed``


# (heads, nope, rope, value, boards): RoPE parts of 64 (two heads a 128-lane tile, the published width), 32 (four) and
# 128 (one); 16 heads are two grid steps of 8, so dk_pe sums over the steps of a board too; 5 boards are 5 steps of 1.
LATENT_CASES = [(2, 16, 64, 16, 4), (4, 32, 64, 16, 5), (16, 16, 64, 8, 2), (4, 16, 32, 16, 3), (2, 16, 128, 32, 2)]
LATENT_OUTPUTS = ["mixed", "d_q", "d_k", "d_k_pe", "d_v"]


@functools.lru_cache(maxsize=None)
def both_latent(heads, nope, rope_dim, value, boards, wrong=""):
    *args, cotangent = latent_inputs(heads, nope, rope_dim, value, boards)

    def value_and_gradients(f):
        out, pull = jax.vjp(lambda *a: f(*a, heads), *args)
        return (out, *pull(cotangent.astype(out.dtype)))

    return jax.jit(lambda: value_and_gradients(latent_kernel))(), jax.jit(lambda: value_and_gradients(functools.partial(plain_latent, wrong=wrong)))()


@pytest.mark.parametrize("output", LATENT_OUTPUTS)
@pytest.mark.parametrize("heads,nope,rope_dim,value,boards", LATENT_CASES)
def test_the_latent_form_matches_the_published_formula(heads, nope, rope_dim, value, boards, output):
    got, want = (side[LATENT_OUTPUTS.index(output)] for side in both_latent(heads, nope, rope_dim, value, boards))
    assert got.shape == want.shape
    assert got.dtype == (jnp.bfloat16 if output in ("mixed", "d_v") else jnp.float32)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL)


@pytest.mark.parametrize("wrong", ["rope_on_nope", "scale_of_nope", "key_per_head", "rotate_half"])
def test_the_tolerances_catch_a_wrong_latent_formula(wrong):
    """RoPE on the NoPE part too, a scale of 1 / sqrt(nope), a RoPE key
    taken per head, rotate-half on the published column order: each
    misses at least one tolerance by more than 1.5x."""
    got, want = both_latent(4, 32, 64, 16, 5, wrong)
    misses = [rel(g, w) / (FORWARD_TOL if i == 0 else GRADIENT_TOL) for i, (g, w) in enumerate(zip(got, want))]
    assert max(misses) > 1.5, misses


def test_the_latent_column_order_takes_every_published_column_once():
    order = latent_column_order(3, 4, 6)
    assert sorted(order) == list(range(30))
    assert list(order[:12]) == [0, 1, 2, 3, 10, 11, 12, 13, 20, 21, 22, 23]  # every head's NoPE columns first
    assert list(order[12:18]) == [4, 6, 8, 5, 7, 9]  # head 0's pairs (2i, 2i + 1) as the two halves rotate-half turns
    assert list(latent_column_order(1, 0, 4)) == [0, 2, 1, 3]


def test_a_mixture_of_the_two_forms_is_refused():
    q, k, k_pe, v, _ = latent_inputs(2, 16, 64, 16, 2)
    gain = jnp.ones((16,), jnp.float32)
    with pytest.raises(ValueError, match="not a mixture"):
        board_attention(q[..., :32], k, v, gain, gain, LATENT_THETA, EPS, True, k_pe=k_pe)
    with pytest.raises(ValueError, match="not a mixture"):
        board_attention(q[..., :32], k, v, None, None, None, EPS, True, q_pe=q[..., 32:160], k_pe=k_pe)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        board_attention(q[..., :32], k, v, None, None, LATENT_THETA, EPS, True, q_pe=q[..., 32:96], k_pe=k_pe)  # one head's RoPE columns for two


# -- the fifth block's form: a norm without a query gain, a gain a key-value head, RoPE on the first columns of a head --------

# (heads, key-value heads, head_dim, rotary_dim, boards): the published 8 over 2 of 128 with 64 rotated (4 query heads a
# key-value head: 4 boards a grid step) on a batch the block divides and one it does not; a tiny group of 4; all of a
# head rotated through the partial tables; a quarter of it.
PART_CASES = [(8, 2, 128, 64, 4), (8, 2, 16, 8, 6), (4, 1, 16, 8, 5), (4, 2, 16, 16, 3), (2, 2, 16, 4, 8), (6, 2, 16, 8, 3)]  # the last: an odd group (PR 61)
PART_OUTPUTS = ["mixed", "d_q", "d_k", "d_v", "d_gain"]
PART_THETA = 5_000_000.0


def plain_part(q, k, v, gain, head_dim, rotary_dim, wrong=""):
    """The fifth block's core, float32: q and k normed without a gain, k under ``gain`` [kv_heads, head_dim], rotate-half
    RoPE inside the FIRST ``rotary_dim`` columns of every head, the rest passing."""
    boards = q.shape[0]
    split = lambda y: y.astype(jnp.float32).reshape(boards, 64, -1, head_dim)
    unit = lambda x: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    half = rotary_dim // 2
    angle = np.arange(64)[:, None] / PART_THETA ** (np.arange(half) / half)[None, :]
    cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, -1), jnp.float32)[:, None, :] for f in (np.cos, np.sin))

    def turn(x):
        part = x[..., -rotary_dim:] if wrong == "last_columns" else x[..., :rotary_dim]
        part = part * cos + jnp.concatenate([-part[..., half:], part[..., :half]], -1) * sin
        return jnp.concatenate([x[..., :-rotary_dim], part] if wrong == "last_columns" else [part, x[..., rotary_dim:]], -1)

    q, v = turn(unit(split(q))), split(v)
    k = turn(unit(split(k)) * (gain[::-1] if wrong == "gains_exchanged" else gain)[None, None])
    k, v = (jnp.repeat(y, q.shape[2] // y.shape[2], axis=2) for y in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(head_dim)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision="highest").reshape(boards, 64, -1)


@functools.lru_cache(maxsize=None)
def both_part(heads, kv_heads, head_dim, rotary_dim, boards, wrong=""):
    q, k, v, _, _, cotangent = inputs(boards, heads, head_dim, seed=11, kv_heads=kv_heads)
    gain = jnp.asarray(1.0 + 0.3 * np.random.default_rng(12).standard_normal((kv_heads, head_dim)), jnp.float32)

    def sides(f):
        out, pull = jax.vjp(f, q, k, v, gain)
        return (out, *pull(cotangent.astype(out.dtype)))

    return (jax.jit(lambda: sides(lambda q, k, v, g: board_attention(q, k, v, None, g, PART_THETA, EPS, True, rotary_dim=rotary_dim)))(),
            jax.jit(lambda: sides(lambda q, k, v, g: plain_part(q, k, v, g, head_dim, rotary_dim, wrong)))())


@pytest.mark.parametrize("output", PART_OUTPUTS)
@pytest.mark.parametrize("heads,kv_heads,head_dim,rotary_dim,boards", PART_CASES)
def test_a_gain_a_key_value_head_and_rope_on_part_of_a_head_match_the_plain_formula(heads, kv_heads, head_dim, rotary_dim, boards, output):
    got, want = (side[PART_OUTPUTS.index(output)] for side in both_part(heads, kv_heads, head_dim, rotary_dim, boards))
    assert got.shape == want.shape and (output != "d_gain" or got.shape == (kv_heads, head_dim))
    assert got.dtype == (jnp.bfloat16 if output in ("mixed", "d_v") else jnp.float32)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL)


@pytest.mark.parametrize("wrong", ["last_columns", "gains_exchanged"])
def test_the_tolerances_catch_rope_on_the_wrong_columns_and_a_gain_on_the_wrong_head(wrong):
    got, want = both_part(8, 2, 16, 8, 6, wrong)
    assert rel(got[0], want[0]) > 3 * FORWARD_TOL and rel(got[1], want[1]) > 3 * GRADIENT_TOL


def test_rope_on_all_of_a_head_is_the_same_through_either_table():
    q, k, v, _, g_k, _ = inputs(4, 4, 16, seed=13, kv_heads=2)
    gain = jnp.broadcast_to(g_k, (2, 16))
    whole = board_attention(q, k, v, None, gain, PART_THETA, EPS, True)
    part = board_attention(q, k, v, None, gain, PART_THETA, EPS, True, rotary_dim=16)
    assert rel(part, whole) < 1e-6


def test_a_part_that_is_odd_or_wider_than_a_head_and_a_part_of_a_latent_are_refused():
    q, k, v, g_q, g_k, _ = inputs(2, 2, 16)
    for rotary_dim in (5, 18, 0):
        with pytest.raises(ValueError, match="even part of a head"):
            board_attention(q, k, v, g_q, g_k, THETA, EPS, True, rotary_dim=rotary_dim)
    with pytest.raises(ValueError, match="not a mixture"):
        board_attention(q, k, v, None, None, THETA, EPS, True, q_pe=q, k_pe=k[..., :16], rotary_dim=8)


# -- the seventh block's form: qk-norm under one gain each, a head of 256, RoPE on its first 64 columns, 8 query heads a key-value head --------

# (heads, key-value heads, head_dim, rotary_dim, boards): the published head and group (4 boards a grid step since PR 61; here 2) on one key-value head, and tiny ones
WIDE_CASES = [(8, 1, 256, 64, 2), (4, 2, 32, 8, 3), (6, 2, 32, 8, 2)]  # the last: an odd group (PR 61)
WIDE_THETA = 10_000_000.0


@functools.lru_cache(maxsize=None)
def both_wide(heads, kv_heads, head_dim, rotary_dim, boards, turned=None):
    """The kernel pair told both gains and ``rotary_dim`` against ``plain_part``'s formula under a query gain too; ``turned``: the
    columns the plain side rotates (a misreading: all of the head)."""
    q, k, v, g_q, g_k, cotangent = inputs(boards, heads, head_dim, seed=17, kv_heads=kv_heads)

    def plain(q, k, v, g_q, g_k):
        # ``plain_part`` norms the query without a gain and multiplies the key's: the query's gain goes in through the key's side of
        # no product, so it is written out here: unit(q) * g_q, rotated as ``plain_part`` rotates
        split = lambda y: y.astype(jnp.float32).reshape(boards, 64, -1, head_dim)
        unit = lambda x: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
        part = turned or rotary_dim
        half = part // 2
        angle = np.arange(64)[:, None] / WIDE_THETA ** (np.arange(half) / half)[None, :]
        cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, -1), jnp.float32)[:, None, :] for f in (np.cos, np.sin))
        turn = lambda x: jnp.concatenate([x[..., :part] * cos + jnp.concatenate([-x[..., half:part], x[..., :half]], -1) * sin, x[..., part:]], -1)
        qh, kh, vh = turn(unit(split(q)) * g_q), turn(unit(split(k)) * g_k), split(v)
        kh, vh = (jnp.repeat(y, heads // kv_heads, axis=2) for y in (kh, vh))
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh, precision="highest") / np.sqrt(head_dim)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vh, precision="highest").reshape(boards, 64, -1)

    def sides(f):
        out, pull = jax.vjp(f, q, k, v, g_q, g_k)
        return (out, *pull(cotangent.astype(out.dtype)))

    return (jax.jit(lambda: sides(lambda *a: board_attention(*a, WIDE_THETA, EPS, True, rotary_dim=rotary_dim)))(), jax.jit(lambda: sides(plain))())


WIDE_OUTPUTS = ["mixed", "d_q", "d_k", "d_v", "d_g_q", "d_g_k"]


@pytest.mark.parametrize("output", WIDE_OUTPUTS)
@pytest.mark.parametrize("heads,kv_heads,head_dim,rotary_dim,boards", WIDE_CASES)
def test_a_head_of_256_with_64_columns_turned_under_both_gains_matches_the_plain_formula(heads, kv_heads, head_dim, rotary_dim, boards, output):
    got, want = (side[WIDE_OUTPUTS.index(output)] for side in both_wide(heads, kv_heads, head_dim, rotary_dim, boards))
    assert got.shape == want.shape and got.dtype == (jnp.bfloat16 if output in ("mixed", "d_v") else jnp.float32)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL), (output, rel(got, want))


def test_the_tolerances_catch_rope_on_all_256_columns():
    got, want = both_wide(8, 1, 256, 64, 2, turned=256)
    assert rel(got[0], want[0]) > 3 * FORWARD_TOL and rel(got[1], want[1]) > 3 * GRADIENT_TOL


# -- the form without a norm (the fourth block's: told ``head_dim`` in the gains' place), at an even and an odd group ---------------------

# (heads, key-value heads, head_dim, boards, theta)
UNNORMED_CASES = [(4, 2, 16, 6, THETA), (8, 2, 16, 3, None), (6, 2, 16, 4, THETA)]
UNNORMED_OUTPUTS = ["mixed", "d_q", "d_k", "d_v"]


@functools.lru_cache(maxsize=None)
def both_unnormed(heads, kv_heads, head_dim, boards, theta):
    q, k, v, _, _, cotangent = inputs(boards, heads, head_dim, seed=19, kv_heads=kv_heads)
    q, k = q / 1.5, k / 1.5  # unnormed rows of unit scale: scores O(1), as the tolerances assume

    def plain_unnormed(q, k, v):
        split = lambda y: y.astype(jnp.float32).reshape(boards, 64, -1, head_dim)
        turn = (lambda x: x) if theta is None else (lambda x: rope(x, head_dim))
        qh, kh, vh = turn(split(q)), turn(split(k)), split(v)
        kh, vh = (jnp.repeat(y, heads // kv_heads, axis=2) for y in (kh, vh))
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh, precision="highest") / np.sqrt(head_dim)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vh, precision="highest").reshape(boards, 64, -1)

    def sides(f):
        out, pull = jax.vjp(f, q, k, v)
        return (out, *pull(cotangent.astype(out.dtype)))

    return (jax.jit(lambda: sides(lambda q, k, v: board_attention(q, k, v, None, None, theta, EPS, True, head_dim=head_dim)))(),
            jax.jit(lambda: sides(plain_unnormed))())


@pytest.mark.parametrize("output", UNNORMED_OUTPUTS)
@pytest.mark.parametrize("heads,kv_heads,head_dim,boards,theta", UNNORMED_CASES)
def test_the_form_without_a_norm_matches_the_plain_formula_at_even_and_odd_groups(heads, kv_heads, head_dim, boards, theta, output):
    got, want = (side[UNNORMED_OUTPUTS.index(output)] for side in both_unnormed(heads, kv_heads, head_dim, boards, theta))
    assert got.shape == want.shape and got.dtype == (jnp.bfloat16 if output in ("mixed", "d_v") else jnp.float32)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL), (output, rel(got, want))


# -- two query heads a product (PR 61) against the parent's bodies, one head at a time ------------------------------------------------------
#
# The bodies of ``_forward_kernel`` and ``_backward_kernel`` as the parent commit (8be8117; the file is 572dbfe's) had them, kept HERE as the reference and not in
# the program: inside a grid step the group's heads run one after another against the same ``kb``, ``vb``, and ``dv``, ``dk_rot`` are
# float32 sums of the heads' parts. The helpers they call are the tree's own (PR 61 changed none of them).


def parent_forward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, out_ref, *, eps, rope, unroll, norm=True, half=None, q_gain=True):
    cos, sin = cos_ref[...], sin_ref[...]
    gq, gk = gq_ref[...] if q_gain else None, gk_ref[...]
    head_dim = k_ref.shape[-1]
    turn = (lambda x: kernels._rope(x, cos, sin) if half is None else kernels._part_rope(x, cos, sin, half)) if rope else (lambda x: x)
    normed = (lambda x, gain: kernels._unit(x, eps)[0] if gain is None else kernels._unit(x, eps)[0] * gain) if norm else (lambda x, gain: x)

    def board(b, carry):
        group = q_ref.shape[-1] // head_dim
        for g in range(group):
            qb = turn(normed(q_ref[kernels._head(b, g, head_dim, group)], gq)).astype(jnp.bfloat16)
            if g == 0:
                kb = turn(normed(k_ref[b], gk)).astype(jnp.bfloat16)
            p = kernels._softmax(kernels._scores(kb, qb)).astype(jnp.bfloat16)
            mixed = jax.lax.dot_general(p, v_ref[b], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            out_ref[kernels._head(b, g, head_dim, group)] = mixed.astype(out_ref.dtype)
        return carry

    kernels._each_board(q_ref.shape[0], board, 0, unroll)


def parent_backward_kernel(q_ref, k_ref, v_ref, gq_ref, gk_ref, cos_ref, sin_ref, do_ref, dq_ref, dk_ref, dv_ref, dgq_ref, dgk_ref, *,
                           eps, rope, unroll, norm=True, half=None, q_gain=True):
    cos, sin = cos_ref[...], sin_ref[...]
    gq, gk = gq_ref[...] if q_gain else None, gk_ref[...]
    bf16, f32 = jnp.bfloat16, jnp.float32
    head_dim = k_ref.shape[-1]
    scale = np.float32(1.0 / math.sqrt(head_dim))
    turn = (lambda x: kernels._rope(x, cos, sin) if half is None else kernels._part_rope(x, cos, sin, half)) if rope else (lambda x: x)
    unit = (lambda x: kernels._unit(x, eps)) if norm else (lambda x: (x, None))
    gained = (lambda u, gain: u if gain is None else u * gain) if norm else (lambda u, gain: u)

    def board(b, carry):
        dgq, dgk = carry
        group = q_ref.shape[-1] // head_dim
        for g in range(group):
            uq, rq = unit(q_ref[kernels._head(b, g, head_dim, group)])
            if g == 0:
                uk, rk = unit(k_ref[b])
            qb = turn(gained(uq, gq)).astype(bf16)
            if g == 0:
                kb = turn(gained(uk, gk)).astype(bf16)
                vb = v_ref[b]
            do = do_ref[kernels._head(b, g, head_dim, group)]
            p = kernels._softmax(kernels._scores(kb, qb))
            dv_g = jnp.dot(p.astype(bf16), do, preferred_element_type=f32)
            dv = dv_g if g == 0 else dv + dv_g
            if g == group - 1:
                dv_ref[b] = dv.astype(dv_ref.dtype)
            dq_rot, dk_g = kernels._score_gradients(p, kb, qb, vb, do, scale)
            dk_rot = dk_g if g == 0 else dk_rot + dk_g
            dq, dgq_g = kernels._unrope_unnorm(dq_rot, uq, rq, gq, cos, sin, rope, half)
            dgq = dgq + dgq_g
            if g == group - 1:
                dk, dgk_b = kernels._unrope_unnorm(kernels._rounded(dk_rot), uk, rk, gk, cos, sin, rope, half)
                dq_ref[kernels._head(b, g, head_dim, group)], dk_ref[b] = dq, dk
            else:
                dq_ref[kernels._head(b, g, head_dim, group)] = dq
        return dgq, dgk + dgk_b

    zero = jnp.zeros((64, head_dim), f32)
    dgq, dgk = kernels._each_board(q_ref.shape[0], board, (zero, zero), unroll)
    dgq_ref[0] = jnp.sum(dgq, axis=0, keepdims=True)
    dgk_ref[0] = jnp.sum(dgk, axis=0, keepdims=True)


PARENT_BODIES = {"_forward_kernel": parent_forward_kernel, "_backward_kernel": parent_backward_kernel}


def under(patches, trace, *args):
    """``trace(*args)`` with ``patches`` over the kernels' module's names while it runs (none: the tree's own)."""
    with pytest.MonkeyPatch.context() as patched:
        for name, value in patches.items():
            patched.setattr(kernels, name, value)
        return trace(*args)


def six_outputs(patches, args):
    """The pair's six outputs under the interpreter, bare: traced here, under whatever ``patches`` put in the module."""
    return under(patches, functools.partial(value_and_gradients, kernel), *args)


#: What a float32 sum of a pair's two parts in another order, or one product's accumulation in their place, can move: the last bit of a
#: float32 before a rounding to bfloat16 that may then fall the other way on a few elements of thousands (relative L2). A tenth of the
#: tightest tolerance. The readings here are 0.0 for all six: XLA:CPU accumulates a product down its rows in order, so one product over
#: a pair's 128 queries IS the two halves added; the MXU's order is its own (``tools/attention_alone.py --against``, on the chip).
REORDERED_SUM = ROUNDING / 10


@pytest.mark.parametrize("output", OUTPUTS)
def test_two_heads_a_product_give_what_the_parents_one_head_bodies_gave(output):
    """A group of 4 (two pairs a key-value head), two key-value heads, 3 boards: the packed bodies against the parent's, every output."""
    got, want = packed_and_parent()
    i = OUTPUTS.index(output)
    assert got[i].shape == want[i].shape and got[i].dtype == want[i].dtype
    assert rel(got[i], want[i]) < REORDERED_SUM, (output, rel(got[i], want[i]))


@functools.lru_cache(maxsize=None)
def packed_and_parent():
    args = inputs(3, 8, 16, seed=23, kv_heads=2)
    return six_outputs({}, args), six_outputs(PARENT_BODIES, args)


def test_the_packed_bodies_hold_half_the_parents_products_and_a_lone_head_its_own():
    """Products in the traced bodies, a board: 7 a query head in the parent's (2 forward, 5 in the gradient), 7 a PAIR here; an odd
    group's last head is the one-head body, and a group of 1 the parent's count."""
    def products(group, bodies):
        traced = under(bodies, jax.make_jaxpr(functools.partial(value_and_gradients, kernel)), *inputs(1, group, 16, kv_heads=1))
        return str(traced).count("dot_general")

    assert [products(group, {}) for group in (1, 2, 3, 4)] == [7, 7, 14, 14]
    assert [products(group, PARENT_BODIES) for group in (1, 2, 3, 4)] == [7, 14, 21, 28]


def test_the_tolerances_catch_a_pair_written_to_each_others_lanes():
    """Head g + 1's rows of a packed result written to head g's lanes (and back): ``mixed`` and ``d_q`` miss by far."""
    args = inputs(3, 8, 16, seed=23, kv_heads=2)
    wrong = six_outputs({"_rows": lambda x, i, parts: x if parts == 1 else x[(1 - i) * 64:(2 - i) * 64]}, args)
    want = jax.jit(functools.partial(value_and_gradients, plain))(*args)
    assert rel(wrong[0], want[0]) > 3 * FORWARD_TOL and rel(wrong[1], want[1]) > 3 * GRADIENT_TOL
    assert rel(wrong[3], want[3]) < GRADIENT_TOL  # d_v sums over a pair's queries either way: it cannot tell


# -- tools/attention_alone.py: the pair timed alone -----------------------------------------------------------------------------------------


@pytest.mark.parametrize("masked", [{}, {"block_length": 4, "streams": 2}], ids=["plain", "block_masked"])
def test_attention_alone_prints_its_three_times_and_what_it_ran_on(capsys, masked):
    """The tool as a builder runs it on the chip, here at a tiny shape under the interpreter (the times are the interpreter's and say
    nothing of a device: ``interpret`` and ``device`` say so in the line): its three programs' times, the gradient kernel's by itself
    among them, and against its own tree's file every output equal; the plain pair, and the block-masked one under both copies."""
    assert attention_alone.PROGRAMS == ("forward_ms", "forward_and_gradient_ms", "gradient_ms")
    shape = ["--boards", "2", "--heads", "4", "--kv-heads", "2", "--d", "16", *(str(word) for name, value in masked.items() for word in ("--" + name.replace("_", "-"), value))]
    assert attention_alone.main([*shape, "--calls", "2", "--seed", "3000000019", "--against", kernels.__file__]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["interpret"] is True and line["device"] == jax.devices()[0].device_kind and line["finite"] is True
    assert (line["boards"], line["heads"], line["kv_heads"], line["d"]) == (2, 4, 2, 16) and {name: line.get(name) for name in masked} == masked
    for times in (line, line["against"]):
        for program in attention_alone.PROGRAMS:
            assert 0.0 < times[program]["min"] <= times[program]["median"] <= times[program]["max"]
    assert sorted(line["largest_difference"]) == sorted(attention_alone.OUTPUTS) and all(d == 0.0 for d in line["largest_difference"].values())
    assert all(line["scale"][name] > 0.0 for name in attention_alone.OUTPUTS)
