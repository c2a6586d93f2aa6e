"""The mix of compressed convolutional attention (ops/cca_mix.py: ``cca_mix``
and its gradient ``cca_mix_grad``) under the Pallas interpreter against a
float64 sum over taps written out square by square: forward, every
gradient, and what may not reach what (another board; a later square)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.ops.cca_mix import cca_mix

SQUARES = 64
#: query heads, key heads, head width, the two kernel sizes, boards: the published 8 / 2 / 128 / (2, 2) on two boards among them.
CASES = [(8, 2, 128, (2, 2), 2), (4, 1, 8, (2, 2), 8), (4, 2, 16, (3, 2), 4), (2, 2, 8, (1, 3), 5), (6, 2, 8, (4, 1), 3)]
IDS = ["published", "one_key_head", "three_taps", "no_conv0_mixing", "no_conv1_mixing"]


def operands(seed: int, heads: int, kv_heads: int, hd: int, taps, boards: int):
    rng = np.random.default_rng(seed)
    columns, groups = (heads + kv_heads) * hd, heads + kv_heads
    normal = lambda *shape, scale=1.0: (scale * rng.standard_normal(shape)).astype(np.float32)
    return (normal(boards, SQUARES, columns), normal(columns, taps[0], scale=0.7), normal(columns, scale=0.1),
            normal(groups, taps[1], hd, hd, scale=1.0 / np.sqrt(hd)), normal(columns, scale=0.1))


def plain(x, w0, b0, w1, b1, heads: int, kv_heads: int):
    """The formula in float64, a tap and a square at a time (conv1's operands rounded to bfloat16, as the stated precision has them)."""
    x, w0, b0, b1 = (np.asarray(y, np.float64) for y in (x, w0, b0, b1))
    boards, _, columns = x.shape
    hd, group = columns // (heads + kv_heads), heads // kv_heads
    rounded = lambda y: np.asarray(jnp.asarray(y, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32), np.float64)
    w1 = rounded(w1)
    a = np.zeros_like(x) + b0
    for t in range(SQUARES):
        for k in range(w0.shape[1]):
            if t - (w0.shape[1] - 1) + k >= 0:
                a[:, t] += w0[:, k] * x[:, t - (w0.shape[1] - 1) + k]
    a, c = rounded(a), np.zeros_like(x) + b1
    for t in range(SQUARES):
        for k in range(w1.shape[1]):
            if t - (w1.shape[1] - 1) + k >= 0:
                for g in range(heads + kv_heads):
                    c[:, t, g * hd:(g + 1) * hd] += a[:, t - (w1.shape[1] - 1) + k, g * hd:(g + 1) * hd] @ w1[g, k]
    by_head = x.reshape(boards, SQUARES, heads + kv_heads, hd)
    xq, xk = by_head[:, :, :heads], by_head[:, :, heads:]
    m_q = (xq + np.repeat(xk, group, axis=2)) / 2
    m_k = (xq.reshape(boards, SQUARES, kv_heads, group, hd).mean(axis=3) + xk) / 2
    q = c[..., :heads * hd] + m_q.reshape(boards, SQUARES, -1)
    k = c[..., heads * hd:] + m_k.reshape(boards, SQUARES, -1)
    return q, k, np.array([np.sum((c - x) ** 2), np.sum(x ** 2)])


def smooth(x, w0, b0, w1, b1, heads, kv_heads):
    """The same formula in float32 ``jax.numpy`` at ``highest``, for the gradients (no rounding: the tolerance carries it)."""
    boards, _, columns = x.shape
    hd, group = columns // (heads + kv_heads), heads // kv_heads
    earlier = lambda u, by: u if by == 0 else jnp.pad(u[:, :-by], ((0, 0), (by, 0), (0, 0)))
    a = b0 + sum(w0[:, k] * earlier(x, w0.shape[1] - 1 - k) for k in range(w0.shape[1]))
    by_head = lambda u: u.reshape(boards, SQUARES, heads + kv_heads, hd)
    c = b1 + sum(jnp.einsum("bsgi,gio->bsgo", by_head(earlier(a, w1.shape[1] - 1 - k)), w1[:, k], precision="highest")
                 for k in range(w1.shape[1])).reshape(boards, SQUARES, columns)
    xq, xk = by_head(x)[:, :, :heads], by_head(x)[:, :, heads:]
    m_q = (xq + jnp.repeat(xk, group, axis=2)) / 2
    m_k = (xq.reshape(boards, SQUARES, kv_heads, group, hd).mean(axis=3) + xk) / 2
    return c[..., :heads * hd] + m_q.reshape(boards, SQUARES, -1), c[..., heads * hd:] + m_k.reshape(boards, SQUARES, -1)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("heads,kv_heads,hd,taps,boards", CASES, ids=IDS)
def test_the_mix_matches_a_float64_sum_over_taps(heads, kv_heads, hd, taps, boards):
    args = operands(1, heads, kv_heads, hd, taps, boards)
    q, k, sums = cca_mix(*(jnp.asarray(a) for a in args), heads, kv_heads, True)
    want_q, want_k, want_sums = plain(*args, heads, kv_heads)
    assert q.shape == want_q.shape and k.shape == want_k.shape and q.dtype == k.dtype == jnp.float32
    assert rel(q, want_q) < 2e-5 and rel(k, want_k) < 2e-5, (rel(q, want_q), rel(k, want_k))
    assert rel(sums, want_sums) < 1e-4, (sums, want_sums)


@pytest.mark.parametrize("heads,kv_heads,hd,taps,boards", CASES, ids=IDS)
def test_every_gradient_of_the_mix_matches_the_formulas(heads, kv_heads, hd, taps, boards):
    args = tuple(jnp.asarray(a) for a in operands(2, heads, kv_heads, hd, taps, boards))
    rng = np.random.default_rng(3)
    weigh = [jnp.asarray(rng.standard_normal((boards, SQUARES, n * hd)), jnp.float32) for n in (heads, kv_heads)]

    def loss(mix):
        def fn(*a):
            q, k = mix(*a)[:2]
            return jnp.sum(q * weigh[0]) + jnp.sum(k * weigh[1])
        return fn

    got = jax.grad(loss(lambda *a: cca_mix(*a, heads, kv_heads, True)), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(lambda *a: smooth(*a, heads, kv_heads)), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("x", "conv0_w", "conv0_b", "conv1_w", "conv1_b"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert rel(g, w) < 0.01, (name, rel(g, w))  # conv1's three products take bfloat16 operands: 0.002-0.004 read


def test_the_sums_carry_no_gradient():
    args = tuple(jnp.asarray(a) for a in operands(4, 4, 1, 8, (2, 2), 2))
    grads = jax.grad(lambda *a: jnp.sum(cca_mix(*a, 4, 1, True)[2]), argnums=(0, 1, 3))(*args)
    assert all(not np.any(np.asarray(g)) for g in grads)


@pytest.mark.parametrize("heads,kv_heads,hd,taps,boards", CASES[1:3], ids=IDS[1:3])
def test_a_board_sees_no_other_board_and_a_square_no_later_square(heads, kv_heads, hd, taps, boards):
    args = operands(5, heads, kv_heads, hd, taps, boards)
    mix = lambda x: [np.asarray(y) for y in cca_mix(jnp.asarray(x), *(jnp.asarray(a) for a in args[1:]), heads, kv_heads, True)[:2]]
    base = mix(args[0])
    other = args[0].copy()
    other[1] += 1.0  # another board's input: board 0 and board 2 do not move
    for got, want in zip(mix(other), base):
        assert np.array_equal(np.delete(got, 1, axis=0), np.delete(want, 1, axis=0)) and not np.array_equal(got[1], want[1])
    later = args[0].copy()
    later[:, 40:] += 1.0  # squares 40 and on: squares 0-39 do not move, square 40 does
    for got, want in zip(mix(later), base):
        assert np.array_equal(got[:, :40], want[:, :40]) and not np.array_equal(got[:, 40], want[:, 40])
