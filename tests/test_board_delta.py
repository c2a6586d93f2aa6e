"""The delta rule's kernel pair (``ops/board_delta.py``) under the Pallas
interpreter against the literal 64-step recurrence in float64, forward and
every gradient; a board's state starts from zero and no square sees a later
one; decays so fast that ``exp(-c)`` would overflow float32 stay finite and
right; and two mutations of the recurrence, each a plausible misreading of
the layer, read far outside the tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.ops.board_delta import L2_EPS, board_delta

SQUARES = 64
#: (boards, heads, d): the published head on two boards, narrow heads on a block of boards and a remainder, one head.
CASES = {"published": (2, 2, 128), "narrow": (9, 4, 16), "one_head": (3, 1, 32)}
NAMES = ("q", "k", "v", "g", "beta")


def operands(case, seed=0, fastest=1.6):
    """q, k, v as silu of a convolution leaves them (any sign, unnormed); ``g`` in the layer's own range at its start:
    ``-exp(A_log) softplus(.)`` with rates in [1, 16] and steps log-uniform in [0.001, 0.1] (``fastest`` scales the range)."""
    boards, heads, d = CASES[case]
    rng = np.random.default_rng([seed, heads, d])
    bf16 = lambda y: jnp.asarray(y, jnp.bfloat16)
    inner = (boards, SQUARES, heads * d)
    rate = np.repeat(rng.uniform(1.0, 16.0, heads), d)
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), inner))
    return {
        "q": bf16(rng.standard_normal(inner)), "k": bf16(rng.standard_normal(inner)), "v": bf16(rng.standard_normal(inner)),
        "g": jnp.asarray(-rate * step * (fastest / 1.6), jnp.float32),
        "beta": jnp.asarray(rng.uniform(0.05, 0.95, (boards, SQUARES, heads)), jnp.float32),
    }


def recurrence(q, k, v, g, beta, decay_first=True, unit_beta=False):
    """The literal recurrence, a state [d, d] a head, in the dtype it is given: ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1}
    + beta k v^T``, ``o_t = S_t^T q_t``. The two mutations: the decay applied AFTER the rank-one correction, and beta 1."""
    boards, _, heads = beta.shape
    q, k, v, g = (y.reshape(boards, SQUARES, heads, -1) for y in (q, k, v, g))
    d = q.shape[-1]
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / np.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    if unit_beta:
        beta = jnp.ones_like(beta)

    def square(state, now):
        q_t, k_t, v_t, g_t, b_t = now  # [boards, heads, d] x 4, [boards, heads]
        alpha = jnp.exp(g_t)[..., :, None]
        if decay_first:
            state = alpha * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhcv,bhc->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        if not decay_first:
            state = alpha * state
        return state, jnp.einsum("bhcv,bhc->bhv", state, q_t)

    start = jnp.zeros((boards, heads, d, d), q.dtype)
    _, o = jax.lax.scan(square, start, tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(boards, SQUARES, -1)


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - np.asarray(want, np.float64)) / np.linalg.norm(np.asarray(want, np.float64)))


def wanted(ops, weight, **mutation):
    with jax.enable_x64(True):
        wide = {name: jnp.asarray(np.asarray(value, np.float64)) for name, value in ops.items()}
        fn = lambda o: recurrence(*(o[name] for name in NAMES), **mutation)
        want = np.asarray(fn(wide))
        grads = jax.grad(lambda o: jnp.sum(fn(o) * jnp.asarray(weight, jnp.float64)))(wide)
        return want, {name: np.asarray(value) for name, value in grads.items()}


def kernel(ops, weight):
    got = board_delta(*(ops[name] for name in NAMES), True)
    grads = jax.grad(lambda o: jnp.sum(board_delta(*(o[name] for name in NAMES), True).astype(jnp.float32) * weight))(ops)
    return got, grads


# Readings (CPU interpreter, seeds 0-1): forward 0.0033-0.0040 of the result's norm; dq, dk, dv 0.0033-0.0041 (bfloat16 cotangents),
# dg 0.0075-0.0094, dbeta 0.0028-0.0034. The decay after the correction reads 0.155 forward (dg 0.42), beta fixed at 1 0.61.
TOL = 0.02


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_pair_against_the_literal_recurrence(case):
    ops = operands(case)
    weight = np.random.default_rng(7).standard_normal(ops["q"].shape).astype(np.float32)
    want, want_grads = wanted(ops, weight)
    got, grads = kernel(ops, weight)
    assert got.dtype == jnp.bfloat16 and got.shape == ops["q"].shape
    assert rel(got, want) < TOL, rel(got, want)
    for name in NAMES:
        assert grads[name].shape == ops[name].shape and grads[name].dtype == ops[name].dtype, name
        assert rel(grads[name], want_grads[name]) < TOL, (name, rel(grads[name], want_grads[name]))


@pytest.mark.parametrize("fastest", [12.0, 40.0])
def test_a_channel_that_forgets_fast_overflows_nothing(fastest):
    """A square's log-decay down to -12 and -40: ``exp(-c)`` passes float32's largest number within 8 and within 3
    squares, and every exponent the kernels take is <= 0."""
    ops = operands("one_head", seed=3, fastest=fastest)
    weight = np.random.default_rng(8).standard_normal(ops["q"].shape).astype(np.float32)
    want, want_grads = wanted(ops, weight)
    got, grads = kernel(ops, weight)
    assert np.isfinite(np.asarray(got, np.float32)).all() and rel(got, want) < TOL
    for name in NAMES:
        assert np.isfinite(np.asarray(grads[name], np.float32)).all(), name
        assert rel(grads[name], want_grads[name]) < TOL, (name, rel(grads[name], want_grads[name]))


def test_a_board_starts_from_zero_and_no_square_sees_a_later_one():
    ops = operands("narrow", seed=1)
    first = np.asarray(board_delta(*(ops[name] for name in NAMES), True), np.float32)
    other = {name: value.at[1:].set(jnp.flip(value[1:], axis=0)) for name, value in ops.items()}  # other boards round board 0
    assert np.array_equal(np.asarray(board_delta(*(other[name] for name in NAMES), True), np.float32)[0], first[0])
    later = {name: value.at[:, 40:].set((value[:, 40:] * 0.5).astype(value.dtype)) for name, value in ops.items()}
    changed = np.asarray(board_delta(*(later[name] for name in NAMES), True), np.float32)
    assert np.array_equal(changed[:, :40], first[:, :40]) and not np.array_equal(changed[:, 40:], first[:, 40:])
    # square 0 of every board reads a zero state: o_0 = beta_0 (k^_0 . q^_0) v_0
    boards, heads, d = CASES["narrow"]
    q, k, v = (np.asarray(ops[name], np.float64)[:, 0].reshape(boards, heads, d) for name in ("q", "k", "v"))
    unit = lambda y: y / np.sqrt(np.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
    want = np.asarray(ops["beta"], np.float64)[:, 0, :, None] * np.sum(unit(q) * unit(k), axis=-1, keepdims=True) / np.sqrt(d) * v
    assert rel(first[:, 0], want.reshape(boards, -1)) < TOL


@pytest.mark.parametrize("mutation", [dict(decay_first=False), dict(unit_beta=True)], ids=["decay_after_the_correction", "beta_fixed_at_1"])
def test_a_misread_recurrence_is_not_the_kernels(mutation):
    ops = operands("narrow", seed=2)
    weight = np.random.default_rng(9).standard_normal(ops["q"].shape).astype(np.float32)
    want, want_grads = wanted(ops, weight, **mutation)
    got, grads = kernel(ops, weight)
    assert rel(got, want) > 5 * TOL, rel(got, want)
    assert min(rel(grads[name], want_grads[name]) for name in NAMES if np.any(want_grads[name])) > 5 * TOL  # a fixed beta has no gradient


def test_shapes_that_are_not_heads_of_a_board_are_refused():
    ops = operands("one_head")
    with pytest.raises(ValueError, match="board_delta"):
        board_delta(ops["q"][:, :32], ops["k"], ops["v"], ops["g"], ops["beta"], True)
    with pytest.raises(ValueError, match="board_delta"):
        board_delta(ops["q"], ops["k"], ops["v"], ops["g"], jnp.ones((3, SQUARES, 5)), True)
    with pytest.raises(ValueError, match="lane"):
        board_delta(ops["q"], ops["k"], ops["v"], ops["g"], ops["beta"], False)
