"""The state-space scan's kernel pair (``ops/board_scan.py``) under the
Pallas interpreter against a float64 sequential recurrence, forward and
every gradient, and the two things the dual form has to keep: boards do
not mix, and no square sees a later one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.ops.board_scan import board_scan

SQUARES = 64
#: (boards, heads, groups, P, N): the published mixer on two boards, a group of one tile, heads as wide as a tile, one group.
CASES = {"published": (2, 64, 8, 64, 128), "one_tile": (3, 4, 2, 8, 16), "wide_heads": (2, 2, 1, 128, 16), "one_group": (16, 8, 1, 16, 8)}
NAMES = ("x", "b", "c", "step", "a", "skip")


def operands(case, seed=0):
    boards, heads, groups, p, n = CASES[case]
    rng = np.random.default_rng([seed, heads, p])
    bf16 = lambda y: jnp.asarray(y, jnp.bfloat16)
    return {
        "x": bf16(rng.standard_normal((boards, SQUARES, heads * p))),
        "b": bf16(rng.standard_normal((boards, SQUARES, groups * n)) / np.sqrt(n)),
        "c": bf16(rng.standard_normal((boards, SQUARES, groups * n))),
        # Mamba-2's ranges: steps log-uniform in [0.001, 0.1], rates in [-16, -1]
        "step": jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (boards, SQUARES, heads))), jnp.float32),
        "a": jnp.asarray(-rng.uniform(1.0, 16.0, heads), jnp.float32),
        "skip": jnp.asarray(rng.standard_normal(heads), jnp.float32),
    }, groups


def recurrence(x, b, c, step, a, skip, groups):
    """The sequential recurrence, a state [P, N] a head, float64."""
    boards, _, heads = step.shape
    x = x.reshape(boards, SQUARES, heads, -1)
    b, c = (jnp.repeat(y.reshape(boards, SQUARES, groups, -1), heads // groups, axis=2) for y in (b, c))

    def square(state, now):
        x_t, b_t, c_t, d_t = now  # [boards, heads, P], [boards, heads, N] x 2, [boards, heads]
        state = jnp.exp(d_t * a)[..., None, None] * state + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + skip[:, None] * x_t

    start = jnp.zeros((boards, heads, x.shape[-1], b.shape[-1]), x.dtype)
    _, y = jax.lax.scan(square, start, tuple(jnp.moveaxis(y, 1, 0) for y in (x, b, c, step)))
    return jnp.moveaxis(y, 0, 1).reshape(boards, SQUARES, -1)


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - np.asarray(want, np.float64)) / np.linalg.norm(np.asarray(want, np.float64)))


# Readings (CPU interpreter, seeds 0-2): forward 0.002-0.003 of the result's norm; gradients 0.003-0.006 (x, b, c: bfloat16
# cotangents), step 0.004-0.008, a and skip under 0.004. A kernel without the decay reads 0.3 and more.
TOL = 0.02


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_pair_against_the_sequential_recurrence(case):
    ops, groups = operands(case)
    with jax.enable_x64(True):
        wide = {k: jnp.asarray(np.asarray(v, np.float64)) for k, v in ops.items()}
        weight = jnp.asarray(np.random.default_rng(7).standard_normal(ops["x"].shape))
        want = recurrence(*(wide[k] for k in NAMES), groups)
        want_grads = jax.grad(lambda o: jnp.sum(recurrence(*(o[k] for k in NAMES), groups) * weight))(wide)
        want, want_grads, weight = np.asarray(want), {k: np.asarray(v) for k, v in want_grads.items()}, np.asarray(weight, np.float32)
    got = board_scan(*(ops[k] for k in NAMES), groups, True)
    assert got.dtype == jnp.bfloat16 and got.shape == ops["x"].shape
    assert rel(got, want) < TOL
    grads = jax.grad(lambda o: jnp.sum(board_scan(*(o[k] for k in NAMES), groups, True).astype(jnp.float32) * weight))(ops)
    for name in NAMES:
        assert grads[name].shape == ops[name].shape and grads[name].dtype == ops[name].dtype, name
        assert rel(grads[name], want_grads[name]) < TOL, (name, rel(grads[name], want_grads[name]))


def test_a_board_does_not_see_another_and_no_square_a_later_one():
    ops, groups = operands("one_tile", seed=1)
    first = np.asarray(board_scan(*(ops[k] for k in NAMES), groups, True), np.float32)
    other = dict(ops)
    for name in ("x", "b", "c", "step"):
        other[name] = ops[name].at[1].set(ops[name][1] * 2 + 1)  # another board's inputs
        other[name] = other[name].at[0, 40:].set(other[name][0, 40:] * 3 - 1)  # and board 0's later squares
    second = np.asarray(board_scan(*(other[k] for k in NAMES), groups, True), np.float32)
    assert np.array_equal(first[0, :40], second[0, :40]) and np.array_equal(first[2], second[2])
    assert not np.array_equal(first[0, 40:], second[0, 40:]) and not np.array_equal(first[1], second[1])


def test_shapes_that_are_not_a_groups_heads_are_refused():
    ops, _ = operands("one_tile")
    with pytest.raises(ValueError, match="4 heads in 3 groups"):
        board_scan(*(ops[k] for k in NAMES), 3, True)
