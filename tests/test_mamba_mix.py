"""The two kernel pairs of a Mamba-2 mixer's float32 chains (ops/mamba_mix.py:
``mamba_conv`` / ``mamba_conv_grad`` and ``mamba_gate_norm`` /
``mamba_gate_norm_grad``) under the Pallas interpreter against the plain
``jax.numpy`` formula that ``trunk._mamba`` was until PR 44 (a padded
convolution, ``jax.nn.silu``, three slices; ``y * silu(z)`` and
``_rms_norm`` over a ``[tokens, groups, width]`` view): values, every
gradient, and what may not reach what (another board; a later square)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models.trunk import _rms_norm
from fishnet_tpu.ops import mamba_mix
from fishnet_tpu.ops.mamba_mix import mamba_conv, mamba_gate_norm

SQUARES = 64
#: boards, the widths of x, B and C, taps: the tests' tiny mixer (one block of three boards), whole lane tiles over three grid
#: steps of four boards (x two tiles wide, so a result's second tile is read off its first), and a ragged last tile.
CONV_CASES = {"tiny": (3, (32, 16, 16), 4), "lane_tiles_three_steps": (12, (256, 128, 128), 4), "ragged_two_taps": (5, (160, 24, 24), 2)}
#: tokens, inner, groups: the tiny mixer's, four lane tiles a group (the published 512) over three grid steps, one group.
NORM_CASES = {"tiny": (192, 32, 2), "four_tiles_a_group_three_steps": (384, 1024, 2), "one_group": (64, 48, 1)}


def board_conv(x, w, b):
    """``trunk._board_conv`` as PR 41 wrote it: ``y[t] = b + sum_k w[:, k] x[t - (taps - 1) + k]``, nothing before square 0."""
    taps = w.shape[-1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(padded[:, k:k + SQUARES] * w[:, k] for k in range(taps))


def plain_conv(u, w, b, widths):
    out = jax.nn.silu(board_conv(u, w, b))
    edges = np.cumsum((0,) + tuple(widths))
    return tuple(out[..., lo:hi].astype(jnp.bfloat16) for lo, hi in zip(edges[:-1], edges[1:]))


def plain_gate_norm(y, z, gain, groups, eps):
    tokens, inner = y.shape
    gated = y.astype(jnp.float32) * jax.nn.silu(z)
    return _rms_norm(gated.reshape(-1, groups, inner // groups), gain.reshape(groups, -1), eps).reshape(tokens, inner).astype(jnp.bfloat16)


def conv_operands(seed: int, boards: int, widths, taps: int):
    rng = np.random.default_rng(seed)
    columns = sum(widths)
    normal = lambda *shape, scale=1.0: jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)
    return normal(boards, SQUARES, columns, scale=2.0), normal(columns, taps, scale=0.5), normal(columns, scale=0.3)


def norm_operands(seed: int, tokens: int, inner: int):
    rng = np.random.default_rng(seed)
    normal = lambda *shape, scale=1.0: jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)
    return normal(tokens, inner).astype(jnp.bfloat16), normal(tokens, inner, scale=2.0), 1.0 + normal(inner, scale=0.3)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def ulps_apart(got, want) -> float:
    """The largest difference of two bfloat16 arrays in units of the larger one's last place (8 bits: 2^-7 of its power of
    two); a value under 1/64 counts as 1/64, since next to a zero of silu the float32 sums' own last places are many of its."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    place = 2.0 ** (np.floor(np.log2(np.maximum(np.maximum(np.abs(got), np.abs(want)), 1 / 64))) - 7)
    return float(np.max(np.abs(got - want) / place))


@pytest.mark.parametrize("boards,widths,taps", CONV_CASES.values(), ids=CONV_CASES)
def test_the_convolution_writes_the_scans_operands_as_the_plain_formula_rounds_them(boards, widths, taps):
    u, w, b = conv_operands(1, boards, widths, taps)
    got, want = mamba_conv(u, w, b, widths, True), plain_conv(u, w, b, widths)
    assert len(got) == len(widths)
    for g, f, width in zip(got, want, widths):
        assert g.shape == (boards, SQUARES, width) and g.dtype == jnp.bfloat16
        # the same float32 arithmetic but for the order of a sum and the form of the sigmoid: the last place of a bfloat16 at most
        assert ulps_apart(g, f) <= 1.0 and rel(g, f) < 1e-3, (ulps_apart(g, f), rel(g, f))
    # a board's first three squares see nothing before square 0: the bias and the taps that reach
    first = jnp.concatenate(got, axis=-1)[:, :taps - 1].astype(jnp.float32)
    alone = jax.nn.silu(b + sum(w[:, taps - 1 - by] * jnp.pad(u[:, :taps - 1], ((0, 0), (by, 0), (0, 0)))[:, :taps - 1] for by in range(taps)))
    assert rel(first, alone) < 4e-3, rel(first, alone)


@pytest.mark.parametrize("boards,widths,taps", CONV_CASES.values(), ids=CONV_CASES)
def test_every_gradient_of_the_convolution_matches_the_plain_formulas(boards, widths, taps):
    u, w, b = conv_operands(2, boards, widths, taps)
    rng = np.random.default_rng(3)
    weigh = [jnp.asarray(rng.standard_normal((boards, SQUARES, width)), jnp.float32) for width in widths]
    loss = lambda conv: lambda *a: sum(jnp.sum(out.astype(jnp.float32) * m) for out, m in zip(conv(*a), weigh))
    got = jax.grad(loss(lambda *a: mamba_conv(*a, widths, True)), argnums=(0, 1, 2))(u, w, b)
    want = jax.grad(loss(lambda *a: plain_conv(*a, widths)), argnums=(0, 1, 2))(u, w, b)
    for name, g, f in zip(("u", "conv_w", "conv_b"), got, want):
        assert g.shape == f.shape and g.dtype == f.dtype == jnp.float32, name
    # u's cotangent is rounded to bfloat16 once, in the kernel: what the x B C product's two transposes round it to anyway
    assert np.array_equal(np.asarray(got[0]), np.asarray(got[0].astype(jnp.bfloat16), np.float32))
    assert ulps_apart(got[0], want[0].astype(jnp.bfloat16)) <= 1.0 and rel(got[0], want[0]) < 3e-3, (ulps_apart(got[0], want[0].astype(jnp.bfloat16)), rel(got[0], want[0]))
    for name, g, f in zip(("conv_w", "conv_b"), got[1:], want[1:]):
        assert rel(g, f) < 2e-5, (name, rel(g, f))  # both round the cotangents to bfloat16 first and are float32 after: 1e-7 to 3e-6 read


@pytest.mark.parametrize("boards,widths,taps", [CONV_CASES["tiny"], CONV_CASES["lane_tiles_three_steps"]], ids=["tiny", "lane_tiles_three_steps"])
def test_a_board_sees_no_other_board_and_a_square_no_later_square(boards, widths, taps):
    u, w, b = conv_operands(5, boards, widths, taps)
    conv = lambda x: np.asarray(jnp.concatenate(mamba_conv(jnp.asarray(x), w, b, widths, True), axis=-1).astype(jnp.float32))
    base = conv(u)
    other = np.array(u)
    other[1] += 1.0  # the second board of the first block: its neighbours in the block do not move
    got = conv(other)
    assert np.array_equal(np.delete(got, 1, axis=0), np.delete(base, 1, axis=0)) and not np.array_equal(got[1], base[1])
    later = np.array(u)
    later[:, 40:] += 1.0  # squares 40 and on: squares 0-39 do not move, square 40 does
    got = conv(later)
    assert np.array_equal(got[:, :40], base[:, :40]) and not np.array_equal(got[:, 40], base[:, 40])
    # and the gradient: a cotangent on one board's squares 40 and on reaches that board alone, and squares 40 - (taps - 1) and on
    weigh = np.zeros((boards, SQUARES, sum(widths)), np.float32)
    weigh[1, 40:] = 1.0
    du = np.asarray(jax.grad(lambda x: jnp.sum(jnp.concatenate(mamba_conv(x, w, b, widths, True), axis=-1).astype(jnp.float32) * weigh))(u))
    assert not np.any(np.delete(du, 1, axis=0)) and not np.any(du[1, :40 - (taps - 1)]) and np.all(np.any(du[1, 40 - (taps - 1):] != 0, axis=-1))


@pytest.mark.parametrize("tokens,inner,groups", NORM_CASES.values(), ids=NORM_CASES)
def test_the_gated_grouped_norm_writes_what_the_plain_formula_rounds(tokens, inner, groups):
    y, z, gain = norm_operands(1, tokens, inner)
    got, want = mamba_gate_norm(y, z, gain, groups, 1e-5, True), plain_gate_norm(y, z, gain, groups, 1e-5)
    assert got.shape == (tokens, inner) and got.dtype == jnp.bfloat16
    assert ulps_apart(got, want) <= 1.0 and rel(got, want) < 1e-3, (ulps_apart(got, want), rel(got, want))
    # a group's mean square is its own: scaling one group's gate leaves every other group's columns as they were
    width = inner // groups
    scaled = z.at[:, :width].multiply(3.0)
    moved = mamba_gate_norm(y, scaled, gain, groups, 1e-5, True)
    assert np.array_equal(np.asarray(moved[:, width:], np.float32), np.asarray(got[:, width:], np.float32))
    assert not np.array_equal(np.asarray(moved[:, :width], np.float32), np.asarray(got[:, :width], np.float32))


@pytest.mark.parametrize("tokens,inner,groups", NORM_CASES.values(), ids=NORM_CASES)
def test_every_gradient_of_the_gated_grouped_norm_matches_the_plain_formulas(tokens, inner, groups):
    y, z, gain = norm_operands(2, tokens, inner)
    weigh = jnp.asarray(np.random.default_rng(3).standard_normal((tokens, inner)), jnp.float32)
    loss = lambda norm: lambda *a: jnp.sum(norm(*a).astype(jnp.float32) * weigh)
    got = jax.grad(loss(lambda *a: mamba_gate_norm(*a, groups, 1e-5, True)), argnums=(0, 1, 2))(y, z, gain)
    want = jax.grad(loss(lambda *a: plain_gate_norm(*a, groups, 1e-5)), argnums=(0, 1, 2))(y, z, gain)
    assert got[0].dtype == want[0].dtype == jnp.bfloat16 and got[1].dtype == got[2].dtype == jnp.float32
    assert ulps_apart(got[0], want[0]) <= 1.0 and rel(got[0], want[0]) < 2e-3, (ulps_apart(got[0], want[0]), rel(got[0], want[0]))  # y's: bfloat16 both
    # z's cotangent is rounded to bfloat16 once, in the kernel: what the z product's two transposes round it to anyway
    assert got[1].shape == want[1].shape and np.array_equal(np.asarray(got[1]), np.asarray(got[1].astype(jnp.bfloat16), np.float32))
    assert ulps_apart(got[1], want[1].astype(jnp.bfloat16)) <= 1.0 and rel(got[1], want[1]) < 3e-3, (ulps_apart(got[1], want[1].astype(jnp.bfloat16)), rel(got[1], want[1]))
    assert got[2].shape == want[2].shape and rel(got[2], want[2]) < 2e-5, rel(got[2], want[2])


def test_off_the_interpreter_a_width_that_is_not_whole_lane_tiles_is_refused():
    u, w, b = conv_operands(1, 2, (32, 16, 16), 4)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        mamba_conv(u, w, b, (32, 16, 16), False)
    y, z, gain = norm_operands(1, 64, 32)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        mamba_gate_norm(y, z, gain, 2, 1e-5, False)
    with pytest.raises(ValueError, match="mamba_conv: u"):
        mamba_conv(u, w, b, (32, 16), True)
    with pytest.raises(ValueError, match="mamba_gate_norm: y"):
        mamba_gate_norm(y, z[:32], gain, 2, 1e-5, True)
    assert mamba_mix._CONV_BOARDS * SQUARES % 8 == 0 and mamba_mix._NORM_ROWS % 8 == 0  # whole sublane tiles a block
