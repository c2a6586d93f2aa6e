"""The two kernel pairs of a Mamba-2 mixer's float32 chains (ops/mamba_mix.py:
``mamba_conv`` / ``mamba_conv_grad`` and ``mamba_gate_norm`` /
``mamba_gate_norm_grad``) under the Pallas interpreter against the plain
``jax.numpy`` formula that ``trunk._mamba`` was until PR 44 (a padded
convolution, ``jax.nn.silu``, three slices; ``y * silu(z)`` and
``_rms_norm`` over a ``[tokens, groups, width]`` view): values, every
gradient, and what may not reach what (another board; a later square).
Since PR 55 also the delta mixers' pair (``head_norm_gate`` /
``head_norm_gate_grad``) against the two lines of ``trunk._gdn`` and
``trunk._kda`` it replaced (``_rms_norm`` over a ``[tokens, heads, d]``
view times ``silu(z)`` or ``sigmoid(z)``), alone and inside both mixers,
and ``tools/scope_ops.py``'s reduction on a trace of three operations."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.trunk import _rms_norm
from fishnet_tpu.ops import mamba_mix
from fishnet_tpu.ops.mamba_mix import head_norm_gate, mamba_conv, mamba_gate_norm
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import GDN, KDA

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


# -- the delta mixers' gated head norm (PR 55) ---------------------------------------------------------------------------------------

#: heads, a head's columns, tokens: one head of one whole lane tile over three grid steps of 64 rows; three heads over five steps of 16
#: rows; the seventh trunk's 32 heads over nine steps of 16 rows. No token count is a multiple of the row block (128).
HEAD_CASES = {"one_head_a_lane_tile": (1, 128, 192), "three_heads": (3, 32, 80), "thirty_two_heads": (32, 16, 144)}
GATES = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


def plain_head_norm_gate(o, z, gain, gate, eps, interpret=None):
    """The two lines ``trunk._gdn`` (``silu``) and ``trunk._kda`` (``sigmoid``) held until PR 55, and ``_matmul``'s rounding of their result."""
    tokens, inner = o.shape
    normed = _rms_norm(o.reshape(tokens, -1, gain.shape[0]), gain, eps)  # a head's own norm, one gain for all heads
    return (normed * GATES[gate](z.reshape(normed.shape))).reshape(tokens, inner).astype(jnp.bfloat16)


def head_operands(seed: int, heads: int, d: int, tokens: int):
    rng = np.random.default_rng([seed, heads, d])
    normal = lambda *shape, scale=1.0: jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)
    return normal(tokens, heads * d).astype(jnp.bfloat16), normal(tokens, heads * d, scale=2.0), 1.0 + normal(d, scale=0.3)


@pytest.mark.parametrize("heads,d,tokens", HEAD_CASES.values(), ids=HEAD_CASES)
@pytest.mark.parametrize("gate", GATES)
def test_the_gated_head_norm_writes_what_the_two_plain_lines_round(gate, heads, d, tokens):
    o, z, gain = head_operands(1, heads, d, tokens)
    got, want = head_norm_gate(o, z, gain, gate, 1e-6, True), plain_head_norm_gate(o, z, gain, gate, 1e-6)
    assert got.shape == (tokens, heads * d) and got.dtype == jnp.bfloat16
    assert ulps_apart(got, want) <= 1.0 and rel(got, want) < 1e-3, (ulps_apart(got, want), rel(got, want))
    # a head's mean square is its own: scaling the LAST head's o leaves every other head's columns as they were, and its own but for eps
    moved = head_norm_gate(o.at[:, -d:].multiply(4.0), z, gain, gate, 1e-6, True)
    assert np.array_equal(np.asarray(moved[:, :-d], np.float32), np.asarray(got[:, :-d], np.float32))
    assert rel(moved[:, -d:], got[:, -d:]) < 1e-4
    assert not np.array_equal(np.asarray(head_norm_gate(o, -z, gain, gate, 1e-6, True), np.float32), np.asarray(got, np.float32))


@pytest.mark.parametrize("heads,d,tokens", HEAD_CASES.values(), ids=HEAD_CASES)
@pytest.mark.parametrize("gate", GATES)
def test_every_gradient_of_the_gated_head_norm_matches_the_plain_lines(gate, heads, d, tokens):
    o, z, gain = head_operands(2, heads, d, tokens)
    weigh = jnp.asarray(np.random.default_rng(3).standard_normal((tokens, heads * d)), jnp.float32)
    loss = lambda norm: lambda *a: jnp.sum(norm(*a, gate, 1e-6, True).astype(jnp.float32) * weigh)
    got = jax.grad(loss(head_norm_gate), argnums=(0, 1, 2))(o, z, gain)
    want = jax.grad(loss(plain_head_norm_gate), argnums=(0, 1, 2))(o, z, gain)
    assert got[0].dtype == want[0].dtype == jnp.bfloat16 and got[1].dtype == got[2].dtype == jnp.float32
    assert ulps_apart(got[0], want[0]) <= 1.0 and rel(got[0], want[0]) < 2e-3, (ulps_apart(got[0], want[0]), rel(got[0], want[0]))  # o's: bfloat16 both
    # z's cotangent is rounded to bfloat16 once, in the kernel: what the two transposes of the product that made z round it to anyway
    assert got[1].shape == want[1].shape and np.array_equal(np.asarray(got[1]), np.asarray(got[1].astype(jnp.bfloat16), np.float32))
    assert ulps_apart(got[1], want[1].astype(jnp.bfloat16)) <= 1.0 and rel(got[1], want[1]) < 3e-3, (ulps_apart(got[1], want[1].astype(jnp.bfloat16)), rel(got[1], want[1]))
    assert got[2].shape == want[2].shape == (d,) and rel(got[2], want[2]) < 2e-5, rel(got[2], want[2])  # the one gain: summed over rows, steps AND heads


def test_the_gated_head_norm_refuses_what_it_cannot_run():
    o, z, gain = head_operands(1, 3, 32, 64)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        head_norm_gate(o, z, gain, "silu", 1e-6, False)
    with pytest.raises(ValueError, match="head_norm_gate: o"):
        head_norm_gate(o, z, gain, "tanh", 1e-6, True)  # a gate it has no formula for: never another path
    with pytest.raises(ValueError, match="head_norm_gate: o"):
        head_norm_gate(o, z, gain[:20], "silu", 1e-6, True)  # 96 columns are no whole heads of 20
    with pytest.raises(ValueError, match="head_norm_gate: y"):
        head_norm_gate(o, z[:32], gain, "silu", 1e-6, True)


@pytest.mark.parametrize("block", ["gdn", "kda"])
def test_a_delta_mixer_whole_is_the_mixer_under_the_two_plain_lines(block, monkeypatch):
    """``trunk._gdn`` and ``trunk._kda`` as a step runs them (the residual, a cotangent that waits for the result), with the
    kernel pair and with the parent's two XLA lines in its place: the mixer's result and the gradient of every tensor it owns
    and of the stream. What ``z``'s cotangent does not reach is the same to a bfloat16's last place flipped here and there
    (read: equal to five digits); what it reaches (the weights that make z, the layer's norm, the stream) differs by the
    rounding of that cotangent to bfloat16, which on the chip the transposed products do to the plain lines' float32 too and
    XLA:CPU's do not: 2^-9 an element, 0.0034 read on ``kda_ga`` over two seeds, whose sums cancel most."""
    cfg, kind, through_z = (GDN, trunk._gdn, ("gdn_qkvz",)) if block == "gdn" else (KDA, trunk._kda, ("kda_ga", "kda_gb"))
    sublayer = next(s for s in trunk.trunk_plan(cfg) if s.kind == block)
    params = trunk.centred_gains(AzTrainer(cfg).init(3).params, cfg)
    own = {k: v + 0.05 * jax.random.normal(jax.random.PRNGKey(i), v.shape) for i, (k, v) in enumerate(sorted(trunk.sublayer_params(params, sublayer).items()))}
    x = jax.random.normal(jax.random.PRNGKey(7), (3 * 64, cfg.hidden), jnp.float32)

    def run():
        return jax.value_and_grad(lambda x, p: jnp.sum(jnp.square(x + kind(x, p, cfg, sublayer)[0])), argnums=(0, 1))(x, own)

    got_loss, (got_x, got) = run()
    calls = []
    monkeypatch.setattr(trunk, "head_norm_gate", lambda *a: calls.append(a[3]) or plain_head_norm_gate(*a))
    want_loss, (want_x, want) = run()
    assert calls == [{"gdn": "silu", "kda": "sigmoid"}[block]]
    assert abs(float(got_loss) - float(want_loss)) < 1e-6 * float(want_loss)
    assert set(through_z) < set(want) and rel(got_x, want_x) < 2e-3
    for name in want:
        assert float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (8e-3 if name in (*through_z, "attn_norm") else 1e-4), (name, rel(got[name], want[name]))


# -- tools/scope_ops.py: a traced step by operation -----------------------------------------------------------------------------------

def test_scope_ops_joins_a_traced_steps_operations_to_their_scopes_by_name():
    from benchmark import tracelib
    from tools import scope_ops

    text = """HloModule jit_step

ENTRY %main.9 (a: bf16[64,256]) -> f32[64,2,128] {
  %a = bf16[64,256]{1,0} parameter(0)
  %copy.7 = f32[64,2,128]{2,1,0} copy(%a)
  %head_norm_gate.1 = bf16[64,256]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(forward)/layer00.gdn/jit(_head_norm_call)/head_norm_gate/pallas_call"}
  ROOT %fusion.3 = f32[64,2,128]{2,1,0} fusion(%a), kind=kLoop, calls=%fused.3, metadata={op_name="jit(step)/transpose(jvp(forward))/layer00.gdn/mul"}
}
"""
    op = lambda name, shape, start, dur: tracelib.Op(name, shape, start, dur, sorted(tracelib.hlo_kinds(text).get(name, ())))
    steps = [("jit_step", 0.0, 1000.0), ("jit_step", 2000.0, 1000.0)]
    ops = [op("copy.7", "f32[64,2,128]", at, 300.0) for at in (0.0, 2000.0)] + [op("head_norm_gate.1", "bf16[64,256]", at, 200.0) for at in (300.0, 2300.0)]
    ops += [op("fusion.3", "f32[64,2,128]", 500.0, 100.0)]  # in the first step alone
    step_ms, found = scope_ops.rows(tracelib.Trace(sorted(ops, key=lambda o: o.start_ns), steps, []), text, r"layer00\.gdn|unscoped")
    assert step_ms == pytest.approx((2 * 300 + 2 * 200 + 100) / 2 / 1e6)
    assert [(round(ms * 1e6), line) for ms, line in found] == [
        (300, "unscoped (no_scope) copy.7 f32[64,2,128] copy -"),
        (200, "forward jvp(forward)/layer00.gdn head_norm_gate.1 bf16[64,256] custom-call pallas_call"),
        (50, "backward transpose(jvp(forward))/layer00.gdn fusion.3 f32[64,2,128] - mul")]
    assert [line for _, line in scope_ops.rows(tracelib.Trace(ops, steps, []), text, "backward")[1]] == ["backward transpose(jvp(forward))/layer00.gdn fusion.3 f32[64,2,128] - mul"]
