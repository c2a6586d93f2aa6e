"""The delta rule's kernel pair (``ops/board_delta.py``) under the Pallas
interpreter against the literal 64-step recurrence in float64, forward and
every gradient; a board's state starts from zero and no square sees a later
one; decays so fast that ``exp(-c)`` would overflow float32 stay finite and
right; and two mutations of the recurrence, each a plausible misreading of
the layer, read far outside the tolerance. The differentiated forward keeps
``T``, ``U`` and the two score tables, bit for bit the chunk form written
once below as the oracle; the gradient kernel reads them and makes no
solve; the primal writes ``o`` alone. The forward solves two chains a
product (two boards of a head, or a key head's two value heads, side by
side on 128 lanes): the packed solve is each chain's own, bit for bit, and
level 0 of the solve, written without its two products, the parent's. The
gradient works two chains a product too (two boards of a head stacked
along the rows, a key head's two value heads packed as the forward packs
them): its five gradients are the single-chain bodies' of the parent commit,
kept below as a test's helper, bit for bit, and its bodies hold half the
products. ``tools/delta_alone.py`` (the pair timed alone) runs at a tiny
shape."""

import contextlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.ops import board_delta as kernels
from fishnet_tpu.ops.board_delta import L2_EPS, board_delta
from tools import delta_alone

SQUARES = 64
#: (boards, heads, d): the published head on two boards (a block of 2), narrow heads on nine blocks of one board, one head on
#: three, and one head on a block of 8 (four loop turns of a pair of boards).
CASES = {"published": (2, 2, 128), "narrow": (9, 4, 16), "one_head": (3, 1, 32), "a_block_of_8": (8, 1, 32)}
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


# -- what the differentiated forward keeps, and what the gradient reads ---------------------------------------------------------------


def parent_solve(mk, v, beta):
    """``_solve`` as the parent commit (be60ccd) had it, copied: ONE chain, six levels of two products each, level 0 among them."""
    t, j, row = kernels._squares()
    a = beta * mk
    tm = (t == j).astype(jnp.float32)
    for p in range(6):  # blocks of 1, 2, .. 32 joined two by two: [[T1, 0], [-T2 A21 T1, T2]]
        ap = jnp.where(kernels._level(p, t, j, row)[0], a, 0.0)
        tm = tm - kernels._exact(kernels._exact(tm, ap), tm)
    return tm, kernels._exact(tm, beta * v)


def chunk_oracle(q, k, v, g, beta):
    """The chunk form of one head of one board as the gradient kernel made it for itself before the forward kept it
    (``_chunk`` of the parent commit, its arithmetic written once more here in plain ``jax.numpy`` over the module's
    level masks and products): float32 ``[64, d]`` and beta ``[64, 1]`` -> T, U, Mk and Mq."""
    f32 = jnp.float32
    t, j, row = kernels._squares()
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    qn, kn = unit(q) * (1.0 / np.sqrt(q.shape[-1])), unit(k)
    c = kernels._exact((t >= j).astype(f32), g)
    mq = jnp.where(t == j, jnp.sum(qn * kn, axis=-1, keepdims=True), 0.0)
    mk = jnp.zeros((SQUARES, SQUARES), f32)
    for p in range(6):
        pairs, upper, lower = kernels._level_decays(p, c, t, j, row)
        kr = kn * lower
        mq = mq + jnp.where(pairs, kernels._dot(qn * upper, kr, kernels._NT), 0.0)
        mk = mk + jnp.where(pairs, kernels._dot(kn * upper, kr, kernels._NT), 0.0)
    return *parent_solve(mk, v, beta), mk, mq


#: LLVM's optimizations off, for a compile whose result is compared bit for bit with another program's (``exactly``).
UNCONTRACTED = {"xla_backend_optimization_level": 0}


def exactly(fn, *args):
    """``fn(*args)`` compiled with LLVM's optimizations off. Two differently fused XLA:CPU programs of the same arithmetic
    differ in the last bit otherwise (a product contracted into the sum after it in one loop and not in the other: the
    parent's ``dg`` against this tree's reads 4e-8 apart at the default level and equal at this one); on the chip Mosaic
    reassociates nothing, and the pair is the parent's bit for bit there (PERF.md, PR 49)."""
    return jax.jit(fn).lower(*args).compile(compiler_options=UNCONTRACTED)(*args)


def oracle_kept(ops):
    """The oracle's tables of every board and head in the kept arrays' layout: ``[T | Mk]`` float32 a head's 128 lanes, ``U``
    float32 in q's columns, ``Mq`` bfloat16 in the lower 64 lanes of a head's 128 (the upper 64 are never written: zero here)."""
    boards, _, heads = ops["beta"].shape
    d = ops["q"].shape[-1] // heads

    def board_and_head(b, h):
        return [ops[name][b, :, h * d:(h + 1) * d].astype(jnp.float32) for name in ("q", "k", "v", "g")] + [ops["beta"][b, :, h:h + 1]]

    one = jax.jit(chunk_oracle).lower(*board_and_head(0, 0)).compile(compiler_options=UNCONTRACTED)
    solve, u, mq = np.zeros((boards, SQUARES, heads * 128), np.float32), np.zeros(ops["q"].shape, np.float32), np.zeros((boards, SQUARES, heads * 128), np.float32)
    for b in range(boards):
        for h in range(heads):
            tm, u[b, :, h * d:(h + 1) * d], mk, mq[b, :, h * 128:h * 128 + SQUARES] = (np.asarray(x, np.float32) for x in one(*board_and_head(b, h)))
            solve[b, :, h * 128:(h + 1) * 128] = np.concatenate([tm, mk], axis=-1)
    return jnp.asarray(solve), jnp.asarray(u), jnp.asarray(mq, jnp.bfloat16)


def written(kept):
    """The lanes of the three kept arrays that the forward writes: all of ``[T | Mk]`` and of ``U``, the lower half of ``Mq``'s tiles."""
    solve, u, mq = (np.asarray(x, np.float32) for x in kept)
    return solve, u, mq.reshape(*mq.shape[:2], -1, 128)[..., :SQUARES]


@pytest.mark.parametrize("case", CASES)
def test_the_differentiated_forward_keeps_the_chunk_form_bit_for_bit(case):
    ops = operands(case, seed=4)
    inputs = tuple(ops[name] for name in NAMES)
    o, (*handed_on, (solve, u, mq)) = exactly(lambda *a: kernels._board_delta_fwd(*a, True), *inputs)
    assert np.array_equal(np.asarray(o, np.float32), np.asarray(exactly(lambda *a: board_delta(*a, True), *inputs), np.float32))
    for name, handed, came in zip(NAMES, handed_on, inputs):  # the five inputs are handed on as they came
        assert handed.dtype == came.dtype and np.array_equal(np.asarray(handed, np.float32), np.asarray(came, np.float32)), name
    boards, heads, _ = CASES[case]
    assert (solve.shape, solve.dtype, mq.shape, mq.dtype) == ((boards, SQUARES, heads * 128), jnp.float32, (boards, SQUARES, heads * 128), jnp.bfloat16)
    assert (u.shape, u.dtype) == (ops["q"].shape, jnp.float32)
    for name, got, want in zip(("[T | Mk]", "U", "Mq"), written((solve, u, mq)), written(oracle_kept(ops))):
        assert np.array_equal(got, want), (name, float(np.abs(got - want).max()))
    unit_lower = np.asarray(solve).reshape(boards, SQUARES, heads, 2, SQUARES)[:, :, :, 0].transpose(0, 2, 1, 3)  # T of a board and head
    assert np.array_equal(np.triu(unit_lower, 1), np.zeros_like(unit_lower)) and (np.diagonal(unit_lower, axis1=-2, axis2=-1) == 1.0).all()


@pytest.mark.parametrize("case", CASES)
def test_the_gradient_from_the_kept_tables_is_the_gradient_from_tables_made_again(case):
    """The gradient kernel fed the oracle's tables (what it made for itself before) gives the five gradients of
    ``jax.grad`` through the kept ones, bit for bit; and fed a ``T`` that is not the inverse it gives others: it reads, it
    does not solve."""
    ops = operands(case, seed=5)
    weight = jnp.asarray(np.random.default_rng(11).standard_normal(ops["q"].shape), jnp.float32)
    grads = exactly(jax.grad(lambda o: jnp.sum(board_delta(*(o[name] for name in NAMES), True).astype(jnp.float32) * weight)), ops)
    inputs = tuple(ops[name] for name in NAMES)
    from_tables = lambda *kept: kernels._board_delta_bwd(True, (*inputs, kept), weight.astype(jnp.bfloat16))
    solve, u, mq = oracle_kept(ops)
    for name, got in zip(NAMES, exactly(from_tables, solve, u, mq)):
        assert got.dtype == grads[name].dtype and np.array_equal(np.asarray(got, np.float32), np.asarray(grads[name], np.float32)), name
    other = exactly(from_tables, solve * 0.5, u, mq)
    assert not np.array_equal(np.asarray(other[2], np.float32), np.asarray(grads["v"], np.float32))  # dv = beta T^T dU


def _two_boards_of_a_head():
    """``Diag(beta) Mk`` and ``beta V`` of head 0 of the published case's two boards, as the first form's forward makes them."""
    ops = operands("published", seed=6)
    _, heads, d = CASES["published"]
    chains = []
    for b in range(2):
        q, k, v, g = (ops[name][b, :, :d].astype(jnp.float32) for name in ("q", "k", "v", "g"))
        qn, kn, _, _, c, _ = kernels._normed(q, k, g)
        beta = ops["beta"][b, :, :1]
        chains.append((beta * kernels._tables(qn, kn, c)[1], beta * v))
    return chains


def _two_value_heads_of_a_key_head():
    """The same of the two value heads on the one key head of the second form's published case, board 0."""
    ops = head_operands("published_two_a_key_head", seed=6, fastest=0.5)  # rates under 0.5: both heads remember, Mk is no rounding
    d = ops["q"].shape[-1]
    t, j, _ = kernels._squares()
    kn, _ = kernels._unit(ops["k"][0].astype(jnp.float32))
    kk = kernels._dot(kn, kn, kernels._NT)
    chains = []
    for h in range(2):
        beta = ops["beta"][0, :, h:h + 1]
        mk = jnp.where(t > j, kk * kernels._head_decay(ops["g"][0, :, h:h + 1], t, j), 0.0)
        chains.append((beta * mk, beta * ops["v"][0, :, h * d:(h + 1) * d].astype(jnp.float32)))
    return chains


@pytest.mark.parametrize("without", [None, 0, 1], ids=["both_chains", "the_first_chain_s_Mk_zero", "the_second_chain_s_Mk_zero"])
@pytest.mark.parametrize("chains", [_two_boards_of_a_head, _two_value_heads_of_a_key_head], ids=["two_boards_of_a_head", "two_value_heads_of_a_key_head"])
def test_the_packed_solve_of_two_chains_is_each_chains_own_solve_bit_for_bit(chains, without):
    """``_solve`` handed two chains side by side gives ``[T_a | T_b]`` and ``[U_a ; U_b]``, each the single ``_solve``'s of its
    chain to the last bit (``exactly``: a 128-deep sum whose other 64 terms are exact zeros is the 64-deep sum, on XLA:CPU as
    on the chip's array). A chain whose ``Mk`` is zero keeps ``T = I`` and ``U = beta
    V`` beside one that does not: a block diagonal that leaked a block would show there."""
    made = [(jnp.zeros_like(a) if n == without else a, bv) for n, (a, bv) in enumerate(chains())]
    packed = jnp.concatenate([a for a, _ in made], axis=1), jnp.concatenate([bv for _, bv in made], axis=0)
    assert packed[0].shape == (SQUARES, 2 * SQUARES) and packed[1].shape == (2 * SQUARES, 128)
    tm, u = (np.asarray(x) for x in exactly(kernels._solve, *packed))
    for n, (a, bv) in enumerate(made):
        own_tm, own_u = (np.asarray(x) for x in exactly(kernels._solve, a, bv))
        at = slice(n * SQUARES, (n + 1) * SQUARES)
        assert np.array_equal(tm[:, at], own_tm) and np.array_equal(u[at], own_u), n
        assert np.array_equal(np.triu(own_tm, 1), np.zeros_like(own_tm)) and (np.diagonal(own_tm) == 1.0).all()
        if n == without:
            assert np.array_equal(own_tm, np.eye(SQUARES, dtype=np.float32)) and np.array_equal(own_u, np.asarray(bv))
        else:
            assert np.abs(own_tm - np.eye(SQUARES)).max() > 1e-3  # a chain that is there


@pytest.mark.parametrize("packed", [False, True], ids=["one_chain", "a_packed_pair"])
@pytest.mark.parametrize("chains", [_two_boards_of_a_head, _two_value_heads_of_a_key_head], ids=["two_boards_of_a_head", "two_value_heads_of_a_key_head"])
def test_level_0_in_closed_form_is_the_six_level_product_form_bit_for_bit(chains, packed):
    """Before level 0 ``T`` is the identity, so the level's ``T - (T A_0) T`` is ``I - A_0`` and ``_solve`` writes it so, without
    the two products: five levels of products where the parent had six, and every ``T`` and ``U`` the parent's to the last bit
    (``I A_0 I`` at ``highest`` is ``A_0``, exactly), of one chain and of each chain of a packed pair."""
    made = chains()
    ones = jnp.ones((SQUARES, 1), jnp.float32)  # the chains come with beta folded in: the parent's ``beta * mk`` of them under beta 1 is themselves
    wanted = [tuple(np.asarray(x) for x in exactly(parent_solve, a, bv, ones)) for a, bv in made]
    if packed:
        tm, u = (np.asarray(x) for x in exactly(kernels._solve, jnp.concatenate([a for a, _ in made], axis=1), jnp.concatenate([bv for _, bv in made], axis=0)))
        got = [(tm[:, n * SQUARES:(n + 1) * SQUARES], u[n * SQUARES:(n + 1) * SQUARES]) for n in range(2)]
    else:
        got = [tuple(np.asarray(x) for x in exactly(kernels._solve, a, bv)) for a, bv in made]
    for (tm, u), (parent_tm, parent_u) in zip(got, wanted):
        assert np.array_equal(tm, parent_tm) and np.array_equal(u, parent_u)
        assert np.abs(np.diagonal(tm, -1)).max() > 1e-3  # level 0's pairs are there: the closed form is no identity
    closed = jax.make_jaxpr(kernels._solve)(*made[0])
    assert sum(eqn.primitive.name == "dot_general" for eqn in closed.eqns) == 2 * 5 + 1  # five levels of two, and U


def _kernel_calls(jaxpr, jitted=None):
    """Every ``pallas_call`` equation of a jaxpr and of the jaxprs its equations hold, each with the ``jax.jit`` equation
    nearest around it (None for a bare call)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield jitted, eqn
        for held in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(held, eqn if eqn.primitive.name in ("pjit", "jit") else jitted)


@pytest.mark.parametrize("boards", [2, 8, 9], ids=["a_block_of_2", "a_block_of_8", "nine_blocks_of_1"])
@pytest.mark.parametrize("differentiated", [False, True], ids=["primal", "differentiated"])
def test_the_primal_writes_o_alone_and_the_differentiated_forward_the_kept_arrays(differentiated, boards):
    """No gradient asked: one kernel with one output. Differentiated: the forward kernel's four outputs (o, ``[T | Mk]``,
    ``U``, ``Mq``: 80 KB a board and head at their padded size) and a gradient kernel that reads the five inputs, the
    three kept arrays and o's cotangent. Off the interpreter each kernel sits alone under its own ``jax.jit``, whose results
    and arguments are the kernel's own: what the forward wrote is what the gradient reads, nothing between. A kernel's body
    holds its loop over a grid step's boards rolled (start-up pays for every copy of a body that Mosaic lowers): a PAIR of
    boards a turn where the block is even (two chains a product), in the forward and in the gradient, one board where it is odd."""
    _, heads, d = CASES["published"]
    ops = {name: jax.ShapeDtypeStruct((boards, *value.shape[1:]), value.dtype) for name, value in operands("published").items()}
    args = tuple(ops[name] for name in NAMES)
    block = math.gcd(boards, 8)
    fn = (lambda *a: jax.vjp(lambda *b: board_delta(*b, False), *a)[1](a[0])) if differentiated else (lambda *a: board_delta(*a, False))
    calls = list(_kernel_calls(jax.make_jaxpr(fn)(*args).jaxpr))
    assert [jitted.params["name"] for jitted, _ in calls] == (["_forward_call", "_gradient_call"] if differentiated else ["_forward_call"])
    for jitted, call in calls:  # a jitted call is its kernel and casts that change nothing: results and kept operands pass straight through
        assert [id(v) for v in jitted.params["jaxpr"].jaxpr.outvars] == [id(v) for v in call.outvars]
        loops = [eqn.params for eqn in call.params["jaxpr"].eqns if eqn.primitive.name in ("scan", "while")]
        turns = block // 2 if block % 2 == 0 else block
        assert [(loop["length"], loop["unroll"]) for loop in loops] == [(turns, 1)]  # ONE turn's body in the kernel: unrolled, every start lowers it a board
    names = [call.params["name"] for _, call in calls]
    if not differentiated:
        assert names == ["board_delta"] and [v.aval.shape for v in calls[0][1].outvars] == [ops["q"].shape]
        return
    assert names == ["board_delta", "board_delta_grad"]
    (forward_jit, forward), (gradient_jit, gradient) = calls
    kept = [(v.aval.shape, v.aval.dtype) for v in forward.outvars[1:]]
    assert kept == [((boards, SQUARES, heads * 128), jnp.float32), (ops["q"].shape, jnp.float32), ((boards, SQUARES, heads * 128), jnp.bfloat16)]
    assert sum(int(np.prod(shape)) * np.dtype(dtype).itemsize for shape, dtype in kept) == boards * heads * 80 * 1024  # budget 96 KB
    assert len(gradient.invars) == 9 and len(gradient.outvars) == 5
    assert [id(v) for v in gradient.invars[5:8]] == [id(v) for v in gradient_jit.params["jaxpr"].jaxpr.invars[5:8]]
    assert [id(v) for v in gradient_jit.invars[5:8]] == [id(v) for v in forward_jit.outvars[1:]]  # read as they were written


# -- the second form: a decay a head and token, value heads in groups on a key head (Gated DeltaNet) ----------------------------------

#: (boards, key heads, value heads a key head, d): the published head with two value heads a key head on two boards, narrow heads
#: one to one on a block of boards and a remainder, three value heads on one key head.
HEAD_CASES = {"published_two_a_key_head": (2, 1, 2, 128), "narrow_one_a_key_head": (9, 2, 1, 16), "narrow_two_a_key_head": (3, 2, 2, 16),
              "three_a_key_head": (2, 1, 3, 32)}


def head_operands(case, seed=0, fastest=16.0):
    """q and k at the key heads, v at the value heads; ``g`` one a value head and square in the layer's own range at its start,
    ``-exp(A_log) softplus(a + dt_bias)`` with rates uniform in (0, ``fastest``) and ``dt_bias`` 1: a head near 0 never forgets, one
    at 16 keeps e^-21 of its state a square."""
    boards, key_heads, per, d = HEAD_CASES[case]
    heads = key_heads * per
    rng = np.random.default_rng([seed, heads, d])
    bf16 = lambda y: jnp.asarray(y, jnp.bfloat16)
    rate, step = rng.uniform(0.0, fastest, heads), np.log1p(np.exp(1.0 + 0.3 * rng.standard_normal((boards, SQUARES, heads))))
    return {
        "q": bf16(rng.standard_normal((boards, SQUARES, key_heads * d))), "k": bf16(rng.standard_normal((boards, SQUARES, key_heads * d))),
        "v": bf16(rng.standard_normal((boards, SQUARES, heads * d))), "g": jnp.asarray(-rate * step, jnp.float32),
        "beta": jnp.asarray(rng.uniform(0.05, 0.95, (boards, SQUARES, heads)), jnp.float32),
    }


def head_recurrence(q, k, v, g, beta, key_head_of=None, **mutation):
    """The first form's literal recurrence on what the second form means: value head h reads key head ``h // per`` (or
    ``key_head_of(h)``: a misreading) and every channel of a head decays alike."""
    boards, _, heads = beta.shape
    d = v.shape[-1] // heads
    key_heads = q.shape[-1] // d
    of = [(key_head_of or (lambda h: h // (heads // key_heads)))(h) for h in range(heads)]
    by_value_head = lambda y: jnp.concatenate([y[..., i * d:(i + 1) * d] for i in of], axis=-1)
    return recurrence(by_value_head(q), by_value_head(k), v, jnp.repeat(g, d, axis=-1), beta, **mutation)


def head_wanted(ops, weight, **mutation):
    with jax.enable_x64(True):
        wide = {name: jnp.asarray(np.asarray(value, np.float64)) for name, value in ops.items()}
        fn = lambda o: head_recurrence(*(o[name] for name in NAMES), **mutation)
        grads = jax.grad(lambda o: jnp.sum(fn(o) * jnp.asarray(weight, jnp.float64)))(wide)
        return np.asarray(fn(wide)), {name: np.asarray(value) for name, value in grads.items()}


# Readings (CPU interpreter, seeds 0-1, the four cases): forward 0.0030-0.0042 of the result's norm; dq, dk, dv 0.0031-0.0046, dg
# 0.0034-0.0062, dbeta 0.0027-0.0036. Value head h on key head h % key heads reads 1.3 forward, the decay after the correction 0.17.
@pytest.mark.parametrize("case", HEAD_CASES)
def test_the_second_form_against_the_literal_recurrence(case):
    ops = head_operands(case)
    weight = np.random.default_rng(7).standard_normal(ops["v"].shape).astype(np.float32)
    want, want_grads = head_wanted(ops, weight)
    got, grads = kernel(ops, weight)
    assert got.dtype == jnp.bfloat16 and got.shape == ops["v"].shape
    print("second form", case, rel(got, want), {name: round(rel(grads[name], want_grads[name]), 4) for name in NAMES})
    assert rel(got, want) < TOL, rel(got, want)
    for name in NAMES:
        assert grads[name].shape == ops[name].shape and grads[name].dtype == ops[name].dtype, name
        assert rel(grads[name], want_grads[name]) < TOL, (name, rel(grads[name], want_grads[name]))


@pytest.mark.parametrize("fastest", [0.05, 60.0])
def test_a_head_that_never_forgets_and_one_that_forgets_a_square_at_once(fastest):
    """Rates near 0 (``exp(g)`` ~ 1: the plain delta rule, the solve at its worst conditioning) and up to 60 (a square keeps e^-80:
    ``L`` is the identity in float32): every exponent is a sum of ``g`` <= 0, and nothing is a difference of cumulative sums."""
    ops = head_operands("narrow_two_a_key_head", seed=3, fastest=fastest)
    weight = np.random.default_rng(8).standard_normal(ops["v"].shape).astype(np.float32)
    want, want_grads = head_wanted(ops, weight)
    got, grads = kernel(ops, weight)
    assert np.isfinite(np.asarray(got, np.float32)).all() and rel(got, want) < TOL
    for name in NAMES:
        assert np.isfinite(np.asarray(grads[name], np.float32)).all(), name
        if np.linalg.norm(want_grads[name]) > 1e-6 * np.linalg.norm(np.asarray(ops[name], np.float64)):  # at 60 no square reads a decay
            assert rel(grads[name], want_grads[name]) < TOL, (name, rel(grads[name], want_grads[name]))


@pytest.mark.parametrize("mutation", [dict(key_head_of=lambda h: h % 2), dict(decay_first=False), dict(unit_beta=True)],
                         ids=["value_head_h_on_key_head_h_mod_key_heads", "decay_after_the_correction", "beta_fixed_at_1"])
def test_a_misread_second_form_is_not_the_kernels(mutation):
    ops = head_operands("narrow_two_a_key_head", seed=2)
    weight = np.random.default_rng(9).standard_normal(ops["v"].shape).astype(np.float32)
    want, want_grads = head_wanted(ops, weight, **mutation)
    got, grads = kernel(ops, weight)
    assert rel(got, want) > 5 * TOL, rel(got, want)
    # dg is the slowest head's (the others keep e^-10 of a state a square and less), and heads 0 and 3 read their own key head either way
    seen_by = [name for name in NAMES if np.any(want_grads[name]) and not ("key_head_of" in mutation and name == "g")]
    assert min(rel(grads[name], want_grads[name]) for name in seen_by) > 5 * TOL


def test_the_second_form_reads_q_and_k_a_key_head_and_keeps_T_and_U_alone():
    """The operands of the two ``pallas_call``s are the layer's own arrays: q and k at the KEY heads, g and beta ``[boards, 64,
    value heads]``; nothing repeated a value head, no decay a channel. Kept: ``T`` (a key head's two value heads side by side
    in ONE 128-lane tile) and ``U``, 48 KB a board and value head; each kernel alone under its ``jax.jit``, its loop rolled."""
    boards, key_heads, per, d = 8, 2, 2, 128
    shape = lambda heads, dtype: jax.ShapeDtypeStruct((boards, SQUARES, heads), dtype)
    args = (shape(key_heads * d, jnp.bfloat16), shape(key_heads * d, jnp.bfloat16), shape(key_heads * per * d, jnp.bfloat16),
            shape(key_heads * per, jnp.float32), shape(key_heads * per, jnp.float32))
    calls = list(_kernel_calls(jax.make_jaxpr(lambda *a: jax.vjp(lambda *b: board_delta(*b, False), *a)[1](a[2]))(*args).jaxpr))
    assert [jitted.params["name"] for jitted, _ in calls] == ["_forward_call", "_gradient_call"]
    (_, forward), (_, gradient) = calls
    assert [call.params["name"] for _, call in calls] == ["board_delta", "board_delta_grad"]
    assert [v.aval.shape for v in forward.invars] == [a.shape for a in args]
    assert [(v.aval.shape, v.aval.dtype) for v in forward.outvars] == [
        (args[2].shape, jnp.bfloat16), ((boards, SQUARES, key_heads * 128), jnp.float32), (args[2].shape, jnp.float32)]
    assert [v.aval.shape for v in gradient.invars] == [a.shape for a in args] + [(boards, SQUARES, key_heads * 128), args[2].shape, args[2].shape]
    assert [v.aval.shape for v in gradient.outvars] == [a.shape for a in args]
    for _, call in calls:
        loops = [eqn.params for eqn in call.params["jaxpr"].eqns if eqn.primitive.name in ("scan", "while")]
        assert [(loop["length"], loop["unroll"]) for loop in loops] == [(8, 1)]
    with pytest.raises(ValueError, match="board_delta"):  # five value heads are no whole groups on two key heads
        board_delta(jnp.zeros((2, SQUARES, 32)), jnp.zeros((2, SQUARES, 32)), jnp.zeros((2, SQUARES, 80)), jnp.zeros((2, SQUARES, 5)), jnp.zeros((2, SQUARES, 5)), True)


# -- the gradient, two chains a product: against the parent's single-chain bodies -------------------------------------------------------


def parent_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref, u_ref, mq_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """``_backward_kernel`` as the parent commit (be9aa8a) had it, copied: ONE board a loop turn, every product a ``[64, .]`` one."""
    f32, h = jnp.float32, kernels.pl.program_id(1)
    _dot, _exact, _NT, _TN = kernels._dot, kernels._exact, kernels._NT, kernels._TN

    def board(i, carry):
        beta, own = kernels._own_lane(beta_ref, i, h)
        v, do = v_ref[i].astype(f32), do_ref[i]
        qn, kn, rq, rk, c, scale = kernels._normed(q_ref[i].astype(f32), k_ref[i].astype(f32), g_ref[i])
        tm, mk, u, mq = solve_ref[i, :, :SQUARES], solve_ref[i, :, SQUARES:], u_ref[i], mq_ref[i, :, :SQUARES]
        t, j, row = kernels._squares()
        dmq = jnp.where(t >= j, _dot(do, u, _NT), 0.0)
        w = _exact(tm, _dot(mq, do, _TN), _TN)
        da = -jnp.where(t > j, _exact(w, u, _NT), 0.0)
        dv_ref[i] = (beta * w).astype(dv_ref.dtype)
        dbeta = jnp.sum(w * v, axis=-1, keepdims=True) + jnp.sum(da * mk, axis=-1, keepdims=True)
        dbeta_ref[i] = jnp.where(own, dbeta, dbeta_ref[i])
        dmk = beta * da
        on_diagonal = jnp.sum(jnp.where(t == j, dmq, 0.0), axis=-1, keepdims=True)
        dqn, dkn, dc = on_diagonal * kn, on_diagonal * qn, jnp.zeros_like(c)
        for p in range(6):
            pairs, upper, lower = kernels._level_decays(p, c, t, j, row)
            ql, kl, kr = qn * upper, kn * upper, kn * lower
            dq_pairs, dk_pairs = jnp.where(pairs, dmq, 0.0), jnp.where(pairs, dmk, 0.0)
            dql, dkl = _dot(dq_pairs, kr), _dot(dk_pairs, kr)
            dkr = _dot(dq_pairs, ql, _TN) + _dot(dk_pairs, kl, _TN)
            dqn, dkn = dqn + dql * upper, dkn + dkl * upper + dkr * lower
            dc = dc + ql * dql + kl * dkl - kr * dkr
        dg_ref[i] = _exact((t >= j).astype(f32), dc, _TN)
        dqn = dqn * scale
        qy = qn * (1.0 / scale)
        dq_ref[i] = (rq * (dqn - qy * jnp.sum(qy * dqn, axis=-1, keepdims=True))).astype(dq_ref.dtype)
        dk_ref[i] = (rk * (dkn - kn * jnp.sum(kn * dkn, axis=-1, keepdims=True))).astype(dk_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], board, 0)


def parent_head_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref, u_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """``_head_backward_kernel`` as the parent commit (be9aa8a) had it, copied: a key head's value heads one after another, each its
    own chain of ``[64, 64]`` products, and four products a key head after them."""
    f32, key_head = jnp.float32, kernels.pl.program_id(1)
    _dot, _exact, _NT, _TN = kernels._dot, kernels._exact, kernels._NT, kernels._TN
    d = q_ref.shape[-1]
    per = v_ref.shape[-1] // d

    def board(i, carry):
        t, j, _ = kernels._squares()
        lower = (t >= j).astype(f32)
        qn, kn, rq, rk, scale, qk, kk = kernels._key_head(q_ref, k_ref, i)
        dqk, dkk = jnp.zeros_like(qk), jnp.zeros_like(kk)
        for s in range(per):
            h, columns = key_head * per + s, slice(s * d, (s + 1) * d)
            g, own = kernels._own_lane(g_ref, i, h)
            beta, _ = kernels._own_lane(beta_ref, i, h)
            decay = kernels._head_decay(g, t, j)
            mq, mk = qk * decay, jnp.where(t > j, kk * decay, 0.0)
            v, do = v_ref[i, :, columns].astype(f32), do_ref[i, :, columns]
            tm, u = solve_ref[i, :, s * SQUARES:(s + 1) * SQUARES], u_ref[i, :, columns]
            dmq = jnp.where(t >= j, _dot(do, u, _NT), 0.0)
            w = _exact(tm, _dot(mq, do, _TN), _TN)
            da = -jnp.where(t > j, _exact(w, u, _NT), 0.0)
            dv_ref[i, :, columns] = (beta * w).astype(dv_ref.dtype)
            dbeta = jnp.sum(w * v, axis=-1, keepdims=True) + jnp.sum(da * mk, axis=-1, keepdims=True)
            dbeta_ref[i] = jnp.where(own, dbeta, dbeta_ref[i])
            dmk = beta * da
            spans = _exact(lower, dmq * mq + dmk * mk, _TN)
            dg_ref[i] = jnp.where(own, jnp.sum(jnp.where(t > j, spans, 0.0), axis=-1, keepdims=True), dg_ref[i])
            dqk, dkk = dqk + dmq * decay, dkk + dmk * decay
        dqn = _dot(dqk, kn) * scale
        dkn = _dot(dqk, qn, _TN) + _dot(dkk, kn) + _dot(dkk, kn, _TN)
        qy = qn * (1.0 / scale)
        dq_ref[i] = (rq * (dqn - qy * jnp.sum(qy * dqn, axis=-1, keepdims=True))).astype(dq_ref.dtype)
        dk_ref[i] = (rk * (dkn - kn * jnp.sum(kn * dkn, axis=-1, keepdims=True))).astype(dk_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], board, 0)


PARENT_BODIES = {"_backward_kernel": parent_backward_kernel, "_head_backward_kernel": parent_head_backward_kernel}


@contextlib.contextmanager
def gradient_bodies(monkeypatch, parent: bool):
    """This tree's gradient bodies, or (``parent``) the parent's single-chain bodies in their place while a call is traced: the call,
    its grid and its BlockSpecs are the tree's either way. A jitted call keeps its trace, so each side starts from none."""
    with monkeypatch.context() as patched:
        for name, body in PARENT_BODIES.items() if parent else ():
            patched.setattr(kernels, name, body)
        kernels._gradient_call.clear_cache()
        yield
    kernels._gradient_call.clear_cache()


def gradient_call(monkeypatch, parent: bool):
    """``board_delta``'s gradient call (``residuals, do -> dq, dk, dv, dg, dbeta``) under the interpreter, over either side's bodies."""
    def call(residuals, do):
        with gradient_bodies(monkeypatch, parent):
            return kernels._gradient_call.__wrapped__(*residuals, do, interpret=True)  # bare: traced here, under whichever bodies stand
    return call


#: Both forms' cases, each with the turn it takes: the first form's blocks of 2, of 8 and of 1 (nine boards, and three), the second
#: form's pairs of value heads, single value heads and three on a key head (a pair and one left over); and, where a turn is a packed
#: pair, the pair with its first or its second chain forgetting everything at once (``Mk`` zero, ``T`` the identity).
PACKED = [("first", "published"), ("first", "a_block_of_8"), ("second", "published_two_a_key_head")]
GRADIENT_CASES = [("first", case, None) for case in CASES] + [("second", case, None) for case in HEAD_CASES] + [(*packed, without) for packed in PACKED for without in (0, 1)]
CHAINS = {None: "both_chains", 0: "the_first_chain_s_Mk_zero", 1: "the_second_chain_s_Mk_zero"}


@pytest.mark.parametrize("form,case,without", GRADIENT_CASES, ids=[f"{form}_form_{case}_{CHAINS[without]}" for form, case, without in GRADIENT_CASES])
def test_the_packed_gradient_is_the_parents_single_chain_gradient_bit_for_bit(form, case, without, monkeypatch):
    """Every one of dq, dk, dv, dg, dbeta, to the last bit (``exactly``): no two products are joined along a contraction, a
    chain's sums are made on its own lanes in the parent's order, a block diagonal's zero blocks add exact zeros and a dropped
    off-diagonal block changes no kept element. ``published``, ``a_block_of_8`` and ``published_two_a_key_head`` are the packed
    paths; ``narrow`` (nine boards: blocks of one) and ``one_head`` (three) the first form's fall-back, ``narrow_one_a_key_head``
    the second form's, ``three_a_key_head`` a pair and one left over in one body. ``without``: that chain of every packed pair (the
    even or the odd boards; a key head's first or second value head) decays by e^-1000 a square, so its ``Mk`` is zero and its ``T``
    the identity beside a chain that remembers, as ``test_the_packed_solve_of_two_chains_...`` has it of the forward: a block
    diagonal that leaked a block, or a select that kept the wrong half, would show there."""
    ops = operands(case, seed=12) if form == "first" else head_operands(case, seed=12, fastest=0.5)  # rates under 0.5: every head remembers
    if without is not None:
        chain = np.arange(ops["g"].shape[0])[:, None, None] if form == "first" else np.arange(ops["g"].shape[-1])[None, None, :]
        ops["g"] = jnp.where(chain % 2 == without, -1e3, ops["g"])
    inputs = tuple(ops[name] for name in NAMES)
    do = jnp.asarray(np.random.default_rng(13).standard_normal(ops["v"].shape), jnp.bfloat16)
    _, residuals = exactly(lambda *a: kernels._board_delta_fwd(*a, True), *inputs)
    if without is not None:  # the case is what it says: the kept T of that chain is the identity, of the other it is not
        boards = ops["g"].shape[0]
        tables = np.asarray(residuals[-1][0]).reshape(boards, SQUARES, -1, SQUARES).transpose(0, 2, 1, 3)  # [board, 64-lane table, t, j]
        if form == "first":  # a head's tile is [T | Mk]: of board b, T is table 2 h
            forgetful, mindful = tables[without::2, 0::2], tables[1 - without::2, 0::2]
        else:  # a key head's tile is [T_a | T_b]
            forgetful, mindful = tables[:, without::2], tables[:, 1 - without::2]
        assert (forgetful == np.eye(SQUARES, dtype=np.float32)).all() and np.abs(mindful - np.eye(SQUARES)).max() > 1e-3
    got, want = (exactly(gradient_call(monkeypatch, parent), residuals, do) for parent in (False, True))
    for name, mine, parents in zip(NAMES, got, want):
        mine, parents = np.asarray(mine, np.float32), np.asarray(parents, np.float32)
        assert np.isfinite(parents).all() and np.abs(parents).max() > 1e-3, name  # a gradient that is there
        assert np.array_equal(mine, parents), (name, float(np.abs(mine - parents).max()))


def _products(jaxpr):
    """The operand shapes of every ``dot_general`` of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield tuple(v.aval.shape for v in eqn.invars)
        for held in jax.core.jaxprs_in_params(eqn.params):
            yield from _products(held)


def _cell(form, boards=128, d=128, **heads):
    """The operands' shapes of ``board_delta`` on a cell's batch: the sixth trunk's 16 heads, the seventh's 16 key and 32 value heads."""
    sds = jax.ShapeDtypeStruct
    if form == "first":
        wide = (boards, SQUARES, heads.get("heads", 16) * d)
        return (sds(wide, jnp.bfloat16),) * 3 + (sds(wide, jnp.float32), sds((boards, SQUARES, heads.get("heads", 16)), jnp.float32))
    key_heads, value_heads = heads.get("key_heads", 16), heads.get("value_heads", 32)
    by_head = sds((boards, SQUARES, value_heads), jnp.float32)
    return (sds((boards, SQUARES, key_heads * d), jnp.bfloat16),) * 2 + (sds((boards, SQUARES, value_heads * d), jnp.bfloat16), by_head, by_head)


@pytest.mark.parametrize("form,shape,a_turn,turns", [
    # kda_trunk_train_b128: boards 2 t, 2 t + 1 a turn; the parent's 36 products a BOARD (c, dMq, Mq^T dO, w, dA, six levels of r and
    # four, dg) are 25 a PAIR: every one of them one product of the pair, the six r ONE product, a level's dQL and dKL the halves of one
    ("first", {}, 25, 4),
    ("first", dict(boards=9), 25, 1),  # an odd block: one board a turn, the same lines on [64, .]
    # gdn_trunk_train_b128: the parent's 2 + 6 a value head + 4 = 18 a board and key head are 1 + 6 a PAIR + 2 = 9
    ("second", {}, 9, 8),
    ("second", dict(value_heads=16), 9, 8),  # one value head a key head: the single chain, the key head's products joined all the same
    ("second", dict(value_heads=48), 15, 8),  # three: a pair and one left over, six each
], ids=["kda_cell", "an_odd_block", "gdn_cell", "one_value_head_a_key_head", "three_value_heads_a_key_head"])
def test_the_gradient_bodies_hold_a_pair_s_products_once(form, shape, a_turn, turns, monkeypatch):
    """The mechanism's counter (engagement is static: shapes decide it while the body is traced): the ``dot_general``s of each
    gradient body on the two cells' shapes, this tree's against the parent's single-chain body traced in its place. A loop turn of
    the first form holds 25 products for TWO boards where the parent's held 36 for one; the second form's 9 for a key head's two value
    heads where the parent's held 18. On the cells' shapes no product of a pair is a single chain's ``[64, 64] x [64, .]``
    but the second form's two triangles (``g`` and ``dD`` meet the triangle of ONE board). The loop stays rolled."""
    args = _cell(form, **shape)
    counted = {}
    for parent in (False, True):
        with gradient_bodies(monkeypatch, parent):
            traced = jax.make_jaxpr(lambda *a: jax.vjp(lambda *b: board_delta(*b, False), *a)[1](a[2]))(*args)
        (gradient,) = [call.params["jaxpr"] for _, call in _kernel_calls(traced.jaxpr) if call.params["name"] == "board_delta_grad"]
        (loop,) = [eqn.params for eqn in gradient.eqns if eqn.primitive.name in ("scan", "while")]
        counted[parent] = (list(_products(gradient)), loop["length"], loop["unroll"])
    products, length, unroll = counted[False]
    assert (len(products), length, unroll) == (a_turn, turns, 1), (len(products), length, unroll)
    per = shape.get("value_heads", 32) // 16
    parents, parent_length, _ = counted[True]
    assert (len(parents), parent_length) == ((36, math.gcd(shape.get("boards", 128), 8)) if form == "first" else (2 + 6 * per + 4, 8))
    if not shape:  # a cell: a pair's products are a pair's
        single = [product for product in products if product[0] == (SQUARES, SQUARES) and product[1][0] == SQUARES]
        assert single == ([] if form == "first" else [((SQUARES, SQUARES), (SQUARES, 2 * SQUARES))] * 2), single
        assert len(products) * (1 if form == "second" else 0.5) <= len(parents) / 2  # a board's share: half the parent's, or less


# -- tools/delta_alone.py: the pair timed alone ----------------------------------------------------------------------------------------


@pytest.mark.parametrize("form,shape", [("gdn", ["--heads", "1", "--per", "2"]), ("kda", ["--heads", "2"])])
def test_delta_alone_prints_its_four_times_and_what_it_ran_on(form, shape, capsys):
    """The tool as a builder runs it on the chip, here at a tiny shape under the interpreter (the times are the interpreter's and say
    nothing of a device: ``interpret`` and ``device`` say so in the line): its four programs' times, the gradient kernel's by itself
    among them, and against its own tree's file every array equal bit for bit."""
    assert delta_alone.PROGRAMS == ("forward_ms", "forward_kept_ms", "forward_and_gradient_ms", "gradient_ms")
    assert delta_alone.main(["--form", form, "--boards", "2", "--d", "16", "--calls", "2", "--seed", "1", *shape, "--against", kernels.__file__]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["interpret"] is True and line["device"] == jax.devices()[0].device_kind and line["form"] == form and line["finite"] is True
    for times in (line, line["against"]):
        for program in delta_alone.PROGRAMS:
            assert 0.0 < times[program]["min"] <= times[program]["median"] <= times[program]["max"]
    kept = ["kept0", "kept1"] + (["kept2"] if form == "kda" else [])
    assert sorted(line["bit_equal"]) == sorted(["o", "o_kept", *kept, *delta_alone.GRADIENTS]) and all(same is True for same in line["bit_equal"].values())
