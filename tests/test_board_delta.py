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
level 0 of the solve, written without its two products, the parent's.
``tools/delta_alone.py`` (the pair timed alone) runs at a tiny shape."""

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
    holds its loop over a grid step's boards rolled (start-up pays for every copy of a body that Mosaic lowers): the forward's
    a PAIR of boards a turn where the block is even (two chains a product), one board where it is odd; the gradient's a board."""
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
        turns = block // 2 if call.params["name"] == "board_delta" and block % 2 == 0 else block
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


# -- tools/delta_alone.py: the pair timed alone ----------------------------------------------------------------------------------------


@pytest.mark.parametrize("form,shape", [("gdn", ["--heads", "1", "--per", "2"]), ("kda", ["--heads", "2"])])
def test_delta_alone_prints_its_three_times_and_what_it_ran_on(form, shape, capsys):
    """The tool as a builder runs it on the chip, here at a tiny shape under the interpreter (the times are the interpreter's and say
    nothing of a device: ``interpret`` and ``device`` say so in the line): its three programs' times, and against its own tree's file
    every array equal bit for bit."""
    assert delta_alone.main(["--form", form, "--boards", "2", "--d", "16", "--calls", "2", "--seed", "1", *shape, "--against", kernels.__file__]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["interpret"] is True and line["device"] == jax.devices()[0].device_kind and line["form"] == form and line["finite"] is True
    for times in (line, line["against"]):
        for program in delta_alone.PROGRAMS:
            assert 0.0 < times[program]["min"] <= times[program]["median"] <= times[program]["max"]
    kept = ["kept0", "kept1"] + (["kept2"] if form == "kda" else [])
    assert sorted(line["bit_equal"]) == sorted(["o", "o_kept", *kept, *delta_alone.GRADIENTS]) and all(same is True for same in line["bit_equal"].values())
