"""The ninth block of the square-token trunk (models/trunk.py with
``TrunkConfig.block_length``: SDAR-30B-A3B-Chat's sdar_moe block TRAINED BY
BLOCK DIFFUSION over a board: a clean and a noised copy of every board
through one set of weights under the three-part block mask, a mask
embedding, a denoiser beside the AZ heads) at a tiny size on the CPU, on a
worker of its own: the mask against its three rules, the kernel pair under
the interpreter against a ``jax.numpy`` masked softmax, what must not leak,
the program against the benchmark's plain reference, the misreadings the
comparison has to see, the noise and its maker, the served forward, the
share tied to the model (16 shares of 8 experts add up to the uncut
reference's layer), the plan and its scopes, the new field's refusals, the
checkpoint round trip, and its step pin."""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_config_from_params, az_forward
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.ops.board_attention import block_mask, board_attention
from fishnet_tpu.train.az_trainer import AzTrainer, az_batch_specs
from fishnet_tpu.train.data import block_noise
from trunk_tiny import BLOCKS, CANCELLING, GRAD_CANCELLING_TOL, GRAD_TENSOR_TOL, MELLUM, SDAR, SDAR_CONFIG, SDAR_MODEL, _all, batch_of, noised_batch, rel  # noqa: E402

# The plain reference is the benchmark's own (benchmark/reference/sdar_trunk.py: the published layer and the papers' training, its own literal
# mask, importing nothing of the program), at a tiny size; the program reads its parameters as they are (benchmark/families/sdar_trunk.py).

from benchmark.families import sdar_trunk as sdar_family  # noqa: E402
from benchmark.reference import sdar_trunk as sdar_reference  # noqa: E402
from tools.step_text import HOW_TO_SEE_WHAT_MOVED, lowered_step_text  # noqa: E402

SQUARES = 64

# -- the mask ------------------------------------------------------------------------------------------------------------------------------


@pytest.mark.parametrize("block_length", [1, 4, 8, 64])
def test_the_mask_is_its_three_rules_square_by_square(block_length):
    """Literally, a (query, key) pair at a time; the program's mask, the benchmark reference's own and the count the roofline goes by agree."""
    mask = block_mask(block_length, 2)
    blk = lambda s: s // block_length
    for i in range(SQUARES):
        for j in range(SQUARES):
            assert mask[i, j] == (blk(j) <= blk(i)) and not mask[i, SQUARES + j]  # a clean query: clean keys up to its own block, never a noised key
            assert mask[SQUARES + i, j] == (blk(j) < blk(i)) and mask[SQUARES + i, SQUARES + j] == (blk(j) == blk(i))  # a noised query
    assert np.array_equal(mask, sdar_reference.allowed(block_length, 2)) and np.array_equal(block_mask(block_length, 1), mask[:SQUARES, :SQUARES])
    assert np.array_equal(block_mask(block_length, 1), sdar_reference.allowed(block_length, 1)) and mask.any(axis=1).all()  # every query sees a key
    blocks = SQUARES // block_length
    allowed_pairs = block_length * block_length * blocks * (blocks + 1) // 2
    assert mask[:SQUARES].sum() == mask[SQUARES:].sum() == allowed_pairs and (block_length != 4 or allowed_pairs == 2176)


@pytest.mark.parametrize("wrong", [dict(block_length=3), dict(block_length=0), dict(block_length=128), dict(block_length=4, streams=3)])
def test_a_block_that_does_not_divide_a_board_is_refused(wrong):
    with pytest.raises(ValueError, match="block"):
        block_mask(**wrong)


# -- the kernel pair under the interpreter against a plain masked softmax -------------------------------------------------------------------

EPS = 1e-6
ROUNDING = 2.0 ** -8
#: ``tests/test_board_attention.py``'s count: four roundings on the way to ``mixed``, six on the way to a gradient.
FORWARD_TOL, GRADIENT_TOL = 4 * ROUNDING, 6 * ROUNDING
THETA = 1e4


def plain_masked(q, k, v, g_q, g_k, block_length, streams, clean_unmasked=False):
    """The core from the layer equations in float32: both copies normed, turned by the SQUARE index, ONE softmax over the allowed keys."""
    boards, rows, head_dim = q.shape[0], q.shape[1], g_q.shape[0]
    half = head_dim // 2
    split = lambda y: y.astype(jnp.float32).reshape(boards, rows, -1, head_dim)
    norm = lambda x, g: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g
    angle = (np.arange(rows) % SQUARES)[:, None] / THETA ** (np.arange(half) / half)[None, :]
    c, s = (jnp.asarray(np.concatenate([f(angle)] * 2, -1), jnp.float32)[:, None, :] for f in (np.cos, np.sin))
    turn = lambda x: x * c + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * s
    q, k, v = turn(norm(split(q), g_q)), turn(norm(split(k), g_k)), split(v)
    k, v = (jnp.repeat(y, q.shape[2] // y.shape[2], axis=2) for y in (k, v))
    mask = block_mask(block_length, streams).copy()
    if clean_unmasked:
        mask[:SQUARES, :SQUARES] = True
    scores = jnp.where(mask, jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(head_dim), -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision="highest").reshape(boards, rows, -1)


def core_inputs(boards, heads, kv_heads, head_dim, streams, seed):
    rng = np.random.default_rng(seed)
    shape, kv_shape = (boards, SQUARES * streams, heads * head_dim), (boards, SQUARES * streams, kv_heads * head_dim)
    gain = lambda: jnp.asarray(1.0 + 0.1 * rng.standard_normal(head_dim), jnp.float32)
    return (jnp.asarray(1.5 * rng.standard_normal(shape), jnp.float32), jnp.asarray(1.5 * rng.standard_normal(kv_shape), jnp.float32),
            jnp.asarray(rng.standard_normal(kv_shape), jnp.bfloat16), gain(), gain(), jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))


def value_and_gradients(f, q, k, v, g_q, g_k, cotangent):
    out, pull = jax.vjp(f, q, k, v, g_q, g_k)
    return (out, *pull(cotangent.astype(out.dtype)))


OUTPUTS = ["mixed", "d_q", "d_k", "d_v", "d_q_norm", "d_k_norm"]
#: (heads, key-value heads, boards, block length, streams): a group of 1 and of 8 (this block's: four pairs a product each) under both copies, the
#: served form (the clean copy alone), and an odd group (a pair and a last head alone) under both and served.
CASES = {"group_1": (2, 2, 3, 4, 2), "group_8": (8, 1, 2, 8, 2), "served_group_8": (8, 1, 2, 4, 1), "group_3": (3, 1, 2, 4, 2), "served_group_3": (3, 1, 2, 8, 1)}


@functools.lru_cache(maxsize=None)
def both(case):
    heads, kv_heads, boards, block_length, streams = CASES[case]
    args = core_inputs(boards, heads, kv_heads, 16, streams, seed=7)
    kernel = lambda q, k, v, g_q, g_k: board_attention(q, k, v, g_q, g_k, THETA, EPS, True, block_length=block_length, streams=streams)
    plain = functools.partial(plain_masked, block_length=block_length, streams=streams)
    return (jax.jit(functools.partial(value_and_gradients, kernel))(*args), jax.jit(functools.partial(value_and_gradients, plain))(*args),
            jax.jit(functools.partial(value_and_gradients, functools.partial(plain, clean_unmasked=True)))(*args))


@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("case", CASES)
def test_the_masked_kernel_pair_matches_the_plain_masked_softmax_and_its_gradient(case, output):
    """Forward and every gradient against ``jax.vjp`` of the plain float32 formula under the literal mask: the masked entries are out of the
    softmax's maximum and sums in the forward and in the gradient's recomputation, dk and dv of the clean copy sum both copies' queries and
    the group. The same formula with the clean copy left unmasked misses by several times the tolerance."""
    got, want, unmasked = (side[OUTPUTS.index(output)] for side in both(case))
    assert got.shape == want.shape and got.dtype == (jnp.bfloat16 if output in ("mixed", "d_v") else jnp.float32)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL), rel(got, want)
    assert rel(got, unmasked) > 3 * GRADIENT_TOL, rel(got, unmasked)


def test_the_masked_form_is_the_normed_forms_whole_head_and_nothing_else():
    q, k, v, g_q, g_k, _ = core_inputs(2, 2, 2, 16, 2, seed=1)
    for wrong in (dict(theta=None), dict(rotary_dim=8), dict(g_q=None), dict(g_k=jnp.ones((2, 16)))):
        with pytest.raises(ValueError, match="block mask"):
            board_attention(**{**dict(q=q, k=k, v=v, g_q=g_q, g_k=g_k, theta=THETA, eps=EPS, interpret=True, block_length=4, streams=2), **wrong})
    with pytest.raises(ValueError, match="copies"):  # 128 rows are two copies, and the caller has to say so
        board_attention(q, k, v, g_q, g_k, THETA, EPS, True, block_length=4, streams=1)
    # a block of the whole board and the clean copy alone is the unmasked core (another kernel, the same arithmetic)
    one = tuple(y[:, :SQUARES] for y in (q, k, v))
    whole = board_attention(*one, g_q, g_k, THETA, EPS, True, block_length=SQUARES, streams=1)
    assert rel(whole, board_attention(*one, g_q, g_k, THETA, EPS, True).astype(jnp.float32)) < ROUNDING


# -- what must not leak ----------------------------------------------------------------------------------------------------------------------

#: What is changed -> (the rows of q's copy whose output must not move, the rows of k and v that are overwritten), at a block of 4: block 5 is
#: squares 20-23. ``rows(copy, squares)`` are the copy's rows of a board's 128.
rows = lambda copy, squares: np.asarray(squares) + SQUARES * copy
block5, later, earlier = np.arange(20, 24), np.arange(24, SQUARES), np.arange(0, 20)
LEAKS = {
    "a_noised_query_and_its_own_blocks_clean_squares": (rows(1, block5), rows(0, block5)),
    "a_noised_query_and_a_later_blocks_clean_squares": (rows(1, block5), rows(0, later)),
    "a_noised_query_and_another_blocks_noised_squares": (rows(1, block5), np.concatenate([rows(1, earlier), rows(1, later)])),
    "a_clean_query_and_any_noised_square": (rows(0, np.arange(SQUARES)), rows(1, np.arange(SQUARES))),
    "a_clean_query_and_a_later_clean_block": (rows(0, block5), rows(0, later)),
}


@pytest.mark.parametrize("leak", LEAKS)
def test_a_key_that_is_not_allowed_changes_nothing(leak):
    """Bit for bit, forward and in dq: overwriting the keys and values a query may not see (with numbers a hundred times as large) leaves its
    output and its gradient as they were; overwriting one it may see does not."""
    queries, changed = LEAKS[leak]
    q, k, v, g_q, g_k, cotangent = core_inputs(2, 4, 1, 16, 2, seed=3)
    cotangent = jnp.zeros_like(cotangent).at[:, queries].set(cotangent[:, queries])  # these queries' own part of the gradient
    run = jax.jit(lambda k, v: value_and_gradients(lambda *a: board_attention(*a, THETA, EPS, True, block_length=4, streams=2), q, k, v, g_q, g_k, cotangent)[:2])
    noise = np.random.default_rng(9).standard_normal(k.shape) * 100.0
    other_k, other_v = (y.at[:, changed].set(jnp.asarray(noise, y.dtype)[:, changed]) for y in (k, v))
    (mixed, d_q), (other_mixed, other_d_q) = run(k, v), run(other_k, other_v)
    assert np.array_equal(np.asarray(mixed[:, queries], np.float32), np.asarray(other_mixed[:, queries], np.float32))
    assert np.array_equal(np.asarray(d_q[:, queries]), np.asarray(other_d_q[:, queries])) and np.any(np.asarray(d_q[:, queries]))
    seen = rows(0, [0]) if queries[0] >= SQUARES else rows(0, [20])  # a clean square of an earlier block; the clean query's own
    seen_k = k.at[:, seen].set(jnp.asarray(noise, k.dtype)[:, seen])
    assert not np.array_equal(np.asarray(run(seen_k, v)[0][:, queries], np.float32), np.asarray(mixed[:, queries], np.float32))


@pytest.mark.parametrize("streams", [2, 1])
def test_the_two_heads_of_a_product_get_each_its_own_rows_back(streams):
    """Bit for bit: heads 0 and 1 (one product since PR 63) and head 2 (a lone last head) are given different queries and cotangents; head 0's
    ``mixed`` and ``d_q`` are what they are with head 1's queries and cotangent overwritten, and head 1's move: a ``_rows`` that hands a head
    its neighbour's half, or a stacked norm that reads the neighbour's rows, is seen."""
    q, k, v, g_q, g_k, cotangent = core_inputs(2, 3, 1, 16, streams, seed=11)
    run = jax.jit(lambda q, cotangent: value_and_gradients(lambda *a: board_attention(*a, THETA, EPS, True, block_length=4, streams=streams), q, k, v, g_q, g_k, cotangent)[:2])
    noise = np.random.default_rng(13).standard_normal(q.shape)
    head = lambda x, h: np.asarray(x, np.float32)[..., 16 * h:16 * (h + 1)]
    other_q, other_cotangent = (y.at[..., 16:32].set(jnp.asarray(3.0 * noise, y.dtype)[..., 16:32]) for y in (q, cotangent))
    (mixed, d_q), (other_mixed, other_d_q) = run(q, cotangent), run(other_q, other_cotangent)
    for h in (0, 2):
        assert np.array_equal(head(mixed, h), head(other_mixed, h)) and np.array_equal(head(d_q, h), head(other_d_q, h)) and np.any(head(d_q, h))
    assert not np.array_equal(head(mixed, 1), head(other_mixed, 1)) and not np.array_equal(head(d_q, 1), head(other_d_q, 1))
    assert not np.array_equal(head(mixed, 0), head(mixed, 1)) and not np.array_equal(head(d_q, 0), head(d_q, 1))


# -- the noise and its maker -------------------------------------------------------------------------------------------------------------------


@pytest.mark.parametrize("block_length", [4, 8])
def test_block_noise_is_a_level_a_block_and_a_mask_a_square(block_length):
    level, masked = block_noise(np.random.default_rng(3), 512, block_length, 1e-3)
    again = block_noise(np.random.default_rng(3), 512, block_length, 1e-3)
    assert level.shape == (512, SQUARES // block_length) and level.dtype == np.float32 and masked.shape == (512, SQUARES) and masked.dtype == bool
    assert np.array_equal(level, again[0]) and np.array_equal(masked, again[1]) and 1e-3 <= level.min() and level.max() <= 1.0
    assert abs(level.mean() - 0.5005) < 0.01 and abs(masked.mean() - 0.5005) < 0.01  # t uniform on [t_min, 1]: half the squares in the mean
    by_level = np.repeat(level, block_length, axis=1)
    assert masked[by_level > 0.9].mean() > 0.9 and masked[by_level < 0.1].mean() < 0.1  # a square is masked with its OWN block's level
    floor, _ = block_noise(np.random.default_rng(3), 64, block_length, 0.75)
    assert floor.min() >= 0.75
    for wrong in (dict(block_length=3), dict(block_length=0), dict(t_min=0.0), dict(t_min=1.5)):
        with pytest.raises(ValueError):
            block_noise(np.random.default_rng(0), 4, **{"block_length": block_length, **wrong})


def test_the_benchmarks_batches_carry_the_programs_noise_made_from_their_own_rows():
    pool = {"planes": np.zeros((32, 8, 8, 19), np.float32), "moves": np.zeros((32, 218), np.int32), "legal": np.zeros((32, 218), bool),
            "probs": np.zeros((32, 218), np.float32), "value_target": np.zeros((32,), np.float32)}
    idx = np.asarray([3, 1, 4, 1, 5, 9, 2, 6])
    one, two, other = sdar_family.build_batch(pool, idx), sdar_family.build_batch(pool, idx), sdar_family.build_batch(pool, idx[::-1])
    assert set(one) == {"planes", "policy_target", "value_target", "block_level", "square_masked"} <= set(az_batch_specs())
    assert np.array_equal(one["square_masked"], two["square_masked"]) and np.array_equal(one["block_level"], two["block_level"])
    assert not np.array_equal(one["square_masked"], other["square_masked"]) and one["block_level"].shape == (8, 16) and one["square_masked"].shape == (8, 64)
    want = block_noise(np.random.default_rng([0x626C6F636B, *idx.tolist()]), 8, 4, 1e-3)  # THE maker, nothing drawn beside it
    assert np.array_equal(one["block_level"], want[0]) and np.array_equal(one["square_masked"], want[1])


# -- the block against the benchmark's reference ------------------------------------------------------------------------------------------


def sdar_params(seed: int, model=SDAR_MODEL):
    return {k: jnp.asarray(v) for k, v in sdar_reference.init_params(seed, model).items()}


@functools.lru_cache(maxsize=None)
def sdar_program(block_length: int = 4):
    return sdar_family.loss_and_grads(AzTrainer(dataclasses.replace(SDAR, block_length=block_length)))


# Readings over seeds 1-2 at L = 4 and seed 1 at L = 8 (CPU, a head of 16): all gradients as one vector 0.003-0.004, the worst single tensor wk or q_norm
# 0.007-0.009 but for the cancelling ones (policy_b, value_b 0.04-0.10); the misreadings below read 0.13 and more on the tensors that see them.
SDAR_GRAD_ALL_TOL = 0.02


@pytest.mark.parametrize("seed,block_length", [(1, 4), (2, 4), (1, 8)])
def test_sdar_loss_and_every_gradient_match_the_benchmarks_reference(seed, block_length):
    params, batch = sdar_params(seed), noised_batch(seed, block_length=block_length)
    config = {**SDAR_CONFIG, "model": {**SDAR_MODEL, "block_length": block_length}}
    loss, got = sdar_program(block_length)(params, batch)
    want_loss, want = jax.value_and_grad(sdar_reference.loss)(params, batch, config)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(SDAR)) and {"mask_embed", "denoise_w", "denoise_b"} <= set(want)
    print("sdar", seed, block_length, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.01 * float(want_loss)
    assert _all(got, want) < SDAR_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL / 5), name
    terms = [float(x) for x in sdar_reference.loss_terms(params, batch, config)]
    assert min(terms) > 0.1 * max(terms) and abs(sum(terms) - float(want_loss)) < 1e-4 * float(want_loss)  # three terms of one scale: none rides unseen


#: A misreading of the block -> the tensors whose gradient has to read over the floor beside it (the sound program reads under 0.01 on all of them).
MISREADINGS = {"noised_sees_own_clean": (("wq", "wk"), 0.1), "clean_unmasked": (("wq", "wk"), 0.08), "no_level_weight": (("denoise_w", "mask_embed", "router_w"), 0.3),
               "positions_shifted": (("wq", "wk"), 0.3)}


@pytest.mark.parametrize("wrong", MISREADINGS)
def test_the_tolerance_catches_a_wrong_ninth_block(wrong):
    """A noised query that also sees the clean keys of its own block (the answer leaks); the clean copy bidirectional; the 1 / t weight dropped;
    the noised copy turned by positions 64-127: each reads far over the sound program's error on a tensor that sees it."""
    params, batch = sdar_params(1), noised_batch(1)
    _, got = sdar_program()(params, batch)
    want = jax.grad(sdar_reference.loss)(params, batch, {**SDAR_CONFIG, "model": {**SDAR_MODEL, "misread": wrong}})
    seen_by, floor = MISREADINGS[wrong]
    print("sdar wrong", wrong, {k: round(rel(got[k], want[k]), 3) for k in seen_by})
    assert min(rel(got[name], want[name]) for name in seen_by) > floor


def test_no_masked_square_is_no_denoising_loss_and_no_gradient_into_the_denoiser():
    params = sdar_params(2)
    batch = {**noised_batch(2), "square_masked": jnp.zeros((8, SQUARES), bool)}
    trained, buffers = {k: v for k, v in params.items() if k != "expert_bias"}, {"expert_bias": params["expert_bias"]}
    (loss, metrics), grads = jax.value_and_grad(AzTrainer(SDAR)._loss, has_aux=True)(trained, batch, buffers)
    assert float(metrics["denoise_loss"]) == 0.0 and float(metrics["masked_squares"]) == 0.0 and abs(float(loss) - float(metrics["policy_loss"]) - float(metrics["value_loss"])) < 1e-5
    assert not np.any(np.asarray(grads["denoise_w"])) and not np.any(np.asarray(grads["denoise_b"])) and not np.any(np.asarray(grads["mask_embed"]))
    assert np.any(np.asarray(grads["wq"]))  # the clean stream still learns


# -- what is served ---------------------------------------------------------------------------------------------------------------------------


def test_the_served_forward_is_the_training_forwards_clean_stream_bit_for_bit():
    """``az_forward`` (the ``--engine az-mcts --az-net-file`` call sites') on a block-diffusion trunk is the clean stream alone under its
    block-causal rule: no noise, no second stream, no denoiser, and exactly what the training forward's heads read, whatever the noise."""
    state = AzTrainer(SDAR).init(3)
    params, batch = {**state.params, **state.buffers}, noised_batch(3)
    served = jax.jit(lambda p, x: az_forward(p, x, SDAR))(params, batch["planes"])
    training = jax.jit(lambda p, x, m: trunk.trunk_forward_counted(p, x, SDAR, m))
    policy, value, counters, denoiser = training(params, batch["planes"], batch["square_masked"])
    assert np.array_equal(np.asarray(served[0]), np.asarray(policy)) and np.array_equal(np.asarray(served[1]), np.asarray(value))
    other = training(params, batch["planes"], ~batch["square_masked"])
    assert np.array_equal(np.asarray(other[0]), np.asarray(policy)) and not np.array_equal(np.asarray(other[3]), np.asarray(denoiser))
    assert denoiser.shape == (8, SQUARES, trunk.SQUARE_CLASSES) and denoiser.dtype == jnp.float32
    assert float(counters["moved_rows"]) > 0 and float(jnp.sum(counters["expert_slots"])) == 2 * 8 * 128 * 3  # both copies' tokens are routed: 128 a board
    assert float(jnp.sum(trunk.trunk_forward_counted(params, batch["planes"], SDAR)[2]["expert_slots"])) == 2 * 8 * 64 * 3  # served: 64
    with pytest.raises(ValueError, match="block-diffusion"):
        trunk.trunk_forward_counted(MELLUM_PARAMS(), batch["planes"], MELLUM, batch["square_masked"])
    # the clean copy is NOT the unmasked trunk: a square's features see the board up to the end of its own block (the configuration's ``assumed`` says so)
    unmasked = dataclasses.replace(SDAR, block_length=0)
    shared = {k: v for k, v in params.items() if k in trunk.trunk_param_shapes(unmasked) or k == "expert_bias"}
    assert not np.array_equal(np.asarray(trunk.trunk_forward(shared, batch["planes"], unmasked)[0]), np.asarray(policy))


def MELLUM_PARAMS():
    state = AzTrainer(MELLUM).init(0)
    return {**state.params, **state.buffers}


def test_a_block_diffusion_net_goes_from_the_learner_through_its_file_into_the_search(tmp_path):
    """The normal path end to end: steps on noised batches, the ``.npz`` that ``--az-net-file`` takes, the configuration recovered from it, the
    MCTS pool evaluating positions with it."""
    from fishnet_tpu.search.mcts import MctsConfig, MctsPool

    trainer = AzTrainer(SDAR)
    state = trainer.init(4)
    for step in range(2):
        state, metrics = trainer.step(state, noised_batch(step))
    assert {"denoise_loss", "masked_squares", "noise_level_mean", "held_slots", "moved_rows", "expert_load_max", "router_entropy"} <= set(metrics)
    assert np.isfinite(float(metrics["loss"])) and float(metrics["masked_squares"]) == float(jnp.sum(noised_batch(1)["square_masked"]))
    assert abs(float(metrics["noise_level_mean"]) - float(jnp.mean(noised_batch(1)["block_level"]))) < 1e-6
    trainer.export(state, str(tmp_path / "sdar.npz"))
    loaded = dict(np.load(tmp_path / "sdar.npz"))
    assert az_config_from_params(loaded) == SDAR and loaded[trunk.HPARAMS].shape == (23,) and loaded[trunk.HPARAMS][-1] == 4.0
    assert loaded["mask_embed"].shape == (64,) and loaded["denoise_w"].shape == (64, 13) and loaded["denoise_b"].shape == (13,)
    params = {k: jnp.asarray(v) for k, v in loaded.items() if k != trunk.HPARAMS}
    pool = MctsPool(params, MctsConfig(batch_capacity=16, az=SDAR))
    sid = pool.submit("6k1/5ppp/8/8/8/8/5PPP/3R2K1 w - - 0 1", [], visits=24)
    for _ in range(2000):
        pool.step()
        if pool.active() == 0:
            break
    assert pool.harvest(sid).best_move
    # a file of the eighth block is what it was: no value more
    other = AzTrainer(MELLUM)
    other.export(other.init(0), str(tmp_path / "older.npz"))
    assert dict(np.load(tmp_path / "older.npz"))[trunk.HPARAMS].shape == (22,)
    with pytest.raises(KeyError, match="square_masked"):  # the step draws nothing: a batch without its noise is an error, not a fallback
        trainer.step(trainer.init(0), batch_of(0))


def test_the_learners_own_batches_carry_the_noise():
    from fishnet_tpu.train import selfplay

    games = [selfplay._Game(selfplay.Board(selfplay.STARTPOS), records=[selfplay._Record(np.zeros((8, 8, 19), np.float32), np.zeros(4672, np.float32), True)] * 6,
                            outcome_white=1.0)]
    calls = []
    original = selfplay.play_games
    selfplay.play_games = lambda pool, cfg, seed: calls.append(seed) or games
    try:
        playing = lambda net: types.SimpleNamespace(cfg=types.SimpleNamespace(az=net))  # a pool by the one thing asked of it: the net it plays for
        plain = selfplay.selfplay_batch(playing(MELLUM), seed=5)
        noised = selfplay.selfplay_batch(playing(dataclasses.replace(SDAR, block_length=8)), seed=5)
    finally:
        selfplay.play_games = original
    assert set(plain) == {"planes", "policy_target", "value_target"} and set(noised) == set(plain) | {"block_level", "square_masked"}
    assert noised["block_level"].shape == (6, 8) and noised["square_masked"].shape == (6, 64) and noised["block_level"].min() >= 1e-3
    assert not hasattr(selfplay.SelfPlayConfig(), "block_length")  # L is the net's (TrunkConfig.block_length) and nobody else's


# -- the share tied to the model (guide section 4): sixteen chips share a layer's 128 experts ---------------------------------------------------

UNCUT = {**SDAR_MODEL, "num_hidden_layers": 1, "num_experts": 128, "num_routed_experts": 128, "first_held_expert": 0, "num_experts_per_tok": 8}


@pytest.mark.parametrize("left_out", [None, "a_share", "a_share_counted_twice"])
def test_sixteen_expert_shares_add_up_to_the_uncut_layer(left_out):
    """One layer of the block UNCUT under noise, as the benchmark's reference computes it (all 128 experts, every expert on every token of both
    streams, top-8 renormalised). Against the program's pieces put together as sixteen chips would: attention whole (every chip computes it
    alike: counted once), the routed part as the sum of SIXTEEN shares of 8 experts (routing over all 128, weights renormalised over all eight
    chosen, held or not). Leaving a share out or counting one twice is seen, in both streams."""
    params = sdar_params(5, UNCUT)
    batch = noised_batch(5, 2)
    want = jnp.concatenate(sdar_reference.streams(params, batch["planes"], batch["square_masked"], UNCUT, lambda y: y, lambda y: y), axis=1).reshape(-1, SDAR.hidden)

    share = dataclasses.replace(SDAR, layers=1, experts=128, experts_per_token=8, held_experts=(0, 8))
    tokens, marked = trunk._two_streams(batch["planes"], batch["square_masked"])
    embedded = jnp.dot(tokens, params["embed_w"], precision="highest") + params["embed_b"] + marked * params["mask_embed"]
    x = embedded
    # the reference's columns put the favourite experts first where all are held (``init_params``): the share that is left out or doubled is theirs
    firsts = {None: range(0, 128, 8), "a_share": range(8, 128, 8), "a_share_counted_twice": (0, *range(0, 128, 8))}[left_out]
    for sublayer in trunk.trunk_plan(share, 2):
        own = trunk.sublayer_params(params, sublayer)
        if sublayer.kind != "routed":
            assert (sublayer.kind, sublayer.rope, sublayer.streams) == ("attention", True, 2)
            x = x + trunk._attention(x, own, share, sublayer)[0]
            continue
        n2 = trunk._rms_norm(x, own["moe_norm"], share.rms_eps)
        out = jnp.zeros_like(x)
        for first in firsts:
            held = {**own, **{name: params[name][0, first:first + 8] for name in ("experts_gate", "experts_up", "experts_down")}}
            out = out + trunk._experts(n2, held, dataclasses.replace(share, held_experts=(first, 8)), sublayer.layer)[0]
        x = x + out
    got = trunk._rms_norm(x, params["final_norm"], share.rms_eps)
    start = trunk._rms_norm(embedded, params["final_norm"], share.rms_eps)  # what the layer ADDED, so that the embedding does not hide a share
    by_stream = lambda y: y.reshape(2, 2, SQUARES, -1)
    errors = [rel(by_stream(got - start)[:, copy], by_stream(want - start)[:, copy]) for copy in range(2)]
    print("shares", left_out, errors)
    assert max(errors) < 0.05 if left_out is None else min(errors) > 0.1, (left_out, errors)  # 0.002 whole; 0.15 and more a share out or doubled


# -- the plan, the scopes, the field's refusals, the pin ---------------------------------------------------------------------------------------


def test_the_plan_tells_the_attention_its_streams_and_the_step_runs_under_the_trunks_scopes():
    served, training = trunk.trunk_plan(SDAR), trunk.trunk_plan(SDAR, 2)
    assert [s.kind for s in served] == [s.kind for s in training] == ["attention", "routed"] * 2
    assert [s.streams for s in served] == [1] * 4 and [s.streams for s in training] == [2, 1, 2, 1]  # the core alone is told; a routed sublayer does not know a board
    assert all(a._replace(streams=1) == b for a, b in zip(training, served)) and served == trunk.trunk_plan(SDAR, 1) and training is trunk.trunk_plan(SDAR, 2)
    assert all(s.streams == 1 for cfg, _ in BLOCKS.values() if cfg is not SDAR for s in trunk.trunk_plan(cfg))
    assert set(trunk.trunk_param_shapes(SDAR)) - set(trunk.trunk_param_shapes(dataclasses.replace(SDAR, block_length=0))) == {"mask_embed", "denoise_w", "denoise_b"}
    trainer = AzTrainer(SDAR)
    text = jax.jit(trainer._step).lower(jax.eval_shape(trainer._init, jax.random.PRNGKey(0)), noised_batch(0)).as_text(debug_info=True)
    scopes = set(re.findall(r"(?:jvp\(forward\)|transpose\(jvp\(forward\)\))/([a-z_0-9]+(?:\.[a-z]+)?)/", text))
    layers = {f"layer{i:02d}.{part}" for i in range(2) for part in ("attention", "router", "dispatch", "experts", "combine")}
    assert scopes == layers | {"embed", "final_norm", "policy_head", "value_head", "denoise"}, sorted(scopes)
    assert re.search(r"jvp\(loss\)/denoise/", text) and re.search(r"transpose\(jvp\(loss\)\)/denoise/", text)  # the third term under ``loss``, forward and backward
    assert "board_attention_blocks" in text and "board_attention_blocks_grad" in text and "board_attention_grad" not in text  # the masked pair, never the plain one


FIELDS = {f.name: getattr(SDAR, f.name) for f in dataclasses.fields(SDAR)}
REFUSED = {
    "a_block_of_3": dict(block_length=3), "a_block_over_a_board": dict(block_length=128), "a_negative_block": dict(block_length=-4),
    "beside_a_latent": dict(kv_heads=None, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16), "beside_cca": dict(cca=(2, 2)),
    "beside_a_pattern": dict(pattern="*E*E", qk_norm=True), "beside_mixers": dict(mixers=("attention", "attention")), "without_qk_norm": dict(qk_norm=False),
    "beside_rotary_dim": dict(rotary_dim=8), "beside_a_nope_layer": dict(nope_layers=(1,)),
    "beside_full_attention_layers": dict(full_attention_layers=(1,), rope_type="yarn", rope_factor=16.0, original_max_position_embeddings=2048),
}


@pytest.mark.parametrize("wrong", REFUSED)
def test_the_new_field_is_refused_beside_what_the_masked_core_does_not_compute(wrong):
    with pytest.raises(ValueError, match="block_length"):
        TrunkConfig(**{**FIELDS, **REFUSED[wrong]})


#: sha256 of the tiny lowered step program (``tools/step_text.py --block sdar``), as ``tests/test_hybrid_trunk.py PARENT_STEP_SHA256`` holds the four
#: older blocks': read anew on PR 63's tree, whose blocks pair takes the tiny plan's 4 heads over 1 two a product (``tools/step_text.py --block
#: sdar --no-ids`` on both trees: the diff is the pair's bodies and the interpreter's loops round its two calls a layer, 8 boards a grid step
#: where 1 was and the bias ``[192, 128]``). The eight older blocks' pins (``test_hybrid_trunk.py``, ``test_cca_trunk.py``,
#: ``test_gdn_trunk.py``, ``test_mellum_trunk.py``) pass UNEDITED on it: the masked form is a kernel pair of its own beside theirs.
SDAR_STEP_SHA256 = "c614434c85efc16f1043508f08916f32c1a86ecffc1c6dc4e22cd4cb77bb10fd"


def test_the_ninth_blocks_lowered_step_is_pinned():
    cfg, batch = BLOCKS["sdar"]
    assert cfg is SDAR
    text = lowered_step_text(cfg, batch(1))
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == SDAR_STEP_SHA256, HOW_TO_SEE_WHAT_MOVED.format(block="sdar")
