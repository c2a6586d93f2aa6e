"""The eighth block of the square-token trunk (models/trunk.py with
``TrunkConfig.full_attention_layers``: Mellum2's mellum block, grouped-query
attention without a gate under TWO RoPE tables by layer kind, a renormalised
softmax router over experts of which a share is held, no shared expert, no
dense layer) at a tiny size on the CPU, on a worker of its own: the YaRN
tables against a literal float64 computation, the kernel pair under a
scaled table against ``jax.grad`` of the plain formula, the program against
the benchmark's plain reference, the misreadings the comparison has to see,
the share tied to the model (four expert shares add up to the uncut
reference's layers), the plan and its scopes, the new fields' refusals, the
checkpoint round trip, and its step pin."""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_config_from_params
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.ops.board_attention import board_attention, rope_tables, yarn_rope_tables
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import AFMOE, BLOCKS, CANCELLING, GDN, GRAD_CANCELLING_TOL, GRAD_TENSOR_TOL, MELLUM, MELLUM_CONFIG, MELLUM_MODEL, MELLUM_ROPE, TINY, _all, batch_of, rel  # noqa: E402

# The plain reference is the benchmark's own (benchmark/reference/mellum_trunk.py: the published equations, its own YaRN arithmetic,
# importing nothing of the program), at a tiny size; the program reads its parameters as they are (benchmark/families/mellum_trunk.py:
# no column order to map, ``expert_bias`` split off by the second trunk's adapter).

from benchmark.families import mellum_trunk as mellum_family  # noqa: E402
from benchmark.reference import mellum_trunk as mellum_reference  # noqa: E402
from tools.step_text import HOW_TO_SEE_WHAT_MOVED, lowered_step_text  # noqa: E402

PUBLISHED = dict(theta=500000.0, head_dim=128, factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0, beta_slow=1.0,
                 attention_factor=1.2772588722239782)
SECOND_SET = dict(theta=10000.0, head_dim=64, factor=4.0, original_max_position_embeddings=4096, beta_fast=16.0, beta_slow=2.0, attention_factor=0.1 * math.log(4.0) + 1.0)


def literal_yarn(theta, head_dim, factor, original_max_position_embeddings, beta_fast, beta_slow, attention_factor):
    """YaRN as the paper and the public ``rope_type`` yarn state it, pair by pair in float64 Python numbers: (lo, hi), cos and sin
    ``[64, head_dim // 2]`` with the attention factor in both."""
    def pair_of(turns):
        return head_dim * math.log(original_max_position_embeddings / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(pair_of(beta_fast)), 0), min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    cos, sin = np.zeros((64, head_dim // 2)), np.zeros((64, head_dim // 2))
    for j in range(head_dim // 2):
        ramp = min(max((j - lo) / (hi - lo), 0.0), 1.0)
        plain = theta ** (-2.0 * j / head_dim)
        frequency = (1.0 - ramp) * plain + ramp * plain / factor
        for square in range(64):
            cos[square, j], sin[square, j] = attention_factor * math.cos(square * frequency), attention_factor * math.sin(square * frequency)
    return (lo, hi), cos, sin


@pytest.mark.parametrize("numbers,ramp", [(PUBLISHED, (18, 35)), (SECOND_SET, (12, 21))], ids=["published", "second_set"])
def test_the_yarn_tables_are_the_literal_float64_computation(numbers, ramp):
    """cos ``[64, head_dim]`` both halves alike, the sine with rotate-half's sign on its first half, the attention factor in both
    (cos^2 + sin^2 = factor^2 on every row and pair), the ramp between the published pairs 18 and 35 of 64."""
    (lo, hi), want_cos, want_sin = literal_yarn(**numbers)
    assert (lo, hi) == ramp
    cos, sin = yarn_rope_tables(**numbers)
    half = numbers["head_dim"] // 2
    assert cos.shape == sin.shape == (64, numbers["head_dim"]) and cos.dtype == sin.dtype == np.float32
    assert np.array_equal(cos[:, :half], cos[:, half:]) and np.array_equal(sin[:, :half], -sin[:, half:])
    assert np.max(np.abs(cos[:, :half] - want_cos)) < 1e-6 and np.max(np.abs(sin[:, half:] - want_sin)) < 1e-6
    assert np.allclose(cos.astype(np.float64) ** 2 + sin.astype(np.float64) ** 2, numbers["attention_factor"] ** 2, atol=1e-6)
    plain_cos, plain_sin = rope_tables(numbers["theta"], numbers["head_dim"])
    # the fast pairs (under lo) turn as the plain table's, times the factor; the slow ones (over hi) at 1 / factor of the plain frequency
    assert np.allclose(cos[:, :lo], numbers["attention_factor"] * plain_cos[:, :lo], atol=1e-6) and np.allclose(sin[:, :lo], numbers["attention_factor"] * plain_sin[:, :lo], atol=1e-6)
    slow = 63 * numbers["theta"] ** (-2.0 * (half - 1) / numbers["head_dim"]) / numbers["factor"]
    assert abs(cos[63, half - 1] - numbers["attention_factor"] * math.cos(slow)) < 1e-6


def test_the_published_attention_factor_is_yarns_own_of_its_factor():
    assert abs(PUBLISHED["attention_factor"] - (0.1 * math.log(PUBLISHED["factor"]) + 1.0)) < 1e-12
    assert abs(PUBLISHED["attention_factor"] ** 2 - 1.6314) < 1e-4  # what a full layer's scores are scaled by


# -- the kernel pair under a scaled table ------------------------------------------------------------------------------------------------

EPS = 1e-6
ROUNDING = 2.0 ** -8
#: ``tests/test_board_attention.py``'s count: four roundings on the way to ``mixed``, six on the way to a gradient.
FORWARD_TOL, GRADIENT_TOL = 4 * ROUNDING, 6 * ROUNDING
TINY_YARN = dict(theta=1e4, head_dim=16, factor=16.0, original_max_position_embeddings=2048, beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782)


def plain_under_tables(q, k, v, g_q, g_k, cos, sin, key_factor=1.0):
    """The core from the layer equations in float32, turned by GIVEN tables ``[64, head_dim // 2]`` (unsigned sine)."""
    boards, head_dim = q.shape[0], g_q.shape[0]
    half = head_dim // 2
    split = lambda y: y.astype(jnp.float32).reshape(boards, 64, -1, head_dim)
    norm = lambda x, g: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g
    c, s = (jnp.asarray(np.concatenate([t, t], -1), jnp.float32)[:, None, :] for t in (cos, sin))
    turn = lambda x: x * c + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * s
    q, k, v = turn(norm(split(q), g_q)), turn(norm(split(k), g_k)) * key_factor, split(v)
    k, v = (jnp.repeat(y, q.shape[2] // y.shape[2], axis=2) for y in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(head_dim)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision="highest").reshape(boards, 64, -1)


def core_inputs(boards, heads, kv_heads, head_dim, seed):
    rng = np.random.default_rng(seed)
    shape, kv_shape = (boards, 64, heads * head_dim), (boards, 64, kv_heads * head_dim)
    gain = lambda: jnp.asarray(1.0 + 0.1 * rng.standard_normal(head_dim), jnp.float32)
    return (jnp.asarray(1.5 * rng.standard_normal(shape), jnp.float32), jnp.asarray(1.5 * rng.standard_normal(kv_shape), jnp.float32),
            jnp.asarray(rng.standard_normal(kv_shape), jnp.bfloat16), gain(), gain(), jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))


def value_and_gradients(f, q, k, v, g_q, g_k, cotangent):
    out, pull = jax.vjp(f, q, k, v, g_q, g_k)
    return (out, *pull(cotangent.astype(out.dtype)))


OUTPUTS = ["mixed", "d_q", "d_k", "d_v", "d_q_norm", "d_k_norm"]
#: (heads, key-value heads, boards): group 1 (the first block's geometry) and group 8 (this block's: 2 boards a grid step).
SCALED_CASES = {"group_1": (2, 2, 6), "group_8": (8, 1, 4)}


@functools.lru_cache(maxsize=None)
def both_under_yarn(case):
    heads, kv_heads, boards = SCALED_CASES[case]
    args = core_inputs(boards, heads, kv_heads, 16, seed=7)
    _, cos, sin = literal_yarn(**TINY_YARN)
    tables = yarn_rope_tables(**TINY_YARN)
    kernel = lambda q, k, v, g_q, g_k: board_attention(q, k, v, g_q, g_k, TINY_YARN["theta"], EPS, True, tables=tables)
    return (jax.jit(functools.partial(value_and_gradients, kernel))(*args),
            jax.jit(functools.partial(value_and_gradients, functools.partial(plain_under_tables, cos=cos, sin=sin)))(*args),
            jax.jit(functools.partial(value_and_gradients, functools.partial(plain_under_tables, cos=cos, sin=sin, key_factor=1.0 / TINY_YARN["attention_factor"])))(*args))


@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("case", SCALED_CASES)
def test_the_kernel_pair_under_a_scaled_table_matches_the_plain_formulas_gradient(case, output):
    """Forward and every gradient under YaRN's tables (rows scaled by 1.277: no unit rotations) against ``jax.vjp`` of the plain
    float32 formula turned by the literal tables: the backward kernel's un-rotation is the transpose of the SCALED rotation. The
    same formula with the factor on the query alone (``factor_once``) misses by several times the tolerance."""
    got, want, once = (side[OUTPUTS.index(output)] for side in both_under_yarn(case))
    assert got.shape == want.shape and got.dtype == (jnp.bfloat16 if output in ("mixed", "d_v") else jnp.float32)
    assert rel(got, want) < (FORWARD_TOL if output == "mixed" else GRADIENT_TOL), rel(got, want)
    assert rel(got, once) > 3 * GRADIENT_TOL, rel(got, once)


def test_tables_are_for_the_normed_forms_whole_head_and_nothing_else():
    q, k, v, g_q, g_k, _ = core_inputs(2, 2, 2, 16, seed=1)
    tables = yarn_rope_tables(**TINY_YARN)
    for wrong in (dict(theta=None), dict(rotary_dim=8), dict(q_pe=q, k_pe=k[..., :16])):
        with pytest.raises(ValueError, match="tables"):
            board_attention(q, k, v, g_q, g_k, **{"theta": 1e4, **wrong}, eps=EPS, interpret=True, tables=tables)
    # the plain tables handed in are the layer that makes its own from theta, to the last bit
    told = board_attention(q, k, v, g_q, g_k, 1e4, EPS, True, tables=rope_tables(1e4, 16))
    assert np.array_equal(np.asarray(told, np.float32), np.asarray(board_attention(q, k, v, g_q, g_k, 1e4, EPS, True), np.float32))


# -- the block against the benchmark's reference ------------------------------------------------------------------------------------------


def mellum_params(seed: int, model=MELLUM_MODEL):
    return {k: jnp.asarray(v) for k, v in mellum_reference.init_params(seed, model).items()}


@pytest.fixture(scope="module")
def mellum_program():
    return mellum_family.loss_and_grads(AzTrainer(MELLUM))


# Readings over seeds 1-3 (CPU, a head of 16; the reference's router columns placed, ``init_params``: since REVIEW of PR 56 at ranks 1 of the top-3 and
# 3, 4, 5 past the cut): all gradients as one vector 0.006-0.014, the worst single tensor router_w 0.020-0.060 (moe_norm 0.044 on seed 3) but for the
# cancelling ones (with the columns as drawn: 0.006-0.030 and router_w 0.075 on seed 2); the misreadings below read 0.2 and more on the tensors that
# see them.
MELLUM_GRAD_ALL_TOL = 0.04


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_mellum_loss_and_every_gradient_match_the_benchmarks_reference(mellum_program, seed):
    params, batch = mellum_params(seed), batch_of(seed)
    loss, got = mellum_program(params, batch)
    want_loss, want = jax.value_and_grad(mellum_reference.loss)(params, batch, MELLUM_CONFIG)
    assert not np.any(np.asarray(want.pop("expert_bias"))) and not np.any(np.asarray(got.pop("expert_bias")))
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(MELLUM)) and "wgate" not in want and "shared_up" not in want and "dense_up" not in want
    print("mellum", seed, abs(float(loss) - float(want_loss)) / float(want_loss), _all(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert abs(float(loss) - float(want_loss)) < 0.01 * float(want_loss)
    assert _all(got, want) < MELLUM_GRAD_ALL_TOL
    for name in want:
        assert got[name].shape == want[name].shape and float(jnp.linalg.norm(want[name])) > 0, name
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name
    # the full layer is row 3 of the stacked attention tensors: the YaRN table's own gradients, not diluted by the three plain layers
    assert max(rel(got[name][3], want[name][3]) for name in ("wq", "wk", "q_norm", "k_norm")) < 0.15


#: A misreading of the block -> the tensors whose gradient has to read over the floor beside it (the sound program reads under 0.08 on all of them).
MISREADINGS = {"plain_full_layer": (("wq", "wk"), 0.2), "no_attention_factor": (("wq", "wk"), 0.2), "factor_once": (("wq", "wk"), 0.12), "ramp_swapped": (("wq", "wk"), 0.3),
               "not_renormalised": (("router_w",), 0.2), "renormalised_over_held": (("router_w", "experts_down"), 0.4), "kv_head_mod": (("wq", "wk", "wo"), 0.6)}


@pytest.mark.parametrize("wrong", MISREADINGS)
def test_the_tolerance_catches_a_wrong_eighth_block(mellum_program, wrong):
    """The full layer turned by the sliding layers' plain table; YaRN's frequencies without the attention factor; the factor on the
    query and not on the key; the ramp from ``beta_slow`` to ``beta_fast`` (the fast pairs interpolated); the chosen weights not
    renormalised; renormalised over the HELD chosen alone; query head h on key-value head ``h % kv_heads``: each reads far over
    the sound program's error on a tensor that sees it."""
    params, batch = mellum_params(1), batch_of(1)
    _, got = mellum_program(params, batch)
    want = jax.grad(mellum_reference.loss)(params, batch, {**MELLUM_CONFIG, "model": {**MELLUM_MODEL, "misread": wrong}})
    seen_by, floor = MISREADINGS[wrong]
    print("mellum wrong", wrong, {k: round(rel(got[k], want[k]), 3) for k in seen_by})
    assert min(rel(got[name], want[name]) for name in seen_by) > floor


def test_the_reference_refuses_a_rope_type_it_does_not_know():
    with pytest.raises(ValueError, match="rope_type"):
        mellum_reference._rope_table({"rope_type": "llama3", "rope_theta": 1e4}, 16)
    cos, sin, scale = mellum_reference._rope_table(MELLUM_ROPE["full_attention"], 16)
    (_lo, _hi), want_cos, want_sin = literal_yarn(**TINY_YARN)
    assert scale == TINY_YARN["attention_factor"] and np.allclose(scale * cos, want_cos, atol=1e-12) and np.allclose(scale * sin, want_sin, atol=1e-12)


# -- the share tied to the model (guide section 4): four chips share a layer's sixteen experts --------------------------------------------

UNCUT = {**MELLUM_MODEL, "kept_layer_types": ["sliding_attention", "full_attention"], "num_hidden_layers": 2, "num_experts": 16, "first_held_expert": 0}


@pytest.mark.parametrize("left_out", [None, "a_share", "a_share_counted_twice", "renormalised_a_share"])
def test_four_expert_shares_add_up_to_the_uncut_layers(left_out):
    """Two layers of the block UNCUT, as the benchmark's reference computes them (all 16 experts, every expert on every token): a
    sliding and a full layer, each with its routed feed-forward. Against the program's pieces put together as four chips would:
    attention whole (every chip computes it alike: counted once), the routed part as the sum of FOUR expert shares (4 of 16
    each, routing over all 16, weights renormalised over all three chosen, held or not). Leaving a share out, counting one
    twice, or renormalising each share over its own held chosen is seen."""
    params = mellum_params(5, UNCUT)
    planes = batch_of(5)["planes"]
    want = mellum_reference.features(params, planes, UNCUT, lambda y: y, lambda y: y).reshape(-1, MELLUM.hidden)

    share = dataclasses.replace(MELLUM, layers=2, full_attention_layers=(1,), held_experts=(0, 4))
    embedded = jnp.dot(planes.reshape(-1, 19), params["embed_w"], precision="highest") + params["embed_b"]  # float32: the embedding is no share's
    x = embedded
    for sublayer in trunk.trunk_plan(share):
        own = trunk.sublayer_params(params, sublayer)
        if sublayer.kind != "routed":
            assert (sublayer.kind, sublayer.rope, sublayer.rope_type) == ("attention", True, "yarn" if sublayer.layer == "layer01" else "default")
            x = x + trunk._attention(x, own, share, sublayer)[0]
            continue
        n2 = trunk._rms_norm(x, own["moe_norm"], share.rms_eps)
        full = {name: params[name][int(sublayer.layer[-2:])] for name in ("experts_gate", "experts_up", "experts_down")}
        out = jnp.zeros_like(x)
        # the reference's columns put the favourite experts first where all 16 are held (``init_params``): the share that is left out or doubled is theirs
        firsts = {None: (0, 4, 8, 12), "a_share": (4, 8, 12), "a_share_counted_twice": (0, 0, 4, 8, 12), "renormalised_a_share": (0, 4, 8, 12)}[left_out]
        for first in firsts:
            cfg = dataclasses.replace(share, held_experts=(first, 4))
            held = {**own, **{name: full[name][first:first + 4] for name in full}}
            part = trunk._experts(n2, held, cfg, sublayer.layer)[0]
            if left_out == "renormalised_a_share":  # each chip's held weights made to add up to 1: what ``renormalised_over_held`` misreads
                expert, weight, _ = trunk._route(n2, held, cfg)
                mine = jnp.sum(jnp.where((expert >= first) & (expert < first + 4), weight, 0.0), axis=-1, keepdims=True)
                part = part / jnp.maximum(mine, 1e-6)
            out = out + part
        x = x + out
    got = trunk._rms_norm(x, params["final_norm"], share.rms_eps)
    # what the two layers ADDED to the stream, so that the embedding (sqrt(hidden) times the branches' scale) does not hide a share
    start = trunk._rms_norm(embedded, params["final_norm"], share.rms_eps)
    error = rel(got - start, want - start)
    print("shares", left_out, error)
    assert error < 0.05 if left_out is None else error > 0.15, (left_out, error)


# -- the plan, the scopes, the fields' refusals, the checkpoint, the pin ---------------------------------------------------------------------


def test_the_plan_tells_each_attention_sublayer_its_table_and_the_step_runs_under_the_trunks_scopes():
    plan = trunk.trunk_plan(MELLUM)
    assert [(s.layer, s.kind, s.index, s.norm, s.rope, s.rope_type) for s in plan if s.kind == "attention"] == [
        ("layer00", "attention", 0, "attn_norm", True, "default"), ("layer01", "attention", 1, "attn_norm", True, "default"),
        ("layer02", "attention", 2, "attn_norm", True, "default"), ("layer03", "attention", 3, "attn_norm", True, "yarn")]
    assert [s.kind for s in plan] == ["attention", "routed"] * 4 and all(s.post_norm is None and s.rope_type == "default" for s in plan if s.kind == "routed")
    assert all(s.rope_type == "default" for cfg, _ in BLOCKS.values() if cfg is not MELLUM for s in trunk.trunk_plan(cfg))  # the seven older blocks: one table a trunk
    assert set(trunk.trunk_param_shapes(MELLUM)) == set(trunk.trunk_param_shapes(dataclasses.replace(TINY, kv_heads=2)))  # the first kind's tensors, no ``wgate``: no new row of ``_OWNS``
    trainer = AzTrainer(MELLUM)
    text = jax.jit(trainer._step).lower(jax.eval_shape(trainer._init, jax.random.PRNGKey(0)), batch_of(0)).as_text(debug_info=True)
    scopes = set(re.findall(r"(?:jvp\(forward\)|transpose\(jvp\(forward\)\))/([a-z_0-9]+(?:\.[a-z]+)?)/", text))
    layers = {f"layer{i:02d}.{part}" for i in range(4) for part in ("attention", "router", "dispatch", "experts", "combine")}
    assert scopes == layers | {"embed", "final_norm", "policy_head", "value_head"}, sorted(scopes ^ (layers | {"embed", "final_norm", "policy_head", "value_head"}))


def test_the_eighth_blocks_counters_checkpoint_and_refusals(tmp_path):
    trainer = AzTrainer(MELLUM)
    state, metrics = trainer.step(trainer.init(0), batch_of(0))
    assert {"held_slots", "moved_rows", "expert_load_max", "router_entropy"} <= set(metrics) and "latent_rms" not in metrics and "shared_gate_mean" not in metrics
    assert 0 < float(metrics["held_slots"]) <= 4 * 8 * 64 * 3 and np.isfinite(float(metrics["loss"]))
    trainer.export(state, str(tmp_path / "mellum.npz"))
    loaded = dict(np.load(tmp_path / "mellum.npz"))
    assert az_config_from_params(loaded) == MELLUM  # the layer kinds and YaRN's numbers from trunk_hparams, everything else from shapes
    assert loaded[trunk.HPARAMS].shape == (len(trunk._HPARAMS) + len(trunk._ROPE_HPARAMS),) and list(loaded[trunk.HPARAMS][-7:]) == [8.0, 1.0, 16.0, 2048.0, 32.0, 1.0, 1.2772588722239782]
    assert loaded["wq"].shape == (4, 64, 128) and loaded["wk"].shape == (4, 64, 32) and "wgate" not in loaded and trunk.MIXERS not in loaded
    planes = batch_of(0)["planes"]
    restored = {k: jnp.asarray(v) for k, v in loaded.items() if k != trunk.HPARAMS}
    want = trunk.trunk_forward({**state.params, **state.buffers}, planes, MELLUM)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(trunk.trunk_forward(restored, planes, az_config_from_params(loaded)), want))
    # an older file (the layer kinds' values cut off: what every trunk wrote before this block) reads as ONE plain table, and is another net
    older = {**loaded, trunk.HPARAMS: loaded[trunk.HPARAMS][:len(trunk._HPARAMS)]}
    plain = dataclasses.replace(MELLUM, full_attention_layers=(), rope_type="default", rope_factor=1.0, original_max_position_embeddings=0, attention_factor=1.0)
    assert az_config_from_params(older) == plain
    assert not np.array_equal(np.asarray(trunk.trunk_forward(restored, planes, plain)[0]), np.asarray(want[0]))
    for cfg in (AFMOE, GDN):  # a file of an older block is what it was: no value more, and it loads as it did
        other = AzTrainer(cfg)
        other.export(other.init(0), str(tmp_path / "older.npz"))
        written = dict(np.load(tmp_path / "older.npz"))
        assert written[trunk.HPARAMS].shape == (len(trunk._HPARAMS),) and az_config_from_params(written) == cfg
    with pytest.raises(ValueError, match="values"):
        az_config_from_params({**loaded, trunk.HPARAMS: np.concatenate([loaded[trunk.HPARAMS], [0.0]])})


FIELDS = {f.name: getattr(MELLUM, f.name) for f in dataclasses.fields(MELLUM)}
REFUSED = {
    "a_layer_that_is_none": dict(full_attention_layers=(4,)), "a_nope_layer": dict(nope_layers=(3,)), "beside_rotary_dim": dict(rotary_dim=8),
    "beside_a_latent": dict(kv_heads=None, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16), "beside_cca": dict(cca=(2, 2)),
    "an_unknown_rope_type": dict(rope_type="llama3"), "yarn_without_its_layers": dict(full_attention_layers=()), "a_factor_under_1": dict(rope_factor=0.5),
    "no_original_context": dict(original_max_position_embeddings=0), "betas_swapped": dict(beta_fast=1.0, beta_slow=32.0), "no_attention_factor": dict(attention_factor=0.0),
    "yarns_numbers_beside_default": dict(rope_type="default", full_attention_layers=()), "a_window_under_a_board": dict(sliding_window=32),
    # both kinds plain: the layers named would turn as every other layer does, a combination that says nothing (name none)
    "full_layers_under_the_default_table": dict(rope_type="default", rope_factor=1.0, original_max_position_embeddings=0, attention_factor=1.0),
}


@pytest.mark.parametrize("wrong", REFUSED)
def test_each_new_field_is_refused_beside_what_cannot_have_it(wrong):
    with pytest.raises(ValueError):
        TrunkConfig(**{**FIELDS, **REFUSED[wrong]})


def test_full_attention_layers_name_attention_layers_of_the_other_layouts_too():
    hybrid, _ = BLOCKS["hybrid"]  # pattern MEMEM*E: layer 5 is its attention layer
    yarn = dict(rope_type="yarn", rope_factor=16.0, original_max_position_embeddings=2048, attention_factor=1.2772588722239782)
    assert [s.rope_type for s in trunk.trunk_plan(dataclasses.replace(hybrid, full_attention_layers=(5,), **yarn)) if s.kind == "attention"] == ["yarn"]
    with pytest.raises(ValueError, match="not attention layers"):
        dataclasses.replace(hybrid, full_attention_layers=(0,), **yarn)  # a Mamba-2 mixer
    with pytest.raises(ValueError, match="not attention layers"):
        dataclasses.replace(GDN, rotary_dim=None, full_attention_layers=(0,), **yarn)  # a GDN mixer


#: sha256 of the tiny lowered step program (``tools/step_text.py --block mellum``), as ``tests/test_hybrid_trunk.py PARENT_STEP_SHA256`` holds the four
#: older blocks': read on PR 56's tree, which brought the block. The seven older blocks' pins (``test_hybrid_trunk.py``, ``test_cca_trunk.py``,
#: ``test_gdn_trunk.py``) pass UNEDITED on it: the kernel pair is told its tables and their lowered steps are the parent's. PR 61 MEANT
#: to move it (the kernel pair's query heads two a product, two pairs a key-value head here; PR 60 brought the same change, was measured by the driver and refused on one pair of runs of ``train_pos_per_s``, its tree thrown away; PR 61 asked again): read anew on PR 61's tree, its parent
#: 8be8117 read 8e5c63d1...1ac0; the ``tools/step_text.py --block mellum --no-ids`` dumps differ inside the two kernels' calls alone, 190 -> 134
#: ``stablehlo.dot_general``, 66 loops both: with the parent's two bodies (``tests/test_board_attention.py PARENT_BODIES``) and its 16 (board, head)s a step patched over the module, the text hashes to the parent's pin.
MELLUM_STEP_SHA256 = "beb7b7a96e7367c1d16c228f6ce01c8884cd8a20daf366b5ee7ae2f1eddbadaf"


def test_the_eighth_blocks_lowered_step_is_pinned():
    cfg, batch = BLOCKS["mellum"]
    assert cfg is MELLUM
    text = lowered_step_text(cfg, batch(1))
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == MELLUM_STEP_SHA256, HOW_TO_SEE_WHAT_MOVED.format(block="mellum")
