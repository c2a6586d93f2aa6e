"""The second block of the square-token trunk (models/trunk.py: Trinity-Mini's
afmoe block, a share of whose experts is held) at a tiny size on the CPU,
split by PR 46 from ``test_moe_trunk.py`` (which keeps the first block and
the mechanisms every block shares) so that each runs on a worker of its
own: the program against a plain reference written from the layer
equations, wrong references against the tolerances, the shares of an
expert layer against the uncut layer, the balance update, the checkpoint,
and what the extent of a share's moves and the recomputed routed branch
may not change."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_checkpoint, az_config_from_params, az_forward, init_az_params
from fishnet_tpu.models.trunk import TrunkConfig, trunk_forward
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import (  # noqa: E402
    AFMOE,
    BATCH,
    CANCELLING,
    GRAD_ALL_TOL,
    GRAD_CANCELLING_TOL,
    GRAD_TENSOR_TOL,
    LOGITS_TOL,
    TINY,
    VALUE_TOL,
    _norm,
    _rope,
    batch_of,
    conditioned_params,
    rel,
)


def afmoe_params(seed: int, cfg: TrunkConfig = AFMOE):
    """``conditioned_params`` and an ``expert_bias`` of a few balance
    steps (multiples of the rate, each layer's mean zero)."""
    params = conditioned_params(seed, cfg)
    rng = np.random.default_rng(seed + 1000)
    bias = cfg.balance_rate * rng.integers(-3, 4, (cfg.routed_layers, cfg.experts))
    params["expert_bias"] = jnp.asarray(bias - bias.mean(-1, keepdims=True), jnp.float32)
    return params


def _gated(n, p, kind, i):
    return (jax.nn.silu(n @ p[f"{kind}_gate"][i]) * (n @ p[f"{kind}_up"][i])) @ p[f"{kind}_down"][i]


def afmoe_weights(n, router_w, bias, cfg, wrong=""):
    """[.., experts] combine weights, zero off the chosen: sigmoid scores,
    the choice on score + bias, renormalised over all the chosen, scaled."""
    score = jax.nn.sigmoid(n @ router_w)
    chosen = score + (0.0 if wrong == "no_bias" else bias)
    kth = jnp.sort(chosen, -1)[..., -cfg.experts_per_token][..., None]
    picked = jnp.where(chosen >= kth, score, 0.0)
    if wrong != "not_renormalised":
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return picked * cfg.route_scale * (1.5 if wrong == "scale" else 1.0)


def afmoe_reference_forward(p, planes, cfg, wrong=""):
    """The second block's layer equations (models/trunk.py), float32,
    every HELD expert applied to every token and masked by the choice.
    ``wrong`` leaves one piece of the mathematics out."""
    b, group = planes.shape[0], cfg.heads // cfg.kv_heads
    first, count = cfg.held
    x = (planes.reshape(b, 64, 19) @ p["embed_w"] + p["embed_b"]) * cfg.embed_scale
    for i in range(cfg.layers):
        n1 = _norm(x, p["attn_norm"][i], cfg.rms_eps)
        q = _norm((n1 @ p["wq"][i]).reshape(b, 64, cfg.heads, cfg.head_dim), p["q_norm"][i], cfg.rms_eps)
        k = _norm((n1 @ p["wk"][i]).reshape(b, 64, cfg.kv_heads, cfg.head_dim), p["k_norm"][i], cfg.rms_eps)
        v = (n1 @ p["wv"][i]).reshape(b, 64, cfg.kv_heads, cfg.head_dim)
        if (i not in cfg.nope_layers) != (wrong == "rope_swapped"):
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        k, v = (jnp.repeat(y, group, axis=2) for y in (k, v))  # query head h attends key-value head h // group
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(cfg.head_dim)
        near = np.abs(np.arange(64)[:, None] - np.arange(64)[None, :]) < cfg.sliding_window
        if i not in cfg.nope_layers:
            scores = jnp.where(near, scores, -1e30)  # the window, applied literally: all true on a board
        mixed = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v).reshape(b, 64, -1)
        if wrong != "no_gate":
            mixed = mixed * jax.nn.sigmoid(n1 @ p["wgate"][i])
        x = x + _norm(mixed @ p["wo"][i], p["post_attn_norm"][i], cfg.rms_eps)
        n2 = _norm(x, p["moe_norm"][i], cfg.rms_eps)
        r = i - cfg.dense_layers
        if r < 0:
            out = _gated(n2, p, "dense", i)
        else:
            weights = afmoe_weights(n2, p["router_w"][r], p["expert_bias"][r], cfg, wrong)
            out = 0.0 if wrong == "no_shared" else _gated(n2, p, "shared", r)
            for e in range(count):
                act = jax.nn.silu(n2 @ p["experts_gate"][r, e]) * (n2 @ p["experts_up"][r, e])
                out = out + weights[..., first + e, None] * (act @ p["experts_down"][r, e])
        x = x + (out if wrong == "no_post_norm" else _norm(out, p["post_mlp_norm"][i], cfg.rms_eps))
    x = _norm(x, p["final_norm"], cfg.rms_eps)
    logits = (x @ p["policy_w"][0, 0] + p["policy_b"]).reshape(b, -1)
    v = jax.nn.relu(x @ p["value_w"][0, 0] + p["value_b"]).reshape(b, -1)
    v = jax.nn.relu(v @ p["value_fc1_w"] + p["value_fc1_b"])
    return logits, jnp.tanh(v @ p["value_fc2_w"] + p["value_fc2_b"])[:, 0]


def afmoe_reference_loss(p, batch, cfg, wrong=""):
    logits, value = afmoe_reference_forward(p, batch["planes"], cfg, wrong)
    policy = -jnp.mean(jnp.sum(batch["policy_target"] * jax.nn.log_softmax(logits, -1), -1))
    return policy + jnp.mean((value - batch["value_target"]) ** 2)


@pytest.fixture(scope="module")
def afmoe_program():
    trainer = AzTrainer(AFMOE)
    forward = jax.jit(lambda p, x: trunk_forward(p, x, AFMOE))
    split = lambda p: ({k: v for k, v in p.items() if k != "expert_bias"}, {"expert_bias": p["expert_bias"]})
    grad = jax.jit(lambda p, b: jax.grad(lambda q: trainer._loss(q, b, split(p)[1])[0])(split(p)[0]))
    return forward, grad


AFMOE_WRONG = ["no_gate", "no_shared", "scale", "not_renormalised", "rope_swapped", "no_post_norm"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_afmoe_forward_matches_the_plain_reference(afmoe_program, seed):
    params, batch = afmoe_params(seed), batch_of(seed)
    logits, value = afmoe_program[0](params, batch["planes"])
    want_logits, want_value = afmoe_reference_forward(params, batch["planes"], AFMOE)
    print("afmoe forward", seed, rel(logits, want_logits), float(jnp.max(jnp.abs(value - want_value))))
    assert rel(logits, want_logits) < LOGITS_TOL, rel(logits, want_logits)
    assert float(jnp.max(jnp.abs(value - want_value))) < VALUE_TOL


@pytest.mark.parametrize("wrong", AFMOE_WRONG)
def test_the_tolerance_catches_left_out_afmoe_mathematics(afmoe_program, wrong):
    params, batch = afmoe_params(1), batch_of(1)
    logits, _value = afmoe_program[0](params, batch["planes"])
    missed = rel(logits, afmoe_reference_forward(params, batch["planes"], AFMOE, wrong)[0])
    print("afmoe wrong", wrong, missed)
    assert missed > 1.5 * LOGITS_TOL, (wrong, missed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_afmoe_gradient_of_the_trainers_loss_matches_the_plain_reference(afmoe_program, seed):
    params, batch = afmoe_params(seed), batch_of(seed)
    got = afmoe_program[1](params, batch)
    want = jax.grad(afmoe_reference_loss)(params, batch, AFMOE)
    assert not np.any(np.asarray(want.pop("expert_bias")))  # no gradient through the bias or the choice
    assert set(got) == set(want) == set(trunk.trunk_param_shapes(AFMOE))
    total = lambda a, b: np.sqrt(sum(float(jnp.sum((a[k] - b[k]) ** 2)) for k in b) / sum(float(jnp.sum(b[k] ** 2)) for k in b))
    print("afmoe grad", seed, total(got, want), {k: round(rel(got[k], want[k]), 4) for k in want})
    assert total(got, want) < GRAD_ALL_TOL
    for name in want:
        assert float(jnp.linalg.norm(want[name])) > 0, name  # every tensor has a gradient to compare
        assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name
    for wrong in ("no_gate", "scale"):
        assert total({**got, "expert_bias": 0.0 * params["expert_bias"]}, jax.grad(afmoe_reference_loss)(params, batch, AFMOE, wrong)) > 1.5 * GRAD_ALL_TOL, wrong


@pytest.mark.parametrize("block", ["afmoe_4_shares_of_4", "mla_16_shares_of_8"])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(block):
    """16 experts in 4 shares of 4, as four chips of an expert-parallel
    deployment hold them: each share routes over all 16, computes its own
    experts' part for the tokens routed to them and leaves the rest out.
    The four routed parts and the shared expert ONCE are the uncut
    reference layer; a share alone is not; and every slot falls in
    exactly one share (``held_slots`` of the four add up to all slots).
    The third block's case (below, after its reference): 128 experts in 16
    shares of 8, top-6, two shared experts, latent attention counted once."""
    if block.startswith("mla"):
        from test_mla_trunk import _the_sixteen_shares_of_a_latent_layer_add_up  # the third block's file, beside its reference

        return _the_sixteen_shares_of_a_latent_layer_add_up()
    whole = TrunkConfig(hidden=64, heads=4, head_dim=16, layers=1, experts=16, experts_per_token=4, expert_width=32,
                        shared_width=32, router_score="sigmoid", route_norm=True, route_scale=2.826, balance_rate=0.001)
    rng = np.random.default_rng(11)
    layer = trunk.sublayer_params(afmoe_params(11, whole), trunk.trunk_plan(whole)[1])
    assert layer.pop("moe_norm").shape == (64,) and set(layer) <= set(trunk._OWNS["routed"])
    n2 = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    weights = afmoe_weights(n2, layer["router_w"], layer["expert_bias"], whole)
    expert = lambda e: (jax.nn.silu(n2 @ layer["experts_gate"][e]) * (n2 @ layer["experts_up"][e])) @ layer["experts_down"][e]
    want = _gated(n2, {k: v[None] for k, v in layer.items()}, "shared", 0) + sum(weights[:, e, None] * expert(e) for e in range(16))

    def share(first):
        cfg = TrunkConfig(**{**whole.__dict__, "held_experts": (first, 4)})
        held = {k: (v[first:first + 4] if k.startswith("experts_") else v) for k, v in layer.items()}
        mixed, counters = jax.jit(lambda n, l: trunk._experts(n, l, cfg, "layer00"))(n2, held)
        return mixed, counters["expert_slots"]

    parts = [share(first) for first in (0, 4, 8, 12)]
    for first, (mixed, slots) in zip((0, 4, 8, 12), parts):
        own = sum(weights[:, e, None] * expert(e) for e in range(first, first + 4))
        assert rel(mixed, own) < 0.02, (first, rel(mixed, own))
        assert np.array_equal(slots, np.asarray((weights > 0).sum(0)))  # every share counts all 16 experts' slots alike
    total = sum(mixed for mixed, _ in parts) + trunk._gated_ffn(n2, layer, "shared")
    assert rel(total, want) < 0.02, rel(total, want)
    assert rel(parts[0][0] + trunk._gated_ffn(n2, layer, "shared"), want) > 0.3  # one share is not the layer
    # the uncut program (all 16 held, no offset) is the same sum
    uncut, _ = jax.jit(lambda n, l: trunk._experts(n, l, whole, "layer00"))(n2, layer)
    assert rel(uncut, sum(mixed for mixed, _ in parts)) < 0.01
    # the gradient to a share's weights comes from its own slots alone, and an absent expert's rows pass none back
    cfg = TrunkConfig(**{**whole.__dict__, "held_experts": (4, 4)})
    held = {k: (v[4:8] if k.startswith("experts_") else v) for k, v in layer.items()}
    loss = lambda n, l: jnp.sum(trunk._experts(n, l, cfg, "layer00")[0] ** 2)
    d_n2, d_layer = jax.jit(jax.grad(loss, (0, 1)))(n2, held)
    plain = lambda n, l: jnp.sum(sum(afmoe_weights(n, l["router_w"], l["expert_bias"], whole)[:, 4 + e, None] * (
        (jax.nn.silu(n @ l["experts_gate"][e]) * (n @ l["experts_up"][e])) @ l["experts_down"][e]) for e in range(4)) ** 2)
    want_n2, want_layer = jax.grad(plain, (0, 1))(n2, held)
    assert rel(d_n2, want_n2) < 0.05, rel(d_n2, want_n2)
    for name in ("experts_gate", "experts_up", "experts_down", "router_w"):
        assert rel(d_layer[name], want_layer[name]) < 0.05, (name, rel(d_layer[name], want_layer[name]))
    assert not np.any(np.asarray(d_layer["expert_bias"]))


def test_the_balance_update_against_a_hand_count():
    """Four experts, mean load 10: the one over it goes down by the rate,
    the two under it up, the one at it stays, and the layer's mean change
    (+0.001 / 4) is taken out of all four."""
    bias = jnp.asarray([[0.0, 0.002, -0.001, 0.0], [0.0, 0.0, 0.0, 0.0]], jnp.float32)
    slots = jnp.asarray([[25.0, 3.0, 2.0, 10.0], [10.0, 10.0, 10.0, 10.0]], jnp.float32)
    got = np.asarray(trunk.balanced_bias(bias, slots, 0.001))
    assert np.allclose(got[0], [-0.001 - 0.00025, 0.003 - 0.00025, 0.0 - 0.00025, -0.00025], atol=1e-9)
    assert np.allclose(got[1], 0.0)  # an even layer does not move
    # the trainer applies it to the state's buffer from the step's own routing, and AdamW never sees the buffer
    trainer = AzTrainer(AFMOE, learning_rate=1e-3)
    state, batch = trainer.init(3), batch_of(3)
    assert set(state.buffers) == {"expert_bias"} and "expert_bias" not in state.params
    assert jax.tree_util.tree_structure(state.opt_state) == jax.tree_util.tree_structure(trainer.optimizer.init(state.params))
    _, _, counters = jax.jit(lambda p, x: trunk.trunk_forward_counted(p, x, AFMOE))({**state.params, **state.buffers}, batch["planes"])
    new, metrics = trainer.step(state, batch)  # donates ``state``
    want = trunk.balanced_bias(jnp.zeros((2, 16)), counters["expert_slots"], 0.001)
    assert np.allclose(new.buffers["expert_bias"], want, atol=1e-9) and float(jnp.max(jnp.abs(want))) > 0
    assert "expert_slots" not in metrics and float(metrics["expert_bias_abs_max"]) == 0.0  # the bias the step's forward read
    assert float(metrics["held_slots"]) == float(jnp.sum(counters["expert_slots"][:, 4:12]))
    assert float(jnp.sum(counters["expert_slots"])) == 2 * BATCH * 64 * 4  # every slot of both routed layers is counted
    _, metrics = trainer.step(new, batch)
    assert 0.0 < float(metrics["expert_bias_abs_max"]) <= 0.002


def test_a_window_shorter_than_a_board_is_refused():
    with pytest.raises(ValueError, match="sliding_window 32 is under the 64 tokens"):
        TrunkConfig(sliding_window=32)
    assert TrunkConfig(sliding_window=64).sliding_window == 64  # masks nothing: |i - j| < 64 on a board
    for wrong in (dict(heads=4, kv_heads=3), dict(router_score="tanh"), dict(held_experts=(60, 8)),
                  dict(dense_layers=1), dict(layers=2, dense_layers=1), dict(nope_layers=(1,))):
        with pytest.raises(ValueError):
            TrunkConfig(**wrong)


@pytest.fixture(scope="module")
def afmoe_trainer():
    """The tiny second block's trainer for the tests that take a whole step of it: the step compiles once a file."""
    return AzTrainer(AFMOE)


def test_afmoe_checkpoint_round_trips_and_the_first_blocks_files_still_load(afmoe_trainer, tmp_path):
    trainer = afmoe_trainer
    state, _ = trainer.step(trainer.init(0), batch_of(0))
    trainer.export(state, str(tmp_path / "afmoe.npz"))
    loaded = dict(np.load(tmp_path / "afmoe.npz"))
    assert az_config_from_params(loaded) == AFMOE
    assert np.array_equal(loaded["expert_bias"], state.buffers["expert_bias"]) and np.any(loaded["expert_bias"])
    logits, value = jax.jit(lambda p, x: az_forward(p, x, AFMOE))(loaded, batch_of(0)["planes"])
    assert logits.shape == (BATCH, 4672) and bool(jnp.all(jnp.isfinite(value)))
    # a file written before the second block carries three hyperparameters; the rest default to the first block
    old = az_checkpoint(init_az_params(jax.random.PRNGKey(0), TINY), TINY)
    old["trunk_hparams"] = old["trunk_hparams"][:3]
    assert az_config_from_params(old) == TINY
    with pytest.raises(ValueError, match="mismatched"):
        az_config_from_params({k: v for k, v in loaded.items() if k != "expert_bias"})


def test_recomputing_the_routed_branch_changes_no_number():
    """``recompute_experts`` keeps nothing of slot size for the backward
    pass and makes it again there: the loss and every gradient are the
    ones the kept intermediates give, bit for bit, and the backward
    pass's operations keep their layer's scope names at the second level
    of the path, where the benchmark's scope table reads them."""
    again = TrunkConfig(**{**AFMOE.__dict__, "recompute_experts": True})
    params, batch = afmoe_params(4), batch_of(4)
    trained = {k: v for k, v in params.items() if k != "expert_bias"}
    grads = {}
    for cfg in (AFMOE, again):
        trainer = AzTrainer(cfg)
        grads[cfg] = jax.jit(jax.value_and_grad(lambda q, t=trainer: t._loss(q, batch, {"expert_bias": params["expert_bias"]})[0]))(trained)
    assert float(grads[AFMOE][0]) == float(grads[again][0])
    for name, want in grads[AFMOE][1].items():
        assert np.array_equal(np.asarray(want), np.asarray(grads[again][1][name])), name
    trainer = AzTrainer(again)
    state = trainer.init(0)
    text = trainer._step_jit.lower(state, batch).compile().as_text()
    import re
    names = set(re.findall(r'op_name="jit\(_step\)/(transpose\(jvp\(forward\)\)/layer\d+\.\w+)/', text))
    assert {f"transpose(jvp(forward))/layer0{i}.{part}" for i in (1, 2) for part in ("dispatch", "experts", "combine")} <= names, names


def _afmoe_loss_and_grads(cfg, params, batch):
    trainer = AzTrainer(cfg)
    trained = {k: v for k, v in params.items() if k != "expert_bias"}
    return jax.jit(jax.value_and_grad(lambda q: trainer._loss(q, batch, {"expert_bias": params["expert_bias"]}), has_aux=True))(trained)


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
def test_the_extent_of_a_shares_moves_changes_no_number(monkeypatch, recompute):
    """A share (experts 4-11 of 16: not the first) moves the rows of its
    extent alone and leaves NaN in every tail (the interpreter's
    uninitialised memory); moved in full, the same tails hold the zeros
    ``gmm`` writes for absent experts. The loss and every gradient are
    the same to the bit: nothing reads a tail but through a select."""
    cfg = TrunkConfig(**{**AFMOE.__dict__, "recompute_experts": recompute})
    params, batch = afmoe_params(6), batch_of(6)
    (loss, aux), grads = _afmoe_loss_and_grads(cfg, params, batch)
    assert 0 < float(aux["held_slots"]) < 2 * BATCH * 64 * 4 and float(aux["moved_rows"]) < 2 * BATCH * 64 * 4  # a share indeed
    monkeypatch.setattr(trunk, "_extent", lambda held: None)  # the oracle: every move in full
    (full_loss, _), full_grads = _afmoe_loss_and_grads(cfg, params, batch)
    assert np.isfinite(float(loss)) and float(loss) == float(full_loss)
    for name, want in full_grads.items():
        assert np.array_equal(np.asarray(grads[name]), np.asarray(want)), name


@pytest.mark.parametrize("bias,held_share", [(10.0, 1.0), (-10.0, 0.0)], ids=["every_slot_held", "no_slot_held"])
def test_a_share_is_dropless_at_both_ends(bias, held_share):
    """A choice pushed wholly onto the held experts (8 of 16 held, top-4),
    and wholly off them: the moves cover every row, or one block of a
    kernel that has nothing to move, and the step's loss and gradients
    are the plain reference's, all finite. Nothing is capped either way."""
    # Seed 2: its choices stand clear of ties. Seed 7, used through PR 35, has two tokens whose fourth and fifth expert in the second
    # routed layer are one rounding apart; PR 36's products round differently, the two flipped, and the gradients read 0.102 against 0.1.
    params, batch = afmoe_params(2), batch_of(2)
    push = jnp.zeros((AFMOE.routed_layers, AFMOE.experts)).at[:, 4:12].set(bias)  # sigmoid scores lie in (0, 1)
    params["expert_bias"] = push
    (loss, aux), got = _afmoe_loss_and_grads(AFMOE, params, batch)
    slots = AFMOE.routed_layers * BATCH * 64 * AFMOE.experts_per_token
    assert float(aux["held_slots"]) == held_share * slots and float(aux["moved_rows"]) == held_share * slots
    want_loss, want = jax.value_and_grad(afmoe_reference_loss)(params, batch, AFMOE)
    assert np.isfinite(float(loss)) and abs(float(loss) - float(want_loss)) < 0.01 * abs(float(want_loss)), (float(loss), float(want_loss))
    want.pop("expert_bias")
    total = lambda a, b: np.sqrt(sum(float(jnp.sum((a[k] - b[k]) ** 2)) for k in b) / sum(float(jnp.sum(b[k] ** 2)) for k in b))
    assert total(got, want) < GRAD_ALL_TOL, total(got, want)
    for name in want:
        assert np.all(np.isfinite(got[name])), name
        if float(jnp.linalg.norm(want[name])) == 0:  # no slot held: the experts and the router reach no loss
            assert name in ("experts_gate", "experts_up", "experts_down", "router_w") and held_share == 0 and not np.any(np.asarray(got[name])), name
        else:
            assert rel(got[name], want[name]) < (GRAD_CANCELLING_TOL if name in CANCELLING else GRAD_TENSOR_TOL), name


def test_moved_rows_against_a_hand_count(afmoe_trainer):
    """``moved_rows``: what the row moves of a step's routed layers cover.
    Where every expert is held, every slot of every layer; for a share,
    each layer's held count rounded up to the moves' block (512 rows at
    2,048 slots a layer)."""
    _, _, counters = jax.jit(lambda p, x: trunk.trunk_forward_counted(p, x, TINY))(conditioned_params(1), batch_of(1)["planes"])
    assert float(counters["moved_rows"]) == TINY.layers * BATCH * 64 * TINY.experts_per_token and "held_slots" not in counters
    _, _, counters = jax.jit(lambda p, x: trunk.trunk_forward_counted(p, x, AFMOE))(afmoe_params(1), batch_of(1)["planes"])
    held = np.asarray(counters["expert_slots"])[:, 4:12].sum(axis=1)  # a layer
    assert held.sum() == float(counters["held_slots"]) and np.all(held % 512 != 0)  # the rounding shows
    assert float(counters["moved_rows"]) == sum(-(-int(h) // 512) * 512 for h in held)
    _, metrics = afmoe_trainer.step(afmoe_trainer.init(1), batch_of(1))
    assert 0 < float(metrics["moved_rows"]) <= 2 * BATCH * 64 * 4 and float(metrics["moved_rows"]) % 512 == 0  # in the step's metrics


def _gated_out_case(seed: int, cfg: TrunkConfig = AFMOE):
    """The gated out-projection's operands at the tiny net's widths as the layer hands them over: the float32 normed
    stream, ``mixed`` bfloat16 as the kernel writes it, the two weights, the post-norm's gain and a cotangent."""
    rng = np.random.default_rng(seed)
    tokens, inner = BATCH * 64, cfg.heads * cfg.head_dim
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return (normal(tokens, cfg.hidden), normal(tokens, inner).astype(jnp.bfloat16), normal(cfg.hidden, inner) / np.sqrt(cfg.hidden),
            normal(inner, cfg.hidden) / np.sqrt(inner), 1.0 + 0.1 * normal(cfg.hidden)), normal(tokens, cfg.hidden)


def _plain_gated_out(n1, mixed, gate_w, out_w, gain, wrong=""):
    """The branch as ``_attention`` wrote it until PR 50, left to autodiff, and the post-norm the loop applies to it."""
    gate = {"": jax.nn.sigmoid, "no_gate": jnp.ones_like, "silu": jax.nn.silu}[wrong]
    return trunk._rms_norm(trunk._matmul(mixed.astype(jnp.float32) * gate(trunk._matmul(n1, gate_w)), out_w), gain, AFMOE.rms_eps)


def _gated_out_under_its_norm(n1, mixed, gate_w, out_w, gain):
    """The program's side of the comparison: ``trunk._gated_out`` and the same post-norm."""
    return trunk._rms_norm(trunk._gated_out(n1, mixed, gate_w, out_w), gain, AFMOE.rms_eps)


GATED_OUT_NAMES = ("n1", "mixed", "wgate", "wo", "post_attn_norm")


@pytest.mark.parametrize("seed", [0, 1])
def test_the_gated_out_projection_against_autodiff_of_the_plain_formula(seed):
    """``_gated_out`` (one ``custom_vjp`` round the gate and ``W_o``) under
    the post-norm against ``jax.vjp`` of ``mixed.astype(float32) *
    sigmoid(n1 W_gate)`` through ``W_o`` and the same norm: the value is
    the plain formula's to the bit (the rule rounds where ``_matmul``
    does), and all five gradients are its gradients: the normed stream's
    (float32), ``mixed``'s (bfloat16, as the kernel's result is), both
    weights' and the post-norm's gain's, which reaches the rule as
    ``d_out``."""
    operands, cot = _gated_out_case(seed)
    got, pull = jax.vjp(_gated_out_under_its_norm, *operands)
    want, plain_pull = jax.vjp(_plain_gated_out, *operands)
    assert got.dtype == jnp.float32 and bool(jnp.all(got == want))
    for name, operand, g, w in zip(GATED_OUT_NAMES, operands, pull(cot), plain_pull(cot)):
        assert g.shape == operand.shape and g.dtype == operand.dtype and rel(g, w) < 1e-2, (name, rel(g, w))


@pytest.mark.parametrize("wrong", ["no_gate", "silu"])
def test_the_tolerance_catches_another_gates_gradients(wrong):
    """Against a branch without the gate, or gated by ``silu``, the same
    comparison fails on every gradient the gate touches: the 1e-2 above
    is not room for another function."""
    operands, cot = _gated_out_case(2)
    grads = jax.vjp(_gated_out_under_its_norm, *operands)[1](cot)
    others = jax.vjp(lambda *a: _plain_gated_out(*a, wrong=wrong), *operands)[1](cot)
    far = {name: rel(g, w) for name, g, w in zip(GATED_OUT_NAMES, grads, others)}
    assert all(far[name] > 0.1 for name in ("n1", "mixed", "wgate", "wo")), far
