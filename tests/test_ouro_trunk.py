"""The tenth block of the square-token trunk (models/trunk.py with
``TrunkConfig.loop_steps``: Ouro-2.6B's ouro block, a stack of layers RUN
SEVERAL TIMES over the same weights with an exit after every pass: the final
norm, the two heads and a gate a board, the loss the expected loss under the
exit distribution) at a tiny size on the CPU, on a worker of its own: the
loop against hand-written applications of the plan, a tied weight's gradient
against the sum of its per-pass gradients, the exit distribution and the
served rule, the program against the benchmark's plain reference, the
misreadings the comparison has to see, a looped trunk WITH routed layers, the
nine older blocks at ``loop_steps`` 1, the router-less checkpoint, the new
fields' refusals, the plan's scopes and span fields, and its step pin."""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.models import trunk
from fishnet_tpu.models.az import az_checkpoint, az_config_from_params, az_forward, az_forward_counted, init_az_params
from fishnet_tpu.models.heads import policy_value_heads
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer
from trunk_tiny import BLOCKS, OURO, OURO_CONFIG, OURO_MODEL, TINY, _all, batch_of, conditioned_params, rel  # noqa: E402

# The plain reference is the benchmark's own (benchmark/reference/ouro_trunk.py: the published layer and the report's training, a Python loop over
# passes and layers, importing nothing of the program), at a tiny size; the program reads its parameters as they are (benchmark/families/ouro_trunk.py).

from benchmark.families import ouro_trunk as ouro_family  # noqa: E402
from benchmark.reference import ouro_trunk as ouro_reference  # noqa: E402
from benchmark.reference.precision import cast_for, grad_cast_for  # noqa: E402
from tools.step_text import HOW_TO_SEE_WHAT_MOVED, lowered_step_text  # noqa: E402

T = OURO.loop_steps
#: What a configuration file says beside its ``model`` group, as the family's ``trunk_config`` reads it, for the tiny net.
_FILE_KEYS = {"model_type": "ouro", "hidden_act": "silu", "layer_types": ["full_attention"] * 48, "published": {"kept_layers": [0, 1]}, "rope_scaling": None,
              "sliding_window": None, "use_sliding_window": False, "num_attention_heads": 4, "num_key_value_heads": 4}
MISREADINGS = ("three_passes", "last_pass_gradient", "remainder_lost", "no_entropy", "heads_without_final_norm", "no_middle_norms")


def reference_params(seed):
    return {k: jnp.asarray(v) for k, v in ouro_reference.init_params(seed, OURO_MODEL).items()}


@functools.lru_cache(maxsize=None)
def family_trainer():
    """The tiny net's trainer as the family makes it from a configuration file's keys (one a process: its loss is traced once for all the seeds)."""
    train = {**OURO_CONFIG["train"], "optimizer": "adamw", "weight_decay": 1e-4, "learning_rate": 3e-4}
    trainer = ouro_family.make_trainer({**OURO_CONFIG, **_FILE_KEYS, "model": OURO_MODEL, "train": train})
    return trainer, ouro_family.loss_and_grads(trainer)


@functools.lru_cache(maxsize=None)
def program_loss_and_grads(seed):
    return family_trainer()[1](reference_params(seed), batch_of(seed))


# -- the loop ------------------------------------------------------------------------------------------------------------------------------


def test_the_tiny_net_is_the_one_the_family_makes_of_the_reference_models_keys():
    assert ouro_family.trunk_config({**OURO_MODEL, **_FILE_KEYS, "model": OURO_MODEL}) == OURO
    assert {k: v.shape for k, v in reference_params(1).items()} == trunk.trunk_param_shapes(OURO)


def test_the_loop_is_the_plan_applied_loop_steps_times_and_an_exit_after_each():
    """``trunk_forward_counted`` against the plan walked by hand: the stream of pass t the input of pass t + 1 through the SAME slices, then the
    final norm, the heads and the gate on each pass's stream, one at a time; bit for bit up to what XLA fuses differently (none on the CPU)."""
    params, batch = reference_params(3), batch_of(3)
    logits, value, counters, gates = trunk.trunk_forward_counted(params, batch["planes"], OURO)
    assert logits.shape == (T, 8, 4672) and value.shape == (T, 8) and gates.shape == (T, 8) and gates.dtype == jnp.float32
    plan = trunk.trunk_plan(OURO)
    x = trunk._matmul(batch["planes"].reshape(8 * 64, 19), params["embed_w"]) + params["embed_b"]
    streams = []
    for t in range(T):
        for sublayer in plan:  # a hand-written application: every sublayer by its own slice, its branch through its post-norm
            p = trunk.sublayer_params(params, sublayer)
            branch, counted = trunk._KINDS[sublayer.kind][0](x, p, OURO, sublayer)
            assert counted == {}
            x = x + trunk._rms_norm(branch, p[sublayer.post_norm], OURO.rms_eps)
        streams.append(x)
        f = trunk._rms_norm(x, params["final_norm"], OURO.rms_eps)
        want_logits, want_value = policy_value_heads(params, f.reshape(8, 8, 8, 64).astype(jnp.bfloat16))
        want_gate = jnp.mean(jnp.sum(f * params["exit_gate_w"][:, 0], axis=-1).reshape(8, 64), axis=-1) + params["exit_gate_b"]
        assert rel(logits[t], want_logits) < 1e-6 and float(jnp.max(jnp.abs(value[t] - want_value))) < 1e-6 and float(jnp.max(jnp.abs(gates[t] - want_gate))) < 1e-5
    assert set(counters) == {"loop_update_rms"}
    want = jnp.sqrt(jnp.mean(jnp.square(streams[-1] - streams[-2])) / jnp.mean(jnp.square(streams[-2])))
    assert abs(float(counters["loop_update_rms"]) - float(want)) < 1e-6 and 0.0 < float(want) < 2.0
    # one pass more is another net: the passes are not a fixed point at these weights
    longer = trunk.trunk_forward_counted(params, batch["planes"], dataclasses.replace(OURO, loop_steps=T + 1))
    assert rel(longer[0][:T], logits) < 1e-6 and rel(longer[0][T], logits[T - 1]) > 1e-3


def test_a_tied_weights_gradient_is_the_sum_of_its_per_pass_gradients():
    """The same loss with every pass given its OWN copy of the layers' tensors (T untied stacks, each started at the tied values): the tied gradient
    of a layer's tensor is the sum over the passes of the untied copies' gradients, and no pass's share is nothing."""
    params, batch = reference_params(4), batch_of(4)
    layer_tensors = [name for name, shape in trunk.trunk_param_shapes(OURO).items() if len(shape) >= 2 and shape[0] == OURO.layers and name != "exit_gate_w"]
    assert {"wq", "wk", "wv", "wo", "dense_gate", "dense_up", "dense_down", "attn_norm", "post_attn_norm", "moe_norm", "post_mlp_norm"} == set(layer_tensors)
    once = dataclasses.replace(OURO, loop_steps=1)
    trainer = AzTrainer(OURO, exit_entropy_weight=0.1)

    def untied(copies):
        x = trunk._matmul(batch["planes"].reshape(8 * 64, 19), params["embed_w"]) + params["embed_b"]
        streams = []
        for own in copies:
            x, _ = trunk._one_pass(x, {**params, **own}, once, trunk.trunk_plan(once))
            streams.append(x)
        f = trunk._rms_norm(jnp.stack(streams), params["final_norm"], OURO.rms_eps)
        logits, value = policy_value_heads(params, f.reshape(T * 8, 8, 8, 64).astype(jnp.bfloat16))
        gates = jnp.mean(jnp.sum(f * params["exit_gate_w"][:, 0], axis=-1).reshape(T, 8, 64), axis=-1) + params["exit_gate_b"]
        policy = -jnp.sum(batch["policy_target"] * jax.nn.log_softmax(logits.reshape(T, 8, -1), axis=-1), axis=-1)
        from fishnet_tpu.train.az_trainer import _expected_exit_terms
        return _expected_exit_terms(policy, (value.reshape(T, 8) - batch["value_target"]) ** 2, gates, 1.0, 0.1)["loss"]

    copies = [{name: params[name] for name in layer_tensors} for _ in range(T)]
    loss, per_pass = jax.value_and_grad(untied)(copies)
    tied_loss, tied = jax.value_and_grad(lambda p: trainer._loss(p, batch)[0])(params)
    assert abs(float(loss) - float(tied_loss)) < 1e-5
    for name in layer_tensors:
        parts = [g[name] for g in per_pass]
        assert rel(tied[name], sum(parts)) < 2e-3, name  # a float32 sum in another order
        assert all(float(jnp.linalg.norm(part)) > 0.02 * float(jnp.linalg.norm(tied[name])) for part in parts), name


# -- the exits -----------------------------------------------------------------------------------------------------------------------------


def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_the_remainder():
    rng = np.random.default_rng(5)
    gates = jnp.asarray(np.concatenate([rng.normal(0, 2.0, (5, 64)), np.full((5, 1), 40.0), np.full((5, 1), -40.0)], axis=1), jnp.float32)
    log_p = trunk.exit_log_distribution(gates)
    p, lam = np.asarray(jnp.exp(log_p), np.float64), 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    assert log_p.shape == gates.shape and np.all(np.isfinite(np.asarray(log_p))) and np.allclose(p.sum(axis=0), 1.0, atol=1e-6)
    stay = np.cumprod(1.0 - lam, axis=0)
    assert np.allclose(p[0], lam[0], atol=1e-6) and np.allclose(p[1:-1], lam[1:-1] * stay[:-2], atol=1e-6)
    assert np.allclose(p[-1], stay[-2], atol=1e-6) and np.allclose(p[-1], 1.0 - p[:-1].sum(axis=0), atol=1e-6)  # the remainder, whatever the last gate says
    assert np.array_equal(np.asarray(trunk.exit_log_distribution(gates.at[-1].set(7.0))), np.asarray(log_p))  # the last pass's own gate is not read
    assert np.allclose(p, np.asarray(ouro_reference.exit_distribution(gates)), atol=1e-6)  # the reference's plain products
    assert np.allclose(p[:, -2], [1, 0, 0, 0, 0], atol=1e-12) and np.allclose(p[:, -1], [0, 0, 0, 0, 1], atol=1e-12)  # saturated gates: the first pass, the last
    # two passes: p = (lambda_1, 1 - lambda_1)
    two = np.asarray(jnp.exp(trunk.exit_log_distribution(gates[:2])), np.float64)
    assert np.allclose(two[0], lam[0], atol=1e-6) and np.allclose(two[1], 1.0 - lam[0], atol=1e-6)


def test_the_served_forward_takes_a_positions_pass_by_the_exit_rule():
    """``early_exit_threshold`` 1.0 (the published one) serves the last pass; a threshold under every ``lambda_1`` the first; between them a
    batch mixes its positions, each by its own cumulative sum; and the reference's served forward agrees."""
    params, batch = reference_params(6), batch_of(6)
    logits, value, _, gates = az_forward_counted(params, batch["planes"], OURO)
    cdf = np.cumsum(np.asarray(jnp.exp(trunk.exit_log_distribution(gates)), np.float64), axis=0)
    served = lambda threshold: az_forward(params, batch["planes"], dataclasses.replace(OURO, exit_threshold=threshold))
    last = served(1.0)
    assert np.array_equal(np.asarray(last[0]), np.asarray(logits[-1])) and np.array_equal(np.asarray(last[1]), np.asarray(value[-1]))
    assert np.array_equal(np.asarray(trunk.served_pass(gates, 1.0)), np.full(8, T - 1))
    low = float(cdf[0].min()) * 0.5  # under every board's lambda_1
    first = served(low)
    assert np.array_equal(np.asarray(first[0]), np.asarray(logits[0])) and np.array_equal(np.asarray(first[1]), np.asarray(value[0]))
    between = float(np.median(cdf[0]))  # half the boards have left after the first pass
    chosen = np.asarray(trunk.served_pass(gates, between))
    assert np.array_equal(chosen, [int(np.argmax(cdf[:, b] >= between)) if (cdf[:, b] >= between).any() else T - 1 for b in range(8)])
    assert len(set(chosen.tolist())) > 1 and (chosen == 0).sum() >= 3
    mixed = served(between)
    for b in range(8):
        assert np.array_equal(np.asarray(mixed[0][b]), np.asarray(logits[chosen[b], b])) and float(mixed[1][b]) == float(value[chosen[b], b])
    with jax.default_matmul_precision("highest"):
        want = ouro_reference.forward(params, batch["planes"], {**OURO_MODEL, "early_exit_threshold": between}, cast_for("float32"), grad_cast_for("float32"))
    assert rel(mixed[0], want[0]) < 0.05 and float(jnp.max(jnp.abs(mixed[1] - want[1]))) < 0.03


# -- the program against the plain reference -------------------------------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_loss_and_every_gradient_agree_with_the_plain_reference(seed):
    """Readings over seeds 1-7 (CPU, bfloat16 products against float32 at ``highest``): the loss within 2e-4 of its value, all gradients as one
    vector 0.012-0.022, the worst single tensor 0.026 (``exit_gate_w``) BUT FOR the two convolutions' biases, ``policy_b`` 0.27-0.47 and
    ``value_b`` 0.12-0.56: the heads run in bfloat16 (``models/heads.py``, every net's), a bias's gradient is the sum of its bfloat16 cotangent
    over ``loop_steps`` x boards x 64 squares, and XLA:CPU adds those 1,536 terms one by one in bfloat16 (the program's ``value_b`` reads
    0.0315 on all four planes, a bfloat16 sum that stopped growing, where float32 reads 0.072); on the chip the sum is float32 and the two
    are held by the cell's ``grad_rel_l2_small_max``."""
    params, batch = reference_params(seed), batch_of(seed)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(lambda p: ouro_reference.loss(p, batch, OURO_CONFIG))(params)
    trainer = family_trainer()[0]
    assert trainer.cfg == OURO and trainer.exit_entropy_weight == 0.1
    got_loss, got = program_loss_and_grads(seed)
    assert set(got) == set(want) and abs(float(got_loss) - float(want_loss)) < 1e-3 * abs(float(want_loss))
    assert _all(got, want) < 0.05, _all(got, want)
    for name in want:
        assert rel(got[name], want[name]) < (0.8 if name in ("policy_b", "value_b") else 0.06), (name, rel(got[name], want[name]))


@pytest.mark.parametrize("misread", MISREADINGS)
def test_the_program_is_none_of_the_misreadings(misread):
    """Readings over seeds 1-3 against each misread reference, all gradients as one vector: three_passes 0.049-0.067, no_entropy 0.10-0.17,
    no_middle_norms 0.36-0.44, last_pass_gradient 0.55-0.65, remainder_lost 0.78-0.97, heads_without_final_norm 0.99 (sound: 0.017-0.024)."""
    params, batch = reference_params(2), batch_of(2)
    _, got = program_loss_and_grads(2)
    config = {**OURO_CONFIG, "model": {**OURO_MODEL, "misread": misread}}
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: ouro_reference.loss(p, batch, config))(params)
    assert _all(got, want) > 0.04, (misread, _all(got, want))


def test_the_metrics_of_a_step_are_the_exits_own_and_consistent():
    trainer = AzTrainer(OURO)
    state, metrics = trainer.step(trainer.init(1), batch_of(1))
    m = {k: float(v) for k, v in metrics.items()}
    assert set(m) == {"loss", "policy_loss", "value_loss", "exit_step_mean", "exit_entropy", "loss_first_pass", "loss_last_pass", "loop_update_rms"}
    assert 1.0 <= m["exit_step_mean"] <= T and 0.0 < m["exit_entropy"] <= np.log(T) + 1e-6 and all(np.isfinite(v) for v in m.values())
    assert abs(m["loss"] - (m["policy_loss"] + trainer.value_weight * m["value_loss"] - trainer.exit_entropy_weight * m["exit_entropy"])) < 1e-5
    # a fresh gate sits at a half: p = (1/2, 1/4, 1/4) over three passes, the mean exit 1.75, the entropy 1.5 ln 2
    assert abs(m["exit_step_mean"] - 1.75) < 0.02 and abs(m["exit_entropy"] - 1.5 * np.log(2.0)) < 0.01
    assert int(state.step) == 1 and state.buffers == {}


# -- a loop over routed layers, and the nine older blocks at one pass -------------------------------------------------------------------------


def test_a_looped_trunk_with_routed_layers_sums_an_experts_slots_over_its_passes():
    """The first block's tiny net walked twice: the routing counters fold over the passes too, ``expert_slots`` (what a balance update would read) is
    an expert's slots of the whole step, and the trainer steps it."""
    cfg = dataclasses.replace(TINY, loop_steps=2)
    params = {**conditioned_params(7, cfg)}
    batch = batch_of(7)
    logits, value, counters, gates = trunk.trunk_forward_counted(params, batch["planes"], cfg)
    assert logits.shape == (2, 8, 4672) and gates.shape == (2, 8)
    assert counters["expert_slots"].shape == (cfg.layers, cfg.experts) and float(jnp.sum(counters["expert_slots"])) == 2 * cfg.layers * 8 * 64 * cfg.experts_per_token
    assert float(counters["moved_rows"]) == 2 * cfg.layers * 8 * 64 * cfg.experts_per_token and "loop_update_rms" in counters
    once = trunk.trunk_forward_counted({k: v for k, v in params.items() if not k.startswith("exit_gate")}, batch["planes"], TINY)
    assert rel(logits[0], once[0]) < 1e-6  # the first exit is the one-pass net's answer
    trainer = AzTrainer(cfg)
    _, metrics = trainer.step(trainer.init(0), batch)
    assert np.isfinite(float(metrics["loss"])) and "expert_slots" not in metrics and "router_entropy" in metrics


def test_loop_steps_one_is_the_default_and_returns_what_it_always_did():
    """The nine older tiny blocks name no ``loop_steps``: their configurations read 1, their forward returns three results (four with noise), no
    gate and no new counter. (Their lowered steps are pinned bit for bit where they always were: ``test_hybrid_trunk.py``, ``test_cca_trunk.py``,
    ``test_gdn_trunk.py``, ``test_mellum_trunk.py``, ``test_sdar_trunk.py``, which pass unedited on this tree.)"""
    for block, (cfg, make_batch) in BLOCKS.items():
        if block == "ouro":
            continue
        assert cfg.loop_steps == 1 and cfg.exit_threshold == 1.0 and dataclasses.replace(cfg, loop_steps=1) == cfg
        shapes = trunk.trunk_param_shapes(cfg)
        assert "exit_gate_w" not in shapes and "loop_update_rms" not in jax.eval_shape(
            lambda: trunk.trunk_forward_counted({k: jnp.zeros(s) for k, s in {**shapes, **trunk.trunk_buffer_shapes(cfg)}.items()}, jnp.zeros((2, 8, 8, 19)), cfg)[2])


# -- the checkpoint ------------------------------------------------------------------------------------------------------------------------


def test_a_routerless_checkpoint_round_trips_with_its_two_loop_numbers(tmp_path):
    cfg = dataclasses.replace(OURO, exit_threshold=0.75)
    trainer = AzTrainer(cfg)
    state = trainer.init(2)
    trainer.export(state, str(tmp_path / "ouro.npz"))
    arrays = dict(np.load(tmp_path / "ouro.npz"))
    assert "router_w" not in arrays and "experts_up" not in arrays and len(arrays[trunk.HPARAMS]) == len(trunk._HPARAMS) + len(trunk._ROPE_HPARAMS) + 1 + 2
    assert list(arrays[trunk.HPARAMS][-3:]) == [0.0, 3.0, 0.75]  # no block length, the loop's two numbers
    read = az_config_from_params(arrays)
    assert read == cfg and (read.loop_steps, read.exit_threshold, read.dense_layers, read.routed_layers, read.qk_norm, read.post_norms) == (3, 0.75, 2, 0, False, True)
    assert az_checkpoint(state.params, cfg).keys() == arrays.keys()
    planes = batch_of(2)["planes"]
    assert np.array_equal(np.asarray(az_forward({k: jnp.asarray(v) for k, v in arrays.items() if k != trunk.HPARAMS}, planes, read)[0]), np.asarray(az_forward(state.params, planes, cfg)[0]))
    # the nine older blocks' files are what they were: none carries the loop's numbers
    for block, (older, _) in BLOCKS.items():
        if block != "ouro":
            file = trunk.trunk_checkpoint({name: np.zeros(shape, np.float32) for name, shape in {**trunk.trunk_param_shapes(older), **trunk.trunk_buffer_shapes(older)}.items()}, older)
            assert len(file[trunk.HPARAMS]) <= len(trunk._HPARAMS) + len(trunk._ROPE_HPARAMS) + 1 and az_config_from_params(file) == older
    # what does not fit says which tensor told the reader what the file is
    with pytest.raises(ValueError, match=r"not a trunk checkpoint \(read as one by dense_up without a routed layer's tensors\): missing \['wq'\]"):
        az_config_from_params({k: v for k, v in arrays.items() if k != "wq"})
    with pytest.raises(ValueError, match=r"read as one by its router_w\): missing \['experts_gate'"):
        az_config_from_params({**arrays, "router_w": np.zeros((2, 64, 8), np.float32)})
    with pytest.raises(ValueError, match=r"not an AZ checkpoint \(read as a tower's: it has no router_w or router_down, and not both embed_w and final_norm"):
        az_config_from_params({k: v for k, v in arrays.items() if k != "final_norm"})
    with pytest.raises(ValueError, match="loop_steps 1 among them"):
        az_config_from_params({**arrays, trunk.HPARAMS: np.concatenate([arrays[trunk.HPARAMS][:-2], [1.0, 1.0]])})
    with pytest.raises(ValueError, match="mismatched keys"):  # the gate's tensors are the loop's: a file that says one pass has none
        az_config_from_params({**arrays, trunk.HPARAMS: arrays[trunk.HPARAMS][:-3]})


# -- the refusals --------------------------------------------------------------------------------------------------------------------------

FIELDS = dict(hidden=64, heads=4, head_dim=16, layers=2, value_hidden=32, dense_layers=2, dense_width=96, qk_norm=False, post_norms=True)
REFUSED = {
    "no_pass": (dict(loop_steps=0), "loop_steps 0 is under 1"),
    "under_a_pattern": (dict(loop_steps=2, pattern="*E", dense_layers=0, dense_width=0, post_norms=False), "loop_steps 2 .* a pattern"),
    "under_block_diffusion": (dict(loop_steps=2, block_length=4, qk_norm=True), "loop_steps 2 .* block_length"),
    "threshold_zero": (dict(loop_steps=2, exit_threshold=0.0), "exit_threshold 0.0 is not over 0"),
    "threshold_over_one": (dict(loop_steps=2, exit_threshold=1.5), "exit_threshold 1.5 is not over 0 and at most 1"),
    "threshold_without_a_loop": (dict(exit_threshold=0.5), "stands beside no loop"),
    "more_dense_layers_than_layers": (dict(dense_layers=3), "3 dense layers are not 0 to the 2 layers"),
    "dense_layers_without_a_width": (dict(dense_width=0), "have no width"),
}


@pytest.mark.parametrize("wrong", REFUSED)
def test_a_loop_is_refused_in_words_where_it_is_not_computed(wrong):
    fields, words = REFUSED[wrong]
    with pytest.raises(ValueError, match=words):
        TrunkConfig(**{**FIELDS, **fields})


def test_a_trunk_of_dense_layers_alone_is_a_trunk_with_or_without_a_loop():
    cfg = TrunkConfig(**FIELDS)  # ``dense_layers == layers``: no routed layer, one pass
    assert cfg.routed_layers == 0 and cfg.loop_steps == 1 and not any(name in trunk.trunk_param_shapes(cfg) for name in trunk._OWNS["routed"])
    params = init_az_params(jax.random.PRNGKey(0), cfg)
    logits, value, counters = az_forward_counted(params, batch_of(1)["planes"], cfg)
    assert logits.shape == (8, 4672) and value.shape == (8,) and counters == {}
    assert az_config_from_params(az_checkpoint(params, cfg)) == cfg
    trainer = AzTrainer(cfg)
    _, metrics = trainer.step(trainer.init(0), batch_of(1))
    assert set(metrics) == {"loss", "policy_loss", "value_loss"}


# -- the plan's scopes, the span's fields, the step pin ----------------------------------------------------------------------------------------


def test_a_layers_scopes_cover_its_passes_and_the_init_span_counts_them():
    from fishnet_tpu.telemetry.spans import RECORDER

    trainer = AzTrainer(OURO)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    text = trainer._step_jit.lower(state, batch_of(1)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for phase in ("jvp(forward)", "transpose(jvp(forward))"):
        for scope in ("embed", "layer00.attention", "layer00.dense", "layer01.attention", "layer01.dense", "final_norm", "policy_head", "value_head", "exit_gate"):
            assert any(f"/{phase}/{scope}/" in name for name in names), (phase, scope)
    assert any("/jvp(loss)/exit/" in name for name in names) and any("/transpose(jvp(loss))/exit/" in name for name in names)
    # no routed layer's scope, and the pass is no level of a name: a layer's scope comes right under its phase (the interpreter's own loops lie below it)
    assert not any(re.search(r"layer\d\d\.(router|experts|combine|dispatch)|pass\d", name) for name in names)
    assert all(re.search(r"^jit\(_step\)/(jvp|transpose\(jvp)\(forward\)+/layer\d\d\.", name) for name in names if re.search(r"layer\d\d\.", name))
    started = time.monotonic()
    trainer.init(0)
    span = [s for s in RECORDER.spans() if s["t"] >= started and s["stage"] == "train_init"][-1]
    assert (span["trainer"], span["attention_heads_paired"], span["loop_steps"], span["layer_passes"]) == ("az", 0.0, 3, 6)
    started = time.monotonic()
    AzTrainer(TINY).init(0)
    span = [s for s in RECORDER.spans() if s["t"] >= started and s["stage"] == "train_init"][-1]
    assert (span["loop_steps"], span["layer_passes"]) == (1, 2)


#: sha256 of the tiny lowered step program (``tools/step_text.py --block ouro``), as ``tests/test_hybrid_trunk.py PARENT_STEP_SHA256`` holds the four
#: older blocks': read on the tree of the PR that brought the block (PR 64). The nine older blocks' pins pass UNEDITED on it: at ``loop_steps`` 1 the
#: loop is one walk of the plan, the heads one call on one stream, and nothing of the exits is traced.
OURO_STEP_SHA256 = "05fdb948456d577b651ef00bf95453b57da2274d468326f3be8ab248f199e370"


def test_the_tenth_blocks_lowered_step_is_pinned():
    cfg, batch = BLOCKS["ouro"]
    assert cfg is OURO
    text = lowered_step_text(cfg, batch(1))
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == OURO_STEP_SHA256, HOW_TO_SEE_WHAT_MOVED.format(block="ouro")
