"""The afmoe trunk's benchmark pieces on the CPU at a tiny size: its
operation count, its two reducers, and the ``train_step`` runner and the
comparison that decides ``correct`` on a tiny ``afmoe_trunk``
configuration added to a temp copy as new files and entries only."""

from __future__ import annotations

import copy
import dataclasses
import json
import time

import numpy as np
import pytest

import helpers
from benchmark import correctness, positions, scopes
from benchmark.registry import Registry

REPO = helpers.REPO
CELL = "afmoe_trunk_train_b256"
CONFIG = "trinity-mini-trunk-train"

TINY_TOP = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3,
            "num_dense_layers": 1, "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4,
            "num_experts_per_tok": 4}
TINY_MODEL = {**TINY_TOP, "kept_layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
              "num_routed_experts": 16, "first_held_expert": 4, "value_hidden": 32}
# CPU readings at this size over 6 seeds, 16 positions: see test_control_fails_and_program_passes, which prints them.
TINY_LIMITS = {"grad_rel_l2_all": 0.05, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.45, "loss_rel_diff": 0.001,
               "steps_drop_rel_diff": 0.05}


def tiny_afmoe_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``afmoe_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="afmoe-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the first trunk's tiny cell
    (root / "benchmark" / "configs" / "afmoe-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "afmoe-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/afmoe-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "afmoe_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "afmoe_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "afmoe_trunk_tiny_cell", "config": "afmoe-trunk-tiny", "traffic": "tiny_pool",
                              "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("afmoe_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_afmoe_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts"] and config["train"]["batch"] == 256
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["num_experts"]) == (5, 1, 8)
    assert config["published"] == {"num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128, "kept_layers": [0, 2, 3, 4, 5]}
    # every width as published
    assert (config["hidden_size"], config["head_dim"], config["intermediate_size"], config["moe_intermediate_size"]) == (2048, 128, 6144, 1024)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["num_experts_per_tok"], config["sliding_window"]) == (32, 4, 8, 2048)
    assert config["model"]["kept_layer_types"] == [config["layer_types"][i] for i in config["published"]["kept_layers"]]
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"moe_experts_ms", "moe_routing_ms", "trunk_attention_ms", "trunk_dense_ffn_ms", "moe_held_expert_roofline"} <= names
    assert not {"az_conv_roofline", "nnue_ft_roofline", "moe_expert_roofline"} & names and len(names) == 17
    family = registry.module("families", "afmoe_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.layers, trunk.dense_layers) == (2048, 32, 4, 128, 5, 1)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.dense_width, trunk.shared_width) == (128, (0, 8), 8, 1024, 6144, 1024)
    assert (trunk.nope_layers, trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate) == ((2,), "sigmoid", True, 2.826, 0.001)
    assert trunk.gated_attention and trunk.post_norms and trunk.embed_scale == 2048 ** 0.5 and trunk.recompute_experts
    from fishnet_tpu.models.trunk import trunk_param_shapes
    assert sum(int(np.prod(s)) for s in trunk_param_shapes(trunk).values()) == 401_913_678  # the 401.9 M of the file's reduced_why
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "hidden_size": 1024})
    with pytest.raises(ValueError, match="score_func"):
        family.trunk_config({**config, "score_func": "softmax"})
    with pytest.raises(ValueError, match="sliding_window"):  # the program applies no mask: a window inside a board is refused
        family.trunk_config({**config, "sliding_window": 32, "model": {**config["model"], "sliding_window": 32}})


def test_held_expert_flops_hand_count():
    held = Registry(REPO).module("roofline", "moe_held_experts")
    model = Registry(REPO).config(CONFIG)["model"]
    assert held.held_slots(model, 256) == 8192  # 256 positions x 64 squares x 8 slots x 8 held / 128 experts
    # one row through one product, one pass: 2 x 2048 x 1024 = 4,194,304; three products, three passes, four routed layers
    assert held.step_flops(model, 256) == 8192 * 4_194_304 * 9 * 4 == 1_236_950_581_248
    # a pass of a product: 8 experts x 2048 x 1024 x 2 B of weights + 8,192 rows x (2048 + 1024) x 2 B
    assert held.step_bytes(model, 256) == 36 * (33_554_432 + 50_331_648)
    least = held.least_seconds(model, 256, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "compute" and abs(least["least_s"] - 0.006279) < 1e-6
    assert held.step_flops({**model, "num_experts": 16}, 256) == 2 * held.step_flops(model, 256)


def test_the_two_reducers_on_a_synthetic_split():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer01.experts": 4.0, "transpose(jvp(forward))/layer01.experts": 8.0, "jvp(forward)/layer04.experts": 0.558,
        "jvp(forward)/layer00.dense": 10.0, "transpose(jvp(forward))/layer00.dense": 20.0,
        "jvp(forward)/layer01.shared": 1.0, "transpose(jvp(forward))/layer03.shared": 2.0,
        "jvp(forward)/layer00.attention": 8.0, "jvp(forward)/layer01.router": 1.0, "optimizer": 100.0,
    }
    config = registry.config(CONFIG)
    ctx = {"registry": registry, "config": config, "batch": 256, "device_kind": "TPU v5 lite", "trace": object(),
           "scopes_split": found}
    assert registry.module("reducers", "trunk_dense_ffn_ms").reduce(ctx) == 33.0
    share = registry.module("reducers", "moe_held_expert_roofline").reduce(ctx)
    assert abs(share - 100 * 6.279 / 12.558) < 0.01
    # a program without the scopes (the parent, the first trunk), another family, no trace: nothing, and no error
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/layer00.experts": 5.0}
    assert registry.module("reducers", "trunk_dense_ffn_ms").reduce(ctx) is None
    for name in ("trunk_dense_ffn_ms", "moe_held_expert_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None}) is None
    assert registry.module("reducers", "moe_held_expert_roofline").reduce({**ctx, "config": registry.config("lladamoe-trunk-train")}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny afmoe trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("afmoe_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    # three layers of interpreted kernels: a tiny step takes ~0.25 s here, so the window is 1.5 s
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2_all" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises
    assert traced["correct"] is True and "trunk_dense_ffn_ms" not in traced["metrics"]
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("afmoe-trunk-tiny")
    family = tiny.module("families", "afmoe_trunk")
    reference = tiny.module("reference", "afmoe_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]
        assert sound["_per_tensor"]["expert_bias"] == 0.0  # no gradient through the bias, on either side


def test_the_reference_centres_its_routers_and_pins_its_value_head(tiny):
    """What ``init_params`` promises at any width: every slot is counted,
    the held experts of every routed layer have rows (routing over a
    board's few kinds of token is lumpy: not every held expert has), the
    bias is a few balance steps with each layer's mean taken out, and the
    value head sits away from its relus' corners."""
    import jax.numpy as jnp

    config = tiny.config("afmoe-trunk-tiny")
    model = config["model"]
    family, reference = tiny.module("families", "afmoe_trunk"), tiny.module("reference", "afmoe_trunk")
    for seed in (11, 2**31 + 12, 13, 14):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = family.build_batch(pool, np.arange(32))
        p = reference.init_params(seed, model)
        x, slots = reference._trunk({k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(batch["planes"]), model, lambda a: a, lambda a: a)
        assert slots.shape == (2, 16) and float(slots.sum()) == 2 * 32 * 64 * 4
        assert float(slots[:, 4:8].sum(-1).min()) > 0, (seed, "a routed layer whose held experts no token chose", slots)
        plane = np.asarray(x) @ p["value_w"][0, 0] + p["value_b"]
        assert plane.min() > 0.25, (seed, "a value plane is dead or at the relu's corner", plane.reshape(-1, 4).min(0))
        assert np.allclose(p["expert_bias"].mean(-1), 0.0, atol=1e-7) and np.abs(p["expert_bias"]).max() <= 0.006


def _with_program_config(checker, family, **changes):
    """The program under the comparison rebuilt on a changed ``TrunkConfig``: a piece of the mathematics left out."""
    from fishnet_tpu.train.az_trainer import AzTrainer

    cfg = dataclasses.replace(checker.trainer.cfg, **changes)
    train = checker.config["train"]
    checker.trainer = AzTrainer(cfg, learning_rate=train["learning_rate"], value_weight=train["value_weight"])
    checker._program_grad = family.loss_and_grads(checker.trainer)


@pytest.mark.parametrize("what", ["shared_expert_x0", "gate_x0", "route_scale_x1.5", "not_renormalised", "rope_on_the_full_layer"])
def test_left_out_mathematics_fails(tiny, what):
    """The shared expert or the output gate multiplied by zero, the route
    scale by 1.5, weights not renormalised, RoPE on the layer that has
    none: not correct. (A bias the step does not update is the next test's:
    no number ``correct`` compares can see it.)"""
    import jax.numpy as jnp

    config = tiny.config("afmoe-trunk-tiny")
    family = tiny.module("families", "afmoe_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "afmoe_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    if what == "route_scale_x1.5":
        _with_program_config(checker, family, route_scale=2.826 * 1.5)
    elif what == "not_renormalised":
        _with_program_config(checker, family, route_norm=False)
    elif what == "rope_on_the_full_layer":
        _with_program_config(checker, family, nope_layers=())
    else:
        tensor = {"shared_expert_x0": "shared_down", "gate_x0": "wgate"}[what]
        grad, state_of = checker._program_grad, family.state_from_params
        zeroed = lambda params: {**params, tensor: jnp.zeros_like(params[tensor])}
        checker._program_grad = lambda params, batch: grad(zeroed(params), batch)
        checker.family = type("Family", (), {**{k: getattr(family, k) for k in dir(family) if not k.startswith("__")},
                                             "state_from_params": staticmethod(lambda trainer, params: state_of(trainer, zeroed(params)))})
    numbers = checker.compare(pool, 21)
    ok, line = correctness.judge(numbers, config)
    print(what, line)
    assert not ok and "EXCEEDED" in line, line


def test_the_step_moves_the_bias_as_the_reference_does(tiny):
    """``expert_bias`` after one step of the program, from the reference's
    parameters, against the reference's balance rule on the reference's
    own routing counts. The comparison that decides ``correct`` cannot see
    this update: two steps of 0.001 move no choice that the loss feels,
    and even at a rate of 0.3 with AdamW at 1e-6 the reference's loss
    moves by 0.001 over the steps, under the bfloat16 program's noise
    (PERF.md section 7). So it is held to the reference here, directly; a
    program that leaves the bias where it was differs in every entry."""
    import jax.numpy as jnp

    config = tiny.config("afmoe-trunk-tiny")
    family, reference = tiny.module("families", "afmoe_trunk"), tiny.module("reference", "afmoe_trunk")
    trainer = family.make_trainer(config)
    for seed in (21, 22):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
        params = {k: jnp.asarray(v) for k, v in reference.init_params(seed, config["model"]).items()}
        slots = reference.expert_slots(params, batch["planes"], config["model"])
        want = np.asarray(reference.balanced_bias(params["expert_bias"], slots, config["model"]["load_balance_coeff"]))
        state, metrics = trainer.step(family.state_from_params(trainer, params), batch)
        got = np.asarray(state.buffers["expert_bias"])
        # an expert whose load is within a rounding's swaps of its layer's mean may go the other way: a few of 32
        assert np.mean(np.abs(got - want) < 1e-7) > 0.9, (seed, got - want)
        assert np.all(np.abs(got - np.asarray(params["expert_bias"])) > 1e-4)  # every entry moved, by the rate or the mean
        assert abs(float(metrics["held_slots"]) - float(slots[:, 4:8].sum())) <= 8 + 0.1 * float(slots[:, 4:8].sum())  # but for swaps


def test_the_window_starts_balanced_and_warming_up(tiny):
    """What ``SettledTrainer`` hands the runner: the seed's own
    initialisation with fresh moments, an ``expert_bias`` that the balance
    rule has moved (each layer's mean change taken out, no entry further
    than the rates' sum) and that spreads a new batch's slots more evenly
    over the experts than no bias does; a rate that warms up from 0 (the
    first step moves nothing, the second by rate / warmup_steps), while a
    state made from the reference's parameters steps at the whole rate."""
    import jax
    import jax.numpy as jnp
    from fishnet_tpu.models.trunk import trunk_forward_counted
    from fishnet_tpu.train.az_trainer import AzTrainer

    config = copy.deepcopy(tiny.config("afmoe-trunk-tiny"))
    config["train"]["settle"].update(positions=64, balance_passes=32)
    config["train"]["warmup_steps"] = 1000
    family, reference = tiny.module("families", "afmoe_trunk"), tiny.module("reference", "afmoe_trunk")
    trainer = family.make_trainer(config)
    state, fresh = trainer.init(5), AzTrainer(trainer.cfg).init(5)
    assert all(np.array_equal(np.asarray(state.params[k]), np.asarray(fresh.params[k])) for k in fresh.params)
    assert not any(np.any(np.asarray(x)) for x in jax.tree.leaves(state.opt_state))
    bias = np.asarray(state.buffers["expert_bias"])
    settle = config["train"]["settle"]
    assert np.all(np.abs(bias.mean(axis=-1)) < 1e-6) and np.all(np.std(bias, axis=-1) > settle["rate_last"])
    assert np.max(np.abs(bias)) <= 2 * np.sum(np.geomspace(settle["rate_first"], settle["rate_last"], 32))

    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 77, family)
    batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
    def spread(b):
        slots = trunk_forward_counted({**state.params, "expert_bias": jnp.asarray(b)}, batch["planes"], trainer.cfg)[2]["expert_slots"]
        return float(np.mean(np.std(np.asarray(slots), axis=-1)))
    assert spread(bias) < 0.8 * spread(np.zeros_like(bias))

    rate, before = config["train"]["learning_rate"], {k: np.asarray(v) for k, v in state.params.items()}
    state, _ = trainer.step(state, batch)
    assert all(np.array_equal(before[k], np.asarray(state.params[k])) for k in before)  # the rate starts at 0
    state, _ = trainer.step(state, batch)
    moved = max(float(np.max(np.abs(before[k] - np.asarray(state.params[k])))) for k in before)
    assert 0.5 * rate / 1000 < moved < 2 * rate / 1000
    params = {k: jnp.asarray(v) for k, v in reference.init_params(5, config["model"]).items()}
    stepped, _ = trainer.step(family.state_from_params(trainer, params), batch)
    moved = max(float(np.max(np.abs(np.asarray(params[k]) - np.asarray(stepped.params[k])))) for k in stepped.params)
    assert 0.5 * rate < moved < 2 * rate  # past the warm-up: where the comparison's optimizer steps are taken
