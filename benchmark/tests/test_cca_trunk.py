"""The fifth trunk's benchmark pieces on the CPU at a tiny size: its cut
and its parameter count, its two operation counts, its three reducers,
and the ``train_step`` runner and the comparison that decides ``correct``
on a tiny ``cca_trunk`` configuration added to a temp copy as new files
and entries only."""

from __future__ import annotations

import copy
import json
import time

import numpy as np
import pytest

import helpers
from benchmark import correctness, positions, scopes
from benchmark.registry import Registry

REPO = helpers.REPO
CELL = "cca_trunk_train_b512"
CONFIG = "zaya1-trunk-train"
NEW_METRICS = {"cca_mix_ms", "cca_mix_roofline", "moe_top1_held_expert_roofline"}

# The published ratios at a sixteenth: queries in hidden / 2 columns, keys and values in hidden / 8, 4 query heads a key-value
# head, half a head rotated, 16 experts, one a token, 8 held.
TINY_TOP = {"hidden_size": 128, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 32,
            "router_hidden_size": 32, "num_hidden_layers": 2}
TINY_MODEL = {**TINY_TOP, "rotary_dim": 4, "first_held_expert": 4, "value_hidden": 32}
# CPU readings at this size over 3 seeds, 16 positions (test_control_fails_and_program_passes prints them): all tensors as one read
# 0.0073-0.0080 sound and 0.028-0.033 under the fp8 control; the worst tensor 0.10-0.12 sound (policy_b: a cancelling sum), the worst
# small one 0.04-0.26 (value_b, temp); the steps' fall 0.002-0.006 sound.
TINY_LIMITS = {"grad_rel_l2_all": 0.016, "grad_rel_l2_max": 0.45, "grad_rel_l2_small_max": 0.45, "loss_rel_diff": 0.002,
               "steps_drop_rel_diff": 0.08}


def tiny_cca_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``cca_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="cca-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["published"]["kept_layers"] = [0, 1]
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "cca-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "cca-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/cca-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "cca_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "cca_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "cca_trunk_tiny_cell", "config": "cca-trunk-tiny", "traffic": "tiny_pool", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("cca_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_cca_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts"] and config["train"]["batch"] == 512
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 8)
    assert config["published"]["num_hidden_layers"] == 40 and config["published"]["num_experts"] == 16
    assert config["published"]["kept_layers"] == [0, 1, 2, 3] and config["published"]["held_experts"] == list(range(8))
    # every key of the catalog's row but the two reduced, as published
    catalog = {"attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
               "layer_types": ["hybrid"] * 40, "lm_head_bias": False, "max_position_embeddings": 131072, "model_type": "zaya",
               "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts_per_tok": 1, "num_key_value_heads": 2,
               "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
               "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
                                   "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
                                   "rope_type": "default"},
               "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True, "vocab_size": 262272}
    assert {k: config[k] for k in catalog} == catalog
    assert config["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert any("LEFT OUT" in line and "router state" in line and "residual" in line and "mixture-of-depths" in line for line in config["assumed"])
    assert sum(tag in " ".join(config["assumed"]) for tag in ("[key]", "[CCA]", "[ZAYA1]")) == 3 and "2 chips" in config["deployment"]
    # the share cells' balance rule at a tenth of their rate, a settle that ends there and a hundred times their warm-up (``assumed`` says why)
    shares = registry.config("trinity-mini-trunk-train")
    assert config["train"]["warmup_steps"] == 100 * shares["train"]["warmup_steps"] == 10_000_000
    assert config["train"]["settle"] == {**shares["train"]["settle"], "balance_passes": 128, "rate_last": 0.0001}
    assert config["model"]["load_balance_coeff"] == 0.0001 == 0.1 * shares["model"]["load_balance_coeff"]  # (the trunk's rate: below)
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"moe_experts_ms", "moe_routing_ms", "trunk_attention_ms", "moe_held_slots", "moe_moved_rows", "moe_expert_load_max",
            "moe_router_entropy"} | NEW_METRICS <= names and len(names) == 24
    assert not {"trunk_dense_ffn_ms", "az_conv_roofline", "nnue_ft_roofline", "moe_expert_roofline", "moe_held_expert_roofline",
                "moe_ungated_held_expert_roofline", "mla_latent_ms", "mla_core_roofline", "ssm_mixer_ms", "ssm_scan_roofline"} & names
    for other in ("afmoe_trunk_train_b256", "mla_trunk_train_b256", "moe_trunk_train_b512", "ssm_trunk_train_b128"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint(NEW_METRICS)
    family = registry.module("families", "cca_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.layers) == (2048, 8, 2, 128, 4)
    assert (trunk.cca, trunk.rotary_dim, trunk.router_hidden, trunk.qk_norm, trunk.rope_theta, trunk.rms_eps) == ((2, 2), 64, 256, True, 5e6, 1e-5)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.shared_width, trunk.dense_layers) == (16, (0, 8), 1, 2048, 0, 0)
    assert (trunk.router_score, trunk.balance_rate, trunk.recompute_experts, trunk.routed_layers, trunk.attention_layers) == ("softmax", 0.0001, True, 4, 4)
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["wq"], shapes["wk"], shapes["wv1"], shapes["wv2"], shapes["wo"]) == (
        (4, 2048, 1024), (4, 2048, 256), (4, 2048, 128), (4, 2048, 128), (4, 1024, 2048))
    assert (shapes["conv0_w"], shapes["conv1_w"], shapes["temp"], shapes["router_down"], shapes["router_w3"], shapes["experts_gate"]) == (
        (4, 1280, 2), (4, 10, 2, 128, 128), (4, 2), (4, 2048, 256), (4, 256, 16), (4, 8, 2048, 2048))
    assert not {"wv", "q_norm", "k_norm", "router_w", "shared_gate", "dense_gate"} & set(shapes)
    count = lambda *names: sum(int(np.prod(shapes[n])) // shapes[n][0] for n in names)
    # the configuration file's reckoning (reduced_why), to the unit
    assert count("wq", "wk", "wv1", "wv2", "wo", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "temp") == 5_575_682
    assert count("router_down", "router_down_b", "router_w1", "router_w1_b", "router_w2", "router_w2_b", "router_w3") == 660_224
    assert count("experts_gate", "experts_up", "experts_down") == 100_663_296
    assert sum(int(np.prod(s)) for s in shapes.values()) == 427_880_022 and "427,880,022" in config["reduced_why"]
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "router_hidden_size": 128})
    for key, value in (("model_type", "zaya2"), ("hidden_act", "gelu"), ("attention_bias", True), ("sliding_window", 4096),
                       ("partial_rotary_factor", 1.0), ("layer_types", ["hybrid"] * 39 + ["hybrid_sliding"])):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value})
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        family.trunk_config({**config, "num_experts_per_tok": 2, "model": {**config["model"], "num_experts_per_tok": 2}})
    with pytest.raises(ValueError, match="layer_types"):
        family.trunk_config({**config, "published": {**config["published"], "kept_layers": [1, 2, 3, 4]}})


def test_both_rooflines_from_shapes_by_a_hand_count():
    registry = Registry(REPO)
    model = registry.config(CONFIG)["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    mix = registry.module("roofline", "cca_mix")
    # a token: conv1 is 10 heads x 2 taps x 2 x 128 x 128 operations, three times (forward, to the input, to the weights)
    assert mix.columns(model) == 1280 and mix.layer_flops(model, 512) == 32_768 * 3 * 10 * 2 * 2 * 128 * 128 == 64_424_509_440
    # a token: forward [q~ | k~] in and q | k out 2 x 5,120 B, gradient three such (x and the cotangents of q | k in, x's out), and the
    # value halves' move 256 x (4 + 2) B each way;
    # the weights (conv0 2,560 + 1,280, conv1 327,680 + 1,280 float32) read twice and their gradient written once
    assert mix.layer_bytes(model, 512) == 32_768 * (5 * 5_120 + 2 * 1_536) + 3 * 332_800 * 4 == 943_517_696
    least = mix.least_seconds(model, 512, peaks)
    assert least["bound"] == "memory" and abs(least["least_s"] - 4 * 943_517_696 / 819e9) < 1e-12
    assert mix.layer_flops({**model, "cca_time1": 4}, 512) == 2 * mix.layer_flops(model, 512)
    experts = registry.module("roofline", "moe_top1_held_experts")
    gated = registry.module("roofline", "moe_held_experts")
    assert experts.routed_layers(model) == 4 and experts.held_slots(model, 512) == 512 * 64 * 1 * 8 / 16 == 16_384
    assert experts.step_flops(model, 512) == 16_384 * 2 * 2048 * 2048 * 3 * 3 * 4 == 4_947_802_324_992
    assert experts.step_bytes(model, 512) == (8 * 2048 * 2048 * 2 + 16_384 * (2048 + 2048) * 2) * 3 * 3 * 4
    # the second trunk's count on the same rows and layers is the same number: a sibling file, not another rule
    assert experts.step_flops(model, 512) == gated.step_flops({**model, "num_dense_layers": 0}, 512)
    assert experts.least_seconds(model, 512, peaks)["bound"] == "compute"


def test_the_three_reducers_on_a_synthetic_split():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer00.cca": 1.5, "transpose(jvp(forward))/layer00.cca": 2.5, "jvp(forward)/layer03.cca": 2.0,
        "transpose(jvp(forward))/layer03.cca": 4.0, "jvp(forward)/layer00.attention": 3.0, "transpose(jvp(forward))/layer02.attention": 6.0,
        "jvp(forward)/layer01.experts": 20.0, "transpose(jvp(forward))/layer01.experts": 30.0, "jvp(forward)/layer01.router": 2.0, "optimizer": 100.0,
    }
    config = registry.config(CONFIG)
    ctx = {"registry": registry, "config": config, "batch": 512, "device_kind": "TPU v5 lite", "trace": object(), "scopes_split": found}
    assert registry.module("reducers", "cca_mix_ms").reduce(ctx) == 10.0
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 9.0  # beside the mix, not over it: the two add up to the branch
    assert abs(registry.module("reducers", "cca_mix_roofline").reduce(ctx) - 100 * (4 * 943_517_696 / 819e9 * 1e3) / 10.0) < 1e-9
    share = registry.module("reducers", "moe_top1_held_expert_roofline").reduce(ctx)
    assert abs(share - 100 * (4_947_802_324_992 / 197e12 * 1e3) / 50.0) < 1e-9  # compute-bound; 20 + 30 ms under the experts' scopes
    # a program without the scopes (the parent, the other trunks), another family, no trace: nothing, and no error
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/layer00.dispatch": 5.0}
    for name in NEW_METRICS:
        assert registry.module("reducers", name).reduce(ctx) is None, name
    found.by_path = {"jvp(forward)/layer00.cca": 3.0, "jvp(forward)/layer00.experts": 5.0}
    other = {**ctx, "config": registry.config("trinity-mini-trunk-train")}
    assert registry.module("reducers", "cca_mix_roofline").reduce(other) is None
    assert registry.module("reducers", "moe_top1_held_expert_roofline").reduce(other) is None
    for name in NEW_METRICS:
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny fifth trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("cca_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counters are the recorder's
    assert traced["correct"] is True and not NEW_METRICS & set(traced["metrics"])
    assert "cca_conv_share" in out and "cca_temp_max" in out and "route_top1_weight" in out and "moe_held_slots" in traced["metrics"]
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("cca-trunk-tiny")
    family = tiny.module("families", "cca_trunk")
    reference = tiny.module("reference", "cca_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]
        assert sound["_per_tensor"]["expert_bias"] == 0.0  # no gradient through the bias, on either side


def test_the_reference_peaks_its_router_on_the_piece_pins_its_value_head_and_favours_held_and_absent_alike(tiny):
    import jax.numpy as jnp

    from fishnet_tpu.models.trunk import trunk_param_shapes

    config = tiny.config("cca-trunk-tiny")
    model = config["model"]
    family, reference = tiny.module("families", "cca_trunk"), tiny.module("reference", "cca_trunk")
    from benchmark.reference.precision import cast_for, grad_cast_for

    for seed in (11, 2**31 + 12, 13, 14):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = family.build_batch(pool, np.arange(32))
        p = reference.init_params(seed, model)
        on_device = {k: jnp.asarray(v) for k, v in p.items()}
        x, slots = reference._trunk(on_device, jnp.asarray(batch["planes"]), model, lambda a: a, lambda a: a)
        # the margin: with every product's operands rounded to bfloat16, as the program rounds them, no token chooses another expert
        rounded = reference._trunk(on_device, jnp.asarray(batch["planes"]), model, cast_for("bfloat16"), grad_cast_for("bfloat16"))[1]
        assert np.array_equal(np.asarray(slots), np.asarray(rounded)), (seed, np.asarray(slots) - np.asarray(rounded))
        assert slots.shape == (2, 16) and float(slots.sum()) == 2 * 32 * 64
        held = np.asarray(slots[:, 4:12].sum(-1)) / (32 * 64)
        # the empty square's expert (over half a layer's tokens) is held in the even layer and absent in the odd
        assert 1.0 > held[0] > 0.5 > held[1], (seed, held)  # (a tiny odd layer's other kinds may all fall on absent experts too: 0.0)
        plane = np.asarray(x) @ p["value_w"][0, 0] + p["value_b"]
        assert plane.min() > 0.1, (seed, "a value plane is dead or at the relu's corner", plane.reshape(-1, 4).min(0))
        assert np.allclose(p["expert_bias"].mean(-1), 0.0, atol=1e-7) and np.abs(p["expert_bias"]).max() <= 0.006
        taps = p["conv1_w"]
        assert np.abs(taps[:, :, -1] - np.eye(8)).max() < 0.5 and np.abs(taps[:, :, 0]).max() > 0.01  # near the identity, both taps alive
        assert set(p) == set(trunk_param_shapes(family.trunk_config(config))) | {"expert_bias"}


@pytest.mark.parametrize("what", ["conv1_w_x0", "conv0_w_x1.5", "temp_x1.5", "wv2_x0", "router_w3_x1.5", "experts_down_x0", "optimizer_x0"])
def test_left_out_mathematics_fails(tiny, what):
    """A gradient multiplied by a factor, or an optimizer that does not
    update (``sweep_correct.py --mutate`` does the same at width): not
    correct."""
    from benchmark import sweep_correct

    config = tiny.config("cca-trunk-tiny")
    family = tiny.module("families", "cca_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "cca_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    tensor, factor = what.rsplit("_x", 1)
    sweep_correct.mutate(checker, tensor, float(factor))
    numbers = checker.compare(pool, 21)
    ok, line = correctness.judge(numbers, config)
    print(what, line)
    assert not ok and "EXCEEDED" in line, line


def test_the_step_moves_the_bias_as_the_reference_does(tiny):
    import jax.numpy as jnp

    config = tiny.config("cca-trunk-tiny")
    family, reference = tiny.module("families", "cca_trunk"), tiny.module("reference", "cca_trunk")
    trainer = family.make_trainer(config)
    for seed in (21, 22):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
        params = {k: jnp.asarray(v) for k, v in reference.init_params(seed, config["model"]).items()}
        slots = reference.expert_slots(params, batch["planes"], config["model"])
        want = np.asarray(reference.balanced_bias(params["expert_bias"], slots, config["model"]["load_balance_coeff"]))
        state, metrics = trainer.step(family.state_from_params(trainer, params), batch)
        got = np.asarray(state.buffers["expert_bias"])
        assert np.array_equal(np.abs(got - want) < 1e-7, np.ones_like(got, bool)), (seed, got - want)  # the margin: no choice differs, so no count
        assert float(metrics["held_slots"]) == float(slots[:, 4:12].sum())
        assert 0.4 < float(metrics["route_top1_weight"]) < 0.99 and 0.2 < float(metrics["cca_conv_share"]) < 0.8
