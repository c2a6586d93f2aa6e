"""The pattern trunk's benchmark pieces on the CPU at a tiny size: its
cut, its two operation counts, its three reducers, and the ``train_step``
runner and the comparison that decides ``correct`` on a tiny
``hybrid_trunk`` configuration added to a temp copy as new files and
entries only."""

from __future__ import annotations

import copy
import json
import time

import numpy as np
import pytest

import helpers
from benchmark import correctness, positions, scopes, tracelib
from benchmark.registry import Registry

REPO = helpers.REPO
CELL = "ssm_trunk_train_b128"
CONFIG = "nemotron-twotower-trunk-train"

TINY_TOP = {"hidden_size": 84, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4, "mamba_head_dim": 8,
            "n_groups": 2, "ssm_state_size": 16, "moe_intermediate_size": 232, "intermediate_size": 232,
            "moe_shared_expert_intermediate_size": 58, "n_routed_experts": 8, "num_experts_per_tok": 3}
TINY_MODEL = {k: v for k, v in TINY_TOP.items() if k not in ("intermediate_size", "n_routed_experts")}
TINY_MODEL.update(num_experts=8, num_routed_experts=16, first_held_expert=4, value_hidden=32)
# CPU readings at this size over 3 seeds, 16 positions (test_control_fails_and_program_passes prints them): all tensors as one read
# 0.0081-0.0085 sound and 0.072-0.092 under the fp8 control.
TINY_LIMITS = {"grad_rel_l2_all": 0.025, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.45, "loss_rel_diff": 0.002,
               "steps_drop_rel_diff": 0.08}


def tiny_hybrid_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``hybrid_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="hybrid-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "hybrid-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "hybrid-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/hybrid-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "hybrid_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "hybrid_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "hybrid_trunk_tiny_cell", "config": "hybrid-trunk-tiny", "traffic": "tiny_pool",
                              "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("hybrid_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_hybrid_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts"] and config["train"]["batch"] == 128
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (7, 8)
    assert config["published"]["num_hidden_layers"] == 52 and config["published"]["n_routed_experts"] == 128
    assert config["published"]["kept_layers"] == list(range(7)) and config["model"]["pattern"] == "MEMEM*E"
    # every key of the catalog's row but the two reduced, as published
    catalog = {"attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
               "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", "intermediate_size": 1856,
               "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
               "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
               "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_shared_experts": 1,
               "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 2,
               "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
               "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
               "time_step_floor": 0.0001, "time_step_limit": [0, None], "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
               "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
    assert {k: config[k] for k in catalog} == catalog
    assert len(config["hybrid_override_pattern"]) == 52 and config["hybrid_override_pattern"][:7] == config["model"]["pattern"]
    assert any("LEFT OUT" in line and "denoiser" in line and "block diffusion" in line for line in config["assumed"])
    # the share cells' window: the same warm-up and the same settling, unchanged
    assert config["train"]["warmup_steps"] == 100000 and config["train"]["settle"] == registry.config("trinity-mini-trunk-train")["train"]["settle"]
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"moe_experts_ms", "moe_routing_ms", "trunk_attention_ms", "trunk_dense_ffn_ms", "moe_held_slots", "ssm_mixer_ms", "ssm_scan_roofline",
            "moe_ungated_held_expert_roofline"} <= names and len(names) == 25
    assert not {"az_conv_roofline", "nnue_ft_roofline", "moe_expert_roofline", "moe_held_expert_roofline", "mla_latent_ms", "mla_core_roofline"} & names
    for other in ("afmoe_trunk_train_b256", "mla_trunk_train_b256", "moe_trunk_train_b512"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint({"ssm_mixer_ms", "ssm_scan_roofline", "moe_ungated_held_expert_roofline"})
    family = registry.module("families", "hybrid_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.layers, trunk.pattern) == (2688, 32, 2, 128, 7, "MEMEM*E")
    assert (trunk.mamba_heads, trunk.mamba_head_dim, trunk.mamba_groups, trunk.state_size, trunk.conv_kernel) == (64, 64, 8, 128, 4)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.shared_width) == (128, (0, 8), 6, 1856, 3712)
    assert (trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate, trunk.rope_theta, trunk.rms_eps) == ("sigmoid", True, 2.5, 0.001, 1e4, 1e-5)
    assert not trunk.gated_ffn and not trunk.qk_norm and trunk.recompute_experts and trunk.routed_layers == 3 and trunk.attention_layers == 1
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["mamba_in"], shapes["conv_w"], shapes["dt_bias"], shapes["mamba_out"]) == ((3, 2688, 10304), (3, 6144, 4), (3, 64), (3, 4096, 2688))
    assert (shapes["wq"], shapes["wk"], shapes["experts_up"], shapes["shared_down"], shapes["layer_norm"]) == (
        (1, 2688, 4096), (1, 2688, 256), (3, 8, 2688, 1856), (3, 3712, 2688), (7, 2688))
    assert not {"experts_gate", "shared_gate", "q_norm", "k_norm", "attn_norm", "moe_norm"} & set(shapes)
    count = lambda *names: sum(int(np.prod(shapes[n])) // shapes[n][0] for n in names)
    assert count("mamba_in", "conv_w", "conv_b", "dt_bias", "A_log", "D_skip", "mamba_norm", "mamba_out") + 2688 == 38_744_896
    assert count("wq", "wk", "wv", "wo") + 2688 == 23_399_040
    assert count("router_w", "experts_up", "experts_down", "shared_up", "shared_down") + 2688 == 100_125_312
    assert sum(int(np.prod(s)) for s in shapes.values()) == 440_339_214  # the file's reduced_why
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "ssm_state_size": 64})
    for key, value in (("mlp_hidden_act", "silu"), ("n_group", 8), ("partial_rotary_factor", 0.5), ("chunk_size", 32), ("n_shared_experts", 2),
                       ("time_step_limit", [0, 1.0]), ("routed_scaling_factor", 1.0), ("model_type", "nemotron")):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value})
    with pytest.raises(ValueError, match="pattern"):
        family.trunk_config({**config, "published": {**config["published"], "kept_layers": [1, 2, 3, 4, 5, 6, 7]}})


def test_both_rooflines_from_shapes_by_a_hand_count():
    registry = Registry(REPO)
    model = registry.config(CONFIG)["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    scan = registry.module("roofline", "ssd_scan")
    # a board: 2 x 64 x 64 operations a column: forward 8 groups x 128 + 64 heads x 64; gradient 8 x 3 x 128 + 64 x 2 x 64
    assert scan.layer_flops(model, 128) == 128 * 8192 * ((1024 + 4096) + (3072 + 8192)) == 17_179_869_184
    # a token: x 4096 + B, C 2 x 1024 bfloat16 and the step's 64 float32 = 12,544 B; y or its cotangent 8,192 B
    assert scan.layer_bytes(model, 128) == 8192 * ((12_544 + 8_192) + (12_544 + 8_192 + 12_544)) == 442_499_072
    least = scan.least_seconds(model, 128, peaks)
    assert scan.scan_layers(model) == 3 and least["bound"] == "memory" and abs(least["least_s"] - 3 * 442_499_072 / 819e9) < 1e-12
    assert scan.layer_bytes({**model, "n_groups": 64}, 128) > 1.5 * scan.layer_bytes(model, 128)  # B and C a head, not a group
    experts = registry.module("roofline", "moe_ungated_held_experts")
    gated = registry.module("roofline", "moe_held_experts")
    assert experts.routed_layers(model) == 3 and experts.held_slots(model, 128) == 128 * 64 * 6 * 8 / 128 == 3072
    assert experts.step_flops(model, 128) == 3072 * 2 * 2688 * 1856 * 2 * 3 * 3 == 551_735_525_376
    assert experts.step_bytes(model, 128) == (8 * 2688 * 1856 * 2 + 3072 * (2688 + 1856) * 2) * 2 * 3 * 3
    # the gated count on the same rows is 3 products to these 2, whatever the layer count
    same = {**model, "num_hidden_layers": 3, "num_dense_layers": 0}
    assert abs(experts.step_flops(model, 128) / gated.step_flops(same, 128) - 2 / 3) < 1e-12
    assert experts.least_seconds(model, 128, peaks)["bound"] == "compute"


def test_the_three_reducers_on_a_synthetic_split():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer00.mamba": 7.0, "transpose(jvp(forward))/layer00.mamba": 14.0, "jvp(forward)/layer02.scan": 0.5,
        "transpose(jvp(forward))/layer02.scan": 2.0, "jvp(forward)/layer05.attention": 3.0, "jvp(forward)/layer01.experts": 4.0,
        "transpose(jvp(forward))/layer01.experts": 6.0, "jvp(forward)/layer01.shared": 2.5, "optimizer": 100.0,
    }
    config = registry.config(CONFIG)
    ctx = {"registry": registry, "config": config, "batch": 128, "device_kind": "TPU v5 lite", "trace": object(), "scopes_split": found}
    assert registry.module("reducers", "ssm_mixer_ms").reduce(ctx) == 23.5
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 3.0 and registry.module("reducers", "trunk_dense_ffn_ms").reduce(ctx) == 2.5
    assert abs(registry.module("reducers", "ssm_scan_roofline").reduce(ctx) - 100 * (3 * 442_499_072 / 819e9 * 1e3) / 2.5) < 1e-9
    share = registry.module("reducers", "moe_ungated_held_expert_roofline").reduce(ctx)
    assert abs(share - 100 * (551_735_525_376 / 197e12 * 1e3) / 10.0) < 1e-9  # compute-bound; 4 + 6 ms under the experts' scopes
    # a program without the scopes (the parent, the other trunks), another family, no trace: nothing, and no error
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/layer00.experts": 5.0}
    assert registry.module("reducers", "ssm_mixer_ms").reduce(ctx) is None and registry.module("reducers", "ssm_scan_roofline").reduce(ctx) is None
    other = {**ctx, "config": registry.config("kanana-2-trunk-train")}
    assert registry.module("reducers", "ssm_scan_roofline").reduce(other) is None
    assert registry.module("reducers", "moe_ungated_held_expert_roofline").reduce(other) is None
    for name in ("ssm_mixer_ms", "ssm_scan_roofline", "moe_ungated_held_expert_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny pattern trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("hybrid_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counters are the recorder's
    assert traced["correct"] is True and not {"ssm_mixer_ms", "ssm_scan_roofline", "moe_ungated_held_expert_roofline"} & set(traced["metrics"])
    assert "ssm_decay_min" in out and "ssm_dt_mean" in out and "moe_held_slots" in traced["metrics"]
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("hybrid-trunk-tiny")
    family = tiny.module("families", "hybrid_trunk")
    reference = tiny.module("reference", "hybrid_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]
        assert sound["_per_tensor"]["expert_bias"] == 0.0  # no gradient through the bias, on either side


def test_the_reference_centres_its_routers_pins_its_value_head_and_starts_its_mixers_where_mamba2_does(tiny):
    import jax.numpy as jnp

    from fishnet_tpu.models.trunk import trunk_param_shapes

    config = tiny.config("hybrid-trunk-tiny")
    model = config["model"]
    family, reference = tiny.module("families", "hybrid_trunk"), tiny.module("reference", "hybrid_trunk")
    for seed in (11, 2**31 + 12, 13, 14):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = family.build_batch(pool, np.arange(32))
        p = reference.init_params(seed, model)
        x, slots = reference._trunk({k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(batch["planes"]), model, lambda a: a, lambda a: a)
        assert slots.shape == (3, 16) and float(slots.sum()) == 3 * 32 * 64 * 3
        assert float(slots[:, 4:12].sum(-1).min()) > 0, (seed, "a routed layer whose held experts no token chose", slots)
        plane = np.asarray(x) @ p["value_w"][0, 0] + p["value_b"]
        assert plane.min() > 0.1, (seed, "a value plane is dead or at the relu's corner", plane.reshape(-1, 4).min(0))  # hidden 84 varies more than 2,688
        assert np.allclose(p["expert_bias"].mean(-1), 0.0, atol=1e-7) and np.abs(p["expert_bias"]).max() <= 0.006
        steps, rates = np.log1p(np.exp(p["dt_bias"].astype(np.float64))), np.exp(p["A_log"])
        assert 0.00099 <= steps.min() and steps.max() <= 0.1001 and 1.0 <= rates.min() and rates.max() <= 16.0
        assert set(p) == set(trunk_param_shapes(family.trunk_config(config))) | {"expert_bias"}


@pytest.mark.parametrize("what", ["A_log_x1.5", "D_skip_x0", "conv_w_x0", "mamba_in_x1.5", "experts_up_x0", "route_scale_x1.5"])
def test_left_out_mathematics_fails(tiny, what):
    """A gradient multiplied by a factor (``sweep_correct.py --mutate``
    does the same at width), or the route scale by 1.5: not correct."""
    import dataclasses

    from fishnet_tpu.train.az_trainer import AzTrainer

    config = tiny.config("hybrid-trunk-tiny")
    family = tiny.module("families", "hybrid_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "hybrid_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    if what == "route_scale_x1.5":
        train = checker.config["train"]
        checker.trainer = AzTrainer(dataclasses.replace(checker.trainer.cfg, route_scale=2.5 * 1.5), learning_rate=train["learning_rate"],
                                    value_weight=train["value_weight"])
        checker._program_grad = family.loss_and_grads(checker.trainer)
    else:
        tensor, factor = what.rsplit("_x", 1)
        grad = checker._program_grad

        def scaled(params, batch):
            loss, grads = grad(params, batch)
            return loss, {**grads, tensor: float(factor) * grads[tensor]}

        checker._program_grad = scaled
    numbers = checker.compare(pool, 21)
    ok, line = correctness.judge(numbers, config)
    print(what, line)
    assert not ok and "EXCEEDED" in line, line


def test_the_step_moves_the_bias_as_the_reference_does(tiny):
    import jax.numpy as jnp

    config = tiny.config("hybrid-trunk-tiny")
    family, reference = tiny.module("families", "hybrid_trunk"), tiny.module("reference", "hybrid_trunk")
    trainer = family.make_trainer(config)
    for seed in (21, 22):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
        params = {k: jnp.asarray(v) for k, v in reference.init_params(seed, config["model"]).items()}
        slots = reference.expert_slots(params, batch["planes"], config["model"])
        want = np.asarray(reference.balanced_bias(params["expert_bias"], slots, config["model"]["load_balance_coeff"]))
        state, metrics = trainer.step(family.state_from_params(trainer, params), batch)
        got = np.asarray(state.buffers["expert_bias"])
        assert np.mean(np.abs(got - want) < 1e-7) > 0.9, (seed, got - want)
        assert abs(float(metrics["held_slots"]) - float(slots[:, 4:12].sum())) <= 8 + 0.1 * float(slots[:, 4:12].sum())  # but for swaps
        assert 0.0005 < float(metrics["ssm_dt_mean"]) < 0.5 and 0.0 <= float(metrics["ssm_decay_min"]) <= 1.0
