"""The delta-rule trunk's benchmark pieces on the CPU at a tiny size: its
cut, its operation count, its three reducers, and the ``train_step`` runner
and the comparison that decides ``correct`` on a tiny ``kda_trunk``
configuration added to a temp copy as new files and entries only."""

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
CELL = "kda_trunk_train_b128"
CONFIG = "kimi-linear-trunk-train"

TINY_TOP = {"hidden_size": 64, "num_attention_heads": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 64, "v_head_dim": 16,
            "num_hidden_layers": 5, "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_token": 3}
TINY_LINEAR = {"num_heads": 2, "head_dim": 16}
TINY_MODEL = {k: v for k, v in TINY_TOP.items() if k != "num_experts_per_token"}
TINY_MODEL.update(num_experts_per_tok=3, num_routed_experts=16, first_held_expert=4, value_hidden=32, kda_num_heads=2, kda_head_dim=16)
# CPU readings at this size over 3 seeds, 16 positions: see test_control_fails_and_program_passes, which prints them.
TINY_LIMITS = {"grad_rel_l2_all": 0.04, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.45, "loss_rel_diff": 0.001,
               "steps_drop_rel_diff": 0.05, "grad_rel_l2.kda_A_log": 0.1, "grad_rel_l2.kda_dt_bias": 0.1, "grad_rel_l2.kda_fb": 0.1,
               "grad_rel_l2.kda_beta": 0.1, "grad_rel_l2.kda_conv": 0.1}


def tiny_kda_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``kda_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="kda-trunk-tiny")
    config["linear_attn_config"].update(TINY_LINEAR)
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "kda-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "kda-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/kda-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "kda_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "kda_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "kda_trunk_tiny_cell", "config": "kda-trunk-tiny", "traffic": "tiny_pool", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("kda_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_kda_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "num_attention_heads", "linear_attn_config"] and config["train"]["batch"] == 128
    assert (config["num_hidden_layers"], config["num_experts"], config["num_attention_heads"], config["linear_attn_config"]["num_heads"]) == (5, 8, 16, 16)
    published = config["published"]
    assert {k: published[k] for k in ("num_hidden_layers", "num_experts", "num_attention_heads", "linear_attn_config.num_heads", "kept_layers")} == {
        "num_hidden_layers": 27, "num_experts": 256, "num_attention_heads": 32, "linear_attn_config.num_heads": 32, "kept_layers": [1, 2, 3, 4, 5]}
    # every key of the catalog's row but the four reduced, as published (linear_attn_config but for its head count)
    catalog = {"first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
               "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024, "moe_layer_freq": 1,
               "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "num_experts_per_token": 8,
               "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
               "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840}
    assert {k: config[k] for k in catalog} == catalog
    linear = config["linear_attn_config"]
    assert (linear["head_dim"], linear["short_conv_kernel_size"], linear["full_attn_layers"]) == (128, 4, [4, 8, 12, 16, 20, 24, 27])
    assert linear["kda_layers"] == [i for i in range(1, 28) if i not in linear["full_attn_layers"]]
    assert config["train"]["warmup_steps"] == 100000 and config["train"]["settle"] == registry.config("trinity-mini-trunk-train")["train"]["settle"]
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"kda_mixer_ms", "kda_core_roofline", "kda_state_kept", "mla_latent_ms", "trunk_attention_ms", "trunk_dense_ffn_ms", "moe_held_slots"} <= names
    assert not {"mla_core_roofline", "moe_held_expert_roofline", "ssm_mixer_ms", "cca_mix_ms"} & names and len(names) == 26
    for other in ("mla_trunk_train_b256", "ssm_trunk_train_b128"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint({"kda_mixer_ms", "kda_core_roofline", "kda_state_kept"})
    family = registry.module("families", "kda_trunk")
    trunk = family.trunk_config(config)
    assert trunk.mixers == ("kda", "kda", "kda", "latent", "kda") and trunk.nope_layers == (3,) and trunk.layers == 5 and trunk.dense_layers == 1
    assert (trunk.hidden, trunk.heads, trunk.kda_heads, trunk.kda_head_dim, trunk.conv_kernel) == (2304, 16, 16, 128, 4)
    assert (trunk.kv_lora_rank, trunk.qk_nope_head_dim, trunk.qk_rope_head_dim, trunk.v_head_dim) == (512, 128, 64, 128)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.dense_width, trunk.shared_width) == (256, (0, 8), 8, 1024, 9216, 1024)
    assert (trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate, trunk.rms_eps) == ("sigmoid", True, 2.446, 0.001, 1e-5)
    assert not trunk.post_norms and trunk.embed_scale == 1.0 and trunk.recompute_experts and trunk.pattern is None and trunk.cca is None
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["kda_q"], shapes["kda_conv"], shapes["kda_fa"], shapes["kda_fb"], shapes["kda_beta"], shapes["kda_out"]) == (
        (4, 2304, 2048), (4, 6144, 4), (4, 2304, 128), (4, 128, 2048), (4, 2304, 16), (4, 2048, 2304))
    assert (shapes["wq"], shapes["wkv_b"], shapes["wo"], shapes["attn_norm"]) == ((1, 2304, 3072), (1, 512, 4096), (1, 2048, 2304), (5, 2304))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 416_608_910  # the file's reduced_why
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "kv_lora_rank": 256})
    for key, value in (("q_lora_rank", 1536), ("num_expert_group", 8), ("topk_group", 4), ("moe_router_activation_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"}), ("num_shared_experts", 2), ("num_nextn_predict_layers", 1), ("moe_renormalize", False),
                       ("mla_use_nope", False), ("linear_attn_config", {**linear, "short_conv_kernel_size": 2}),
                       ("linear_attn_config", {**linear, "kda_layers": [1, 2, 3]})):  # layer 5 would have no mixer
        with pytest.raises(ValueError, match="topk_group|num_expert_group" if key == "topk_group" else key):
            family.trunk_config({**config, key: value})


def test_the_core_hand_count():
    core = Registry(REPO).module("roofline", "kda_core")
    model = Registry(REPO).config(CONFIG)["model"]
    assert core.kda_layers(model) == 4
    # a (board, head): fourteen products of 2 x 64 x 64 x 128 operations: four forward, ten in the gradient
    assert core.layer_flops(model, 128) == 128 * 16 * 14 * 2 * 64 * 64 * 128 == 30_064_771_072
    # a token and head: q, k, v bfloat16 and g float32 of 128 columns and beta = 1,284 B; o, or its cotangent, 256 B
    assert core.layer_bytes(model, 128) == 8_192 * 16 * ((1_284 + 256) + (1_284 + 256 + 1_284)) == 571_998_208
    least = core.least_seconds(model, 128, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory" and abs(least["least_s"] - 0.0027936) < 1e-6 and abs(least["compute_s"] - 0.00061045) < 1e-7
    assert core.layer_bytes({**model, "kda_num_heads": 32}, 128) == 2 * core.layer_bytes(model, 128)


def test_the_three_reducers_on_a_synthetic_split():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer00.kda": 2.0, "transpose(jvp(forward))/layer00.kda": 4.0, "jvp(forward)/layer00.delta": 1.5,
        "transpose(jvp(forward))/layer02.delta": 4.5, "jvp(forward)/layer03.attention": 8.0, "jvp(forward)/layer03.latent": 3.0, "optimizer": 100.0,
    }
    config = registry.config(CONFIG)
    trace = tracelib.Trace(ops=[], modules=[("jit__step", 0.0, 100e6)], host_spans=[])
    ctx = {"registry": registry, "config": config, "batch": 128, "device_kind": "TPU v5 lite", "trace": trace, "scopes_split": found,
           "step_counters": [{"kda_state_kept": 0.8, "kda_beta": 0.5}, {"kda_state_kept": 0.9, "kda_beta": 0.5}]}
    assert registry.module("reducers", "kda_mixer_ms").reduce(ctx) == 12.0
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 8.0 and registry.module("reducers", "mla_latent_ms").reduce(ctx) == 3.0
    assert abs(registry.module("reducers", "kda_core_roofline").reduce(ctx) - 100 * 2.7936 / 6.0) < 0.01
    assert abs(registry.module("reducers", "kda_state_kept").reduce(ctx) - 0.85) < 1e-9
    # a program without the scopes or the counters (the parent, the other trunks), no trace: nothing, and no error
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/layer00.experts": 5.0}
    assert registry.module("reducers", "kda_mixer_ms").reduce(ctx) is None and registry.module("reducers", "kda_core_roofline").reduce(ctx) is None
    for name in ("kda_mixer_ms", "kda_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None
    assert registry.module("reducers", "kda_core_roofline").reduce({**ctx, "config": registry.config("kanana-2-trunk-train")}) is None
    assert registry.module("reducers", "kda_state_kept").reduce({**ctx, "step_counters": [{"held_slots": 5.0}]}) is None
    assert registry.module("reducers", "kda_state_kept").reduce({**ctx, "step_counters": None}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny delta-rule trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("kda_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.kda_A_log" in out and "kda_beta" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counters are the program's
    assert traced["correct"] is True and not {"kda_mixer_ms", "kda_core_roofline"} & set(traced["metrics"])
    assert 0.3 < traced["metrics"]["kda_state_kept"]["value"] < 1.0 and "moe_held_slots" in traced["metrics"]
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("kda-trunk-tiny")
    family, reference = tiny.module("families", "kda_trunk"), tiny.module("reference", "kda_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]
        assert sound["_per_tensor"]["expert_bias"] == 0.0  # no gradient through the bias, on either side


@pytest.mark.parametrize("misread", ["decay_after", "unit_beta"])
def test_a_misread_recurrence_is_not_correct(tiny, misread):
    """The reference computing one of the two misreadings of the delta rule
    (``benchmark/sweep_misread.py`` does the same at width): the program is
    then NOT what the reference computes, by a named tensor's limit."""
    config = copy.deepcopy(tiny.config("kda-trunk-tiny"))
    config["model"]["misread"] = misread
    family = tiny.module("families", "kda_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "kda_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    ok, line = correctness.judge(checker.compare(pool, 21), config)
    print(misread, line)
    assert not ok and "EXCEEDED" in line, line


def test_the_step_moves_the_bias_as_the_reference_does(tiny):
    """``expert_bias`` after one step of the program, from the reference's
    parameters, against the reference's balance rule on the reference's own
    routing counts: the comparison that decides ``correct`` cannot see this
    update (PERF.md section 7), so it is held to the reference here."""
    import jax.numpy as jnp

    config = tiny.config("kda-trunk-tiny")
    family, reference = tiny.module("families", "kda_trunk"), tiny.module("reference", "kda_trunk")
    trainer = family.make_trainer(config)
    for seed in (21, 22):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
        params = {k: jnp.asarray(v) for k, v in reference.init_params(seed, config["model"]).items()}
        slots = reference.expert_slots(params, batch["planes"], config["model"])
        want = np.asarray(reference.balanced_bias(params["expert_bias"], slots, config["model"]["load_balance_coeff"]))
        state, metrics = trainer.step(family.state_from_params(trainer, params), batch)
        got = np.asarray(state.buffers["expert_bias"])
        assert got.shape == (4, 16) and np.mean(np.abs(got - want) < 1e-7) > 0.9, (seed, got - want)  # but for a rounding's swaps near a layer's mean
        assert abs(float(metrics["held_slots"]) - float(slots[:, 4:8].sum())) <= 8 + 0.1 * float(slots[:, 4:8].sum())
        assert 0.3 < float(metrics["kda_state_kept"]) < 1.0 and 0.2 < float(metrics["kda_beta"]) < 0.8
