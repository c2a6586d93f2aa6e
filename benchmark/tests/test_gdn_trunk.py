"""The Gated DeltaNet trunk's benchmark pieces on the CPU at a tiny size: its
cut, its operation count, its three reducers, and the ``train_step`` runner
and the comparison that decides ``correct`` on a tiny ``gdn_trunk``
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
CELL = "gdn_trunk_train_b128"
CONFIG = "qwen3-next-trunk-train"

TINY_TOP = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
            "linear_key_head_dim": 32, "linear_value_head_dim": 32, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
            "num_experts": 4, "num_experts_per_tok": 3}
TINY_MODEL = {**TINY_TOP, "rotary_dim": 4, "num_routed_experts": 16, "first_held_expert": 4, "value_hidden": 32}
# CPU readings at this size over 3 seeds, 16 positions: see test_control_fails_and_program_passes, which prints them.
TINY_LIMITS = {"grad_rel_l2_all": 0.06, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.45, "loss_rel_diff": 0.001,
               "steps_drop_rel_diff": 0.05, "grad_rel_l2.gdn_A_log": 0.15, "grad_rel_l2.gdn_dt_bias": 0.15, "grad_rel_l2.gdn_ba": 0.15,
               "grad_rel_l2.gdn_conv": 0.15, "grad_rel_l2.gdn_o_norm": 0.15, "grad_rel_l2.wq": 0.1, "grad_rel_l2.shared_token_gate": 0.15}


def tiny_gdn_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``gdn_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="gdn-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "gdn-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "gdn-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/gdn-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "gdn_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "gdn_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "gdn_trunk_tiny_cell", "config": "gdn-trunk-tiny", "traffic": "tiny_pool", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("gdn_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_gdn_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts"] and config["train"]["batch"] == 128
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 16)
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512, "kept_layers": [0, 1, 2, 3], "held_experts": list(range(16))}
    # every key of the catalog's row but the two reduced, as published
    catalog = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
               "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
               "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
               "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 10, "num_key_value_heads": 2,
               "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
               "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    assert {k: config[k] for k in catalog} == catalog
    assert config["train"]["warmup_steps"] == 100000 and config["train"]["settle"] == registry.config("kimi-linear-trunk-train")["train"]["settle"]
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"gdn_mixer_ms", "gdn_core_roofline", "gdn_state_kept", "trunk_attention_ms", "trunk_dense_ffn_ms", "moe_held_slots", "moe_experts_ms"} <= names
    assert not {"kda_mixer_ms", "kda_core_roofline", "kda_state_kept", "mla_latent_ms", "moe_held_expert_roofline", "ssm_mixer_ms"} & names and len(names) == 25
    for other in ("kda_trunk_train_b128", "afmoe_trunk_train_b256"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint({"gdn_mixer_ms", "gdn_core_roofline", "gdn_state_kept"})
    family = registry.module("families", "gdn_trunk")
    trunk = family.trunk_config(config)
    assert trunk.mixers == ("gdn", "gdn", "gdn", "attention") and trunk.nope_layers == () and trunk.layers == 4 and trunk.dense_layers == 0
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.rotary_dim, trunk.rope_theta) == (2048, 16, 2, 256, 64, 1e7)
    assert (trunk.linear_num_key_heads, trunk.linear_num_value_heads, trunk.linear_key_head_dim, trunk.linear_value_head_dim, trunk.conv_kernel) == (16, 32, 128, 128, 4)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.shared_width) == (512, (0, 16), 10, 512, 512)
    assert (trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate, trunk.rms_eps) == ("softmax", True, 1.0, 0.001, 1e-6)
    assert trunk.gated_attention and trunk.shared_token_gate and trunk.zero_centered_norms and trunk.qk_norm and trunk.recompute_experts
    assert not trunk.post_norms and trunk.embed_scale == 1.0 and trunk.pattern is None and trunk.cca is None and trunk.kv_lora_rank is None
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["gdn_qkvz"], shapes["gdn_ba"], shapes["gdn_conv"], shapes["gdn_A_log"], shapes["gdn_o_norm"], shapes["gdn_out"]) == (
        (3, 2048, 12288), (3, 2048, 64), (3, 8192, 4), (3, 32), (3, 128), (3, 4096, 2048))
    assert (shapes["wq"], shapes["wgate"], shapes["wk"], shapes["q_norm"], shapes["wo"], shapes["attn_norm"]) == (
        (1, 2048, 4096), (1, 2048, 4096), (1, 2048, 512), (1, 256), (1, 4096, 2048), (4, 2048))
    assert (shapes["router_w"], shapes["experts_up"], shapes["shared_up"], shapes["shared_token_gate"]) == (
        (4, 2048, 512), (4, 16, 2048, 512), (4, 2048, 512), (4, 2048, 1))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 346_814_094  # the file's reduced_why
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "head_dim": 128})
    for key, value in (("mlp_only_layers", [0]), ("decoder_sparse_step", 2), ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
                       ("norm_topk_prob", False), ("linear_key_head_dim", 64), ("partial_rotary_factor", 0.5), ("full_attention_interval", 2),
                       ("model_type", "qwen3_moe")):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value})


def test_the_column_orders_by_hand():
    """Two key heads of width 2 with two value heads each, two query heads of width 3: the published per-key-head and
    per-head orders into the program's, and back."""
    from fishnet_tpu.models.trunk import TrunkConfig

    family = Registry(REPO).module("families", "gdn_trunk")
    cfg = TrunkConfig(hidden=8, heads=2, kv_heads=1, head_dim=4, experts=4, experts_per_token=1, expert_width=8, gated_attention=True, shared_width=8,
                      mixers=("gdn", "attention"), linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=2, linear_value_head_dim=2)
    orders = family.column_orders(cfg)
    # a key head's 12 columns: q 0-1, k 2-3, v 4-7 (its two value heads), z 8-11
    assert list(orders["gdn_qkvz"]) == [0, 1, 12, 13, 2, 3, 14, 15, 4, 5, 6, 7, 16, 17, 18, 19, 8, 9, 10, 11, 20, 21, 22, 23]
    assert list(orders["gdn_ba"]) == [0, 1, 4, 5, 2, 3, 6, 7]  # a key head's b (2), a (2)
    assert list(orders["wq"]) == [0, 1, 2, 3, 8, 9, 10, 11] and list(orders["wgate"]) == [4, 5, 6, 7, 12, 13, 14, 15]
    published = {"gdn_qkvz": np.arange(24.0)[None], "gdn_ba": np.arange(8.0)[None], "wq": np.arange(16.0)[None], "wk": np.ones((1, 4))}
    program = family.to_program(cfg, published)
    assert set(program) == {"gdn_qkvz", "gdn_ba", "wq", "wgate", "wk"} and program["wq"].shape == program["wgate"].shape == (1, 8)
    back = family.from_program(cfg, program)
    assert set(back) == set(published) and all(np.array_equal(np.asarray(back[k]), published[k]) for k in published)


def test_the_core_hand_count():
    core = Registry(REPO).module("roofline", "gdn_core")
    model = Registry(REPO).config(CONFIG)["model"]
    assert core.gdn_layers(model) == 3
    # a (board, key head): twenty products of 2 x 64 x 64 x 128 operations: 2 + 2 x 2 forward, 2 + 2 x 4 + 4 in the gradient
    assert core.layer_flops(model, 128) == 128 * 16 * 20 * 2 * 64 * 64 * 128 == 42_949_672_960
    # a token: q and k bfloat16 at 16 key heads (8,192 B), v bfloat16 at 32 value heads (8,192 B), g and beta float32 (256 B); o, or its cotangent, 8,192 B
    assert core.layer_bytes(model, 128) == 8_192 * ((16_640 + 8_192) + (16_640 + 8_192 + 16_640)) == 543_162_368
    least = core.least_seconds(model, 128, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory" and abs(least["least_s"] - 3 * 543_162_368 / 819e9) < 1e-9 and abs(least["compute_s"] - 3 * 42_949_672_960 / 197e12) < 1e-9
    # the count follows the shapes, not the kernel: one value head a key head is 14 products a head, the sixth trunk's count
    assert core.layer_flops({**model, "linear_num_value_heads": 16}, 128) == 128 * 16 * 14 * 2 * 64 * 64 * 128


def test_the_three_reducers_on_a_synthetic_split():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer00.gdn": 2.0, "transpose(jvp(forward))/layer00.gdn": 4.0, "jvp(forward)/layer00.delta": 1.5,
        "transpose(jvp(forward))/layer02.delta": 4.5, "jvp(forward)/layer03.attention": 8.0, "jvp(forward)/layer00.shared": 3.0, "optimizer": 100.0,
    }
    config = registry.config(CONFIG)
    trace = tracelib.Trace(ops=[], modules=[("jit__step", 0.0, 100e6)], host_spans=[])
    ctx = {"registry": registry, "config": config, "batch": 128, "device_kind": "TPU v5 lite", "trace": trace, "scopes_split": found,
           "step_counters": [{"gdn_state_kept": 0.2, "gdn_beta": 0.5, "shared_gate_mean": 0.5}, {"gdn_state_kept": 0.3, "gdn_beta": 0.5, "shared_gate_mean": 0.5}]}
    assert registry.module("reducers", "gdn_mixer_ms").reduce(ctx) == 12.0
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 8.0 and registry.module("reducers", "trunk_dense_ffn_ms").reduce(ctx) == 3.0
    assert abs(registry.module("reducers", "gdn_core_roofline").reduce(ctx) - 100 * (3e3 * 543_162_368 / 819e9) / 6.0) < 0.01
    assert abs(registry.module("reducers", "gdn_state_kept").reduce(ctx) - 0.25) < 1e-9
    # a program without the scopes or the counters (the parent, the other trunks; the sixth trunk has ``delta`` and no ``gdn``), no trace: nothing
    found.by_path = {"jvp(forward)/layer00.kda": 3.0, "jvp(forward)/layer00.delta": 5.0}
    assert registry.module("reducers", "gdn_mixer_ms").reduce(ctx) is None
    assert registry.module("reducers", "gdn_core_roofline").reduce({**ctx, "config": registry.config("kimi-linear-trunk-train")}) is None
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/layer00.experts": 5.0}
    assert registry.module("reducers", "gdn_mixer_ms").reduce(ctx) is None and registry.module("reducers", "gdn_core_roofline").reduce(ctx) is None
    for name in ("gdn_mixer_ms", "gdn_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None
    assert registry.module("reducers", "gdn_state_kept").reduce({**ctx, "step_counters": [{"held_slots": 5.0}]}) is None
    assert registry.module("reducers", "gdn_state_kept").reduce({**ctx, "step_counters": None}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny Gated DeltaNet trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("gdn_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.gdn_A_log" in out and "gdn_beta" in out and "shared_gate_mean" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counters are the program's
    assert traced["correct"] is True and not {"gdn_mixer_ms", "gdn_core_roofline"} & set(traced["metrics"])
    assert 0.0 < traced["metrics"]["gdn_state_kept"]["value"] < 1.0 and "moe_held_slots" in traced["metrics"]
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("gdn-trunk-tiny")
    family, reference = tiny.module("families", "gdn_trunk"), tiny.module("reference", "gdn_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]
        assert sound["_per_tensor"]["expert_bias"] == 0.0  # no gradient through the bias, on either side


@pytest.mark.parametrize("misread", ["rate_times_1.5", "key_head_mod", "gate_sigmoid", "gate_before_norm", "no_token_gate", "rope_all", "plain_gain"])
def test_a_misread_block_is_not_correct(tiny, misread):
    """The reference computing one of the seven misreadings of the block
    (``benchmark/sweep_misread.py`` does the same at width): the program is
    then NOT what the reference computes, by one of the configuration's limits."""
    config = copy.deepcopy(tiny.config("gdn-trunk-tiny"))
    config["model"]["misread"] = misread
    family = tiny.module("families", "gdn_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "gdn_trunk"), config)
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

    config = tiny.config("gdn-trunk-tiny")
    family, reference = tiny.module("families", "gdn_trunk"), tiny.module("reference", "gdn_trunk")
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
        assert 0.3 < float(metrics["gdn_state_kept"]) < 1.0 and 0.2 < float(metrics["gdn_beta"]) < 0.8 and 0.2 < float(metrics["shared_gate_mean"]) < 0.8
