"""The sdar trunk's benchmark pieces on the CPU at a tiny size: its cut,
its noise, its operation count, its four reducers, and the ``train_step``
runner and the comparison that decides ``correct`` on a tiny ``sdar_trunk``
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
CELL = "sdar_trunk_train_b128"
CONFIG = "sdar-30b-a3b-trunk-train"

TINY_TOP = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 4,
            "num_experts_per_tok": 3, "num_hidden_layers": 2}
TINY_MODEL = {**TINY_TOP, "num_routed_experts": 16, "first_held_expert": 4, "value_hidden": 32, "t_min": 0.05, "load_balance_coeff": 0.001}
# CPU readings at this size over 3 seeds, 16 positions (test_control_fails_and_program_passes prints them): sound, all gradients as one vector
# 0.003-0.005 (control 0.04-0.06); the mildest misreading, ``clean_unmasked``, reads wq and wk 0.1 and more.
TINY_LIMITS = {"grad_rel_l2_all": 0.02, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.6, "loss_rel_diff": 0.001, "steps_drop_rel_diff": 0.05,
               "grad_rel_l2.wq": 0.04, "grad_rel_l2.wk": 0.04, "grad_rel_l2.experts_down": 0.03, "grad_rel_l2.policy_w": 0.015, "grad_rel_l2.router_w": 0.08,
               "grad_rel_l2.mask_embed": 0.03, "grad_rel_l2.denoise_w": 0.03}
MISREADINGS = ["noised_sees_own_clean", "clean_unmasked", "no_level_weight", "positions_shifted"]


def tiny_sdar_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``sdar_trunk`` configuration and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="sdar-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "sdar-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "sdar-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/sdar-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "sdar_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "sdar_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "sdar_trunk_tiny_cell", "config": "sdar-trunk-tiny", "traffic": "tiny_pool", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("sdar_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_sdar_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts"] and config["train"]["batch"] == 128 and config["train"]["recompute_experts"] is True
    assert (config["num_hidden_layers"], config["num_experts"]) == (5, 8) and config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8  # the guide's floors
    assert {k: config["published"][k] for k in ("num_hidden_layers", "num_experts", "kept_layers", "held_experts", "chips_sharing_a_layer")} == {
        "num_hidden_layers": 48, "num_experts": 128, "kept_layers": [0, 1, 2, 3, 4], "held_experts": list(range(8)), "chips_sharing_a_layer": 16}
    # every key of the catalog's row but the two reduced, as published
    catalog = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
               "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768,
               "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8, "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
               "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    assert {k: config[k] for k in catalog} == catalog
    assert all(config[k] == v for k, v in config["model"].items() if k in config)
    assert {k for k, v in config["published"].items() if k in config and config[k] != v} == set(config["reduced"])
    assert (config["model"]["block_length"], config["model"]["t_min"], config["train"]["denoise_weight"], config["train"]["value_weight"]) == (4, 0.001, 1.0, 1.0)
    eighth = registry.config("mellum2-trunk-train")  # the eighth trunk's three numbers of the window (a softmax router), its file says how each was found
    assert config["train"]["warmup_steps"] == eighth["train"]["warmup_steps"] and config["train"]["settle"] == eighth["train"]["settle"]
    assert config["model"]["load_balance_coeff"] == eighth["model"]["load_balance_coeff"] == 1e-5
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    own = {"bd_core_roofline", "bd_held_expert_roofline", "bd_masked_share", "bd_denoise_ms"}
    assert own | {"trunk_attention_ms", "moe_held_slots", "moe_experts_ms", "moe_routing_ms", "moe_moved_rows", "moe_expert_load_max", "moe_router_entropy",
                  "step_device_ms", "step_unscoped_ms", "device_idle", "peak_hbm_gib", "feed_wait_ms"} <= names
    assert not {"trunk_dense_ffn_ms", "gqa_core_roofline", "moe_eighth_held_expert_roofline", "mla_core_roofline"} & names and len(names) == 31
    for entry in registry.spec["per_layer"]:
        if entry["name"] in own:
            assert entry["workloads"] == [CELL] and entry["moves"] == "train_pos_per_s"
    for other in ("mellum_trunk_train_b256", "afmoe_trunk_train_b256", "moe_trunk_train_b512"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint(own)
    family = registry.module("families", "sdar_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.rotary_dim, trunk.rope_theta, trunk.layers, trunk.block_length) == (2048, 32, 4, 128, None, 1e6, 5, 4)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.shared_width, trunk.dense_layers) == (128, (0, 8), 8, 768, 0, 0)
    assert (trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate, trunk.rms_eps, trunk.sliding_window) == ("softmax", True, 1.0, 1e-5, 1e-6, None)
    assert trunk.qk_norm and trunk.recompute_experts and not trunk.gated_attention and not trunk.post_norms and trunk.nope_layers == () and trunk.full_attention_layers == ()
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["wq"], shapes["wk"], shapes["wo"], shapes["router_w"], shapes["experts_gate"]) == ((5, 2048, 4096), (5, 2048, 512), (5, 4096, 2048), (5, 2048, 128), (5, 8, 2048, 768))
    assert (shapes["mask_embed"], shapes["denoise_w"], shapes["denoise_b"]) == ((2048,), (2048, 13), (13,))
    assert sum(int(np.prod(s)) for s in shapes.values()) == config["published"]["parameters_here"] == 284_743_515  # the file's reduced_why
    assert 18_874_368 + 4_352 + 262_144 + 128 * 4_718_592 == config["published"]["parameters_of_a_whole_layer"]
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "head_dim": 64})
    for key, value in (("attention_bias", True), ("norm_topk_prob", False), ("mlp_only_layers", [0]), ("use_sliding_window", True), ("model_type", "qwen3_moe"),
                       ("hidden_act", "gelu"), ("rope_scaling", {"rope_type": "yarn", "factor": 4})):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value})
    for key, value in (("block_length", 3), ("num_shared_experts", 1), ("t_min", 0.0)):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, "model": {**config["model"], key: value}})


def test_the_family_takes_the_references_parameters_in_and_noises_its_batches_from_their_rows(tiny):
    import jax.numpy as jnp

    config = tiny.config("sdar-trunk-tiny")
    family, reference = tiny.module("families", "sdar_trunk"), tiny.module("reference", "sdar_trunk")
    trainer = family.make_trainer(config)
    assert family.NOISE == {"block_length": 4, "t_min": 0.05} and trainer.denoise_weight == 1.0
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 5, family)
    batch = family.build_batch(pool, np.arange(8))
    assert batch["block_level"].shape == (8, 16) and batch["square_masked"].shape == (8, 64) and batch["block_level"].min() >= 0.05
    assert all(np.array_equal(a, b) for a, b in zip(family.build_batch(pool, np.arange(8)).values(), batch.values()))  # from the rows: the same batch twice
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    params = {k: jnp.asarray(v) for k, v in reference.init_params(5, config["model"]).items()}
    _, grads = family.loss_and_grads(trainer)(params, batch)
    assert {k: v.shape for k, v in grads.items()} == {k: v.shape for k, v in params.items()} and not np.any(np.asarray(grads["expert_bias"]))
    state = family.state_from_params(trainer, params)
    assert set(state.params) == set(params) - {"expert_bias"} and set(state.buffers) == {"expert_bias"}
    settled = trainer.init(3)  # balanced on the TRAINING forward: both copies of every board under noise
    assert np.any(np.asarray(settled.buffers["expert_bias"])) and abs(float(np.asarray(settled.buffers["expert_bias"]).mean())) < 1e-6
    # a second file of the family made in the same process: build_batch now noises under ITS block, and the older trainer is refused, not fed
    other = family.make_trainer({**config, "model": {**config["model"], "block_length": 8}})
    assert trainer.noise == {"block_length": 4, "t_min": 0.05} and other.noise == family.NOISE == {"block_length": 8, "t_min": 0.05}
    with pytest.raises(RuntimeError, match="another configuration"):
        family.state_from_params(trainer, params)
    with pytest.raises(RuntimeError, match="another configuration"):
        trainer.init(3)
    assert family.build_batch(pool, np.arange(8))["block_level"].shape == (8, 8)


def test_the_core_hand_count():
    core = Registry(REPO).module("roofline", "bd_core")
    model = Registry(REPO).config(CONFIG)["model"]
    assert core.attention_layers(model) == 5
    # blocks of 4: a clean query of block b sees 4 (b + 1) clean keys; a noised one 4 noised keys of its own block and 4 b clean ones: the same count
    assert core.allowed_pairs(model) == 2 * sum(4 * 4 * (b + 1) for b in range(16)) == 2 * 2176 == 4352
    assert core.allowed_pairs({**model, "block_length": 64}) == 2 * 64 * 64 and core.allowed_pairs({**model, "block_length": 1}) == 2 * 2080
    # a (board, query head): seven products of 2 x 4,352 x 128 operations: scores and mix forward; scores, dp, dv, dq, dk in the gradient
    assert core.layer_flops(model, 128) == 128 * 32 * 7 * 2 * 4352 * 128 == 31_943_819_264
    # a token of either copy: q float32 at 32 heads (16,384 B), k float32 (2,048) and v bfloat16 (1,024) at 4 key-value heads, ONCE for their 8 query heads
    # and for both copies' queries; the mix, or its cotangent, bfloat16 at 32 heads (8,192)
    assert core.layer_bytes(model, 128) == 128 * 128 * ((16_384 + 2_048 + 1_024 + 8_192) + (27_648 + 16_384 + 2_048 + 1_024)) == 1_224_736_768
    least = core.least_seconds(model, 128, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory" and abs(least["least_s"] - 5 * 1_224_736_768 / 819e9) < 1e-9 and abs(least["compute_s"] - 5 * 31_943_819_264 / 197e12) < 1e-9
    # the held experts' count is the accepted one's, given the boards' tokens the step routes: two copies of each
    held = Registry(REPO).module("roofline", "moe_held_experts")
    assert held.routed_layers(model) == 5 and held.held_slots(model, 2 * 128) == 8_192
    assert held.step_flops(model, 2 * 128) == 8_192 * 2 * 2048 * 768 * 3 * 3 * 5


def test_the_four_reducers_on_a_synthetic_trace():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {"jvp(forward)/layer00.attention": 20.0, "transpose(jvp(forward))/layer00.attention": 40.0, "jvp(forward)/layer00.experts": 8.0,
                     "transpose(jvp(forward))/layer04.experts": 16.0, "jvp(forward)/denoise": 0.5, "transpose(jvp(forward))/denoise": 1.0,
                     "jvp(loss)/denoise": 0.25, "transpose(jvp(loss))/denoise": 0.25, "jvp(loss)": 3.0, "optimizer": 100.0}
    config = registry.config(CONFIG)
    steps = [{"masked_squares": 4000.0, "noise_level_mean": 0.49, "loss": 1.0}, {"masked_squares": 4192.0, "noise_level_mean": 0.51, "loss": 1.0}]
    ctx = {"registry": registry, "config": config, "batch": 128, "device_kind": "TPU v5 lite", "scopes_split": found,
           "trace": tracelib.Trace(ops=[], modules=[("jit__step", 0.0, 100e6)], host_spans=[]), "step_counters": steps}
    assert registry.module("reducers", "bd_denoise_ms").reduce(ctx) == 2.0
    assert abs(registry.module("reducers", "bd_masked_share").reduce(ctx) - 4096 / 8192) < 1e-12
    least = 1e3 * 8_192 * 2 * 2048 * 768 * 3 * 3 * 5 / 197e12
    assert abs(registry.module("reducers", "bd_held_expert_roofline").reduce(ctx) - 100 * least / 24.0) < 0.01
    assert registry.module("reducers", "bd_core_roofline").reduce(ctx) is None  # no operation of the masked pair's names in this trace
    # another family's configuration, no scope, no counter, no trace: nothing, and nothing raises (the parent's program has none of these)
    other = registry.config("mellum2-trunk-train")
    for name in ("bd_held_expert_roofline", "bd_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "config": other}) is None
    assert registry.module("reducers", "moe_eighth_held_expert_roofline").reduce(ctx) is None and registry.module("reducers", "gqa_core_roofline").reduce(ctx) is None
    assert registry.module("reducers", "bd_masked_share").reduce({**ctx, "step_counters": [{"loss": 1.0}]}) is None
    found.by_path = {"jvp(forward)/layer00.attention": 3.0}
    assert registry.module("reducers", "bd_denoise_ms").reduce(ctx) is None and registry.module("reducers", "bd_held_expert_roofline").reduce(ctx) is None
    for name in ("bd_core_roofline", "bd_held_expert_roofline", "bd_denoise_ms"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny sdar trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("sdar_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.mask_embed" in out and "held_slots" in out and "masked share over" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counters are the program's
    assert traced["correct"] is True and not {"bd_core_roofline", "bd_held_expert_roofline", "bd_denoise_ms", "trunk_attention_ms"} & set(traced["metrics"])
    assert {"moe_held_slots", "moe_moved_rows", "moe_expert_load_max", "moe_router_entropy", "bd_masked_share"} <= set(traced["metrics"])
    assert 0.3 < traced["metrics"]["bd_masked_share"]["value"] < 0.7
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("sdar-trunk-tiny")
    family, reference = tiny.module("families", "sdar_trunk"), tiny.module("reference", "sdar_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]
        assert sound["_per_tensor"]["expert_bias"] == 0.0  # no gradient through the bias, on either side


@pytest.mark.parametrize("misread", MISREADINGS)
def test_a_misread_block_is_not_correct(tiny, misread):
    """The reference computing one of the four misreadings of the block: the program is then NOT what the reference computes, by one of the
    configuration's limits."""
    config = copy.deepcopy(tiny.config("sdar-trunk-tiny"))
    config["model"]["misread"] = misread
    family = tiny.module("families", "sdar_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "sdar_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    ok, line = correctness.judge(checker.compare(pool, 21), config)
    print(misread, line)
    assert not ok and "EXCEEDED" in line, line


def test_the_step_moves_the_bias_as_the_reference_does(tiny):
    """``expert_bias`` after one step of the program, from the reference's parameters, against the reference's balance rule on the reference's
    own routing counts over BOTH streams' tokens: the comparison that decides ``correct`` cannot see this update (PERF.md section 7)."""
    import jax.numpy as jnp

    config = tiny.config("sdar-trunk-tiny")
    family, reference = tiny.module("families", "sdar_trunk"), tiny.module("reference", "sdar_trunk")
    trainer = family.make_trainer(config)
    for seed in (21, 22):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
        params = {k: jnp.asarray(v) for k, v in reference.init_params(seed, config["model"]).items()}
        slots = reference.expert_slots(params, batch, config["model"])
        assert float(slots.sum()) == 2 * 8 * 128 * 3  # two layers, 8 boards of 128 tokens, top-3
        want = np.asarray(reference.balanced_bias(params["expert_bias"], slots, config["model"]["load_balance_coeff"]))
        state, metrics = trainer.step(family.state_from_params(trainer, params), batch)
        got = np.asarray(state.buffers["expert_bias"])
        assert got.shape == (2, 16) and np.mean(np.abs(got - want) < 1e-7) > 0.9, (seed, got - want)  # but for a rounding's swaps near a layer's mean
        assert abs(float(metrics["held_slots"]) - float(slots[:, 4:8].sum())) <= 8 + 0.1 * float(slots[:, 4:8].sum())
