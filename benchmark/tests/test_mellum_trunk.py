"""The mellum trunk's benchmark pieces on the CPU at a tiny size: its cut,
its operation count, its two reducers, and the ``train_step`` runner and
the comparison that decides ``correct`` on a tiny ``mellum_trunk``
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
CELL = "mellum_trunk_train_b256"
CONFIG = "mellum2-trunk-train"

TINY_ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 16, "original_max_position_embeddings": 2048, "beta_fast": 32,
                                "beta_slow": 1, "attention_factor": 1.2772588722239782},
             "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
TINY_TOP = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 4,
            "num_experts_per_tok": 3, "rope_parameters": TINY_ROPE}
TINY_MODEL = {**TINY_TOP, "num_routed_experts": 16, "first_held_expert": 4, "value_hidden": 32}
# CPU readings at this size over 3 seeds, 16 positions (test_control_fails_and_program_passes prints them): sound, all gradients as one vector
# 0.0069-0.0096 (control 0.046-0.058), wq and wk 0.0102-0.0129 (control 0.14-0.15), experts_down 0.0071-0.0129 (0.10-0.19), policy_w 0.0032-0.0040
# (0.047-0.052), router_w 0.0134-0.0325 (0.17-0.35), the worst single tensor policy_b 0.095-0.124, the worst small one value_b 0.05-0.30. The
# mildest misreading, ``not_renormalised``, reads router_w 0.277 and experts_down 0.123.
TINY_LIMITS = {"grad_rel_l2_all": 0.025, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.6, "loss_rel_diff": 0.001, "steps_drop_rel_diff": 0.05,
               "grad_rel_l2.wq": 0.04, "grad_rel_l2.wk": 0.04, "grad_rel_l2.experts_down": 0.03, "grad_rel_l2.policy_w": 0.015, "grad_rel_l2.router_w": 0.08}
MISREADINGS = ["plain_full_layer", "no_attention_factor", "factor_once", "ramp_swapped", "not_renormalised", "renormalised_over_held", "kv_head_mod"]


def tiny_mellum_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``mellum_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="mellum-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "mellum-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "mellum-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/mellum-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "mellum_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "mellum_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "mellum_trunk_tiny_cell", "config": "mellum-trunk-tiny", "traffic": "tiny_pool", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("mellum_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_mellum_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts"] and config["train"]["batch"] == 256 and config["train"]["recompute_experts"] is True
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 8)
    assert config["published"] == {"num_hidden_layers": 28, "num_experts": 64, "kept_layers": [0, 1, 2, 3], "held_experts": list(range(8))}
    # every key of the catalog's row but the two reduced, as published; the nested groups whole
    period = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
    catalog = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168, "layer_types": period * 7,
               "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
               "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-06, "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
               "rope_parameters": {"full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
                                                      "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782},
                                   "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}}
    assert {k: config[k] for k in catalog} == catalog
    # the two copies of every size agree, and ``reduced`` is exactly what the file changes from the published counts
    assert all(config[k] == v for k, v in config["model"].items() if k in config)
    assert {k for k, v in config["published"].items() if k in config and config[k] != v} == set(config["reduced"])
    # the fifth trunk's warm-up, a hundred times the share cells'; their settle's traffic and pool with four times the passes, down to the balance's own rate, a hundredth
    # of theirs (``assumed`` says why each)
    shares = registry.config("trinity-mini-trunk-train")
    assert config["train"]["warmup_steps"] == registry.config("zaya1-trunk-train")["train"]["warmup_steps"] == 100 * shares["train"]["warmup_steps"] == 10_000_000
    assert config["model"]["load_balance_coeff"] == 1e-5 == shares["model"]["load_balance_coeff"] / 100
    assert config["train"]["settle"] == {**shares["train"]["settle"], "balance_passes": 256, "rate_last": config["model"]["load_balance_coeff"]}
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"gqa_core_roofline", "moe_eighth_held_expert_roofline", "trunk_attention_ms", "moe_held_slots", "moe_experts_ms", "moe_routing_ms", "moe_moved_rows",
            "moe_expert_load_max", "moe_router_entropy", "step_device_ms", "device_idle", "peak_hbm_gib", "feed_wait_ms"} <= names
    assert not {"trunk_dense_ffn_ms", "moe_held_expert_roofline", "mla_core_roofline", "mla_latent_ms", "gdn_mixer_ms", "ssm_mixer_ms"} & names and len(names) == 29
    for other in ("afmoe_trunk_train_b256", "moe_trunk_train_b512", "gdn_trunk_train_b128"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint({"gqa_core_roofline", "moe_eighth_held_expert_roofline"})
    family = registry.module("families", "mellum_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.rotary_dim, trunk.rope_theta, trunk.layers) == (2304, 32, 4, 128, None, 5e5, 4)
    assert (trunk.full_attention_layers, trunk.rope_type, trunk.rope_factor, trunk.original_max_position_embeddings, trunk.beta_fast, trunk.beta_slow,
            trunk.attention_factor) == ((3,), "yarn", 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.shared_width, trunk.dense_layers) == (64, (0, 8), 8, 896, 0, 0)
    assert (trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate, trunk.rms_eps, trunk.sliding_window) == ("softmax", True, 1.0, 1e-5, 1e-6, 1024)
    assert trunk.qk_norm and trunk.recompute_experts and not trunk.gated_attention and not trunk.post_norms and trunk.nope_layers == () and trunk.embed_scale == 1.0
    assert trunk.pattern is None and trunk.mixers is None and trunk.cca is None and trunk.kv_lora_rank is None
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["wq"], shapes["wk"], shapes["wv"], shapes["wo"], shapes["q_norm"], shapes["attn_norm"]) == (
        (4, 2304, 4096), (4, 2304, 512), (4, 2304, 512), (4, 4096, 2304), (4, 128), (4, 2304))
    assert (shapes["router_w"], shapes["experts_gate"], shapes["experts_down"]) == ((4, 2304, 64), (4, 8, 2304, 896), (4, 8, 896, 2304))
    assert not {"wgate", "shared_up", "dense_up", "post_attn_norm"} & set(shapes)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 284_016_718  # the file's reduced_why
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "head_dim": 64})
    for key, value in (("attention_bias", True), ("norm_topk_prob", False), ("mlp_layer_types", ["dense"] + ["sparse"] * 27), ("use_sliding_window", False),
                       ("layer_types", ["full_attention"] * 28), ("model_type", "qwen3_moe"), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value})
    llama3 = {"full_attention": {**config["rope_parameters"]["full_attention"], "rope_type": "llama3"}, "sliding_attention": config["rope_parameters"]["sliding_attention"]}
    with pytest.raises(ValueError, match="rope_parameters"):
        family.trunk_config({**config, "rope_parameters": llama3, "model": {**config["model"], "rope_parameters": llama3}})


def test_the_family_takes_the_references_parameters_in_and_gives_gradients_back_under_their_names(tiny):
    """No column order to map: the reference's tensors ARE the program's but for ``expert_bias``, which the trainer holds as a
    buffer; the gradients come back under the reference's names and shapes, the buffer's a zero."""
    import jax.numpy as jnp

    config = tiny.config("mellum-trunk-tiny")
    family, reference = tiny.module("families", "mellum_trunk"), tiny.module("reference", "mellum_trunk")
    trainer = family.make_trainer(config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 5, family)
    batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
    params = {k: jnp.asarray(v) for k, v in reference.init_params(5, config["model"]).items()}
    _, grads = family.loss_and_grads(trainer)(params, batch)
    assert {k: v.shape for k, v in grads.items()} == {k: v.shape for k, v in params.items()} and not np.any(np.asarray(grads["expert_bias"]))
    state = family.state_from_params(trainer, params)
    assert set(state.params) == set(params) - {"expert_bias"} and set(state.buffers) == {"expert_bias"}
    assert all(np.array_equal(np.asarray(state.params[k]), np.asarray(params[k])) for k in state.params)


def test_the_core_hand_count():
    core = Registry(REPO).module("roofline", "gqa_core")
    model = Registry(REPO).config(CONFIG)["model"]
    assert core.attention_layers(model) == 4
    # a (board, query head): seven products of 2 x 64 x 64 x 128 operations: scores and mix forward; scores, dp, dv, dq, dk in the gradient
    assert core.layer_flops(model, 256) == 256 * 32 * 7 * 2 * 64 * 64 * 128 == 60_129_542_144
    # a token: q float32 at 32 heads (16,384 B), k float32 (2,048) and v bfloat16 (1,024) at 4 key-value heads, ONCE for their 8 query heads;
    # the mix, or its cotangent, bfloat16 at 32 heads (8,192)
    assert core.layer_bytes(model, 256) == 16_384 * ((16_384 + 2_048 + 1_024 + 8_192) + (27_648 + 16_384 + 2_048 + 1_024)) == 1_224_736_768
    least = core.least_seconds(model, 256, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory" and abs(least["least_s"] - 4 * 1_224_736_768 / 819e9) < 1e-9 and abs(least["compute_s"] - 4 * 60_129_542_144 / 197e12) < 1e-9
    # the count follows the shapes, not the kernel: a key a query head reads its keys and values once a query head
    assert core.layer_bytes({**model, "num_key_value_heads": 32}, 256) == 16_384 * (2 * (16_384 + 16_384 + 8_192 + 8_192) + 16_384 + 16_384 + 8_192)
    # the held experts' count is the accepted one's, read from this configuration's model group
    held = Registry(REPO).module("roofline", "moe_held_experts")
    assert held.routed_layers(model) == 4 and held.held_slots(model, 256) == 16_384
    assert held.step_flops(model, 256) == 16_384 * 2 * 2304 * 896 * 3 * 3 * 4


def test_the_two_reducers_on_a_synthetic_trace():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {"jvp(forward)/layer00.attention": 20.0, "transpose(jvp(forward))/layer00.attention": 40.0, "jvp(forward)/layer00.experts": 8.0,
                     "transpose(jvp(forward))/layer03.experts": 16.0, "jvp(forward)/layer00.router": 3.0, "optimizer": 100.0}
    config = registry.config(CONFIG)
    ctx = {"registry": registry, "config": config, "batch": 256, "device_kind": "TPU v5 lite", "scopes_split": found,
           "trace": tracelib.Trace(ops=[], modules=[("jit__step", 0.0, 100e6)], host_spans=[]), "step_counters": None}
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 60.0 and registry.module("reducers", "moe_experts_ms").reduce(ctx) == 24.0
    least = 1e3 * 16_384 * 2 * 2304 * 896 * 3 * 3 * 4 / 197e12
    assert abs(registry.module("reducers", "moe_eighth_held_expert_roofline").reduce(ctx) - 100 * least / 24.0) < 0.01
    assert registry.module("reducers", "gqa_core_roofline").reduce(ctx) is None  # no operation of the kernel pair's names in this trace
    # another family's configuration (the accepted reducer answers for the second trunk alone, this one for the eighth alone), no scope, no trace: nothing
    other = registry.config("trinity-mini-trunk-train")
    assert registry.module("reducers", "moe_eighth_held_expert_roofline").reduce({**ctx, "config": other}) is None
    assert registry.module("reducers", "moe_held_expert_roofline").reduce(ctx) is None
    assert registry.module("reducers", "gqa_core_roofline").reduce({**ctx, "config": other}) is None
    found.by_path = {"jvp(forward)/layer00.attention": 3.0}
    assert registry.module("reducers", "moe_eighth_held_expert_roofline").reduce(ctx) is None
    for name in ("moe_eighth_held_expert_roofline", "gqa_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny mellum trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("mellum_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.wk" in out and "held_slots" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counters are the program's
    assert traced["correct"] is True and not {"gqa_core_roofline", "moe_eighth_held_expert_roofline", "trunk_attention_ms"} & set(traced["metrics"])
    assert {"moe_held_slots", "moe_moved_rows", "moe_expert_load_max", "moe_router_entropy"} <= set(traced["metrics"])
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("mellum-trunk-tiny")
    family, reference = tiny.module("families", "mellum_trunk"), tiny.module("reference", "mellum_trunk")
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
    """The reference computing one of the seven misreadings of the block
    (``benchmark/sweep_misread.py`` does the same at width): the program is
    then NOT what the reference computes, by one of the configuration's limits."""
    config = copy.deepcopy(tiny.config("mellum-trunk-tiny"))
    config["model"]["misread"] = misread
    family = tiny.module("families", "mellum_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "mellum_trunk"), config)
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

    config = tiny.config("mellum-trunk-tiny")
    family, reference = tiny.module("families", "mellum_trunk"), tiny.module("reference", "mellum_trunk")
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
