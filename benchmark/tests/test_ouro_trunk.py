"""The ouro trunk's benchmark pieces on the CPU at a tiny size: its cut, what
its family refuses, its operation count, its four reducers, and the
``train_step`` runner and the comparison that decides ``correct`` on a tiny
``ouro_trunk`` configuration added to a temp copy as new files and entries
only."""

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
CELL = "ouro_trunk_train_b32"
CONFIG = "ouro-2.6b-trunk-train"

TINY_TOP = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96, "num_hidden_layers": 2, "total_ut_steps": 3}
TINY_MODEL = {**TINY_TOP, "value_hidden": 32}
# CPU readings at this size over 3 seeds, 16 positions (test_control_fails_and_program_passes prints them): sound, all gradients as one vector
# 0.003-0.006 (control 0.05-0.08); the mildest misreading, ``three_passes``, reads 0.03 and more on all. The two convolutions' biases are the
# small tensors: XLA:CPU sums their bfloat16 cotangents in bfloat16 (tests/test_ouro_trunk.py says so), hence 0.9.
TINY_LIMITS = {"grad_rel_l2_all": 0.02, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.9, "loss_rel_diff": 0.001, "steps_drop_rel_diff": 0.05,
               "grad_rel_l2.wq": 0.04, "grad_rel_l2.dense_down": 0.03, "grad_rel_l2.policy_w": 0.015, "grad_rel_l2.exit_gate_w": 0.04, "grad_rel_l2.final_norm": 0.04}
MISREADINGS = ["three_passes", "last_pass_gradient", "remainder_lost", "no_entropy", "heads_without_final_norm", "no_middle_norms"]


def tiny_ouro_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``ouro_trunk`` configuration and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="ouro-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["published"]["kept_layers"] = [0, 1]
    config["train"]["batch"] = 8
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "ouro-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "ouro-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/ouro-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "ouro_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "ouro_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "ouro_trunk_tiny_cell", "config": "ouro-trunk-tiny", "traffic": "tiny_pool", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("ouro_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_ouro_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers"] and config["train"]["batch"] == 32 and config["num_hidden_layers"] == 6 >= 4  # the guide's floor
    assert {k: config["published"][k] for k in ("num_hidden_layers", "kept_layers", "pipeline_stages", "layer_passes_a_step")} == {
        "num_hidden_layers": 48, "kept_layers": list(range(6)), "pipeline_stages": 8, "layer_passes_a_step": 24}
    # every key of the catalog's row but the one reduced, as published
    catalog = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
               "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False, "total_ut_steps": 4,
               "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in catalog} == catalog
    assert all(config[k] == v for k, v in config["model"].items() if k in config)
    assert {k for k, v in config["published"].items() if k in config and config[k] != v} == set(config["reduced"])
    assert (config["train"]["exit_entropy_weight"], config["train"]["value_weight"], config["control_precision"]) == (0.1, 1.0, "float8_e4m3fn")
    assert "settle" not in config["train"] and "warmup_steps" not in config["train"]  # no router to balance
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    own = {"loop_exit_ms", "loop_exit_step_mean", "loop_step_mfu", "mha_core_roofline"}
    assert own | {"trunk_attention_ms", "trunk_dense_ffn_ms", "step_device_ms", "step_unscoped_ms", "device_idle", "peak_hbm_gib", "feed_wait_ms"} <= names
    assert not {name for name in names if name.startswith(("moe_", "bd_", "gqa_", "mla_", "kda_", "gdn_", "ssm_", "cca_", "az_", "nnue_"))} and len(names) == 26
    for entry in registry.spec["per_layer"]:
        if entry["name"] in own:
            assert entry["workloads"] == [CELL] and entry["moves"] == "train_pos_per_s"
    for other in ("moe_trunk_train_b512", "afmoe_trunk_train_b256", "sdar_trunk_train_b128", "az_train_b4096"):
        assert {m["name"] for m in registry.metrics("per_layer", other)}.isdisjoint(own)
    family = registry.module("families", "ouro_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.kv_heads, trunk.head_dim, trunk.rotary_dim, trunk.rope_theta, trunk.rms_eps, trunk.layers) == (2048, 16, None, 128, None, 1e6, 1e-6, 6)
    assert (trunk.dense_layers, trunk.dense_width, trunk.routed_layers, trunk.shared_width, trunk.loop_steps, trunk.exit_threshold) == (6, 5632, 0, 0, 4, 1.0)
    assert not trunk.qk_norm and trunk.post_norms and not trunk.gated_attention and trunk.nope_layers == () and trunk.balance_rate == 0.0
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["wq"], shapes["wk"], shapes["wo"], shapes["dense_gate"], shapes["dense_down"]) == ((6, 2048, 2048), (6, 2048, 2048), (6, 2048, 2048), (6, 2048, 5632), (6, 5632, 2048))
    assert (shapes["exit_gate_w"], shapes["exit_gate_b"], shapes["post_attn_norm"]) == ((2048, 1), (1,), (6, 2048)) and "router_w" not in shapes and "q_norm" not in shapes
    assert sum(int(np.prod(s)) for s in shapes.values()) == config["published"]["parameters_here"] == 308_599_375  # the file's reduced_why
    assert 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048 == config["published"]["parameters_of_a_layer"] == 51_388_416


def test_the_family_refuses_what_the_program_does_not_compute():
    registry = Registry(REPO)
    config, family = registry.config(CONFIG), registry.module("families", "ouro_trunk")
    with pytest.raises(ValueError, match="disagree on \\['head_dim'\\]"):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "head_dim": 64})
    for key, value in (("sliding_window", 4096), ("use_sliding_window", True), ("rope_scaling", {"rope_type": "yarn", "factor": 4}), ("model_type", "qwen3"),
                       ("hidden_act", "gelu"), ("attention_bias", True), ("layer_types", ["sliding_attention"] * 48)):
        with pytest.raises(ValueError, match=key if key != "use_sliding_window" else "sliding_window"):
            family.trunk_config({**config, key: value})
    with pytest.raises(ValueError, match="num_key_value_heads"):  # key-value heads other than the query heads'
        family.trunk_config({**config, "num_key_value_heads": 4, "model": {**config["model"], "num_key_value_heads": 4}})
    for key, value in (("total_ut_steps", 0), ("early_exit_threshold", 0.0), ("early_exit_threshold", 1.5)):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value, "model": {**config["model"], key: value}})
    with pytest.raises(ValueError, match="AdamW"):
        family.make_trainer({**config, "train": {**config["train"], "weight_decay": 0.0}})
    trainer = family.make_trainer(config)
    assert trainer.exit_entropy_weight == 0.1 and trainer.value_weight == 1.0 and type(trainer).__name__ == "AzTrainer"


def test_the_family_takes_the_references_parameters_in_as_they_are(tiny):
    import jax.numpy as jnp

    config = tiny.config("ouro-trunk-tiny")
    family, reference = tiny.module("families", "ouro_trunk"), tiny.module("reference", "ouro_trunk")
    trainer = family.make_trainer(config)
    assert (trainer.cfg.loop_steps, trainer.cfg.layers, trainer.cfg.routed_layers) == (3, 2, 0)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 5, family)
    batch = {k: jnp.asarray(v) for k, v in family.build_batch(pool, np.arange(8)).items()}
    assert set(batch) == {"planes", "policy_target", "value_target"}
    params = {k: jnp.asarray(v) for k, v in reference.init_params(5, config["model"]).items()}
    loss, grads = family.loss_and_grads(trainer)(params, batch)
    assert {k: v.shape for k, v in grads.items()} == {k: v.shape for k, v in params.items()} and np.isfinite(float(loss))
    state = family.state_from_params(trainer, params)
    assert set(state.params) == set(params) and state.buffers == {}  # no buffer beside the parameters: nothing to balance
    # the reference's gates sit near 0, so that no exit's probability vanishes
    import jax
    _, _, gates = reference.exits(params, batch["planes"], config["model"], lambda x: x, lambda x: x)
    assert float(jnp.max(jnp.abs(gates))) < 2.0 and float(jnp.min(reference.exit_distribution(gates))) > 0.01


def test_the_step_hand_count():
    registry = Registry(REPO)
    count, model = registry.module("roofline", "loop_step"), registry.config(CONFIG)["model"]
    assert count.layer_matrix_parameters(model) == 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51_380_224
    flops = count.step_flops(model, 32)
    # 2,048 tokens a pass, 4 passes, 6 layers, three products a weight and use, two operations a multiply-add
    assert flops["layers"] == 3 * 2 * 2048 * 4 * 6 * 51_380_224 == 15_152_644_620_288
    # a (board, head, pass, layer): seven products of 64 x 64 x 128 multiply-adds
    assert flops["cores"] == 4 * 6 * 32 * 16 * 7 * 2 * 64 * 64 * 128 == 90_194_313_216
    # the heads a pass: a token's 2048 x (73 + 4), a board's 256 x 256 + 256
    assert flops["heads"] == 3 * 2 * 4 * (2048 * 2048 * 77 + 32 * (256 * 256 + 256)) and flops["embed"] == 3 * 2 * 2048 * 19 * 2048
    assert flops["all"] == flops["layers"] + flops["cores"] + flops["heads"] + flops["embed"]
    assert abs(count.least_seconds(model, 32, {"bf16_flops_per_s": 197e12}) - flops["all"] / 197e12) < 1e-12 and 0.0774 < flops["all"] / 197e12 < 0.0775
    # tiny shapes by hand: one layer of hidden 8, 2 heads of 4, width 12, 2 passes, 1 board
    tiny = {"hidden_size": 8, "num_attention_heads": 2, "head_dim": 4, "intermediate_size": 12, "num_hidden_layers": 1, "total_ut_steps": 2,
            "policy_planes": 73, "value_hidden": 16, "input_planes": 19}
    assert count.layer_matrix_parameters(tiny) == 4 * 8 * 8 + 3 * 8 * 12 == 544
    hand = {"layers": 6 * 64 * 2 * 544, "cores": 2 * 2 * 7 * 2 * 64 * 64 * 4, "heads": 6 * 2 * (64 * 8 * 77 + (256 * 16 + 16)), "embed": 6 * 64 * 19 * 8}
    assert count.step_flops(tiny, 1) == {**hand, "all": sum(hand.values())}
    # the one-head core's count is the accepted one's at a group of one: k and v as wide as q, nothing shared
    core = registry.module("roofline", "gqa_core")
    assert core.layer_flops(model, 32) == 32 * 16 * 7 * 2 * 64 * 64 * 128 == flops["cores"] / 24
    assert core.layer_bytes(model, 32) == 32 * 64 * ((8192 + 8192 + 4096 + 4096) + (8192 + 8192 + 4096 + 4096 + 8192 + 8192 + 4096)) == 142_606_336


def test_the_four_reducers_on_a_synthetic_trace():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {"jvp(forward)/layer00.attention": 20.0, "transpose(jvp(forward))/layer00.attention": 40.0, "jvp(forward)/layer00.dense": 30.0,
                     "transpose(jvp(forward))/layer05.dense": 60.0, "jvp(forward)/final_norm": 0.25, "transpose(jvp(forward))/final_norm": 0.5,
                     "jvp(forward)/policy_head": 0.5, "transpose(jvp(forward))/policy_head": 1.0, "jvp(forward)/value_head": 0.125, "transpose(jvp(forward))/value_head": 0.125,
                     "jvp(forward)/exit_gate": 0.0625, "transpose(jvp(forward))/exit_gate": 0.0625, "jvp(loss)/exit": 0.25, "transpose(jvp(loss))/exit": 0.125,
                     "jvp(loss)": 3.0, "optimizer": 10.0}
    config = registry.config(CONFIG)
    steps = [{"exit_step_mean": 1.8, "exit_entropy": 1.2, "loss_first_pass": 9.0, "loss_last_pass": 8.5, "loop_update_rms": 0.2, "loss": 1.0},
             {"exit_step_mean": 2.0, "exit_entropy": 1.1, "loss_first_pass": 9.0, "loss_last_pass": 8.0, "loop_update_rms": 0.3, "loss": 1.0}]
    ctx = {"registry": registry, "config": config, "batch": 32, "device_kind": "TPU v5 lite", "scopes_split": found,
           "trace": tracelib.Trace(ops=[], modules=[("jit__step", 0.0, 200e6)], host_spans=[]), "step_counters": steps}
    assert registry.module("reducers", "loop_exit_ms").reduce(ctx) == 3.0
    assert abs(registry.module("reducers", "loop_exit_step_mean").reduce(ctx) - 1.9) < 1e-12
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 60.0 and registry.module("reducers", "trunk_dense_ffn_ms").reduce(ctx) == 90.0
    assert registry.module("reducers", "mha_core_roofline").reduce(ctx) is None  # no operation of the pair's names in this trace
    # another family's configuration, no scope, no counter, no trace: nothing, and nothing raises (the parent's program has none of these)
    other = registry.config("mellum2-trunk-train")
    for name in ("loop_step_mfu", "mha_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "config": other}) is None
    assert registry.module("reducers", "gqa_core_roofline").reduce(ctx) is None  # the eighth trunk's reader does not read this family
    assert registry.module("reducers", "loop_exit_step_mean").reduce({**ctx, "step_counters": [{"loss": 1.0}]}) is None
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/final_norm": 1.0, "jvp(forward)/policy_head": 1.0}
    assert registry.module("reducers", "loop_exit_ms").reduce(ctx) is None  # a trunk that is not looped: its final norm and heads are no exit's
    for name in ("loop_exit_ms", "loop_step_mfu", "mha_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None


def test_the_two_shares_read_a_traced_steps_device_time(monkeypatch):
    """``loop_step_mfu`` over the median busy time of a step and ``mha_core_roofline`` over the pair's operations, on a trace made by hand."""
    registry = Registry(REPO)
    config = registry.config(CONFIG)
    ops = [tracelib.Op(name=name, shape="bf16[32,64,2048]", start_ns=start, dur_ns=dur) for name, start, dur in (
        ("board_attention.7", 10e6, 4e6), ("board_attention_grad.2", 20e6, 6e6), ("fusion.1", 30e6, 140e6), ("board_attention_blocks.1", 180e6, 1e6))]
    trace = tracelib.Trace(ops=ops, modules=[("jit__step", 0.0, 200e6)], host_spans=[])
    monkeypatch.setattr(tracelib, "busy_ns", lambda trace, window: 160e6)
    ctx = {"registry": registry, "config": config, "batch": 32, "device_kind": "TPU v5 lite", "trace": trace}
    least_ms = 1e3 * registry.module("roofline", "loop_step").step_flops(config["model"], 32)["all"] / 197e12
    assert abs(registry.module("reducers", "loop_step_mfu").reduce(ctx) - 100 * least_ms / 160.0) < 1e-9 and 48.0 < 100 * least_ms / 160.0 < 48.5
    memory_ms = 1e3 * 24 * 142_606_336 / 819e9  # the memory side bounds the core: 4.18 ms a step
    assert abs(registry.module("reducers", "mha_core_roofline").reduce(ctx) - 100 * memory_ms / 10.0) < 1e-9  # the plain pair's 4 + 6 ms, never the blocks pair's


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny ouro trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("ouro_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.exit_gate_w" in out and "exit step mean over" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises; the counter is the program's
    assert traced["correct"] is True and not {"loop_exit_ms", "loop_step_mfu", "mha_core_roofline", "trunk_attention_ms", "trunk_dense_ffn_ms"} & set(traced["metrics"])
    assert "loop_exit_step_mean" in traced["metrics"] and 1.0 <= traced["metrics"]["loop_exit_step_mean"]["value"] <= 3.0
    assert not {name for name in traced["metrics"] if name.startswith("moe_")}
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("ouro-trunk-tiny")
    family, reference = tiny.module("families", "ouro_trunk"), tiny.module("reference", "ouro_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]


@pytest.mark.parametrize("misread", MISREADINGS)
def test_a_misread_block_is_not_correct(tiny, misread):
    """The reference computing one of the six misreadings of the block: the program is then NOT what the reference computes, by one of the
    configuration's limits."""
    config = copy.deepcopy(tiny.config("ouro-trunk-tiny"))
    config["model"]["misread"] = misread
    family = tiny.module("families", "ouro_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "ouro_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    ok, line = correctness.judge(checker.compare(pool, 21), config)
    print(misread, line)
    assert not ok and "EXCEEDED" in line, line
