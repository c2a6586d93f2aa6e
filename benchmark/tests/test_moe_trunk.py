"""The sparse-expert trunk's benchmark pieces on the CPU at a tiny size:
its operation count, its four reducers, and the ``train_step`` runner and
the comparison that decides ``correct`` on a tiny ``moe_trunk``
configuration added to a temp copy as new files and entries only."""

from __future__ import annotations

import copy
import json
import time

import pytest

import helpers
from benchmark import correctness, positions, scopes, sweep_correct
from benchmark.registry import Registry

REPO = helpers.REPO
CELL = "moe_trunk_train_b512"

TINY_SIZES = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 2,
              "num_experts": 8, "num_experts_per_tok": 2, "expert_intermediate_size": 32}
# CPU readings at this size over 6 seeds, 16 positions, with the value head pinned (PR 29): the program reads
# router_w <= 0.019, all <= 0.011, max <= 0.127 (policy_b) and small <= 0.317 (value_b): the CPU sums a bias's
# bfloat16 cotangents in bfloat16, and since every term of value_b's sum now has one sign that sum stalls (the TPU
# sums in float32 and reads 0.001-0.003 there); value_w and value_fc1_w <= 0.0073, loss <= 0.00022, steps <= 0.012.
# The fp8 control reads all >= 0.067 (the number that fails it on every seed), router_w >= 0.122. At this size
# k_norm (32 elements) counts as small, and 1.5x of it reads 0.5: the small limit sits between that and value_b's
# CPU reading, which is the same at every run of a seed.
TINY_LIMITS = {"grad_rel_l2.router_w": 0.1, "grad_rel_l2_all": 0.04, "grad_rel_l2_max": 0.3,
               "grad_rel_l2_small_max": 0.42, "loss_rel_diff": 0.0006, "steps_drop_rel_diff": 0.03}


def tiny_moe_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``moe_trunk`` configuration
    and its cell, reporting this PR's four metrics."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config("lladamoe-trunk-train"))
    config.update(TINY_SIZES, name="moe-trunk-tiny")
    config["model"].update({k: v for k, v in TINY_SIZES.items() if k in config["model"]}, head_dim=16, value_hidden=32)
    config["train"]["batch"] = 8
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}
    (root / "benchmark" / "configs" / "moe-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "moe-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/moe-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "moe_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "moe_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "moe_trunk_tiny_cell", "config": "moe-trunk-tiny", "traffic": "tiny_pool",
                              "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("moe_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_moe_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == ("lladamoe-trunk-train", "playout_pool", 1, "train_step")
    config = registry.config(cell["config"])
    assert config["reduced"] == ["num_hidden_layers"] and config["train"]["batch"] == 512
    assert "grad_rel_l2.router_w" in config["correct"]["limits"] and config["correct"]["chunk"] <= 64
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"moe_experts_ms", "moe_routing_ms", "trunk_attention_ms", "moe_expert_roofline"} <= names
    assert not {"az_conv_roofline", "nnue_ft_roofline"} & names and len(names) == 16
    trunk = registry.module("families", "moe_trunk").trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.head_dim, trunk.layers) == (2048, 16, 128, 1)
    assert (trunk.experts, trunk.experts_per_token, trunk.expert_width) == (64, 8, 1024)
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        registry.module("families", "moe_trunk").trunk_config({**config, "hidden_size": 1024})


def test_moe_expert_flops_hand_count():
    moe = Registry(REPO).module("roofline", "moe_experts")
    model = Registry(REPO).config("lladamoe-trunk-train")["model"]
    assert moe.slots(model, 512) == 262_144  # 512 positions x 64 squares x 8 experts a token
    # one row through one product, one pass: 2 x 2048 x 1024 = 4,194,304; three products, three passes
    assert moe.step_flops(model, 512) == 262_144 * 4_194_304 * 9 == 9_895_604_649_984
    # a pass of a product: 64 experts x 2048 x 1024 x 2 B of weights + 262,144 rows x (2048 + 1024) x 2 B
    assert moe.step_bytes(model, 512) == 9 * (268_435_456 + 1_610_612_736)
    least = moe.least_seconds(model, 512, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "compute" and abs(least["least_s"] - 0.050232) < 1e-5
    assert moe.step_flops({**model, "num_hidden_layers": 2}, 512) == 2 * moe.step_flops(model, 512)


def test_the_four_reducers_on_a_synthetic_split():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer00.experts": 20.0, "transpose(jvp(forward))/layer00.experts": 40.0,
        "jvp(forward)/layer01.experts": 1.0,
        "jvp(forward)/layer00.router": 1.0, "jvp(forward)/layer00.dispatch": 2.0, "transpose(jvp(forward))/layer00.combine": 4.0,
        "jvp(forward)/layer00.attention": 8.0, "transpose(jvp(forward))/layer00.attention": 16.0,
        "jvp(forward)/policy_head": 100.0, "optimizer": 100.0, "jvp(forward)/block00": 100.0,
    }
    config = registry.config("lladamoe-trunk-train")
    ctx = {"registry": registry, "config": config, "batch": 512, "device_kind": "TPU v5 lite", "trace": object(),
           "scopes_split": found}
    assert registry.module("reducers", "moe_experts_ms").reduce(ctx) == 61.0
    assert registry.module("reducers", "moe_routing_ms").reduce(ctx) == 7.0
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 24.0
    share = registry.module("reducers", "moe_expert_roofline").reduce(ctx)
    assert abs(share - 100 * 50.2315 / 61.0) < 0.01
    # a program without the scopes (the parent), another family, no trace: nothing, and no error
    found.by_path = {"jvp(forward)/block00": 3.0}
    for name in ("moe_experts_ms", "moe_routing_ms", "trunk_attention_ms", "moe_expert_roofline"):
        assert registry.module("reducers", name).reduce(ctx) is None
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None}) is None
    assert registry.module("reducers", "moe_expert_roofline").reduce({**ctx, "config": registry.config("az-256x19-train")}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("moe_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 0.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 0.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.router_w" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises
    assert traced["correct"] is True and "moe_experts_ms" not in traced["metrics"]
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("moe-trunk-tiny")
    family = tiny.module("families", "moe_trunk")
    reference = tiny.module("reference", "moe_trunk")
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(seed, {k: v for k, v in sound.items() if k != "_per_tensor"}, {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]


def test_the_reference_pins_its_value_head(tiny):
    """What PR 29 found at full widths holds at any: the final-normed features are nearly one vector on every
    square of every position, so a signed ``value_w`` at the matrices' scale makes each value plane alive
    everywhere, dead everywhere or astride the relu's corner, by the seed (none alive in 2 of 18 seeds read on
    the chip: ``value_fc1_w``'s gradient is then the bfloat16 rounding of one barely-alive unit; one astride the
    corner puts 3x on every tensor). ``init_params`` has to keep every plane and hidden unit alive and away from
    the corner, the tanh off its flat ends, and every draw's pull of one sign. The parent's failed on seed 11."""
    import jax.numpy as jnp
    import numpy as np

    config = tiny.config("moe-trunk-tiny")
    family, reference = tiny.module("families", "moe_trunk"), tiny.module("reference", "moe_trunk")
    for seed in (11, 2**31 + 12, 13, 14, 15, 16):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = family.build_batch(pool, np.arange(32))
        p = reference.init_params(seed, config["model"])
        x = np.asarray(reference.features({k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(batch["planes"]),
                                          config["model"], lambda a: a, lambda a: a))
        plane = x @ p["value_w"][0, 0] + p["value_b"]  # [B, 8, 8, 4]
        assert plane.min() > 0.25, (seed, "a value plane is dead or at the relu's corner", plane.reshape(-1, 4).min(0))
        hidden = plane.reshape(len(x), -1) @ p["value_fc1_w"] + p["value_fc1_b"]
        assert hidden.min() > 0.4 and hidden.max() < 2.0, (seed, hidden.min(), hidden.max())
        z = (hidden @ p["value_fc2_w"] + p["value_fc2_b"])[:, 0]
        assert 0.4 < np.abs(z).min() and np.abs(z).max() < 1.3, (seed, z.min(), z.max())
        pull = (np.tanh(z) - batch["value_target"])[batch["value_target"] == 0]
        assert len(pull) and (np.sign(pull) == np.sign(pull[0])).all(), seed


@pytest.mark.parametrize("tensor,factor", [("router_w", 0.0), ("experts_down", 1.5), ("wq", 0.0), ("k_norm", 1.5),
                                           ("value_w", 0.0), ("value_fc1_w", 1.5)])
def test_left_out_mathematics_fails(tiny, tensor, factor):
    """A zeroed router gradient, a 1.5x-scaled expert matrix, the value head's first layer left out: not correct."""
    config = tiny.config("moe-trunk-tiny")
    family = tiny.module("families", "moe_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "moe_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    sweep_correct.mutate(checker, tensor, factor)
    ok, line = correctness.judge(checker.compare(pool, 21), config)
    assert not ok and "EXCEEDED" in line, line


def test_the_sweep_tool_breaks_the_optimizer_and_tabulates(tiny):
    """``sweep_correct.py --mutate optimizer:0`` (a step that does not move the parameters) reads 1.0 on the
    steps and is not correct; the closing table gives each number its median, largest and limit."""
    config = tiny.config("moe-trunk-tiny")
    family = tiny.module("families", "moe_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "moe_trunk"), config)
    sweep_correct.mutate(checker, "optimizer", 0.0)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    numbers = checker.compare(pool, 21)
    assert numbers["steps_drop_rel_diff"] == pytest.approx(1.0) and not correctness.judge(numbers, config)[0]
    lines = sweep_correct.table(config, correctness.COMPARED, [numbers], [])
    assert lines[0].split()[:5] == ["number", "n", "median", "2nd", "largest"] and len(lines) == 1 + 6 + 22
    assert lines[1].split()[0] == "grad_rel_l2.router_w" and float(lines[1].split()[6]) == 0.1
