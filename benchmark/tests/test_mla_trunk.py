"""The latent-attention trunk's benchmark pieces on the CPU at a tiny
size: its cut, its operation count, its two reducers, and the
``train_step`` runner and the comparison that decides ``correct`` on a
tiny ``mla_trunk`` configuration added to a temp copy as new files and
entries only."""

from __future__ import annotations

import copy
import dataclasses
import json
import time

import numpy as np
import pytest

import helpers
from benchmark import correctness, positions, scopes, tracelib
from benchmark.registry import Registry

REPO = helpers.REPO
CELL = "mla_trunk_train_b256"
CONFIG = "kanana-2-trunk-train"

TINY_TOP = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 64, "qk_head_dim": 80, "v_head_dim": 16, "num_hidden_layers": 3, "intermediate_size": 96,
            "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 3}
TINY_MODEL = {k: v for k, v in TINY_TOP.items() if k not in ("num_key_value_heads", "qk_head_dim", "n_routed_experts")}
TINY_MODEL.update(num_experts=4, num_routed_experts=16, first_held_expert=4, value_hidden=32)
# CPU readings at this size over 3 seeds, 16 positions: see test_control_fails_and_program_passes, which prints them.
TINY_LIMITS = {"grad_rel_l2_all": 0.04, "grad_rel_l2_max": 0.3, "grad_rel_l2_small_max": 0.45, "loss_rel_diff": 0.001,
               "steps_drop_rel_diff": 0.05, "grad_rel_l2.wkv_a": 0.08, "grad_rel_l2.wkv_b": 0.08, "grad_rel_l2.kv_norm": 0.08}


def tiny_mla_checkout(tmp):
    """``helpers.tiny_checkout`` plus a tiny ``mla_trunk`` configuration
    and its cell, reporting what the real cell reports."""
    root = helpers.tiny_checkout(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = copy.deepcopy(Registry(REPO).config(CONFIG))
    config.update(TINY_TOP, name="mla-trunk-tiny")
    config["model"].update(TINY_MODEL)
    config["train"]["batch"] = 8
    config["train"]["settle"].update(traffic="tiny_pool", positions=32, balance_passes=6)
    config["correct"] = {"batch": 16, "chunk": 8, "limits": TINY_LIMITS}  # the steps at the training rate, as the other trunks' tiny cells
    (root / "benchmark" / "configs" / "mla-trunk-tiny.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "mla-trunk-tiny", "source": config["source"], "reduced": config["reduced"],
                            "file": "benchmark/configs/mla-trunk-tiny.json", "why": "test"})
    (root / "benchmark" / "workloads" / "mla_trunk_tiny_cell.json").write_text(
        json.dumps({"name": "mla_trunk_tiny_cell", "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
    spec["workloads"].append({"name": "mla_trunk_tiny_cell", "config": "mla-trunk-tiny", "traffic": "tiny_pool",
                              "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("mla_trunk_tiny_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(tiny_mla_checkout(tmp_path_factory.mktemp("checkout")))


def test_the_cell_its_cut_and_its_metrics_are_declared():
    registry = Registry(REPO)
    cell = registry.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (CONFIG, "playout_pool", 1, "train_step")
    assert (cell["warmup_steps"], cell["trace_steps"]) == (3, 8)
    config = registry.config(CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts"] and config["train"]["batch"] == 256
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (5, 8)
    assert config["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128, "kept_layers": [0, 1, 2, 3, 4]}
    # every key of the catalog's row but the two reduced, as published
    catalog = {"attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768, "model_type": "deepseek_v3",
               "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
               "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
               "rope_theta": 1000000, "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
               "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    assert {k: config[k] for k in catalog} == catalog
    # the second trunk's window: the same warm-up and the same settling, unchanged
    assert config["train"]["warmup_steps"] == 100000 and config["train"]["settle"] == registry.config("trinity-mini-trunk-train")["train"]["settle"]
    names = {m["name"] for m in registry.metrics("per_layer", CELL)}
    assert {"moe_experts_ms", "moe_routing_ms", "trunk_attention_ms", "trunk_dense_ffn_ms", "mla_latent_ms", "mla_core_roofline"} <= names
    assert not {"az_conv_roofline", "nnue_ft_roofline", "moe_expert_roofline", "moe_held_expert_roofline"} & names and len(names) == 18
    assert {m["name"] for m in registry.metrics("per_layer", "afmoe_trunk_train_b256")}.isdisjoint({"mla_latent_ms", "mla_core_roofline"})
    family = registry.module("families", "mla_trunk")
    trunk = family.trunk_config(config)
    assert (trunk.hidden, trunk.heads, trunk.layers, trunk.dense_layers, trunk.kv_heads) == (2048, 32, 5, 1, None)
    assert (trunk.kv_lora_rank, trunk.qk_nope_head_dim, trunk.qk_rope_head_dim, trunk.v_head_dim) == (512, 128, 64, 128)
    assert (trunk.experts, trunk.held, trunk.experts_per_token, trunk.expert_width, trunk.dense_width, trunk.shared_width) == (128, (0, 8), 6, 768, 6144, 1536)
    assert (trunk.router_score, trunk.route_norm, trunk.route_scale, trunk.balance_rate, trunk.rope_theta, trunk.rms_eps) == ("sigmoid", True, 2.448, 0.001, 1e6, 1e-6)
    assert not trunk.gated_attention and not trunk.post_norms and trunk.embed_scale == 1.0 and trunk.recompute_experts and not trunk.nope_layers
    from fishnet_tpu.models.trunk import trunk_param_shapes
    shapes = trunk_param_shapes(trunk)
    assert (shapes["wq"], shapes["wkv_a"], shapes["kv_norm"], shapes["wkv_b"], shapes["wo"]) == (
        (5, 2048, 6144), (5, 2048, 576), (5, 512), (5, 512, 8192), (5, 4096, 2048)) and not {"q_norm", "k_norm", "wk", "wv"} & set(shapes)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 359_558_222  # the file's reduced_why
    with pytest.raises(ValueError):  # the two copies of a size may not drift apart
        family.trunk_config({**config, "kv_lora_rank": 256})
    for key, value in (("q_lora_rank", 1536), ("rope_interleave", False), ("n_group", 8), ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"}), ("n_shared_experts", 1), ("num_key_value_heads", 4)):
        with pytest.raises(ValueError, match=key):
            family.trunk_config({**config, key: value})


def test_the_core_hand_count():
    core = Registry(REPO).module("roofline", "mla_core")
    model = Registry(REPO).config(CONFIG)["model"]
    # a (board, head): 2 x 64 x 64 multiply-adds' operations a column: forward 192 + 128; gradient 192 + 128 + 128 + 192 + 192
    assert core.layer_flops(model, 256) == 256 * 32 * 2 * 64 * 64 * (320 + 832) == 77_309_411_328
    # a token: q 6144 + k_nope 4096 + k_pe 64 float32 = 41,216 B; v, the mix, their cotangents 4096 bfloat16 = 8,192 B each
    assert core.layer_bytes(model, 256) == 16_384 * ((41_216 + 2 * 8_192) + (41_216 + 2 * 8_192 + 41_216 + 8_192)) == 2_696_937_472
    least = core.least_seconds(model, 256, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory" and abs(least["least_s"] - 0.016465) < 1e-6 and abs(least["compute_s"] - 0.001962) < 1e-6
    assert core.layer_bytes({**model, "num_attention_heads": 64}, 256) > 1.99 * core.layer_bytes(model, 256)  # but for the one RoPE key


def test_the_two_reducers_on_a_synthetic_split_and_trace():
    registry = Registry(REPO)
    found = scopes.Split(steps=2)
    found.by_path = {
        "jvp(forward)/layer00.latent": 2.0, "transpose(jvp(forward))/layer00.latent": 4.0, "jvp(forward)/layer03.latent": 0.5,
        "jvp(forward)/layer00.attention": 8.0, "transpose(jvp(forward))/layer01.attention": 16.0, "jvp(forward)/layer01.router": 1.0, "optimizer": 100.0,
    }
    config = registry.config(CONFIG)
    op = lambda name, start, ms: tracelib.Op(name, "bf16[256,64,4096]", start, ms * 1e6, ["custom-call"])
    trace = tracelib.Trace(
        ops=[op("board_attention.5", 10.0, 6.0), op("fusion.7", 7e6, 50.0), op("board_attention_grad.9", 60e6, 12.0),
             op("board_attention.6", 100e6 + 10.0, 6.0), op("board_attention_grad.1", 160e6, 12.0), op("moe_rows_out.3", 180e6, 1.0)],
        modules=[("jit__step", 0.0, 100e6), ("jit__step", 100e6, 100e6)], host_spans=[])
    ctx = {"registry": registry, "config": config, "batch": 256, "device_kind": "TPU v5 lite", "trace": trace, "scopes_split": found}
    assert registry.module("reducers", "mla_latent_ms").reduce(ctx) == 6.5
    assert registry.module("reducers", "trunk_attention_ms").reduce(ctx) == 24.0  # the latent's scopes are not the attention's
    share = registry.module("reducers", "mla_core_roofline").reduce(ctx)
    assert abs(share - 100 * 16.465 / 18.0) < 0.01  # two steps of 6 + 12 ms
    # a program without the scope or the kernels (the parent, the other trunks), no trace: nothing, and no error
    found.by_path = {"jvp(forward)/layer00.attention": 3.0, "jvp(forward)/layer00.experts": 5.0}
    assert registry.module("reducers", "mla_latent_ms").reduce(ctx) is None
    for name in ("mla_latent_ms", "mla_core_roofline"):
        assert registry.module("reducers", name).reduce({**ctx, "scopes_split": None, "trace": None}) is None
    assert registry.module("reducers", "mla_core_roofline").reduce({**ctx, "config": registry.config("trinity-mini-trunk-train")}) is None
    assert registry.module("reducers", "mla_core_roofline").reduce({**ctx, "trace": dataclasses.replace(trace, ops=trace.ops[1:2])}) is None


def test_runner_end_to_end(tiny, capsys):
    """Batch 8 on the tiny latent trunk through ``train_step``, both kinds of run."""
    import jax

    cell = tiny.workload("mla_trunk_tiny_cell")
    runner = tiny.module("runners", cell["runner"])
    plain = runner.run(tiny, cell, 2**31 + 17, 1.5, False, time.monotonic(), jax.devices())
    traced = runner.run(tiny, cell, 2**31 + 17, 1.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "grad_rel_l2.wkv_a" in out
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    # the CPU's profile holds no device plane, so the trace metrics are left out and nothing raises
    assert traced["correct"] is True and not {"mla_latent_ms", "mla_core_roofline"} & set(traced["metrics"])
    json.dumps(traced)


def test_control_fails_and_program_passes(tiny):
    config = tiny.config("mla-trunk-tiny")
    family = tiny.module("families", "mla_trunk")
    reference = tiny.module("reference", "mla_trunk")
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
    the held experts of every routed layer have rows, the bias is a few
    balance steps with each layer's mean taken out, the value head sits
    away from its relus' corners, and a token's largest routing logit
    sits near 0.85 (the constant coordinate the embedding is conditioned
    for carries the centre through two norms a layer and no post-norm)."""
    import jax.numpy as jnp

    config = tiny.config("mla-trunk-tiny")
    model = config["model"]
    family, reference = tiny.module("families", "mla_trunk"), tiny.module("reference", "mla_trunk")
    for seed in (11, 2**31 + 12, 13, 14):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        batch = family.build_batch(pool, np.arange(32))
        p = reference.init_params(seed, model)
        x, slots = reference._trunk({k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(batch["planes"]), model, lambda a: a, lambda a: a)
        assert slots.shape == (2, 16) and float(slots.sum()) == 2 * 32 * 64 * 3
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


@pytest.mark.parametrize("what", ["wkv_b_x0", "kv_norm_x1.5", "wkv_a_x0", "route_scale_x1.5", "one_shared_expert_of_two", "theta_of_the_second_block"])
def test_left_out_mathematics_fails(tiny, what):
    """The gradient of the latent's up-projection or down-projection
    multiplied by zero, of the latent's gain by 1.5 (``sweep_correct.py
    --mutate`` does the same at width); the route scale by 1.5; the
    parameters of the second shared expert zeroed; RoPE at another theta:
    not correct."""
    import jax.numpy as jnp

    config = tiny.config("mla-trunk-tiny")
    family = tiny.module("families", "mla_trunk")
    checker = correctness.Checker(family, tiny.module("reference", "mla_trunk"), config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    if what == "route_scale_x1.5":
        _with_program_config(checker, family, route_scale=2.448 * 1.5)
    elif what == "theta_of_the_second_block":
        _with_program_config(checker, family, rope_theta=10000.0)
    elif what == "one_shared_expert_of_two":
        grad, state_of = checker._program_grad, family.state_from_params
        halved = lambda params: {**params, "shared_down": jnp.asarray(params["shared_down"]).at[:, 32:, :].set(0.0)}
        checker._program_grad = lambda params, batch: grad(halved(params), batch)
        checker.family = type("Family", (), {**{k: getattr(family, k) for k in dir(family) if not k.startswith("__")},
                                             "state_from_params": staticmethod(lambda trainer, params: state_of(trainer, halved(params)))})
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
    """``expert_bias`` after one step of the program, from the reference's
    parameters (through the column permutation), against the reference's
    balance rule on the reference's own routing counts: the comparison
    that decides ``correct`` cannot see this update (PERF.md section 7),
    so it is held to the reference here, directly."""
    import jax.numpy as jnp

    config = tiny.config("mla-trunk-tiny")
    family, reference = tiny.module("families", "mla_trunk"), tiny.module("reference", "mla_trunk")
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
        assert 0.1 < float(metrics["latent_rms"]) < 10.0
