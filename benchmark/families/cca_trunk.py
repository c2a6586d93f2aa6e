"""Adapter for the zaya block as a trunk (ZAYA1-8B's): the program's
``AzTrainer`` on a ``TrunkConfig`` with compressed convolutional
attention, an MLP router and one expert a token behind the calls the
``train_step`` runner makes.

As ``families/hybrid_trunk.py``, whose pieces come from the second
trunk's adapter: the pool encoder and the dense batches are the
AlphaZero family's; the layers choose on ``score + expert_bias``, a
buffer beside the parameters; the window starts from a balanced bias with
the rate at the start of a long warm-up (``SettledTrainer``: a share's
rate follows its routing, and here half of a layer's tokens are at
stake, so the cell holds the routing still). ``trunk_config`` is this
block's own and refuses a file whose two copies of a size disagree or
whose published keys ask for what ``models/trunk.py`` does not compute.

No column is permuted: the program keeps ``wq``, ``wk``, ``wv1`` and
``wv2`` apart under the published names and joins them inside its step,
a head at a time, RoPE as rotate-half on a head's first columns;
``conv0_w`` is ``[1280, taps]`` and ``conv1_w`` ``[10, taps, 128 in, 128
out]`` in program and reference alike (a published ``Conv1d`` weight with
its axes moved, the last tap the token's own). So the reference's
parameters go in and the program's gradients come back as they are, and
``correct`` is decided in the published order."""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import optax

from benchmark.families.az import (  # noqa: F401  (the runner calls them on this module)
    PoolEncoder,
    build_batch,
    step_hlo_text,
)
from benchmark.registry import Registry
from fishnet_tpu.models import az_encoding
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer

#: The second trunk's adapter of THIS checkout (it finds its traffic beside its own file): the window's start is its.
afmoe_trunk = Registry(Path(__file__).resolve().parents[2]).module("families", "afmoe_trunk")
SettledTrainer, loss_and_grads, state_from_params = afmoe_trunk.SettledTrainer, afmoe_trunk.loss_and_grads, afmoe_trunk.state_from_params


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    rope = config["rope_parameters"]["hybrid"]
    unsupported = {
        "model_type": config["model_type"] != "zaya",
        "layer_types": set(config["layer_types"]) != {"hybrid"} or len(config["layer_types"]) != config["published"]["num_hidden_layers"]
                       or list(config["published"]["kept_layers"]) != list(range(model["num_hidden_layers"])),
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": config["attention_bias"] is not False,
        "sliding_window": config["sliding_window"] is not None,
        "rope_parameters": (rope["rope_theta"], rope["partial_rotary_factor"], rope["rope_type"])
                           != (model["rope_theta"], config["partial_rotary_factor"], "default"),
        "partial_rotary_factor": model["rotary_dim"] != config["partial_rotary_factor"] * model["head_dim"],
        "num_experts_per_tok": model["num_experts_per_tok"] != 1,  # the published top-1: norm_topk_prob is void
        "num_experts": model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        layers=model["num_hidden_layers"], cca=(model["cca_time0"], model["cca_time1"]), rotary_dim=model["rotary_dim"],
        router_hidden=model["router_hidden_size"], experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"], rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"], router_score="softmax",
        held_experts=(model["first_held_expert"], model["num_experts"]),
        balance_rate=model["load_balance_coeff"], recompute_experts=bool(config["train"]["recompute_experts"]),
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    return SettledTrainer(trunk_config(config), {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                          optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"])
