"""Adapter for the sparse-expert trunk family: the program's ``AzTrainer``
on a ``TrunkConfig`` behind the calls the ``train_step`` runner makes.

The trunk takes the tower's input planes and ends in the tower's heads,
so the pool encoder, the dense batches and the calls on the trainer are
the AlphaZero family's, imported. ``config["model"]`` carries the
published sizes under the names of the model's own config.json; the
file repeats them at its top level for the driver, and ``make_trainer``
refuses a file whose two copies disagree."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.az import (  # noqa: F401  (the runner calls them on this module)
    PoolEncoder,
    build_batch,
    loss_and_grads,
    state_from_params,
    step_hlo_text,
)
from fishnet_tpu.models import az_encoding
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    unsupported = {
        "moe_layer_freq": config["moe_layer_freq"][: model["num_hidden_layers"]] != [1] * model["num_hidden_layers"],
        "num_key_value_heads": config["num_key_value_heads"] != model["num_attention_heads"],
        "qk_layernorm": config["qk_layernorm"] is not True,
        "partial_rotary_factor": config["partial_rotary_factor"] != 1,
        "moe_router_score_function": config["moe_router_score_function"] != "softmax",
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "routed_scaling_factor": config["routed_scaling_factor"] != 1,
        "shared_expert_intermediate_size": config["shared_expert_intermediate_size"] is not None,
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": config["attention_bias"] is not False,
        "moe_router_enable_expert_bias": config["moe_router_enable_expert_bias"] is not False,
        "clip_qkv": config["clip_qkv"] is not None,
        "rope_scaling": config["rope_scaling"] is not None,
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], head_dim=model["head_dim"],
        layers=model["num_hidden_layers"], experts=model["num_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["expert_intermediate_size"], rope_theta=float(model["rope_theta"]),
        rms_eps=model["rms_norm_eps"], value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    return AzTrainer(trunk_config(config), learning_rate=train["learning_rate"], value_weight=train["value_weight"])
