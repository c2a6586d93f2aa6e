"""Adapter for the kimi_linear block as a trunk (Kimi-Linear-48B-A3B's):
the program's ``AzTrainer`` on a ``TrunkConfig`` whose mixer is told by
layer (Kimi Delta Attention or latent attention without RoPE) behind the
calls the ``train_step`` runner makes.

As ``families/mla_trunk.py``, whose pieces it imports: the pool encoder and
the dense batches are the AlphaZero family's; the routed layers choose on
``score + expert_bias``, a buffer beside the parameters; the window starts
from a balanced bias with the rate at the start of a long warm-up
(``SettledTrainer``); the latent's three tensors go in and their gradients
come back through the third trunk's column permutation (the program keeps
every head's NoPE columns before every head's 64 further columns and every
head's keys before every head's values; with no rotation the order of the
64 is nobody's concern, and the same map serves). The KDA mixer's tensors
are the reference's as they are: ``kda_conv`` is q's, k's and v's taps in
turn, ``[3 x heads x d, taps]``, the last tap the token's own, in program
and reference alike.

``trunk_config`` is this block's own: it reads each kept layer's mixer off
the published ``linear_attn_config`` (its layer numbers count from 1) and
refuses a file whose two copies of a size disagree or whose published keys
ask for what ``models/trunk.py`` does not compute."""

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

#: The third trunk's adapter of THIS checkout: the window's start and the latent's column order are its.
mla_trunk = Registry(Path(__file__).resolve().parents[2]).module("families", "mla_trunk")
SettledTrainer, loss_and_grads, state_from_params = mla_trunk.SettledTrainer, mla_trunk.loss_and_grads, mla_trunk.state_from_params
to_program, from_program = mla_trunk.to_program, mla_trunk.from_program


def mixers_of(config: Dict[str, Any]):
    """Each kept layer's mixer from the published lists (which count layers from 1); None for a layer in neither."""
    linear = config["linear_attn_config"]
    kind = {**{layer: "kda" for layer in linear["kda_layers"]}, **{layer: "latent" for layer in linear["full_attn_layers"]}}
    return [kind.get(layer) for layer in config["published"]["kept_layers"]]


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model, linear = config["model"], config["linear_attn_config"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    mixers = mixers_of(config)
    unsupported = {
        "model_type": config["model_type"] != "kimi_linear",
        "q_lora_rank": config["q_lora_rank"] is not None,
        "hidden_act": config["hidden_act"] != "silu",
        "moe_router_activation_func": config["moe_router_activation_func"] != "sigmoid",
        "moe_renormalize": config["moe_renormalize"] is not True,
        "num_expert_group": (config["num_expert_group"], config["topk_group"]) != (1, 1),
        "rope_scaling": config["rope_scaling"] is not None,
        "num_nextn_predict_layers": config["num_nextn_predict_layers"] != 0,
        "moe_layer_freq": config["moe_layer_freq"] != 1,
        "mla_use_nope": config["mla_use_nope"] is not model["mla_use_nope"],
        "first_k_dense_replace": config["first_k_dense_replace"] != model["num_dense_layers"],
        "num_experts_per_token": config["num_experts_per_token"] != model["num_experts_per_tok"],
        "num_shared_experts": config["num_shared_experts"] != model["num_shared_experts"],
        "routed_scaling_factor": config["routed_scaling_factor"] != model["route_scale"],
        "num_experts": model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
        "linear_attn_config": (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
                              != (model["kda_num_heads"], model["kda_head_dim"], model["short_conv_kernel_size"])
                              or mixers != list(model["mixers"]) or len(mixers) != model["num_hidden_layers"],
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], layers=model["num_hidden_layers"],
        experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"], expert_width=model["moe_intermediate_size"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
        dense_layers=model["num_dense_layers"], dense_width=model["intermediate_size"],
        shared_width=model["moe_intermediate_size"] * model["num_shared_experts"], router_score="sigmoid", route_norm=True,
        route_scale=model["route_scale"], held_experts=(model["first_held_expert"], model["num_experts"]),
        balance_rate=model["load_balance_coeff"], recompute_experts=bool(config["train"]["recompute_experts"]),
        kv_lora_rank=model["kv_lora_rank"], qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], mixers=tuple(mixers),
        nope_layers=tuple(i for i, kind in enumerate(mixers) if kind == "latent") if model["mla_use_nope"] else (),
        kda_heads=model["kda_num_heads"], kda_head_dim=model["kda_head_dim"], conv_kernel=model["short_conv_kernel_size"],
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    return SettledTrainer(trunk_config(config), {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                          optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"])
