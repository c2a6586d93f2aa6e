"""Adapter for the mellum block as a trunk (Mellum2-12B-A2.5B's): the
program's ``AzTrainer`` on a ``TrunkConfig`` whose attention layers turn by
a table a layer KIND (plain on the sliding layers, YaRN with its attention
factor on the full one) behind the calls the ``train_step`` runner makes.

As ``families/gdn_trunk.py``, whose window's start it takes from the second
trunk's adapter the same way: the pool encoder and the dense batches are the
AlphaZero family's; the routed layers choose on ``score + expert_bias``, a
buffer beside the parameters that the reference keeps among its own; the
window starts from a balanced bias with the rate at the start of a long
warm-up (``SettledTrainer``).

**No column order to map.** The published ``q_proj`` / ``k_proj`` lay a
head's 128 columns side by side and turn them by rotate-half, which is the
program's own layout and rotation: the reference's parameters go in and the
program's gradients come back under the same names, tensor for tensor
(``loss_and_grads`` and ``state_from_params`` move ``expert_bias`` alone, and
are ``families/afmoe_trunk.py``'s).

``trunk_config`` is this block's own: it reads each kept layer's kind off
``layer_types`` and the two kinds' tables off ``rope_parameters``, and
refuses a file whose two copies of a size disagree or whose published keys
ask for what ``models/trunk.py`` does not compute (a sliding layer under
anything but the plain table; a dense layer; a shared expert)."""

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

#: The second trunk's adapter of THIS checkout (it finds its traffic beside its own file): the window's start, and the two calls
#: that split ``expert_bias`` off the reference's parameters, are its.
afmoe_trunk = Registry(Path(__file__).resolve().parents[2]).module("families", "afmoe_trunk")
SettledTrainer, loss_and_grads, state_from_params = afmoe_trunk.SettledTrainer, afmoe_trunk.loss_and_grads, afmoe_trunk.state_from_params


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    kept = [config["layer_types"][layer] for layer in config["published"]["kept_layers"]]
    sliding, full = model["rope_parameters"]["sliding_attention"], model["rope_parameters"]["full_attention"]
    unsupported = {
        "model_type": config["model_type"] != "mellum",
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": config["attention_bias"] is not False,
        "norm_topk_prob": config["norm_topk_prob"] is not True,
        "mlp_layer_types": set(config["mlp_layer_types"]) != {"sparse"},
        "layer_types": kept != list(model["kept_layer_types"]) or len(kept) != model["num_hidden_layers"] or set(kept) - {"sliding_attention", "full_attention"},
        "use_sliding_window": config["use_sliding_window"] is not True,
        "rope_parameters": sliding != {"rope_type": "default", "rope_theta": full["rope_theta"]} or full["rope_type"] != "yarn",
        "num_experts": config["num_experts"] != model["num_experts"] or model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
        "num_dense_layers": model["num_dense_layers"] != 0,
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        layers=model["num_hidden_layers"], experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"], rope_theta=float(full["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"], sliding_window=model["sliding_window"],
        router_score="softmax", route_norm=True, held_experts=(model["first_held_expert"], model["num_experts"]),
        balance_rate=model["load_balance_coeff"], recompute_experts=bool(config["train"]["recompute_experts"]),
        full_attention_layers=tuple(i for i, kind in enumerate(kept) if kind == "full_attention"), rope_type="yarn", rope_factor=float(full["factor"]),
        original_max_position_embeddings=int(full["original_max_position_embeddings"]), beta_fast=float(full["beta_fast"]),
        beta_slow=float(full["beta_slow"]), attention_factor=float(full["attention_factor"]),
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    return SettledTrainer(trunk_config(config), {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                          optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"])

