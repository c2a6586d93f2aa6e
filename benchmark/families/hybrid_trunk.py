"""Adapter for the nemotron_h block as a trunk
(Nemotron-Labs-TwoTower-30B-A3B's one declared tower): the program's
``AzTrainer`` on a ``TrunkConfig`` with a layer pattern behind the calls
the ``train_step`` runner makes.

As ``families/mla_trunk.py``, whose pieces come from the second trunk's
adapter: the pool encoder and the dense batches are the AlphaZero
family's; the routed layers choose on ``score + expert_bias``, a buffer
beside the parameters; the window starts from a balanced bias with the
rate at the start of a long warm-up (``SettledTrainer``: a share's rate
follows its routing, so the cell holds the routing still).
``trunk_config`` is this block's own and refuses a file whose two copies
of a size disagree or whose published keys ask for what
``models/trunk.py`` does not compute.

No column is permuted: the program keeps ``mamba_in``'s columns in the
published order ``[z | x | B | C | dt]`` (it splits them on the weights'
side), the attention projections a head at a time, RoPE as rotate-half.
``conv_w`` is the published ``conv1d.weight`` ``[channels, 1, 4]``
without its middle axis. So the reference's parameters go in and the
program's gradients come back as they are, and ``correct`` is decided in
the published order. What the program pads inside its step (an expert's
1,856 columns to 1,920, a moved row's 2,688 to 3,072: ``models/trunk.py
_whole_lanes``, ``_whole_rows``) is no parameter and is never seen here."""

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
    kept = config["published"]["kept_layers"]
    unsupported = {
        "model_type": config["model_type"] != "nemotron_h",
        "pattern": model["pattern"] != "".join(config["hybrid_override_pattern"][i] for i in kept) or len(kept) != model["num_hidden_layers"],
        "mlp_hidden_act": config["mlp_hidden_act"] != "relu2",
        "mamba_hidden_act": config["mamba_hidden_act"] != "silu",
        "norm_topk_prob": config["norm_topk_prob"] is not True,
        "n_group": (config["n_group"], config["topk_group"]) != (1, 1),
        "n_shared_experts": config["n_shared_experts"] != 1,
        "biases": (config["attention_bias"], config["mlp_bias"], config["mamba_proj_bias"], config["use_bias"]) != (False,) * 4
                  or config["use_conv_bias"] is not True,
        "partial_rotary_factor": config["partial_rotary_factor"] != 1,
        "sliding_window": config["sliding_window"] is not None,
        "chunk_size": config["chunk_size"] < 64,  # a board is one chunk: the dual form is exact (ops/board_scan.py)
        "time_step_limit": list(config["time_step_limit"]) != [0, None],
        "time_step": (config["time_step_min"], config["time_step_max"], config["time_step_floor"]) != (0.001, 0.1, 0.0001),
        "layer_norm_epsilon": config["layer_norm_epsilon"] != model["rms_norm_eps"] or config["norm_eps"] != model["rms_norm_eps"],
        "routed_scaling_factor": config["routed_scaling_factor"] != model["route_scale"],
        "intermediate_size": config["intermediate_size"] != model["moe_intermediate_size"],
        "n_routed_experts": config["n_routed_experts"] != model["num_experts"]
                            or model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        qk_norm=False, pattern=model["pattern"], experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"], gated_ffn=False, shared_width=model["moe_shared_expert_intermediate_size"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
        mamba_heads=model["mamba_num_heads"], mamba_head_dim=model["mamba_head_dim"], mamba_groups=model["n_groups"],
        state_size=model["ssm_state_size"], conv_kernel=model["conv_kernel"],
        router_score="sigmoid", route_norm=True, route_scale=model["route_scale"],
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
