"""Adapter for the deepseek_v3 block as a trunk (Kanana-2-30B-A3B's): the
program's ``AzTrainer`` on a ``TrunkConfig`` with latent attention behind
the calls the ``train_step`` runner makes.

As ``families/afmoe_trunk.py``, whose pieces it imports: the pool encoder
and the dense batches are the AlphaZero family's; the routed layers choose
on ``score + expert_bias``, a buffer beside the parameters; the window
starts from a balanced bias with the rate at the start of a long warm-up
(``SettledTrainer``: a share's rate follows its routing, so the cell holds
the routing still). ``trunk_config`` is this block's own and refuses a
file whose two copies of a size disagree or whose published keys ask for
what ``models/trunk.py`` does not compute.

One thing is new: **the column order**. The reference keeps the published
order (``wq`` and ``wkv_b`` a head at a time, RoPE on interleaved pairs);
the program keeps every head's NoPE columns before every head's RoPE
columns, the pairs taken apart, and every head's keys before every head's
values (``models/trunk.py`` says why: the kernels' operands are then the
projections' results as they are). That is a permutation of the columns
of three tensors and changes no score: ``to_program`` takes the
reference's parameters in, ``from_program`` the program's gradients back,
and the comparison that decides ``correct`` is made in the published
order. ``tests/test_moe_trunk.py`` holds the map to a hand count."""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import numpy as np
import optax

from benchmark.families.az import (  # noqa: F401  (the runner calls them on this module)
    PoolEncoder,
    build_batch,
    step_hlo_text,
)
from benchmark.registry import Registry
from fishnet_tpu.models import az_encoding
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.ops.board_attention import latent_column_order
from fishnet_tpu.train.az_trainer import AzTrainer, AzTrainState

#: The second trunk's adapter of THIS checkout (it finds its traffic beside its own file): the window's start is its.
afmoe_trunk = Registry(Path(__file__).resolve().parents[2]).module("families", "afmoe_trunk")
SettledTrainer, balanced, BUFFER = afmoe_trunk.SettledTrainer, afmoe_trunk.balanced, afmoe_trunk.BUFFER


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    unsupported = {
        "model_type": config["model_type"] != "deepseek_v3",
        "q_lora_rank": config["q_lora_rank"] is not None,
        "hidden_act": config["hidden_act"] != "silu",
        "scoring_func": config["scoring_func"] != "sigmoid",
        "topk_method": config["topk_method"] != "noaux_tc",
        "norm_topk_prob": config["norm_topk_prob"] is not True,
        "n_group": (config["n_group"], config["topk_group"]) != (1, 1),
        "rope_scaling": config["rope_scaling"] is not None,
        "rope_interleave": config["rope_interleave"] is not True,
        "attention_bias": config["attention_bias"] is not False,
        "num_key_value_heads": config["num_key_value_heads"] != model["num_attention_heads"],
        "qk_head_dim": config["qk_head_dim"] != model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        "first_k_dense_replace": config["first_k_dense_replace"] != model["num_dense_layers"],
        "moe_layer_freq": config["moe_layer_freq"] != 1,
        "n_shared_experts": config["n_shared_experts"] != model["num_shared_experts"],
        "routed_scaling_factor": config["routed_scaling_factor"] != model["route_scale"],
        "n_routed_experts": config["n_routed_experts"] != model["num_experts"]
                            or model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
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
        v_head_dim=model["v_head_dim"],
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    return SettledTrainer(trunk_config(config), {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                          optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"])


def column_orders(cfg: TrunkConfig) -> Dict[str, np.ndarray]:
    """For each tensor whose columns the program keeps in another order
    than the published one: ``program = published[..., order]``."""
    heads, rank, nope, rope, value = cfg.heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    per_head = np.arange(heads)[:, None] * (nope + value)
    return {
        "wq": latent_column_order(heads, nope, rope),
        "wkv_a": np.concatenate([np.arange(rank), rank + latent_column_order(1, 0, rope)]),
        "wkv_b": np.concatenate([(per_head + np.arange(nope)[None, :]).reshape(-1), (per_head + nope + np.arange(value)[None, :]).reshape(-1)]),
    }


def to_program(cfg: TrunkConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """Parameters in the published column order -> in the program's."""
    orders = column_orders(cfg)
    return {k: (v[..., orders[k]] if k in orders else v) for k, v in params.items()}


def from_program(cfg: TrunkConfig, tensors: Dict[str, Any]) -> Dict[str, Any]:
    """Tensors shaped like the program's parameters (its gradients) -> in the published column order."""
    orders = {k: np.argsort(order) for k, order in column_orders(cfg).items()}
    return {k: (v[..., orders[k]] if k in orders else v) for k, v in tensors.items()}


def loss_and_grads(trainer: AzTrainer):
    """The second trunk's (``jax.value_and_grad`` of the trainer's own
    loss, a zero for the buffer), between the two permutations."""
    import jax

    program = afmoe_trunk.loss_and_grads(trainer)

    def fn(params, batch):
        loss, grads = program(to_program(trainer.cfg, params), batch)
        return loss, from_program(trainer.cfg, grads)

    return jax.jit(fn)


def state_from_params(trainer: AzTrainer, params: Dict[str, Any]) -> AzTrainState:
    return afmoe_trunk.state_from_params(trainer, to_program(trainer.cfg, params))
