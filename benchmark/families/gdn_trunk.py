"""Adapter for the qwen3_next block as a trunk (Qwen3-Next-80B-A3B's): the
program's ``AzTrainer`` on a ``TrunkConfig`` whose mixer is told by layer
(Gated DeltaNet or gated attention at a head of 256) behind the calls the
``train_step`` runner makes.

As ``families/mla_trunk.py``, whose window's start it imports through the
second trunk's adapter: the pool encoder and the dense batches are the
AlphaZero family's; the routed layers choose on ``score + expert_bias``, a
buffer beside the parameters; the window starts from a balanced bias with
the rate at the start of a long warm-up (``SettledTrainer``).

**The column order.** The reference keeps the published order: ``W_qkvz``
and ``W_ba`` key head by key head (a key head's q, k, its value heads' v
and z; its b, a), ``W_q`` head by head (a head's 256 query columns, then
its 256 gate columns). The program keeps ``gdn_qkvz`` as every head's q,
then k, then v, then z (the convolution reads ``[q | k | v]`` side by side
and the core a head's columns where they lie), ``gdn_ba`` as every head's
b, then a, and the attention's gate apart from its query (``wq``, ``wgate``:
the second trunk's tensors). ``to_program`` takes the reference's
parameters in, ``from_program`` the program's gradients back, and the
comparison that decides ``correct`` is made in the published order, the
attention's ``W_q`` with its gate columns as ONE tensor.

``trunk_config`` is this block's own: it reads each kept layer's mixer off
``full_attention_interval`` and refuses a file whose two copies of a size
disagree or whose published keys ask for what ``models/trunk.py`` does not
compute."""

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
from fishnet_tpu.train.az_trainer import AzTrainer, AzTrainState

#: The second trunk's adapter of THIS checkout (it finds its traffic beside its own file): the window's start is its.
afmoe_trunk = Registry(Path(__file__).resolve().parents[2]).module("families", "afmoe_trunk")
SettledTrainer, BUFFER = afmoe_trunk.SettledTrainer, afmoe_trunk.BUFFER


def mixers_of(config: Dict[str, Any]):
    """Each kept layer's mixer: published layer ``i`` is full attention where ``(i + 1) % full_attention_interval == 0``, else GDN."""
    return ["attention" if (layer + 1) % config["full_attention_interval"] == 0 else "gdn" for layer in config["published"]["kept_layers"]]


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    mixers = mixers_of(config)
    unsupported = {
        "model_type": config["model_type"] != "qwen3_next",
        "hidden_act": config["hidden_act"] != "silu",
        "mlp_only_layers": config["mlp_only_layers"] != [],
        "decoder_sparse_step": config["decoder_sparse_step"] != 1,
        "rope_scaling": config["rope_scaling"] is not None,
        "use_sliding_window": config["use_sliding_window"] is not False,
        "norm_topk_prob": config["norm_topk_prob"] is not True,
        "linear_key_head_dim": config["linear_key_head_dim"] != config["linear_value_head_dim"],
        "linear_num_value_heads": config["linear_num_value_heads"] % config["linear_num_key_heads"] != 0,
        "partial_rotary_factor": config["partial_rotary_factor"] * config["head_dim"] != model["rotary_dim"],
        "num_experts": config["num_experts"] != model["num_experts"] or model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
        "full_attention_interval": mixers != list(model["mixers"]) or len(mixers) != model["num_hidden_layers"],
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        layers=model["num_hidden_layers"], experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"], rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"], gated_attention=True, rotary_dim=model["rotary_dim"],
        shared_width=model["shared_expert_intermediate_size"], router_score="softmax", route_norm=True,
        held_experts=(model["first_held_expert"], model["num_experts"]), balance_rate=model["load_balance_coeff"],
        recompute_experts=bool(config["train"]["recompute_experts"]), mixers=tuple(mixers),
        linear_num_key_heads=model["linear_num_key_heads"], linear_num_value_heads=model["linear_num_value_heads"],
        linear_key_head_dim=model["linear_key_head_dim"], linear_value_head_dim=model["linear_value_head_dim"],
        conv_kernel=model["linear_conv_kernel_dim"], shared_token_gate=True, zero_centered_norms=True,
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    return SettledTrainer(trunk_config(config), {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                          optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"])


def column_orders(cfg: TrunkConfig) -> Dict[str, np.ndarray]:
    """For each of the program's tensors whose columns come from a published tensor in another order: ``program =
    published[..., order]``. ``wq`` and ``wgate`` are both columns of the published ``W_q``."""
    d, key_heads, per = cfg.linear_key_head_dim, cfg.linear_num_key_heads, cfg.linear_num_value_heads // cfg.linear_num_key_heads
    a_key_head = np.arange(key_heads)[:, None] * (2 + 2 * per) * d  # where a key head's columns of W_qkvz start
    part = lambda first, width: (a_key_head + first + np.arange(width)[None, :]).reshape(-1)
    ba = np.arange(key_heads)[:, None] * 2 * per
    a_head = np.arange(cfg.heads)[:, None] * 2 * cfg.head_dim
    return {
        "gdn_qkvz": np.concatenate([part(0, d), part(d, d), part(2 * d, per * d), part((2 + per) * d, per * d)]),
        "gdn_ba": np.concatenate([(ba + np.arange(per)[None, :]).reshape(-1), (ba + per + np.arange(per)[None, :]).reshape(-1)]),
        "wq": (a_head + np.arange(cfg.head_dim)[None, :]).reshape(-1),
        "wgate": (a_head + cfg.head_dim + np.arange(cfg.head_dim)[None, :]).reshape(-1),
    }


def to_program(cfg: TrunkConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """Parameters in the published column order -> the program's tensors (``wgate`` out of the published ``wq``)."""
    orders = column_orders(cfg)
    return {**{k: (v[..., orders[k]] if k in orders else v) for k, v in params.items()}, "wgate": params["wq"][..., orders["wgate"]]}


def from_program(cfg: TrunkConfig, tensors: Dict[str, Any]) -> Dict[str, Any]:
    """Tensors shaped like the program's parameters (its gradients) -> in the published column order, ``wgate``'s back among ``wq``'s."""
    import jax.numpy as jnp

    orders = column_orders(cfg)
    back = {k: (v[..., np.argsort(orders[k])] if k in ("gdn_qkvz", "gdn_ba") else v) for k, v in tensors.items() if k != "wgate"}
    published = np.argsort(np.concatenate([orders["wq"], orders["wgate"]]))
    return {**back, "wq": jnp.concatenate([tensors["wq"], tensors["wgate"]], axis=-1)[..., published]}


def loss_and_grads(trainer: AzTrainer):
    """The second trunk's (``jax.value_and_grad`` of the trainer's own
    loss, a zero for the buffer), between the two maps."""
    import jax

    program = afmoe_trunk.loss_and_grads(trainer)

    def fn(params, batch):
        loss, grads = program(to_program(trainer.cfg, params), batch)
        return loss, from_program(trainer.cfg, grads)

    return jax.jit(fn)


def state_from_params(trainer: AzTrainer, params: Dict[str, Any]) -> AzTrainState:
    return afmoe_trunk.state_from_params(trainer, to_program(trainer.cfg, params))
