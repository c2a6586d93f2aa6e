"""Adapter for the ouro block as a trunk (Ouro-2.6B's): the program's
``AzTrainer`` on a ``TrunkConfig`` whose stack of layers is run
``total_ut_steps`` times over the same weights, an exit at every pass, behind
the calls the ``train_step`` runner makes.

The first family without a routed layer: there is no ``expert_bias`` to carry
beside the parameters, no share of experts and nothing to balance before the
window, so the trainer is the program's ``AzTrainer`` as it is (no
``SettledTrainer``), and the pool encoder, the dense batches, the loss with
its gradients, the state and the step's text are the AlphaZero family's,
imported: the reference's parameters go in and the program's gradients come
back under the same names, tensor for tensor (the published ``q_proj`` /
``k_proj`` lay a head's 128 columns side by side and turn them by
rotate-half, the program's own layout).

``trunk_config`` is this block's own: it refuses a file whose two copies of a
size disagree or whose published keys ask for what ``models/trunk.py`` does
not compute for this block (a window, a RoPE scaling, key-value heads other
than the query heads', a bias, fewer than one pass)."""

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
    kept = [config["layer_types"][layer] for layer in config["published"]["kept_layers"]]
    unsupported = {
        "model_type": config["model_type"] != "ouro",
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": config.get("attention_bias", False) is not False,
        "layer_types": set(kept) != {"full_attention"} or len(kept) != model["num_hidden_layers"],
        "sliding_window": config.get("sliding_window") is not None or config.get("use_sliding_window", False) is not False,
        "rope_scaling": config.get("rope_scaling") is not None,
        "num_key_value_heads": config["num_key_value_heads"] != config["num_attention_heads"],
        "total_ut_steps": not isinstance(model["total_ut_steps"], int) or model["total_ut_steps"] < 1,
        "early_exit_threshold": not 0.0 < model["early_exit_threshold"] <= 1.0,
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], head_dim=model["head_dim"], layers=model["num_hidden_layers"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"], value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
        qk_norm=False, post_norms=True, dense_layers=model["num_hidden_layers"], dense_width=model["intermediate_size"],
        loop_steps=model["total_ut_steps"], exit_threshold=float(model["early_exit_threshold"]),
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    return AzTrainer(trunk_config(config), learning_rate=train["learning_rate"], value_weight=train["value_weight"],
                     exit_entropy_weight=train["exit_entropy_weight"])
