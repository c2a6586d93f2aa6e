"""Adapter for the afmoe block as a trunk (Trinity-Mini's): the program's
``AzTrainer`` on a ``TrunkConfig`` behind the calls the ``train_step``
runner makes.

As ``families/moe_trunk.py``: the pool encoder and the dense batches are
the AlphaZero family's, imported; ``config["model"]`` repeats what the
trunk reads under the names of the model's own config.json, and
``trunk_config`` refuses a file whose two copies disagree or whose
published keys ask for what ``models/trunk.py`` does not compute.

One thing differs from the other trunk: the routed layers choose on
``score + expert_bias``, and that buffer lives in the trainer's state
beside the parameters (``AzTrainState.buffers``), outside the optimizer.
The reference keeps it among its parameters, so the two calls that take
the reference's parameters split it off here.

And the window does not start from a fresh learner at the full rate
(``SettledTrainer``): a chip that holds 8 of 128 experts computes the rows
routed to ITS experts, so how long a step takes follows the routing. A
board's tokens are of a few kinds (half are empty squares), so a fresh
router sends some expert every token and most experts none, and AdamW at
the whole rate from its first step moves every router logit by ~0.5 a
step: which experts are the favourites, and whether they are held here,
is then drawn anew every few steps, by the seed (PERF.md section 6, PR 33,
has the readings). So the window starts from a balanced ``expert_bias``
and a rate so early in its warm-up that the routing stands still: the
step at the routing an average chip of the 16 sees."""

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
from fishnet_tpu.models import az_encoding
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer, AzTrainState

BUFFER = "expert_bias"


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    layers = model["kept_layer_types"]
    unsupported = {
        "model_type": config["model_type"] != "afmoe",
        "hidden_act": config["hidden_act"] != "silu",
        "score_func": config["score_func"] != "sigmoid",
        "route_norm": config["route_norm"] is not True,
        "mup_enabled": config["mup_enabled"] is not True,
        "num_shared_experts": config["num_shared_experts"] != 1,
        "rope_scaling": config["rope_scaling"] is not None,
        "n_group": (config["n_group"], config["num_expert_groups"], config["topk_group"], config["num_limited_groups"]) != (1, 1, 1, 1),
        "layer_types": len(layers) != model["num_hidden_layers"] or set(layers) - {"sliding_attention", "full_attention"},
        "num_experts": model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], head_dim=model["head_dim"],
        layers=model["num_hidden_layers"], experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"], rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
        kv_heads=model["num_key_value_heads"], nope_layers=tuple(i for i, kind in enumerate(layers) if kind == "full_attention"),
        sliding_window=model["sliding_window"], gated_attention=True, post_norms=True, embed_scale=float(model["hidden_size"]) ** 0.5,
        dense_layers=model["num_dense_layers"], dense_width=model["intermediate_size"],
        shared_width=model["moe_intermediate_size"] * model["num_shared_experts"], router_score="sigmoid", route_norm=True,
        route_scale=model["route_scale"], held_experts=(model["first_held_expert"], model["num_experts"]),
        balance_rate=model["load_balance_coeff"], recompute_experts=bool(config["train"]["recompute_experts"]),
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    return SettledTrainer(trunk_config(config), {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                          optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"])


class SettledTrainer(AzTrainer):
    """``AzTrainer`` with the configuration's ``train.warmup_steps`` (the
    rate rises linearly from 0 over them, and they are far more than a
    recipe's: the window lies at their start, where all its steps together
    move a router logit by ~0.01, not each by 0.5) whose
    ``init`` hands back a learner whose ``expert_bias`` has been balanced,
    in set-up, by ``train.settle``: ``balance_passes`` forward passes of the
    program's own ``trunk_forward_counted``, each on another batch of a
    small pool of the cell's traffic (``positions`` playout positions from
    the same seed), each followed by the block's own balance rule at a
    rate that falls from ``rate_first`` to ``rate_last`` (at the published
    0.001 a step the same takes a few hundred steps). That is where a
    deployment's bias is after them: every expert near its layer's mean
    load, so the 8 held get ~1/16 of the slots whatever the seed.

    Parameters and moments are the seed's initialisation; the step the
    window runs is ``AzTrainer``'s, untouched, and goes on balancing at the
    published rate."""

    def __init__(self, cfg: TrunkConfig, settle: Dict[str, Any], warmup_steps: int, **kwargs: Any) -> None:
        super().__init__(cfg, **kwargs)
        self.settle, self.warmup_steps = dict(settle), warmup_steps

    def init(self, seed: int = 0) -> AzTrainState:
        return balanced(self, super().init(seed), seed)


def balanced(trainer: SettledTrainer, state: AzTrainState, seed: int) -> AzTrainState:
    import sys

    import jax
    import jax.numpy as jnp

    from benchmark import positions
    from benchmark.registry import Registry
    from fishnet_tpu.models.trunk import balanced_bias, trunk_forward_counted

    settle, cfg = trainer.settle, trainer.cfg
    traffic = Registry(Path(__file__).resolve().parents[2]).traffic(settle["traffic"])
    planes = positions.playout_pool(traffic, seed, sys.modules[__name__], int(settle["positions"]))["planes"]
    rng = np.random.default_rng([int(seed), 0x736574])
    slots = jax.jit(lambda params, bias, batch: trunk_forward_counted({**params, BUFFER: bias}, batch, cfg)[2]["expert_slots"])
    move = jax.jit(balanced_bias)
    bias = state.buffers[BUFFER]
    for rate in np.geomspace(float(settle["rate_first"]), float(settle["rate_last"]), int(settle["balance_passes"])):
        batch = jnp.asarray(planes[rng.integers(0, len(planes), int(settle["batch"]))])
        bias = move(bias, slots(state.params, bias, batch), jnp.float32(rate))
    return AzTrainState(state.params, state.opt_state, state.step, {**state.buffers, BUFFER: bias})


def _split(params: Dict[str, Any]):
    return {k: v for k, v in params.items() if k != BUFFER}, {BUFFER: params[BUFFER]}


def loss_and_grads(trainer: AzTrainer):
    """``jax.value_and_grad`` of the trainer's own loss with respect to
    what it trains; the buffer's gradient is the zero the reference's has
    (no gradient through the bias or the choice)."""
    import jax
    import jax.numpy as jnp

    def fn(params, batch):
        trained, buffers = _split(params)
        (loss, _aux), grads = jax.value_and_grad(trainer._loss, has_aux=True)(trained, batch, buffers)
        return loss, {**grads, BUFFER: jnp.zeros_like(buffers[BUFFER])}

    return jax.jit(fn)


def state_from_params(trainer: AzTrainer, params: Dict[str, Any]) -> AzTrainState:
    import jax.numpy as jnp

    trained, buffers = _split({k: jnp.array(v) for k, v in params.items()})
    # The comparison's optimizer steps are taken past the warm-up, at the rate the configuration gives for them
    # (a plain AzTrainer has no schedule and nothing to set).
    past = jnp.asarray(getattr(trainer, "warmup_steps", 0), jnp.int32)
    moments = tuple(s._replace(count=past) if isinstance(s, optax.ScaleByScheduleState) else s for s in trainer.optimizer.init(trained))
    return AzTrainState(trained, moments, jnp.zeros((), jnp.int32), buffers)
