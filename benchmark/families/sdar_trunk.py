"""Adapter for the sdar_moe block as a trunk trained by block diffusion
(SDAR-30B-A3B-Chat's): the program's ``AzTrainer`` on a ``TrunkConfig`` with
a ``block_length`` behind the calls the ``train_step`` runner makes.

As ``families/mellum_trunk.py`` (the same decoder layer): the pool encoder is
the AlphaZero family's; the routed layers choose on ``score + expert_bias``,
a buffer beside the parameters that the reference keeps among its own
(``loss_and_grads`` and ``state_from_params`` move it alone and are
``families/afmoe_trunk.py``'s); no column order to map.

Two things are this family's own. **A batch carries its noise**:
``build_batch`` is the AlphaZero family's arrays and the two arrays of the
program's ONE maker of noise, ``fishnet_tpu/train/data.py block_noise``
(``block_level`` a board and block, ``square_masked`` a square), drawn by a
generator made from the batch's own pool rows ``idx``, so that the window's
batches and the comparison's are what ``--seed`` makes them and the step
draws nothing. **The window's start is settled on noised batches**
(``NoisedSettledTrainer``): the second trunk's ``SettledTrainer`` balances
``expert_bias`` on forward passes over planes alone, which for this trunk is
the SERVED forward, 64 clean tokens a board; the step routes 128 tokens a
board, half of them a noised copy whose masked squares are another kind of
token, so the bias is balanced on the training forward under fresh noise.

``trunk_config`` refuses a file whose two copies of a size disagree or whose
published keys ask for what ``models/trunk.py`` does not compute: a window,
a shared expert, a dense layer, a RoPE scaling, a block that does not divide
a board."""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import numpy as np
import optax

from benchmark.families.az import PoolEncoder, step_hlo_text  # noqa: F401  (the runner calls them on this module)
from benchmark.families.az import build_batch as az_batch
from benchmark.registry import Registry
from fishnet_tpu.models import az_encoding
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.train.az_trainer import AzTrainer, AzTrainState
from fishnet_tpu.train.data import block_noise

#: The second trunk's adapter of THIS checkout (it finds its traffic beside its own file): the schedule of the window's start, and the two
#: calls that split ``expert_bias`` off the reference's parameters, are its.
afmoe_trunk = Registry(Path(__file__).resolve().parents[2]).module("families", "afmoe_trunk")
loss_and_grads, BUFFER = afmoe_trunk.loss_and_grads, afmoe_trunk.BUFFER

#: What ``build_batch`` noises under. It is handed a pool and rows and no configuration, so this is the LAST trainer made's (``make_trainer``
#: sets it and gives the trainer its own copy, ``trainer.noise``), the published-widths file's values until one is made. A process that makes
#: trainers of two files of this family is held to it: ``same_noise``, wherever a trainer meets a batch here, refuses the older one.
NOISE = {"block_length": 4, "t_min": 1e-3}


def same_noise(trainer: AzTrainer) -> None:
    if getattr(trainer, "noise", NOISE) != NOISE:
        raise RuntimeError(f"this trainer's batches are noised under {trainer.noise}, and build_batch now noises under {NOISE}: another configuration of "
                           "this family was made since, and build_batch is handed none; make the trainer again")


def state_from_params(trainer: AzTrainer, params: Dict[str, Any]) -> AzTrainState:
    same_noise(trainer)  # the comparison's batches are made before this call
    return afmoe_trunk.state_from_params(trainer, params)


def trunk_config(config: Dict[str, Any]) -> TrunkConfig:
    model = config["model"]
    differ = sorted(k for k in model if k in config and config[k] != model[k])
    if differ:
        raise ValueError(f"the configuration's model group and its top level disagree on {differ}")
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    unsupported = {
        "model_type": config["model_type"] != "sdar_moe",
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": config["attention_bias"] is not False,
        "norm_topk_prob": config["norm_topk_prob"] is not True,
        "use_sliding_window": config["use_sliding_window"] is not False or config["sliding_window"] is not None,
        "rope_scaling": config["rope_scaling"] is not None,
        "mlp_only_layers": config["mlp_only_layers"] != [] or config["decoder_sparse_step"] != 1 or model["num_dense_layers"] != 0,
        "num_shared_experts": model.get("num_shared_experts", 0) != 0,
        "num_experts": config["num_experts"] != model["num_experts"] or model["first_held_expert"] + model["num_experts"] > model["num_routed_experts"],
        "block_length": not 0 < model["block_length"] <= 64 or 64 % model["block_length"] != 0,
        "t_min": not 0.0 < model["t_min"] <= 1.0,
    }
    if any(unsupported.values()):
        raise ValueError(f"models/trunk.py does not compute {sorted(k for k, v in unsupported.items() if v)} as given")
    return TrunkConfig(
        hidden=model["hidden_size"], heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        layers=model["num_hidden_layers"], experts=model["num_routed_experts"], experts_per_token=model["num_experts_per_tok"],
        expert_width=model["moe_intermediate_size"], rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
        router_score="softmax", route_norm=True, held_experts=(model["first_held_expert"], model["num_experts"]),
        balance_rate=model["load_balance_coeff"], recompute_experts=bool(config["train"]["recompute_experts"]), block_length=model["block_length"],
    )


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    train = config["train"]
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    cfg = trunk_config(config)
    NOISE.update(block_length=cfg.block_length, t_min=float(config["model"]["t_min"]))
    rate = optax.linear_schedule(0.0, train["learning_rate"], int(train["warmup_steps"]))
    trainer = NoisedSettledTrainer(cfg, {**train["settle"], "batch": train["batch"]}, int(train["warmup_steps"]),
                                   optimizer=optax.adamw(rate, weight_decay=train["weight_decay"]), value_weight=train["value_weight"],
                                   denoise_weight=train["denoise_weight"])
    trainer.noise = dict(NOISE)
    return trainer


def noise_of(idx: np.ndarray) -> Dict[str, np.ndarray]:
    """The noise of the boards at pool rows ``idx``, from the program's maker under a generator made of the rows themselves."""
    rng = np.random.default_rng([0x626C6F636B, *(int(i) for i in idx)])
    block_level, square_masked = block_noise(rng, len(idx), NOISE["block_length"], NOISE["t_min"])
    return {"block_level": block_level, "square_masked": square_masked}


def build_batch(pool: Dict[str, np.ndarray], idx: np.ndarray) -> Dict[str, np.ndarray]:
    """The dense arrays ``AzTrainer.step`` takes of a block-diffusion trunk, for pool rows ``idx``: the AlphaZero family's three and the noise's two."""
    return {**az_batch(pool, idx), **noise_of(idx)}


class NoisedSettledTrainer(afmoe_trunk.SettledTrainer):
    """The second trunk's ``SettledTrainer`` (its schedule, its ``train.settle``, its balance rule) whose balance passes are the TRAINING
    forward: both copies of every board under noise drawn as a batch's is (module docstring)."""

    def init(self, seed: int = 0) -> AzTrainState:
        return balanced(self, AzTrainer.init(self, seed), seed)


def balanced(trainer: NoisedSettledTrainer, state: AzTrainState, seed: int) -> AzTrainState:
    import sys

    import jax
    import jax.numpy as jnp

    from benchmark import positions
    from fishnet_tpu.models.trunk import balanced_bias, trunk_forward_counted

    same_noise(trainer)
    settle, cfg = trainer.settle, trainer.cfg
    traffic = Registry(Path(__file__).resolve().parents[2]).traffic(settle["traffic"])
    planes = positions.playout_pool(traffic, seed, sys.modules[__name__], int(settle["positions"]))["planes"]
    rng = np.random.default_rng([int(seed), 0x736574])
    slots = jax.jit(lambda params, bias, batch, masked: trunk_forward_counted({**params, BUFFER: bias}, batch, cfg, masked)[2]["expert_slots"])
    move = jax.jit(balanced_bias)
    bias = state.buffers[BUFFER]
    for rate in np.geomspace(float(settle["rate_first"]), float(settle["rate_last"]), int(settle["balance_passes"])):
        idx = rng.integers(0, len(planes), int(settle["batch"]))
        bias = move(bias, slots(state.params, bias, jnp.asarray(planes[idx]), jnp.asarray(noise_of(idx)["square_masked"])), jnp.float32(rate))
    return AzTrainState(state.params, state.opt_state, state.step, {**state.buffers, BUFFER: bias})
