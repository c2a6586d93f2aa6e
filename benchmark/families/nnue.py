"""Adapter for the NNUE family: the program's ``Trainer`` and feature
extraction behind the calls the ``train_step`` runner makes."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from fishnet_tpu.train.model import NetConfig
from fishnet_tpu.train.trainer import Trainer, TrainState

PIECE_CP = {"p": 100, "n": 320, "b": 330, "r": 500, "q": 900}


def make_trainer(config: Dict[str, Any]) -> Trainer:
    model, train = config["model"], config["train"]
    if train["optimizer"] != "adam":
        raise ValueError("Trainer's optimizer is Adam")
    cfg = NetConfig(
        num_features=model["num_features"], max_active=model["max_active"],
        l1=model["l1"], l2=model["l2"], l3=model["l3"], num_buckets=model["num_buckets"],
    )
    return Trainer(cfg, learning_rate=train["learning_rate"], wdl_lambda=train["wdl_lambda"])


class PoolEncoder:
    def __init__(self, n_positions: int) -> None:
        self.indices = np.zeros((n_positions, 2, 32), np.int32)
        self.buckets = np.zeros((n_positions,), np.int32)
        self.material: List[int] = []
        self.stm_white: List[bool] = []

    def add(self, board: Any, fen: str, moves: List[str]) -> None:
        i = len(self.stm_white)
        self.indices[i], self.buckets[i] = board.nnue_features()
        placement = fen.split(" ", 1)[0]
        white = sum(v * placement.count(p.upper()) for p, v in PIECE_CP.items())
        black = sum(v * placement.count(p) for p, v in PIECE_CP.items())
        self.stm_white.append(board.turn() == "w")
        self.material.append(white - black)

    def finish(self, white_scores: np.ndarray, rng: np.random.Generator, traffic: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Labels from the seed: material plus noise as the teacher's
        score, the playout's result as the outcome, both from the side
        to move."""
        n = len(self.stm_white)
        white = np.asarray(self.stm_white)
        material = np.asarray(self.material, np.float32) * np.where(white, 1.0, -1.0)
        noise = rng.normal(0.0, float(traffic["nnue_score_noise_cp"]), n)
        return {
            "indices": self.indices[:n],
            "buckets": self.buckets[:n],
            "score_cp": (material + noise).astype(np.float32),
            "outcome": np.where(white, white_scores, 1.0 - white_scores).astype(np.float32),
        }


def build_batch(pool: Dict[str, np.ndarray], idx: np.ndarray) -> Dict[str, np.ndarray]:
    """The arrays ``Trainer.step`` takes, for pool rows ``idx``."""
    return {k: pool[k][idx] for k in ("indices", "buckets", "score_cp", "outcome")}


def loss_and_grads(trainer: Trainer):
    """``jax.value_and_grad`` of the trainer's own loss."""
    import jax

    def fn(params, batch):
        (loss, _pred), grads = jax.value_and_grad(trainer._loss, has_aux=True)(params, batch)
        return loss, grads

    return jax.jit(fn)


def state_from_params(trainer: Trainer, params: Dict[str, Any]) -> TrainState:
    import jax.numpy as jnp

    params = {k: jnp.array(v) for k, v in params.items()}
    return TrainState(params, trainer.optimizer.init(params), jnp.zeros((), jnp.int32))


def step_hlo_text(trainer: Trainer, state: TrainState, batch: Dict[str, Any]) -> str:
    """The compiled step program's text, for classifying traced operations."""
    return trainer._step_jit.lower(state, batch).compile().as_text()
