"""Adapter for the AlphaZero family: the program's ``AzTrainer`` and
encoders behind the calls the ``train_step`` runner makes."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from fishnet_tpu.models import az_encoding
from fishnet_tpu.models.az import AzConfig
from fishnet_tpu.train.az_trainer import AzTrainer, AzTrainState

MAX_MOVES = 218  # the most legal moves a chess position can have
POLICY = az_encoding.POLICY_SIZE


def make_trainer(config: Dict[str, Any]) -> AzTrainer:
    model, train = config["model"], config["train"]
    if model["input_planes"] != az_encoding.INPUT_PLANES:
        raise ValueError("the program encodes %d input planes" % az_encoding.INPUT_PLANES)
    if train["optimizer"] != "adamw" or train["weight_decay"] != 1e-4:
        raise ValueError("AzTrainer's optimizer is AdamW with weight decay 1e-4")
    cfg = AzConfig(
        channels=model["channels"], blocks=model["blocks"],
        value_hidden=model["value_hidden"], policy_planes=model["policy_planes"],
    )
    return AzTrainer(cfg, learning_rate=train["learning_rate"], value_weight=train["value_weight"])


def _move_table() -> Dict[bool, Dict[str, int]]:
    """uci -> policy index for each side to move, from the program's own
    ``move_to_index`` (a table because the pool holds ~2 M moves)."""
    squares = [f + r for r in "12345678" for f in "abcdefgh"]
    table: Dict[bool, Dict[str, int]] = {True: {}, False: {}}
    for white in (True, False):
        for a in squares:
            for b in squares:
                for promo in ("", "q", "n", "b", "r"):
                    try:
                        table[white][a + b + promo] = az_encoding.move_to_index(a + b + promo, white)
                    except ValueError:
                        pass
    return table


class PoolEncoder:
    def __init__(self, n_positions: int) -> None:
        self.planes = np.zeros((n_positions, 8, 8, az_encoding.INPUT_PLANES), np.float32)
        self.moves = np.zeros((n_positions, MAX_MOVES), np.int32)
        self.n_moves = np.zeros((n_positions,), np.int32)
        self.stm_white: List[bool] = []
        self._table = _move_table()

    def add(self, board: Any, fen: str, moves: List[str]) -> None:
        i = len(self.stm_white)
        white = board.turn() == "w"
        self.planes[i] = az_encoding.board_planes(fen)
        table = self._table[white]
        self.moves[i, : len(moves)] = [table[m] for m in moves]
        self.n_moves[i] = len(moves)
        self.stm_white.append(white)

    def finish(self, white_scores: np.ndarray, rng: np.random.Generator, traffic: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Targets from the seed: a Dirichlet over each position's legal
        moves, and the playout's result from the side to move."""
        n = len(self.stm_white)
        legal = np.arange(MAX_MOVES)[None, :] < self.n_moves[:n, None]
        probs = rng.gamma(float(traffic["az_dirichlet_alpha"]), size=(n, MAX_MOVES)).astype(np.float32)
        probs = np.where(legal, np.maximum(probs, 1e-12), 0.0)
        probs /= probs.sum(axis=1, keepdims=True)
        sign = np.where(np.asarray(self.stm_white), 1.0, -1.0)
        return {
            "planes": self.planes[:n],
            "moves": self.moves[:n],
            "legal": legal,
            "probs": probs.astype(np.float32),
            "value_target": ((2.0 * white_scores - 1.0) * sign).astype(np.float32),
        }


def build_batch(pool: Dict[str, np.ndarray], idx: np.ndarray) -> Dict[str, np.ndarray]:
    """The dense arrays ``AzTrainer.step`` takes, for pool rows ``idx``."""
    legal = pool["legal"][idx]
    flat = (np.arange(len(idx), dtype=np.int64)[:, None] * POLICY + pool["moves"][idx])[legal]
    policy = np.zeros((len(idx), POLICY), np.float32)
    policy.reshape(-1)[flat] = pool["probs"][idx][legal]
    return {
        "planes": pool["planes"][idx],
        "policy_target": policy,
        "value_target": pool["value_target"][idx],
    }


def loss_and_grads(trainer: AzTrainer):
    """``jax.value_and_grad`` of the trainer's own loss."""
    import jax

    def fn(params, batch):
        (loss, _aux), grads = jax.value_and_grad(trainer._loss, has_aux=True)(params, batch)
        return loss, grads

    return jax.jit(fn)


def state_from_params(trainer: AzTrainer, params: Dict[str, Any]) -> AzTrainState:
    import jax.numpy as jnp

    params = {k: jnp.array(v) for k, v in params.items()}
    return AzTrainState(params, trainer.optimizer.init(params), jnp.zeros((), jnp.int32))


def step_hlo_text(trainer: AzTrainer, state: AzTrainState, batch: Dict[str, Any]) -> str:
    """The compiled step program's text, for classifying traced operations."""
    return trainer._step_jit.lower(state, batch).compile().as_text()
