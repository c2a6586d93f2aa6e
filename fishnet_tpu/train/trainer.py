"""Sharded NNUE training step.

One jitted function advances (params, opt_state) one step on a sharded
microbatch. Parallelism is annotation-driven (GSPMD): the feature
transformer is tensor-parallel over the ``model`` mesh axis (its L1
columns are the only big dimension in the net) and the batch is
data-parallel over ``data``; gradients all-reduce over ``data`` and the
l1 matmul's contraction psums over ``model``, all inserted by XLA.

Loss (standard NNUE recipe): squared error in WDL space between
sigmoid(pred_cp / SIGMOID_SCALE) and an interpolation of the teacher
score and the game outcome.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fishnet_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from fishnet_tpu.train import model as model_lib
from fishnet_tpu.train import startup, step_metrics
from fishnet_tpu.train.model import NNUE2SCORE, NetConfig, Params
from fishnet_tpu.utils import compile_cache

SIGMOID_SCALE = 410.0  # cp -> expected-score squash

Batch = Dict[str, jax.Array]
# keys: indices int32 [B,2,A]; buckets int32 [B];
#       score_cp float32 [B] (teacher eval); outcome float32 [B] in {0,.5,1}


class TrainState(NamedTuple):
    params: Params
    opt_state: optax.OptState
    step: jax.Array


def param_specs() -> Dict[str, P]:
    """PartitionSpec per parameter. Only tensors with an L1 dimension are
    sharded — everything else is small enough to replicate."""
    return {
        "ft_w": P(None, MODEL_AXIS),
        "ft_b": P(MODEL_AXIS),
        "ft_psqt": P(),
        "l1_w": P(None, None, MODEL_AXIS),
        "l1_b": P(),
        "l2_w": P(),
        "l2_b": P(),
        "out_w": P(),
        "out_b": P(),
    }


def batch_specs() -> Dict[str, P]:
    return {
        "indices": P(DATA_AXIS),
        "buckets": P(DATA_AXIS),
        "score_cp": P(DATA_AXIS),
        "outcome": P(DATA_AXIS),
    }


def _constrain(tree, specs, mesh: Optional[Mesh]):
    if mesh is None:
        return tree
    return {
        k: jax.lax.with_sharding_constraint(v, NamedSharding(mesh, specs[k]))
        for k, v in tree.items()
    }


class Trainer:
    """Owns optimizer + jitted step. ``mesh=None`` runs single-device.

    Index contract: all active indices of one (position, perspective)
    pair lie in one of ``cfg.king_buckets`` equal blocks of the feature
    table (``Board.nnue_features`` output does, for the published 32; a
    net fed arbitrary indices declares 1). The table gradient is a matmul
    per block (``model._table_grad``) and drops an entry outside its
    pair's block; the step's ``ft_block_misses`` metric counts them and
    reads 0 on every batch that keeps the contract."""

    def __init__(
        self,
        cfg: NetConfig = NetConfig(),
        mesh: Optional[Mesh] = None,
        learning_rate: float = 8e-4,
        wdl_lambda: float = 0.75,
        optimizer: Optional[optax.GradientTransformation] = None,
    ) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.wdl_lambda = wdl_lambda
        self.optimizer = optimizer or optax.adam(learning_rate)
        compile_cache.configure()  # before the first jit
        self._init_jit = jax.jit(self._init)
        self._step_jit = jax.jit(self._step, donate_argnums=(0,))
        self._record = step_metrics.STEPS.attach("nnue")

    # -- jitted bodies ----------------------------------------------------

    def _init(self, rng: jax.Array) -> TrainState:
        params = model_lib.init_params(rng, self.cfg)
        params = _constrain(params, param_specs(), self.mesh)
        opt_state = self.optimizer.init(params)
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32))

    def _loss(self, params: Params, batch: Batch) -> Tuple[jax.Array, jax.Array]:
        # forward / loss / optimizer: the scope contract both trainers
        # share (doc/observability.md "Training and compilation").
        with jax.named_scope("forward"):
            pred_cp = (
                model_lib.forward(params, batch["indices"], batch["buckets"], self.cfg)
                * NNUE2SCORE
            )
        with jax.named_scope("loss"):
            q = jax.nn.sigmoid(pred_cp / SIGMOID_SCALE)
            t_score = jax.nn.sigmoid(batch["score_cp"] / SIGMOID_SCALE)
            t = self.wdl_lambda * t_score + (1.0 - self.wdl_lambda) * batch["outcome"]
            loss = jnp.mean(jnp.square(q - t))
        return loss, pred_cp

    def _step(self, state: TrainState, batch: Batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        batch = _constrain(batch, batch_specs(), self.mesh)
        params = _constrain(state.params, param_specs(), self.mesh)
        (loss, pred_cp), grads = jax.value_and_grad(self._loss, has_aux=True)(params, batch)
        grads = _constrain(grads, param_specs(), self.mesh)
        with jax.named_scope("optimizer"):
            updates, opt_state = self.optimizer.update(grads, state.opt_state, params)
            params = optax.apply_updates(params, updates)
            params = model_lib.clip_params(params)
        params = _constrain(params, param_specs(), self.mesh)
        metrics = {
            "loss": loss,
            "pred_cp_mean": jnp.mean(pred_cp),
            "pred_cp_abs": jnp.mean(jnp.abs(pred_cp)),
            "ft_block_misses": model_lib.ft_block_misses(self.cfg, batch["indices"]),
        }
        return TrainState(params, opt_state, state.step + 1), metrics

    # -- public API -------------------------------------------------------

    def init(self, seed: int = 0) -> TrainState:
        with startup.init_span("nnue"):
            if self.mesh is not None:
                with self.mesh:
                    return self._init_jit(jax.random.PRNGKey(seed))
            return self._init_jit(jax.random.PRNGKey(seed))

    def step(self, state: TrainState, batch: Batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        return self._record.run(self._dispatch, state, batch)

    def _dispatch(self, state: TrainState, batch: Batch):
        if self.mesh is not None:
            with self.mesh:
                return self._step_jit(state, batch)
        return self._step_jit(state, batch)

    def export(self, state: TrainState):
        """Quantize trained params into serving weights."""
        params = jax.device_get(state.params)
        return model_lib.quantize(params, self.cfg)
