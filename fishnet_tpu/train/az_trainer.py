"""Sharded training step for the AlphaZero-style policy+value net.

Companion to trainer.py (the NNUE trainer): one jitted function advances
(params, opt_state) one step on a sharded microbatch. The conv tower's
parameters are small relative to its activations, so parallelism is pure
data-parallel over the ``data`` mesh axis (gradients all-reduce over
``data``, inserted by XLA); the tower's channel dimension is sharded over
``model`` only for the stem/residual weights when the mesh has a model
axis, which keeps the same (data, model) mesh shape the NNUE trainer
uses so both families train on one mesh layout.

Loss is the AlphaZero recipe: cross-entropy between the policy head and
MCTS visit-count targets, MSE between the value head and the game
outcome (or a teacher value), plus weight decay via the optimizer. A
block-diffusion trunk (``TrunkConfig.block_length``) adds the denoising
loss of its noised copy: a batch then carries its noise
(``train/data.py block_noise``), and the step draws nothing. A looped
trunk (``TrunkConfig.loop_steps`` over 1) exits after every pass, and its
loss is the expected loss under the exit distribution its gates give, less
an entropy term (``_expected_exit_terms``).

The reference has no training subsystem at all (SURVEY.md §2: nets are
opaque embedded blobs); training being first-class here is what lets the
framework produce the very nets its engines serve.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fishnet_tpu.models.az import AzConfig, NetConfig, az_checkpoint, az_forward_counted, init_az_buffers, init_az_params
from fishnet_tpu.models.az_encoding import PIECE_PLANES
from fishnet_tpu.models.trunk import KERNEL_OPERANDS, TrunkConfig, attention_heads_paired, balanced_bias, exit_log_distribution
from fishnet_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from fishnet_tpu.train import startup, step_metrics
from fishnet_tpu.train.trainer import _constrain
from fishnet_tpu.utils import compile_cache

Batch = Dict[str, jax.Array]
# keys: planes float32 [B,8,8,19]; policy_target float32 [B,4672]
#       (normalized visit counts, zero off legal moves);
#       value_target float32 [B] in [-1, 1]; for a block-diffusion trunk
#       also block_level float32 [B, 64 / L] and square_masked bool [B,64].


class AzTrainState(NamedTuple):
    params: Dict[str, jax.Array]
    opt_state: optax.OptState
    step: jax.Array
    #: What the network reads beside its parameters and the optimizer
    #: never sees (no moments, no weight decay): a balancing trunk's
    #: ``expert_bias``, which the step moves by its own routing counts.
    buffers: Dict[str, jax.Array] = {}


def az_param_spec(name: str, value: jax.Array) -> P:
    """Shard conv kernels' output-channel dim over ``model``; replicate
    biases, the small heads and every tensor of the sparse-expert trunk
    (its experts go over chips with ROADMAP R7)."""
    if name.endswith(("_w1", "_w2")) or name == "stem_w":
        return P(None, None, None, MODEL_AXIS)
    return P()


def _client_default(device: jax.Device, dtype: Any, shape: Tuple[int, ...]) -> Layout:
    """The layout ``device``'s client gives an array of this shape left to itself."""
    return Layout.from_pjrt_layout(device.client.get_default_layout(dtype, shape, device))


def held_layouts(params: Dict[str, Any], mesh: Optional[Mesh], device: jax.Device) -> Dict[str, Layout]:
    """The layout ``device``'s client holds each kernel operand of
    ``params`` (arrays or their shapes) in, where that is not row-major:
    the leaves the step's update has to follow.

    A kernel's operands are row-major by contract (``models/trunk.py
    _row_major`` says the same of the residual stream), so the leaf's
    gradient arrives row-major and XLA, left to itself, runs AdamW on it
    row-major: where the client stores the leaf otherwise (a TPU holds
    ``f32[.., 2688, 1856]`` with 2,688 on the lanes, because 1,856 is
    14.5 lane tiles) the weight and its two moments are then copied whole
    on the way into the step and, donated, on the way out of it, every
    step, under no scope. Every other leaf is XLA's own products' and
    they already run in whatever the client chose. On the CPU, and
    wherever the minor width is whole lanes, row-major is the default and
    nothing is listed.

    The state itself stays in the client's default at every program's
    boundary: an executable whose arguments or results have another
    layout does not survive the persistent compile cache on jax 0.9.0
    (PERF.md section 6, PR 45)."""
    held = {}
    for name in KERNEL_OPERANDS:
        if name in params:
            leaf = params[name]
            shape = leaf.shape if mesh is None else NamedSharding(mesh, az_param_spec(name, leaf)).shard_shape(leaf.shape)
            default = _client_default(device, leaf.dtype, shape).major_to_minor
            if default != tuple(range(leaf.ndim)):
                held[name] = Layout(major_to_minor=default)
    return held


def az_batch_specs() -> Dict[str, P]:
    return {
        "planes": P(DATA_AXIS),
        "policy_target": P(DATA_AXIS),
        "value_target": P(DATA_AXIS),
        "block_level": P(DATA_AXIS),  # a block-diffusion trunk's noise, a board's with its board
        "square_masked": P(DATA_AXIS),
    }


def _denoise_terms(logits: jax.Array, batch: Batch, block_length: int) -> Dict[str, jax.Array]:
    """A block-diffusion trunk's third term and its two counters, float32:
    the cross-entropy of the denoiser's ``logits`` [B, 64, 13] with a
    square's class (empty, or its piece plane) on the MASKED squares, each
    weighted by 1 / its block's level, over ALL B x 64 squares (the
    MDLM / BD3-LM objective under the linear schedule); the squares masked
    in the batch; the mean level."""
    pieces = batch["planes"].reshape(logits.shape[0], -1, batch["planes"].shape[-1])[..., :PIECE_PLANES]
    square_class = jnp.where(jnp.any(pieces > 0, axis=-1), 1 + jnp.argmax(pieces, axis=-1), 0)
    cross_entropy = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), square_class[..., None], axis=-1)[..., 0]
    masked = batch["square_masked"].astype(jnp.float32)
    level = jnp.repeat(batch["block_level"], block_length, axis=1)  # a square's is its block's
    return {"denoise_loss": jnp.mean(masked * cross_entropy / level), "masked_squares": jnp.sum(masked), "noise_level_mean": jnp.mean(batch["block_level"])}


def _expected_exit_terms(policy: jax.Array, value: jax.Array, gate_logits: jax.Array, value_weight: float, entropy_weight: float) -> Dict[str, jax.Array]:
    """A looped trunk's loss and its counters, float32, from every pass's
    two terms a board (``policy``, ``value`` ``[T, B]``: the cross-entropy
    and the squared error of that pass's heads) and the gates' logits ``[T,
    B]``: with ``p`` the exit distribution a board
    (``models/trunk.py exit_log_distribution``) and ``l_t = policy_t +
    value_weight x value_t``, the loss is the mean over boards of ``sum_t
    p_t l_t - entropy_weight x H(p)`` (the expected loss under the learned
    exits, and an entropy term against a gate that collapses onto one
    pass: arXiv:2510.25741, the first training stage). ``policy_loss`` and
    ``value_loss`` are the expected terms; ``exit_step_mean`` is ``sum_t t
    p_t`` in [1, T]; ``loss_first_pass`` and ``loss_last_pass`` are ``l_1``
    and ``l_T``, what iterating bought."""
    log_p = exit_log_distribution(gate_logits)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    expected_policy, expected_value = jnp.mean(jnp.sum(p * policy, axis=0)), jnp.mean(jnp.sum(p * value, axis=0))
    each = jnp.mean(policy + value_weight * value, axis=1)  # [T]: a pass's loss, were every board served from it
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None]
    return {"loss": expected_policy + value_weight * expected_value - entropy_weight * jnp.mean(entropy),
            "policy_loss": expected_policy, "value_loss": expected_value,
            "exit_step_mean": jnp.mean(jnp.sum(steps * p, axis=0)), "exit_entropy": jnp.mean(entropy),
            "loss_first_pass": each[0], "loss_last_pass": each[-1]}


def _constrain_params(params, mesh: Optional[Mesh]):
    specs = {k: az_param_spec(k, v) for k, v in params.items()}
    return _constrain(params, specs, mesh)


class AzTrainer:
    """Owns optimizer + jitted step. ``mesh=None`` runs single-device."""

    def __init__(
        self,
        cfg: NetConfig = AzConfig(),
        mesh: Optional[Mesh] = None,
        learning_rate: float = 2e-3,
        value_weight: float = 1.0,
        optimizer: Optional[optax.GradientTransformation] = None,
        denoise_weight: float = 1.0,
        exit_entropy_weight: float = 0.1,
    ) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.value_weight = value_weight
        self.denoise_weight = denoise_weight  # of a block-diffusion trunk's third term; no other net has it
        self.exit_entropy_weight = exit_entropy_weight  # of a looped trunk's entropy term; no other net has it
        self.optimizer = optimizer or optax.adamw(learning_rate, weight_decay=1e-4)
        compile_cache.configure()  # before the first jit
        self._hold_on(jax.devices()[0] if mesh is None else mesh.devices.flat[0])
        self._init_jit = jax.jit(self._init)
        self._step_jit = jax.jit(self._step, donate_argnums=(0,))
        self._record = step_metrics.STEPS.attach("az")

    def _hold_on(self, device: jax.Device) -> None:
        """Ask ``device``'s client (the mesh's first where there is a
        mesh) how it holds the state this trainer makes: what ``_step``
        follows, and what ``init``'s span reports of it (the leaves of
        those names, moments included, and their bytes)."""
        state = jax.eval_shape(self._init, jax.random.PRNGKey(0))
        self._held = held_layouts(state.params, self.mesh, device)
        followed = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0] if getattr(path[-1], "key", None) in self._held]
        self._held_fields = {"layout_held_leaves": len(followed), "layout_held_bytes": sum(leaf.size * leaf.dtype.itemsize for leaf in followed)}
        # what a trunk's plan says, static as the layouts are: the share of its attention cores' query heads that go two a product, the times
        # the plan is walked a forward pass and the layer passes that makes
        self._plan_fields = {"attention_heads_paired": attention_heads_paired(self.cfg), "loop_steps": self.cfg.loop_steps,
                             "layer_passes": self.cfg.loop_steps * self.cfg.layers} if isinstance(self.cfg, TrunkConfig) else {}

    # -- jitted bodies ----------------------------------------------------

    def _init(self, rng: jax.Array) -> AzTrainState:
        params = init_az_params(rng, self.cfg)
        params = _constrain_params(params, self.mesh)
        opt_state = self.optimizer.init(params)
        return AzTrainState(params, opt_state, jnp.zeros((), jnp.int32), init_az_buffers(self.cfg))

    def _loss(self, params, batch: Batch, buffers: Dict[str, jax.Array] = {}) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        # forward / loss / optimizer: the scope contract both trainers
        # share (doc/observability.md "Training and compilation").
        denoising = bool(getattr(self.cfg, "block_length", 0))  # the batch then carries its noise, and a batch without it is an error
        with jax.named_scope("forward"):
            # ``after``: what follows the counters: a block-diffusion trunk's denoiser's logits, a looped trunk's exit gates' logits
            logits, value, counters, *after = az_forward_counted({**params, **buffers}, batch["planes"], self.cfg,
                                                                    batch["square_masked"] if denoising else None)
        if getattr(self.cfg, "loop_steps", 1) > 1:  # heads [T, B, ..]: the same two terms a pass and a board, then their expectation over the exits
            with jax.named_scope("loss"):
                policy = -jnp.sum(batch["policy_target"] * jax.nn.log_softmax(logits, axis=-1), axis=-1)
                squared = (value - batch["value_target"]) ** 2
                with jax.named_scope("exit"):
                    terms = _expected_exit_terms(policy, squared, after[0], self.value_weight, self.exit_entropy_weight)
            return terms["loss"], {**terms, **counters}
        with jax.named_scope("loss"):
            target = batch["policy_target"]
            # Masked cross-entropy: zero-probability targets (illegal moves)
            # contribute nothing; log-softmax over the full policy space.
            logp = jax.nn.log_softmax(logits, axis=-1)
            policy_loss = -jnp.mean(jnp.sum(target * logp, axis=-1))
            value_loss = jnp.mean((value - batch["value_target"]) ** 2)
            loss = policy_loss + self.value_weight * value_loss
            denoised = {}
            if denoising:
                with jax.named_scope("denoise"):
                    denoised = _denoise_terms(after[0], batch, self.cfg.block_length)
                    loss = loss + self.denoise_weight * denoised["denoise_loss"]
        return loss, {
            "loss": loss,
            "policy_loss": policy_loss,
            "value_loss": value_loss,
            **denoised,
            **counters,  # the trunk's routing counters (models/trunk.py trunk_forward_counted)
        }

    def _step(self, state: AzTrainState, batch: Batch):
        batch = _constrain(batch, az_batch_specs(), self.mesh)
        grads, metrics = jax.grad(self._loss, has_aux=True)(state.params, batch, state.buffers)
        slots = metrics.pop("expert_slots", None)  # [routed layers, experts], not a scalar of the step's metrics
        with jax.named_scope("optimizer"):
            # the update of a leaf the client holds off row-major runs in that layout, and neither the weight nor a
            # moment is relaid on the way in or out (held_layouts)
            grads = {k: with_layout_constraint(g, self._held[k]) if k in self._held else g for k, g in grads.items()}
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            buffers = state.buffers
            if "expert_bias" in buffers:
                buffers = {**buffers, "expert_bias": balanced_bias(buffers["expert_bias"], slots, self.cfg.balance_rate)}
        params = _constrain_params(params, self.mesh)
        return AzTrainState(params, opt_state, state.step + 1, buffers), metrics

    # -- public api -------------------------------------------------------

    def init(self, seed: int = 0) -> AzTrainState:
        with startup.init_span("az", **self._held_fields, **self._plan_fields):
            return self._init_jit(jax.random.PRNGKey(seed))

    def step(self, state: AzTrainState, batch: Batch):
        return self._record.run(self._step_jit, state, batch)

    def export(self, state: AzTrainState, path: str) -> None:
        """Save params as the .npz checkpoint --az-net-file consumes."""
        import numpy as np

        np.savez(path, **az_checkpoint({**state.params, **state.buffers}, self.cfg))
