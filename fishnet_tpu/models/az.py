"""AlphaZero-style policy+value residual network, TPU-shaped.

A conv tower over 8x8x19 input planes with two heads: a 73-plane policy
(4672 logits, az_encoding.py) and a tanh value in [-1, 1] from the side
to move's perspective. The reference has no neural policy/value path at
all (its engines are alpha-beta C++); this family exists for the
batched-PUCT MCTS engine of BASELINE.json config 5.

TPU shaping choices:

* compute in bfloat16 (MXU-native), parameters in float32;
* NHWC layout with channel counts that are multiples of 8 so XLA tiles
  convs onto the MXU without padding waste;
* no batch norm at inference — the net uses pre-activation residual
  blocks with simple bias (training-time normalization is folded in), so
  the whole forward is a fusion-friendly chain of conv+add+relu;
* everything under one ``jax.jit`` with static shapes: the MCTS engine
  always evaluates fixed-capacity microbatches, padding short batches.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from fishnet_tpu.models.az_encoding import INPUT_PLANES, POLICY_SIZE
from fishnet_tpu.models.heads import conv2d as _conv2d
from fishnet_tpu.models.heads import policy_value_heads
from fishnet_tpu.models.trunk import (
    TrunkConfig,
    init_trunk_params,
    trunk_buffer_shapes,
    trunk_checkpoint,
    trunk_config_from_params,
    trunk_forward,
    trunk_forward_counted,
)

Params = Dict[str, jax.Array]


@dataclass(frozen=True)
class AzConfig:
    channels: int = 64
    blocks: int = 6
    value_hidden: int = 128
    policy_planes: int = 73

    @property
    def policy_size(self) -> int:
        return 64 * self.policy_planes


#: A network behind ``az_forward``: the conv tower or the sparse-expert trunk.
NetConfig = Union[AzConfig, TrunkConfig]


def init_az_params(rng: jax.Array, cfg: NetConfig = AzConfig()) -> Params:
    if isinstance(cfg, TrunkConfig):
        return init_trunk_params(rng, cfg)
    c = cfg.channels
    keys = jax.random.split(rng, 4 + 2 * cfg.blocks)

    def conv(key, cin, cout, k=3):
        scale = np.sqrt(2.0 / (k * k * cin))
        return jax.random.normal(key, (k, k, cin, cout), jnp.float32) * scale

    params: Params = {
        "stem_w": conv(keys[0], INPUT_PLANES, c),
        "stem_b": jnp.zeros((c,), jnp.float32),
        "policy_w": conv(keys[1], c, cfg.policy_planes, k=1),
        "policy_b": jnp.zeros((cfg.policy_planes,), jnp.float32),
        "value_w": conv(keys[2], c, 4, k=1),
        "value_b": jnp.zeros((4,), jnp.float32),
        "value_fc1_w": jax.random.normal(keys[3], (4 * 64, cfg.value_hidden), jnp.float32)
        * np.sqrt(2.0 / (4 * 64)),
        "value_fc1_b": jnp.zeros((cfg.value_hidden,), jnp.float32),
        "value_fc2_w": jnp.zeros((cfg.value_hidden, 1), jnp.float32),
        "value_fc2_b": jnp.zeros((1,), jnp.float32),
    }
    for i in range(cfg.blocks):
        params[f"res{i}_w1"] = conv(keys[4 + 2 * i], c, c)
        params[f"res{i}_b1"] = jnp.zeros((c,), jnp.float32)
        params[f"res{i}_w2"] = conv(keys[5 + 2 * i], c, c)
        params[f"res{i}_b2"] = jnp.zeros((c,), jnp.float32)
    return params


def init_az_buffers(cfg: NetConfig = AzConfig()) -> Params:
    """What a training state holds beside the parameters, outside the
    optimizer: a balancing trunk's zero ``expert_bias``, else nothing."""
    shapes = trunk_buffer_shapes(cfg) if isinstance(cfg, TrunkConfig) else {}
    return {name: jnp.zeros(shape, jnp.float32) for name, shape in shapes.items()}


def az_forward(params: Params, planes: jax.Array, cfg: NetConfig = AzConfig()):
    """planes [B, 8, 8, 19] -> (policy_logits [B, 4672], value [B]).

    The configuration's type selects the network: an ``AzConfig`` the
    conv tower below, a ``TrunkConfig`` the sparse-expert trunk
    (``models/trunk.py``). Compute runs in bfloat16; logits/value are
    returned in float32. This is what is SERVED: a looped trunk's answer
    is a position's pass by its exit rule (``trunk_forward``), where the
    counted forward below returns every pass's.
    """
    if isinstance(cfg, TrunkConfig):
        return trunk_forward(params, planes, cfg)
    return az_forward_counted(params, planes, cfg)[:2]


def az_forward_counted(params: Params, planes: jax.Array, cfg: NetConfig = AzConfig(), square_masked: Optional[jax.Array] = None):
    """``az_forward`` and the network's counters for the training step's
    metrics: none for the tower, the routing counters for the trunk. Told
    a batch's ``square_masked`` (a block-diffusion trunk's training
    forward) the denoiser's logits follow the counters; a looped trunk's
    heads are every pass's, ``[T, B, ..]``, and its exit gates' logits
    ``[T, B]`` follow the counters."""
    if isinstance(cfg, TrunkConfig):
        return trunk_forward_counted(params, planes, cfg, square_masked)
    if square_masked is not None:
        raise ValueError("square_masked is a block-diffusion trunk's: the tower has no denoiser")
    return (*policy_value_heads(params, _tower(params, planes, cfg)), {})


def _tower(params: Params, planes: jax.Array, cfg: AzConfig) -> jax.Array:
    x = planes.astype(jnp.bfloat16)
    # Scope names are a contract (doc/observability.md "Training and
    # compilation"): the benchmark's phase metrics join on them.
    with jax.named_scope("stem"):
        x = jax.nn.relu(_conv2d(x, params["stem_w"], params["stem_b"]))
    for i in range(cfg.blocks):
        with jax.named_scope(f"block{i:02d}"):
            h = jax.nn.relu(_conv2d(x, params[f"res{i}_w1"], params[f"res{i}_b1"]))
            h = _conv2d(h, params[f"res{i}_w2"], params[f"res{i}_b2"])
            x = jax.nn.relu(x + h)
    return x


def az_checkpoint(params: Params, cfg: NetConfig) -> Dict[str, np.ndarray]:
    """The arrays of the ``.npz`` that --az-net-file takes."""
    if isinstance(cfg, TrunkConfig):
        return trunk_checkpoint(params, cfg)
    return {k: np.asarray(v) for k, v in params.items()}


def value_to_centipawns(v: float) -> int:
    """Map a [-1, 1] value-head output to centipawns for the fishnet
    protocol (the same tan mapping family Lc0 uses for UCI output)."""
    v = float(np.clip(v, -0.9999, 0.9999))
    return int(round(111.7 * np.tan(1.5620688421 * v)))


def az_config_from_params(params: Params) -> NetConfig:
    """Recover the architecture a checkpoint was trained with.

    Every AzConfig field is determined by parameter shapes, so `.npz`
    checkpoints need no architecture metadata; loading a net trained with
    a non-default config (--az-net-file) reconstructs the right config
    instead of crashing shape-mismatched inside the jitted forward. A
    trunk checkpoint is told from a tower's by its router (one product,
    or the fifth block's MLP: the nine routed trunks' files, as always)
    or, a trunk of dense layers alone having none, by the embedding and
    the final norm every trunk has and no tower does; an error names the
    tensor that told.
    """
    if "router_w" in params or "router_down" in params or ("embed_w" in params and "final_norm" in params):
        return trunk_config_from_params(params)
    required = ("stem_b", "policy_b", "value_fc1_b")
    missing = [k for k in required if k not in params]
    if missing:
        raise ValueError(
            f"not an AZ checkpoint (read as a tower's: it has no router_w or router_down, and not both embed_w and final_norm, a trunk's): "
            f"missing parameter(s) {missing}; got keys {sorted(params)[:8]}..."
        )
    blocks = 0
    while f"res{blocks}_w1" in params:
        blocks += 1
    cfg = AzConfig(
        channels=int(np.shape(params["stem_b"])[0]),
        blocks=blocks,
        value_hidden=int(np.shape(params["value_fc1_b"])[0]),
        policy_planes=int(np.shape(params["policy_b"])[0]),
    )
    # eval_shape: shape-only abstract trace, no device traffic — this runs
    # at client startup where the default backend may be a TPU.
    shapes = jax.eval_shape(lambda: init_az_params(jax.random.PRNGKey(0), cfg))
    expected = {k: v.shape for k, v in shapes.items()}
    got = {k: tuple(np.shape(v)) for k, v in params.items()}
    if {k: tuple(v) for k, v in expected.items()} != got:
        diff = {k for k in set(expected) ^ set(got)} or {
            k for k in expected if tuple(expected[k]) != got.get(k)
        }
        raise ValueError(
            f"AZ checkpoint does not match any {cfg}: mismatched keys {sorted(diff)}"
        )
    return cfg
