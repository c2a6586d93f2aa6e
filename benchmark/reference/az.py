"""Plain reference for the AlphaZero family: forward, loss and AdamW.

Written from the published description (Silver et al. 2018: a 3x3 stem,
residual blocks of two 3x3 convolutions with a skip connection, a policy
head over 8x8x73 move planes and a tanh value head; loss = policy
cross-entropy + value squared error) with this repo's stated departures
(configs/az-256x19-train.json ``assumed``: no batch norm, 1x1 policy
head, 4-filter value head). ``jax.numpy`` only: a convolution is one matrix
product over each square's neighbours. It imports nothing of the program; parameters
carry the names of the program's public ``.npz`` checkpoint format.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]


def init_params(seed: int, model: Dict[str, int]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed: He-normal kernels, small
    non-zero biases and a non-zero last layer, so every tensor has a
    gradient to compare. Each block's second kernel is scaled by
    1/sqrt(blocks): with no normalisation in the net, plain He kernels
    double the activations' variance at every block, the heads saturate,
    and the comparison would be of rounding noise on vanishing gradients.
    The value head's last layer is small and its bias is not, so tanh
    sits at +-0.3 to 0.6: a pre-activation that is a sum of cancelling
    terms near 0, against targets that are mostly 0 (drawn playouts),
    makes the value loss's gradient 2 (v - t) (1 - v^2) the rounding
    error of v itself, in some seeds and not in others."""
    rng = np.random.default_rng([int(seed), 0x617A])
    c, planes = model["channels"], model["input_planes"]

    def kernel(k: int, cin: int, cout: int) -> np.ndarray:
        return (rng.standard_normal((k, k, cin, cout)) * np.sqrt(2.0 / (k * k * cin))).astype(np.float32)

    def bias(n: int) -> np.ndarray:
        return (rng.standard_normal(n) * 0.05).astype(np.float32)

    hidden = model["value_hidden"]
    params = {
        "stem_w": kernel(3, planes, c), "stem_b": bias(c),
        "policy_w": kernel(1, c, model["policy_planes"]), "policy_b": bias(model["policy_planes"]),
        "value_w": kernel(1, c, 4), "value_b": bias(4),
        "value_fc1_w": (rng.standard_normal((256, hidden)) * np.sqrt(2.0 / 256)).astype(np.float32),
        "value_fc1_b": bias(hidden),
        "value_fc2_w": (rng.standard_normal((hidden, 1)) * 0.1 * np.sqrt(1.0 / hidden)).astype(np.float32),
        "value_fc2_b": (rng.choice([-1.0, 1.0], 1) * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }
    for i in range(model["blocks"]):
        params[f"res{i}_w1"], params[f"res{i}_b1"] = kernel(3, c, c), bias(c)
        params[f"res{i}_w2"] = kernel(3, c, c) / np.float32(np.sqrt(model["blocks"]))
        params[f"res{i}_b2"] = bias(c)
    return params


def _conv(x: jax.Array, w: jax.Array, b: jax.Array, cast: Cast, grad_cast: Cast) -> jax.Array:
    """'Same' convolution over the 8x8 board as one matrix product: each
    square's k*k neighbours (zeros off the board) laid side by side,
    times the kernel flattened the same way."""
    k, _, cin, cout = w.shape
    pad = k // 2
    x, w = cast(x), cast(w)
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, dy:dy + 8, dx:dx + 8, :] for dy in range(k) for dx in range(k)], axis=-1
    )
    return grad_cast(jnp.einsum("bhwc,cd->bhwd", patches, w.reshape(k * k * cin, cout))) + cast(b)


def forward(params: Params, planes: jax.Array, model: Dict[str, int], cast: Cast, grad_cast: Cast):
    x = jax.nn.relu(_conv(planes, params["stem_w"], params["stem_b"], cast, grad_cast))

    def block(x, p):
        h = jax.nn.relu(_conv(x, p["w1"], p["b1"], cast, grad_cast))
        h = _conv(h, p["w2"], p["b2"], cast, grad_cast)
        return jax.nn.relu(x + h), None

    # One scanned block, not `blocks` unrolled copies: the same arithmetic,
    # a nineteenth of the program to compile.
    stacked = {k: jnp.stack([params[f"res{i}_{k}"] for i in range(model["blocks"])]) for k in ("w1", "b1", "w2", "b2")}
    x, _ = jax.lax.scan(block, x, stacked)
    policy = _conv(x, params["policy_w"], params["policy_b"], cast, grad_cast)
    logits = policy.reshape(policy.shape[0], -1).astype(jnp.float32)  # (square, plane) order
    v = jax.nn.relu(_conv(x, params["value_w"], params["value_b"], cast, grad_cast))
    v = v.reshape(v.shape[0], -1)
    v = jax.nn.relu(grad_cast(cast(v) @ cast(params["value_fc1_w"])) + cast(params["value_fc1_b"]))
    v = jnp.tanh(grad_cast(cast(v) @ cast(params["value_fc2_w"])) + cast(params["value_fc2_b"]))
    return logits, v[:, 0].astype(jnp.float32)


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    logits, value = forward(params, batch["planes"], config["model"], cast_for(precision), grad_cast_for(precision))
    log_p = jax.nn.log_softmax(logits, axis=-1)
    policy_loss = -jnp.mean(jnp.sum(batch["policy_target"] * log_p, axis=-1))
    value_loss = jnp.mean((value - batch["value_target"]) ** 2)
    return policy_loss + config["train"]["value_weight"] * value_loss


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` AdamW updates on one batch, with
    ``grad(params, batch) -> (loss, gradients)`` of this module's ``loss``
    (Loshchilov & Hutter: decoupled weight decay; b1 0.9, b2 0.999, eps 1e-8)."""
    train = config["train"]
    lr, wd, b1, b2, eps = train["learning_rate"], train["weight_decay"], 0.9, 0.999, 1e-8
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses = []
    for t in range(1, steps + 1):
        value, g = grad(params, batch)
        losses.append(value)
        for k in params:
            gk = g[k].astype(jnp.float32)
            mu[k] = b1 * mu[k] + (1 - b1) * gk
            nu[k] = b2 * nu[k] + (1 - b2) * gk * gk
            step = (mu[k] / (1 - b1 ** t)) / (jnp.sqrt(nu[k] / (1 - b2 ** t)) + eps)
            params[k] = params[k] - lr * (step + wd * params[k])
    return losses
