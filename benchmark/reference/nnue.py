"""Plain reference for the NNUE family: forward, loss and Adam.

Written from the published SFNNv5 description (nnue-pytorch ``model.py``
and ``docs/nnue.md``): a feature transformer that sums the active
HalfKAv2_hm rows for each perspective (1024 columns plus 8 PSQT
columns), clipped to [0, 1]; the two halves of each perspective
multiplied pairwise (x 127/128); eight layer stacks chosen by piece
count, each 1024 -> 15+1 -> 32 -> 1 with a squared-clipped and a clipped
copy of the 15 feeding the 32, the 16th output added to the result, and
half the PSQT difference added as material. Loss: squared error between
sigmoid(600 out / 410) and lambda sigmoid(score / 410) + (1 - lambda)
outcome. It imports nothing of the program; parameters carry the names of
the program's float checkpoint. Each sample's layer stack is gathered
first and applied once, where the program computes all eight and selects.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import Cast, cast_for

Params = Dict[str, Any]
OUTPUT_SCALE = 600.0
SIGMOID_SCALE = 410.0
HIDDEN_CLIP = 127.0 / 64.0
OUT_CLIP = 127.0 * 127.0 / (OUTPUT_SCALE * 16.0)


def init_params(seed: int, model: Dict[str, int]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed; every tensor non-zero, so each
    has a gradient to compare. The stacks' hidden units sit at 0.5 +- 0.2
    before their clipping, as the accumulators do: about one in a hundred
    is clipped at either end, so the clipped paths are exercised, and few
    sit so near an edge that the rounding of a matrix product puts them
    on the other side. With units centred on the edge at 0, a handful of
    such flips decide a stack's gradient error, and some seeds read five
    times what the others do."""
    rng = np.random.default_rng([int(seed), 0x6E6E7565])
    f, l1, l2, l3, b = (model[k] for k in ("num_features", "l1", "l2", "l3", "num_buckets"))

    def unif(shape, bound):
        return rng.uniform(-bound, bound, shape).astype(np.float32)

    l1_b = unif((b, l2 + 1), 0.05)
    l1_b[:, :l2] = 0.5 + unif((b, l2), 0.1)  # the 16th output is the skip to the result: left around 0
    return {
        "ft_w": unif((f, l1), 0.05),
        "ft_b": (0.5 + unif((l1,), 0.1)).astype(np.float32),
        "ft_psqt": unif((f, b), 0.02),
        "l1_w": unif((b, l2 + 1, l1), 0.5 * np.sqrt(1.0 / l1)), "l1_b": l1_b,
        "l2_w": unif((b, l3, 2 * l2), 0.5 * np.sqrt(1.0 / (2 * l2))), "l2_b": (0.5 + unif((b, l3), 0.1)).astype(np.float32),
        "out_w": unif((b, 1, l3), np.sqrt(1.0 / l3)), "out_b": unif((b, 1), 0.05),
    }


def forward(params: Params, indices: jax.Array, buckets: jax.Array, model: Dict[str, int], cast: Cast) -> jax.Array:
    features, l1, l2 = model["num_features"], model["l1"], model["l2"]
    active = (indices < features)[..., None]
    safe = jnp.where(indices < features, indices, 0)
    zero = jnp.zeros((), cast(params["ft_w"]).dtype)
    acc = jnp.sum(jnp.where(active, cast(params["ft_w"])[safe], zero), axis=2) + cast(params["ft_b"])
    psqt = jnp.sum(jnp.where(active, cast(params["ft_psqt"])[safe], zero), axis=2)  # [B, 2, buckets]

    clipped = jnp.clip(acc, 0.0, 1.0)
    half = l1 // 2
    pair = clipped[..., :half] * clipped[..., half:] * (127.0 / 128.0)
    x = pair.reshape(pair.shape[0], l1)  # side to move first

    y = jnp.einsum("bi,boi->bo", cast(x), cast(params["l1_w"])[buckets]) + cast(params["l1_b"])[buckets]
    skip, h = y[:, l2], y[:, :l2]
    act = jnp.concatenate([jnp.minimum(h * h * (127.0 / 128.0), 1.0), jnp.clip(h, 0.0, 1.0)], axis=1)
    z = jnp.einsum("bi,boi->bo", cast(act), cast(params["l2_w"])[buckets]) + cast(params["l2_b"])[buckets]
    z = jnp.clip(z, 0.0, 1.0)
    v = jnp.einsum("bi,boi->bo", cast(z), cast(params["out_w"])[buckets])[:, 0] + cast(params["out_b"])[buckets][:, 0]

    own = jnp.take_along_axis(psqt, buckets[:, None, None], axis=2)[..., 0]  # [B, 2]
    return (v + skip + (own[:, 0] - own[:, 1]) * 0.5).astype(jnp.float32)


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    out = forward(params, batch["indices"], batch["buckets"], config["model"], cast_for(precision))
    lam = config["train"]["wdl_lambda"]
    q = jax.nn.sigmoid(out * OUTPUT_SCALE / SIGMOID_SCALE)
    target = lam * jax.nn.sigmoid(batch["score_cp"] / SIGMOID_SCALE) + (1.0 - lam) * batch["outcome"]
    return jnp.mean(jnp.square(q - target))


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` Adam updates on one batch, with
    ``grad(params, batch) -> (loss, gradients)`` of this module's ``loss`` (Kingma
    & Ba; b1 0.9, b2 0.999, eps 1e-8), the stack weights clipped to what
    int8 quantisation can hold after each update, as nnue-pytorch does."""
    lr, b1, b2, eps = config["train"]["learning_rate"], 0.9, 0.999, 1e-8
    clips = {"l1_w": HIDDEN_CLIP, "l2_w": HIDDEN_CLIP, "out_w": OUT_CLIP}
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
            params[k] = params[k] - lr * (mu[k] / (1 - b1 ** t)) / (jnp.sqrt(nu[k] / (1 - b2 ** t)) + eps)
            if k in clips:
                params[k] = jnp.clip(params[k], -clips[k], clips[k])
    return losses
