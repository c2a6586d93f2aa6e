"""Float NNUE model for training, with exact quantization export.

The reference consumes nets as opaque embedded blobs (reference
assets.rs:128-133, build.rs:306) and has no training subsystem at all;
here training is first-class so the framework can produce the very nets
its evaluator serves. The float forward below is the de-quantized mirror
of the integer pipeline in spec.py / jax_eval.py / cpp/src/nnue.cpp:
every scale factor is chosen so that ``quantize()`` of trained float
params yields an ``NnueWeights`` whose integer eval tracks the float
eval to within a few centipawns.

Scale conventions (nnue-pytorch-style):

* activation unit 1.0  <-> quantized 127
* hidden weight  1.0   <-> quantized 64
* network output 1.0   <-> 600 centipawns (``NNUE2SCORE``)
* the skip neuron is a raw l1 output; with hidden scales (127, 64) its
  integer contribution ``(skip + skip*23/127)/16`` is 600 * skip_f — the
  23/127 fudge exists precisely to make the scales line up.
* PSQT entry 1.0 <-> 9600, so ``(psqt_stm - psqt_opp)/2/16`` is
  600 * (p_stm - p_opp)/2 — matching the float model's
  ``material = (p_stm - p_opp)/2`` term.

Shapes are configurable (``NetConfig``) so multi-chip dry-runs and tests
can use tiny nets; quantization export requires the full spec shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fishnet_tpu.nnue import spec
from fishnet_tpu.nnue.weights import NnueWeights

Params = Dict[str, jax.Array]

NNUE2SCORE = 600.0
# Integer ranges the quantized net must fit in (see quantize()).
HIDDEN_WEIGHT_CLIP = 127.0 / 64.0
OUT_WEIGHT_CLIP = 127.0 * 127.0 / (NNUE2SCORE * spec.FV_SCALE)


@dataclass(frozen=True)
class NetConfig:
    num_features: int = spec.NUM_FEATURES
    max_active: int = spec.MAX_ACTIVE_FEATURES
    l1: int = spec.L1
    l2: int = spec.L2
    l3: int = spec.L3
    num_buckets: int = spec.NUM_PSQT_BUCKETS

    @property
    def l1_half(self) -> int:
        return self.l1 // 2

    def is_full_spec(self) -> bool:
        return (
            self.num_features == spec.NUM_FEATURES
            and self.l1 == spec.L1
            and self.l2 == spec.L2
            and self.l3 == spec.L3
            and self.num_buckets == spec.NUM_PSQT_BUCKETS
        )


def init_params(rng: jax.Array, cfg: NetConfig = NetConfig()) -> Params:
    """He-style init scaled for the clipped [0, 1] activation regime."""
    k_ft, k1, k2, k3 = jax.random.split(rng, 4)
    b = cfg.num_buckets

    def unif(key, shape, bound):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    return {
        # Sparse input: ~32 active features -> keep rows small so the
        # accumulator starts inside the clip window.
        "ft_w": unif(k_ft, (cfg.num_features, cfg.l1), 0.05),
        "ft_b": jnp.full((cfg.l1,), 0.5, jnp.float32),
        "ft_psqt": jnp.zeros((cfg.num_features, b), jnp.float32),
        "l1_w": unif(k1, (b, cfg.l2 + 1, cfg.l1), float(np.sqrt(1.0 / cfg.l1))),
        "l1_b": jnp.zeros((b, cfg.l2 + 1), jnp.float32),
        "l2_w": unif(k2, (b, cfg.l3, 2 * cfg.l2), float(np.sqrt(1.0 / (2 * cfg.l2)))),
        "l2_b": jnp.zeros((b, cfg.l3), jnp.float32),
        "out_w": unif(k3, (b, 1, cfg.l3), float(np.sqrt(1.0 / cfg.l3))),
        "out_b": jnp.zeros((b, 1), jnp.float32),
    }


def forward(
    params: Params, indices: jax.Array, buckets: jax.Array, cfg: NetConfig = NetConfig()
) -> jax.Array:
    """Float forward. ``indices`` int32 [B, 2, A] (stm perspective first),
    padded with any value >= cfg.num_features; ``buckets`` int32 [B].
    Returns float32 [B] in network-output units (multiply by NNUE2SCORE
    for centipawns)."""
    mask = (indices < cfg.num_features)[..., None].astype(jnp.float32)
    safe = jnp.minimum(indices, cfg.num_features - 1)

    # Scope names are a contract (doc/observability.md "Training and
    # compilation"): the benchmark's phase metrics join on them.
    with jax.named_scope("ft_gather"):
        rows = jnp.take(params["ft_w"], safe, axis=0) * mask  # [B, 2, A, L1]
        acc = params["ft_b"] + jnp.sum(rows, axis=2)  # [B, 2, L1]
    with jax.named_scope("ft_psqt"):
        psqt_rows = jnp.take(params["ft_psqt"], safe, axis=0) * mask
        psqt = jnp.sum(psqt_rows, axis=2)  # [B, 2, buckets]

    with jax.named_scope("pairwise"):
        c = jnp.clip(acc, 0.0, 1.0)
        pair = c[..., : cfg.l1_half] * c[..., cfg.l1_half :] * (127.0 / 128.0)
        x = pair.reshape(pair.shape[0], cfg.l1)  # [B, L1], stm half first

    with jax.named_scope("stacks"):
        y_all = (
            jnp.einsum("bi,koi->bko", x, params["l1_w"]) + params["l1_b"][None]
        )  # [B, buckets, L2+1]
        y = jnp.take_along_axis(y_all, buckets[:, None, None], axis=1)[:, 0]

        skip = y[:, cfg.l2]
        h = y[:, : cfg.l2]
        sq = jnp.minimum(h * h * (127.0 / 128.0), 1.0)
        ca = jnp.clip(h, 0.0, 1.0)
        act = jnp.concatenate([sq, ca], axis=1)  # [B, 2*L2]

        z_all = jnp.einsum("bi,koi->bko", act, params["l2_w"]) + params["l2_b"][None]
        z = jnp.clip(jnp.take_along_axis(z_all, buckets[:, None, None], axis=1)[:, 0], 0.0, 1.0)

        v_all = jnp.einsum("bi,koi->bko", z, params["out_w"]) + params["out_b"][None]
        v = jnp.take_along_axis(v_all, buckets[:, None, None], axis=1)[:, 0, 0]

    with jax.named_scope("material"):
        p_sel = jnp.take_along_axis(
            psqt, jnp.repeat(buckets[:, None, None], 2, axis=1), axis=2
        )[..., 0]  # [B, 2]
        material = (p_sel[:, 0] - p_sel[:, 1]) * 0.5
    return v + skip + material


def clip_params(params: Params) -> Params:
    """Project weights back into quantization-representable ranges after
    each optimizer step (quantization-aware training, the standard NNUE
    recipe)."""
    out = dict(params)
    out["l1_w"] = jnp.clip(params["l1_w"], -HIDDEN_WEIGHT_CLIP, HIDDEN_WEIGHT_CLIP)
    out["l2_w"] = jnp.clip(params["l2_w"], -HIDDEN_WEIGHT_CLIP, HIDDEN_WEIGHT_CLIP)
    out["out_w"] = jnp.clip(params["out_w"], -OUT_WEIGHT_CLIP, OUT_WEIGHT_CLIP)
    return out


def quantize(params: Params, cfg: NetConfig = NetConfig()) -> NnueWeights:
    """Export float params to the integer NnueWeights the serving path
    consumes. Only defined for full-spec shapes."""
    if not cfg.is_full_spec():
        raise ValueError("quantize() requires full-spec NetConfig")

    def rnd(x, scale, dtype, lo, hi):
        arr = np.asarray(jax.device_get(x), np.float64) * scale
        return np.clip(np.round(arr), lo, hi).astype(dtype)

    hid = 1 << spec.WEIGHT_SCALE_BITS  # 64
    out_w_scale = NNUE2SCORE * spec.FV_SCALE / 127.0
    out_b_scale = NNUE2SCORE * spec.FV_SCALE
    psqt_scale = NNUE2SCORE * spec.FV_SCALE  # 9600

    weights = NnueWeights(
        ft_weight=rnd(params["ft_w"], 127.0, np.int16, -32768, 32767),
        ft_bias=rnd(params["ft_b"], 127.0, np.int16, -32768, 32767),
        ft_psqt=rnd(params["ft_psqt"], psqt_scale, np.int32, -(2**31) + 1, 2**31 - 1),
        l1_weight=rnd(params["l1_w"], hid, np.int8, -127, 127),
        l1_bias=rnd(params["l1_b"], hid * 127.0, np.int32, -(2**31), 2**31 - 1),
        l2_weight=rnd(params["l2_w"], hid, np.int8, -127, 127),
        l2_bias=rnd(params["l2_b"], hid * 127.0, np.int32, -(2**31), 2**31 - 1),
        out_weight=rnd(params["out_w"], out_w_scale, np.int8, -127, 127),
        out_bias=rnd(params["out_b"], out_b_scale, np.int32, -(2**31), 2**31 - 1),
    )
    weights.validate()
    return weights
