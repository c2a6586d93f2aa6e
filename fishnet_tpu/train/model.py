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

import functools
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
    # The feature table is ``king_buckets`` blocks of equal size, and all
    # active indices of one (position, perspective) pair lie in one block
    # (HalfKAv2_hm: index = king_bucket * 704 + plane * 64 + square). A
    # net fed arbitrary indices says 1: one block spanning the table.
    king_buckets: int = spec.NUM_KING_BUCKETS

    def __post_init__(self) -> None:
        if self.num_features % self.king_buckets:
            raise ValueError(
                f"num_features {self.num_features} is not {self.king_buckets} equal blocks"
            )

    @property
    def l1_half(self) -> int:
        return self.l1 // 2

    @property
    def block_rows(self) -> int:
        return self.num_features // self.king_buckets

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


# Pairs per tile of the table gradient's grouped matmul (its contraction).
GRAD_TILE = 256


def _pair_blocks(cfg: NetConfig, indices: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per (position, perspective) pair its block of the table, int32
    [..., 1], and each slot's row inside that block, int32 [..., A]:
    -1 for padding and for an active index outside the pair's block."""
    valid = indices < cfg.num_features
    block = jnp.where(valid, indices // cfg.block_rows, 0)
    pair_block = jnp.max(block, axis=-1, keepdims=True)
    local = jnp.where(valid & (block == pair_block), indices - pair_block * cfg.block_rows, -1)
    return pair_block, local


def ft_block_misses(cfg: NetConfig, indices: jax.Array) -> jax.Array:
    """Active entries whose index lies outside their pair's block: the
    table gradient drops them. 0 on every batch that keeps NetConfig's
    ``king_buckets`` contract (``Board.nnue_features`` does)."""
    _, local = _pair_blocks(cfg, indices)
    return jnp.sum((indices < cfg.num_features) & (local < 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def accumulate(cfg: NetConfig, table: jax.Array, indices: jax.Array) -> jax.Array:
    """Sum of the active rows of ``table`` [num_features, N] per pair:
    ``indices`` int32 [B, 2, A] -> [B, 2, N]. The gradient with respect
    to ``table`` is ``_table_grad``, not the transpose of these lines."""
    mask = (indices < cfg.num_features)[..., None].astype(table.dtype)
    safe = jnp.minimum(indices, cfg.num_features - 1)
    rows = jnp.take(table, safe, axis=0) * mask  # [B, 2, A, N]
    return jnp.sum(rows, axis=2)


def _accumulate_fwd(cfg, table, indices):
    return accumulate(cfg, table, indices), indices


def _table_grad(cfg: NetConfig, indices: jax.Array, g: jax.Array):
    """d table = M^T @ g, M the [pairs, num_features] matrix that counts
    each pair's active rows. M is zero outside the pair's block, so with
    the pairs ordered by block it is one [block_rows, tile] @ [tile, N]
    product per tile of pairs, each tile inside one block (groups are
    padded to whole tiles), and a sum of the tiles of each block: 1/32 of
    the dense product's operations for the published feature set, and no
    scatter. The counts are exact in float32 and ``HIGHEST`` keeps ``g``
    whole, so the result is the scatter-add's up to the order of sums."""
    k, r = cfg.king_buckets, cfg.block_rows
    pair_block, local = _pair_blocks(cfg, indices)
    local = local.reshape(-1, local.shape[-1])  # [P, A]
    pair_block = pair_block.reshape(-1)  # [P]
    g = g.reshape(local.shape[0], -1)  # [P, N]
    pairs = local.shape[0]
    tile = min(GRAD_TILE, pairs)
    tiles = (pairs + k * (tile - 1)) // tile  # the most the padded groups can take

    order = jnp.argsort(pair_block)
    counts = jnp.sum(pair_block[:, None] == jnp.arange(k), axis=0, dtype=jnp.int32)  # [k]
    group_tiles = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(group_tiles)
    # tiles no group needs go to the last block: all their slots are past its count
    tile_block = jnp.minimum(jnp.searchsorted(tile_end, jnp.arange(tiles), side="right"), k - 1)
    # rank of each slot among its block's pairs; slots past the count are dead
    rank = (jnp.arange(tiles) - (tile_end - group_tiles)[tile_block])[:, None] * tile + jnp.arange(tile)
    live = rank < counts[tile_block][:, None]
    src = order[jnp.where(live, (jnp.cumsum(counts) - counts)[tile_block][:, None] + rank, 0)]

    rows = jnp.where(live[..., None], local[src], -1)  # [tiles, tile, A]
    counted = jnp.sum(rows[..., None] == jnp.arange(r), axis=2, dtype=g.dtype)  # [tiles, tile, r]
    partial = jnp.einsum(
        "tjr,tjn->trn", counted, g[src], precision=jax.lax.Precision.HIGHEST
    )  # [tiles, r, N]
    of_block = (tile_block == jnp.arange(k)[:, None]).astype(g.dtype)  # [k, tiles]
    grad = jnp.einsum("kt,trn->krn", of_block, partial, precision=jax.lax.Precision.HIGHEST)
    return grad.reshape(cfg.num_features, -1)


def _accumulate_bwd(cfg, indices, g):
    return _table_grad(cfg, indices, g), None


accumulate.defvjp(_accumulate_fwd, _accumulate_bwd)


def forward(
    params: Params, indices: jax.Array, buckets: jax.Array, cfg: NetConfig = NetConfig()
) -> jax.Array:
    """Float forward. ``indices`` int32 [B, 2, A] (stm perspective first),
    padded with any value >= cfg.num_features, each pair's active ones
    inside one of ``cfg.king_buckets`` blocks; ``buckets`` int32 [B].
    Returns float32 [B] in network-output units (multiply by NNUE2SCORE
    for centipawns)."""
    # Scope names are a contract (doc/observability.md "Training and
    # compilation"): the benchmark's phase metrics join on them.
    with jax.named_scope("ft_gather"):
        acc = params["ft_b"] + accumulate(cfg, params["ft_w"], indices)  # [B, 2, L1]
    with jax.named_scope("ft_psqt"):
        psqt = accumulate(cfg, params["ft_psqt"], indices)  # [B, 2, buckets]

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
