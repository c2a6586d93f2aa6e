"""Sparse-expert transformer trunk over the 64 squares of a board.

The second network family behind ``az_forward``: where ``models/az.py``
runs a convolution tower over the 8x8x19 planes, this runs a
bidirectional transformer over 64 tokens (one a square, 19 features
each) whose feed-forward is a routed mixture of experts, and ends in the
tower's own policy and value heads. The block is the published one of
LLaDA-MoE-7B-A1B (inclusionAI, config.json: hidden 2048, 16 heads x 128,
qk-norm, RoPE theta 50000, 64 experts top-8, softmax router, expert
width 1024, SiLU, RMSNorm eps 1e-5), a mask predictor with no causal
mask, so running it over a board removes nothing.

Layer equations (``n = RMSNorm(x; g, eps)``, statistics in float32)::

    tokens   t = planes.reshape(B, 64, 19);  x = t @ W_in + b_in
    attention q, k, v = n1 @ W_q, n1 @ W_k, n1 @ W_v   (heads x head_dim)
             q, k <- RMSNorm over head_dim (one gain each), then RoPE
             (theta, rotate-half, all of head_dim) on the square index
             h = x + concat(softmax(q k^T / sqrt(head_dim)) v) @ W_o   (no mask)
    router   p = softmax(n2 @ W_r) over the experts, float32; the
             experts_per_token largest p are the weights w_j, NOT renormalised
    experts  E_e(u) = (silu(u @ W_g[e]) * (u @ W_u[e])) @ W_d[e]
             x' = h + sum_j w_j E_{e_j}(n2)        (dropless: no capacity)
    out      RMSNorm(x'; g_f) -> [B, 8, 8, hidden] -> the heads of models/az.py

Mechanism, attention: the four projections are XLA's; everything
between them (qk-norm, RoPE, the 64 x 64 scores, softmax, mix) is one
Pallas kernel pair (``ops/board_attention.py``: ``board_attention`` and
its gradient ``board_attention_grad``) that takes q, k, v as the
projections write them, ``[B, 64, heads * head_dim]``, and works on one
head of a few boards at a time in VMEM: no ``[.., heads, head_dim]``
view and no scores reach HBM, and the gradient recomputes the softmax
from the same inputs.

Mechanism, experts: the (token, slot) pairs are sorted by expert
(stable), each expert's rows form one group of a grouped matrix product (megablox
``gmm``, a Pallas kernel: Mosaic on the TPU, the Pallas interpreter on
the CPU), the rows are put back in token order and each token's slots
summed under their weights. The rows move through two more Pallas
kernels (``ops/row_move.py``): wherever a row is addressed singly it
lives as ``[rows, hidden // 128, 128]``, one contiguous 4 KiB tile at
hidden 2048, and one DMA moves it; the sorted side that the grouped
products read stays ``[slots, hidden]``. ``rows_out`` (tokens to sorted
slots) and ``rows_back`` (sorted slots to token order) are each other's
transpose, so ``_dispatch`` and ``_combine`` pair them as forward and
gradient and nothing is ever scatter-added. Matrix products run in
bfloat16 with float32 accumulation over float32 parameters, as the
tower's; norms, both softmaxes, the router and the combine weights are
float32.

Parameters are one flat dict (the ``.npz`` checkpoint format), the
layers stacked on a leading axis: ``router_w [L, hidden, experts]``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from fishnet_tpu.models.az_encoding import INPUT_PLANES
from fishnet_tpu.models.heads import policy_value_heads
from fishnet_tpu.ops.board_attention import SQUARES, board_attention
from fishnet_tpu.ops.row_move import row_view, rows_back, rows_out

Params = Dict[str, jax.Array]

_INIT_STD = 0.02


@dataclass(frozen=True)
class TrunkConfig:
    hidden: int = 2048
    heads: int = 16
    head_dim: int = 128
    layers: int = 1
    experts: int = 64
    experts_per_token: int = 8
    expert_width: int = 1024
    rope_theta: float = 50000.0
    rms_eps: float = 1e-5
    value_hidden: int = 256
    policy_planes: int = 73


def trunk_param_shapes(cfg: TrunkConfig) -> Dict[str, Tuple[int, ...]]:
    """Every tensor of a trunk checkpoint by name."""
    n, h, e, w = cfg.layers, cfg.hidden, cfg.experts, cfg.expert_width
    inner = cfg.heads * cfg.head_dim
    return {
        "embed_w": (INPUT_PLANES, h), "embed_b": (h,),
        "attn_norm": (n, h), "wq": (n, h, inner), "wk": (n, h, inner), "wv": (n, h, inner),
        "q_norm": (n, cfg.head_dim), "k_norm": (n, cfg.head_dim), "wo": (n, inner, h),
        "moe_norm": (n, h), "router_w": (n, h, e),
        "experts_gate": (n, e, h, w), "experts_up": (n, e, h, w), "experts_down": (n, e, w, h),
        "final_norm": (h,),
        "policy_w": (1, 1, h, cfg.policy_planes), "policy_b": (cfg.policy_planes,),
        "value_w": (1, 1, h, 4), "value_b": (4,),
        "value_fc1_w": (4 * SQUARES, cfg.value_hidden), "value_fc1_b": (cfg.value_hidden,),
        "value_fc2_w": (cfg.value_hidden, 1), "value_fc2_b": (1,),
    }


def init_trunk_params(rng: jax.Array, cfg: TrunkConfig = TrunkConfig()) -> Params:
    """Normal(0, 0.02) matrices, unit norm gains, zero biases; the value
    head's last layer starts at zero, as the tower's."""
    shapes = trunk_param_shapes(cfg)
    keys = dict(zip(shapes, jax.random.split(rng, len(shapes))))
    params: Params = {}
    for name, shape in shapes.items():
        if name.endswith("_norm"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_b") or name == "value_fc2_w":
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jax.random.normal(keys[name], shape, jnp.float32) * _INIT_STD
    return params


def _rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """bfloat16 operands, float32 accumulation and result."""
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), preferred_element_type=jnp.float32)


def _row_major(x: jax.Array) -> jax.Array:
    """``x`` and its cotangent pinned to the row-major layout. The
    residual stream starts with it: left to itself XLA lays
    ``[tokens, hidden]`` out tokens-minor or square-major (what the
    embedding's 19-deep product and the heads' convolutions like), and
    every projection next to a kernel, whose operands are row-major by
    contract, then re-tiles its operand or its result inside the
    product (4.2 ms a step at the published sizes, PERF.md section 6,
    PR 32). One pin is enough: the layout follows the stream."""
    return with_layout_constraint(x, Layout(major_to_minor=tuple(range(x.ndim))))


def _attention(x: jax.Array, p: Params, cfg: TrunkConfig) -> jax.Array:
    """[tokens, hidden] float32, 64 tokens a board -> the attention
    branch's output, same shape. The projections are XLA's; everything
    between them is ``board_attention``."""
    n1 = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
    by_board = lambda y: y.reshape(-1, SQUARES, y.shape[-1])
    q, k, v = (by_board(_matmul(n1, p[name])) for name in ("wq", "wk", "wv"))
    mixed = board_attention(q, k, v.astype(jnp.bfloat16), p["q_norm"], p["k_norm"],
                            cfg.rope_theta, cfg.rms_eps, _interpret())
    return _matmul(mixed.reshape(x.shape[0], -1), p["wo"])


def _interpret() -> bool:
    """The trunk's Pallas kernels (the attention core, the grouped product
    and the two row moves) are one path everywhere: compiled by Mosaic on a TPU, run by
    the Pallas interpreter elsewhere (the CPU of the tests), never
    another path."""
    return jax.default_backend() != "tpu"


def _slots_by_token(rows: jax.Array, k: int) -> jax.Array:
    """``rows_back``'s result [N * k, sub, lanes] as [N, k, sub, lanes],
    for the sums over a token's k. The barrier keeps XLA from moving the
    float32 convert (or the cotangent's broadcast) to the kernel's side
    of this reshape, where no fusion reaches it and it is written out
    whole, 2 GiB at the published sizes (PERF.md section 6, PR 27)."""
    return jax.lax.optimization_barrier(rows.reshape(-1, k, *rows.shape[1:]))


@jax.custom_vjp
def _dispatch(tokens: jax.Array, order: jax.Array) -> jax.Array:
    """Each token's row to its k slots, the slots sorted by expert:
    ``tokens[order // k]``, [N, hidden] bfloat16 -> [N * k, hidden].
    ``order`` [N * k] lists the (token, slot) pairs in sorted order. The
    gradient brings every slot's row back to its place (a permutation:
    ``rows_back``) and sums each token's k, never a scatter-add (24.8 ms
    against 8.9 for the gather at the published sizes, PERF.md section 5)."""
    k = order.shape[0] // tokens.shape[0]
    return rows_out(row_view(tokens), order // k, interpret=_interpret())


def _dispatch_fwd(tokens, order):
    return _dispatch(tokens, order), order.reshape(tokens.shape[0], -1)


def _dispatch_bwd(order, g):
    n, k = order.shape
    per_slot = _slots_by_token(rows_back(g, order.reshape(n * k), interpret=_interpret()), k)
    return per_slot.sum(axis=1).reshape(n, -1), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out: jax.Array, weight: jax.Array, order: jax.Array) -> jax.Array:
    """The experts' sorted rows ``out`` [N * k, hidden] bfloat16 back in
    token order (``rows_back``) and each token's k summed under
    ``weight`` [N, k], float32 weights and sum: [N, hidden] float32. The
    rows in token order stay in the row view, ``[N, k, hidden // 128,
    128]``, which is also the residual of the weights' gradient. The
    gradient to ``out`` is the dispatch again with a scale: each slot's
    token's cotangent times the slot's weight in float32, then rounded
    to bfloat16 (``rows_out``)."""
    return _combine_fwd(out, weight, order)[0]


def _combine_fwd(out, weight, order):
    n, k = weight.shape
    per_slot = _slots_by_token(rows_back(out, order, interpret=_interpret()), k)
    mixed = jnp.sum(weight[:, :, None, None] * per_slot.astype(jnp.float32), axis=1)
    return mixed.reshape(n, -1), (per_slot, weight, order)


def _combine_bwd(res, g):
    per_slot, weight, order = res
    n, k = weight.shape
    g = row_view(g)
    d_weight = jnp.sum(g[:, None] * per_slot.astype(jnp.float32), axis=(2, 3))
    d_out = rows_out(g, order // k, weight.reshape(n * k)[order], dtype=per_slot.dtype, interpret=_interpret())
    return d_out, d_weight, None


_combine.defvjp(_combine_fwd, _combine_bwd)


#: Largest tile of the grouped product (rows, contraction, columns): the
#: fastest of those tried on a v5e at [262144, 2048] x [64, 2048, 1024]
#: (PERF.md section 5); the next size up does not fit the kernel's VMEM.
_TILE = (512, 1024, 1024)


def grouped_matmul(rows: jax.Array, weights: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``rows[i] @ weights[g(i)]`` where the rows come in runs of
    ``group_sizes`` (int32; their sum is the number of rows; a size may
    be 0). bfloat16 operands and result, float32 accumulation: megablox
    ``gmm``, whose gradients are ``gmm`` on the transposed weights and
    ``tgmm`` (one product a group, summed over the group's rows). The
    number of rows has to be a multiple of 8; it is 64 x experts_per_token
    x positions here."""
    m, k = rows.shape
    tiling = (math.gcd(m, _TILE[0]), min(k, _TILE[1]), min(weights.shape[2], _TILE[2]))
    return megablox.gmm(rows.astype(jnp.bfloat16), weights.astype(jnp.bfloat16), group_sizes,
                        jnp.bfloat16, tiling, None, None, False, _interpret())


def _experts(n2: jax.Array, p: Params, cfg: TrunkConfig, layer: str) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """[N, hidden] float32 normed tokens -> the routed experts' weighted
    sum [N, hidden] float32, and the layer's routing counters."""
    n, k = n2.shape[0], cfg.experts_per_token
    with jax.named_scope(f"{layer}.router"):
        logits = jnp.dot(n2, p["router_w"], precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weight, expert = jax.lax.top_k(probs, k)  # [N, k] each; weights not renormalised
    with jax.named_scope(f"{layer}.dispatch"):
        slot_expert = expert.reshape(n * k)
        order = jnp.argsort(slot_expert, stable=True)
        group_sizes = jnp.sum(slot_expert[:, None] == jnp.arange(cfg.experts)[None, :], axis=0, dtype=jnp.int32)
        rows = _dispatch(n2.astype(jnp.bfloat16), order)
    with jax.named_scope(f"{layer}.experts"):
        gate = grouped_matmul(rows, p["experts_gate"], group_sizes)
        up = grouped_matmul(rows, p["experts_up"], group_sizes)
        out = grouped_matmul(jax.nn.silu(gate) * up, p["experts_down"], group_sizes)
    with jax.named_scope(f"{layer}.combine"):
        mixed = _combine(out, weight, order)
    load = group_sizes.astype(jnp.float32)
    entropy = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-30), axis=-1))
    return mixed, {"expert_load_max": jnp.max(load), "expert_load_min": jnp.min(load), "router_entropy": entropy}


def trunk_forward(params: Params, planes: jax.Array, cfg: TrunkConfig = TrunkConfig()):
    """planes [B, 8, 8, 19] -> (policy_logits [B, 4672], value [B]), float32."""
    return trunk_forward_counted(params, planes, cfg)[:2]


def trunk_forward_counted(params: Params, planes: jax.Array, cfg: TrunkConfig):
    """``trunk_forward`` and the routing counters of the step's metrics:
    the most and the fewest slots any expert of any layer received
    (``expert_load_max``, ``expert_load_min``) and the router's mean
    entropy in nats (``router_entropy``)."""
    b = planes.shape[0]
    # Scope names are a contract (doc/observability.md "Training and compilation").
    with jax.named_scope("embed"):
        x = _row_major(_matmul(planes.reshape(b * SQUARES, INPUT_PLANES), params["embed_w"]) + params["embed_b"])
    counters = []
    for i in range(cfg.layers):
        layer = {name: params[name][i] for name in
                 ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "moe_norm", "router_w",
                  "experts_gate", "experts_up", "experts_down")}
        # One scope a part, the layer in its name: the benchmark's scope
        # table keeps two levels of a path (phase, then this).
        name = f"layer{i:02d}"
        with jax.named_scope(f"{name}.attention"):
            x = x + _attention(x, layer, cfg)
        with jax.named_scope(f"{name}.router"):
            n2 = _rms_norm(x, layer["moe_norm"], cfg.rms_eps)
        mixed, layer_counters = _experts(n2, layer, cfg, name)
        with jax.named_scope(f"{name}.combine"):
            x = x + mixed
        counters.append(layer_counters)
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
    features = x.reshape(b, 8, 8, cfg.hidden).astype(jnp.bfloat16)
    return (*policy_value_heads(params, features), {
        "expert_load_max": jnp.max(jnp.stack([c["expert_load_max"] for c in counters])),
        "expert_load_min": jnp.min(jnp.stack([c["expert_load_min"] for c in counters])),
        "router_entropy": jnp.mean(jnp.stack([c["router_entropy"] for c in counters])),
    })


#: What a trunk checkpoint carries beside its tensors: the three fields
#: of ``TrunkConfig`` that no shape determines, as float64[3].
HPARAMS = "trunk_hparams"


def trunk_checkpoint(params: Params, cfg: TrunkConfig) -> Dict[str, np.ndarray]:
    """The arrays of a trunk ``.npz``: the tensors and ``trunk_hparams`` =
    (experts_per_token, rope_theta, rms_eps)."""
    arrays = {k: np.asarray(v) for k, v in params.items()}
    arrays[HPARAMS] = np.asarray([cfg.experts_per_token, cfg.rope_theta, cfg.rms_eps], np.float64)
    return arrays


def trunk_config_from_params(params: Params) -> TrunkConfig:
    """The ``TrunkConfig`` of a checkpoint, from its shapes and its
    ``trunk_hparams``; a ValueError names what does not fit."""
    required = ("router_w", "experts_gate", "q_norm", "value_fc1_b", "policy_b", HPARAMS)
    missing = [k for k in required if k not in params]
    if missing:
        raise ValueError(f"not a trunk checkpoint: missing {missing}; got keys {sorted(params)[:8]}...")
    layers, hidden, experts = np.shape(params["router_w"])
    head_dim = int(np.shape(params["q_norm"])[1])
    top_k, theta, eps = (float(v) for v in np.asarray(params[HPARAMS]).reshape(3))
    cfg = TrunkConfig(
        hidden=int(hidden), heads=int(np.shape(params["wq"])[2]) // head_dim, head_dim=head_dim, layers=int(layers),
        experts=int(experts), experts_per_token=int(round(top_k)), expert_width=int(np.shape(params["experts_gate"])[3]),
        rope_theta=theta, rms_eps=eps,
        value_hidden=int(np.shape(params["value_fc1_b"])[0]), policy_planes=int(np.shape(params["policy_b"])[0]),
    )
    expected = {**trunk_param_shapes(cfg), HPARAMS: (3,)}
    got = {k: tuple(np.shape(v)) for k, v in params.items()}
    if expected != got:
        diff = set(expected) ^ set(got) or {k for k in expected if expected[k] != got[k]}
        raise ValueError(f"trunk checkpoint does not match any {cfg}: mismatched keys {sorted(diff)}")
    return cfg
