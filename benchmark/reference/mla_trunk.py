"""Plain reference for the deepseek_v3 block as a square-token trunk
(Kanana-2-30B-A3B's): forward, loss, AdamW and the balance update.

Written from the published config.json of
kakaocorp/kanana-2-30b-a3b-instruct-2601 (``model_type`` deepseek_v3,
``q_lora_rank`` null) and, for what it does not say, the public
DeepSeek-V3 modelling code as ``configs/kanana-2-trunk-train.json`` lists
under ``assumed``. ``N`` is RMSNorm (eps 1e-6, statistics in float32),
``n`` the normed input, 64 tokens a board, H = 32 heads::

    embed     x = t W_in + b_in                                         (no scale; W_in is this repo's 19-plane embedding)
    layer     a = x + Attn(N_in(x));   y = a + FFN(N_post_attn(a))      (two norms a layer, no post-norms)
    Attn      q      = n W_q                    [H x 192];   q_nope = q[.., :128],  q_pe = q[.., 128:]   (per head)
              ckv    = n W_kva                  [512 + 64];  c = N_kv(ckv[:512]; gain kv_norm),  k_pe = ckv[512:]   (ONE for all heads)
              kv     = c W_kvb                  [H x 256];   k_nope = kv[.., :128],  v = kv[.., 128:]    (per head)
              q_pe, k_pe <- RoPE(theta 1e6, position = square index 0..63, interleaved pairs (2i, 2i + 1), all 64 columns)
              s_h    = ([q_nope_h | q_pe_h] [k_nope_h | k_pe]^T) / sqrt(192)          within a board, no mask
              out    = concat_h( softmax(s_h) v_h ) W_o                               [H x 128 -> 2048]
    FFN dense (silu(n W_g) * (n W_u)) W_d, width 6144                                 (layer 0: first_k_dense_replace 1)
    FFN MoE   s = sigmoid(n W_r) over all 128 experts, float32
              chosen = top-6 of (s + b),  b = e_score_correction_bias (``expert_bias``), no gradient through b or the choice
              w_j = 2.448 * s[e_j] / (sum_j s[e_j] + 1e-20)                           (norm_topk_prob; over all 6 chosen, held or not)
              out = Shared(n) + sum over chosen e_j HELD HERE of w_j E_{e_j}(n)       Shared: ONE SiLU-gated FFN of width 2 x 768
    balance   after a step, a routed layer's c_e = slots routed to expert e (all 128, held or not):
              d = 0.001 * sign(mean(c) - c);  b <- b + d - mean(d)
    out       N_final(y) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

Everything in the published order, literally: ``wq``'s columns a head at
a time, NoPE then RoPE; ``wkv_b``'s a head at a time, key then value; the
RoPE key copied to every head and joined to the head's NoPE key, one
192-wide product a head; RoPE on the pairs (2i, 2i + 1). (The program
keeps another column order and one RoPE key: ``families/mla_trunk.py``
maps these parameters in and its gradients back.)

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every routed layer, from
``first_held_expert``; what the absent experts would have added is left
out here as in the program.

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch: every
held expert is applied to every token and the result masked by the
choice (a scan whose body is recomputed in the backward pass), every
layer made again in the backward pass. It imports nothing of the program;
the norm, the product in a precision and AdamW are the first trunk's
reference's, the gated feed-forward and the balance rule the second's,
imported. Parameters carry the names of the program's ``.npz`` checkpoint
format, the layers of a kind stacked on a leading axis; ``expert_bias``
is among them, has a zero gradient, and ``train_losses`` moves it by the
balance rule and never by AdamW.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16; the router's product,
the norms (the latent's among them), the softmax and the sigmoid stay
float32 in it, as in any fp8 recipe.
"""

from __future__ import annotations

import json
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import afmoe_trunk as second_block
from benchmark.reference import moe_trunk as first_block
from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = first_block.SQUARES
_rms_norm, _product, _gated, balanced_bias = first_block._rms_norm, first_block._product, second_block._gated, second_block.balanced_bias
BUFFER = "expert_bias"
_EVERY_LAYER = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "moe_norm")
_DENSE_LAYER = ("dense_gate", "dense_up", "dense_down")
_ROUTED_LAYER = ("router_w", BUFFER, "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down")
#: A token's largest routing logit sits here (sigmoid 0.7) whatever the number of experts; the centre follows (below).
_FIRST_LOGIT = 0.85
#: The spread of a router's logits: its matrix at 3.0 / sqrt(hidden) on a normed stream whose constant coordinate takes ~5%.
_LOGIT_SPREAD = 2.85
#: The mean square an attention or feed-forward branch adds to a coordinate of the stream: conditioned matrices pass on
#: ~0.9 of a normed input, a softmax over 64 near-equal keys and a SiLU gate each leave about half (read on the CPU at
#: the published widths, 0.1-0.4). Beside the embedding's 9.4 it moves the routers' centre by under 2%.
_BRANCH = 0.25


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, in the published column order,
    conditioned as the second trunk's reference conditions its own
    (``reference/afmoe_trunk.py init_params`` and ``reference/moe_trunk.py
    init_params`` say why for each): matrices normal(0, 0.9^2 / fan_in),
    gains 1 + 0.1 normal (``kv_norm`` among them), biases 0.05 normal, the
    value head pinned alive, ``expert_bias`` a few balance steps' worth,
    and a router that is peaked (logits spread by ~2.9) and centred below
    zero, where the largest of a token's 128 logits is 0.85: a token's
    first score is ~0.7, its sixth ~0.1, its seventh within ~0.01 of
    that, so a bfloat16 rounding that swaps them exchanges two experts of
    a seventh of the first's weight.

    One thing is conditioned for this block alone. The router has no
    bias, so its centre comes from a coordinate of the stream that is the
    same constant on every token (coordinate 0: ``embed_w[:, 0] = 0``,
    ``moe_norm[:, 0] = 1``, row 0 of each ``router_w`` the centre over
    what the coordinate reads after the norm). The second block's
    embedding is multiplied by sqrt(hidden), which makes that constant 45
    beside coordinates of ~3 and branches of ~1; this block has no such
    multiplier and no post-norms, and with an embedding at the other
    matrices' scale the constant would be 1 beside branches of ~0.5: the
    centre would swing by +-40% a token. So the EMBEDDING is drawn at
    sqrt(hidden) times the other matrices' scale (``embed_w`` normal(0,
    0.9^2), ``embed_b`` 0.05 sqrt(hidden) normal, ``embed_b[0]`` =
    sqrt(hidden)): the stream is what the second block's is after its
    multiplier, and a branch (mean square ~0.25 a coordinate, no
    post-norm) moves the constant by under 1%."""
    rng = np.random.default_rng([int(seed), 0x6D6C61])
    h, planes, hidden = model["hidden_size"], model["input_planes"], model["value_hidden"]
    layers, dense, heads = model["num_hidden_layers"], model["num_dense_layers"], model["num_attention_heads"]
    rank, nope, rope, value = model["kv_lora_rank"], model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    routed, held, experts = layers - dense, model["num_experts"], model["num_routed_experts"]
    w, dw, sw = model["moe_intermediate_size"], model["intermediate_size"], model["moe_intermediate_size"] * model["num_shared_experts"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(n: int, scale: float = 0.05) -> np.ndarray:
        return (scale * rng.standard_normal(n)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    params = {
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": bias(h, 0.05 * np.sqrt(h)),
        "attn_norm": gain(layers, h),
        "wq": matrix(layers, h, heads * (nope + rope), fan_in=h), "wkv_a": matrix(layers, h, rank + rope, fan_in=h),
        "kv_norm": gain(layers, rank), "wkv_b": matrix(layers, rank, heads * (nope + value), fan_in=rank),
        "wo": matrix(layers, heads * value, h, fan_in=heads * value),
        "moe_norm": gain(layers, h),
        "dense_gate": matrix(dense, h, dw, fan_in=h), "dense_up": matrix(dense, h, dw, fan_in=h),
        "dense_down": matrix(dense, dw, h, fan_in=dw),
        "router_w": matrix(routed, h, experts, fan_in=h, scale=3.0),
        "experts_gate": matrix(routed, held, h, w, fan_in=h), "experts_up": matrix(routed, held, h, w, fan_in=h),
        "experts_down": matrix(routed, held, w, h, fan_in=w),
        "shared_gate": matrix(routed, h, sw, fan_in=h), "shared_up": matrix(routed, h, sw, fan_in=h),
        "shared_down": matrix(routed, sw, h, fan_in=sw),
        "final_norm": gain(h),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }
    # The constant coordinate and the routers' centre on it. A square has ~4 planes set (its piece, castling rights, the
    # side to move), each a row of embed_w of mean square 0.81, on a bias of mean square 0.0025 h.
    params["embed_w"][:, 0], params["embed_b"][0], params["moe_norm"][:, 0] = 0.0, np.sqrt(h), 1.0
    embedded = h * (1.0 / h + (1.0 - 1.0 / h) * (4 * 0.81 / h + 0.0025))  # mean square of a coordinate of x = t W_in + b_in
    centre = _FIRST_LOGIT - _LOGIT_SPREAD * NormalDist().inv_cdf(1.0 - 0.5 / experts)  # the largest of `experts` normal draws
    for r in range(routed):
        branches = 2 * (dense + r) + 1  # branches added before this router's norm
        params["router_w"][r, 0, :] = centre * np.sqrt(embedded + _BRANCH * branches) / np.sqrt(h)
    steps = rng.integers(-3, 4, (routed, experts)).astype(np.float64) * model["load_balance_coeff"]
    params[BUFFER] = (steps - steps.mean(axis=-1, keepdims=True)).astype(np.float32)
    return params


def _rope_pairs(x: jax.Array, theta: float) -> jax.Array:
    """[B, 64, heads, rope]: RoPE on the interleaved pairs (2i, 2i + 1), position = square index."""
    rope = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope))
    angle = np.arange(SQUARES, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.asarray(f(angle), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every routed
    layer's slots an expert [routed layers, experts] (all of them, held
    or not)."""
    heads, eps, theta, b = model["num_attention_heads"], model["rms_norm_eps"], model["rope_theta"], planes.shape[0]
    rank, nope, rope = model["kv_lora_rank"], model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    top_k, first, held = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"]
    product = _product(cast, grad_cast)

    def layer(x: jax.Array, p: Params, dense: bool) -> Tuple[jax.Array, jax.Array]:
        n1 = _rms_norm(x, p["attn_norm"], eps)
        q = product("bsh,hd->bsd", n1, p["wq"]).reshape(b, SQUARES, heads, nope + rope)
        ckv = product("bsh,hd->bsd", n1, p["wkv_a"])
        c, k_pe = _rms_norm(ckv[..., :rank], p["kv_norm"], eps), ckv[..., None, rank:]
        kv = product("bsr,rd->bsd", c, p["wkv_b"]).reshape(b, SQUARES, heads, -1)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], theta)], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(_rope_pairs(k_pe, theta), (b, SQUARES, heads, rope))], axis=-1)
        scores = product("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
        mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, SQUARES, -1)
        x = x + product("bsd,dh->bsh", mixed, p["wo"])

        n2 = _rms_norm(x, p["moe_norm"], eps).reshape(b * SQUARES, -1)
        if dense:
            out, count = _gated(product, n2, p["dense_gate"], p["dense_up"], p["dense_down"]), jnp.zeros((0,), jnp.float32)
        else:
            score = jax.nn.sigmoid(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"))
            chosen = score + jax.lax.stop_gradient(p[BUFFER])
            kth = jax.lax.stop_gradient(jnp.sort(chosen, axis=-1)[:, -top_k][:, None])
            picked = jnp.where(chosen >= kth, score, 0.0)  # [tokens, experts], zero off the top k
            weights = model["route_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
            count = jnp.sum(chosen >= kth, axis=0).astype(jnp.float32)

            def one_expert(total, expert):
                w_gate, w_up, w_down, weight = expert
                return total + weight[:, None] * _gated(product, n2, w_gate, w_up, w_down), None

            shared = _gated(product, n2, p["shared_gate"], p["shared_up"], p["shared_down"])
            out, _ = jax.lax.scan(jax.checkpoint(one_expert), shared, (
                p["experts_gate"], p["experts_up"], p["experts_down"], weights[:, first:first + held].T))
        return x + out.reshape(b, SQUARES, -1), count

    x = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    counts = []
    for i in range(model["num_hidden_layers"]):
        r = i - model["num_dense_layers"]
        p = {name: params[name][i] for name in _EVERY_LAYER}
        p.update({name: params[name][i if r < 0 else r] for name in (_DENSE_LAYER if r < 0 else _ROUTED_LAYER)})
        # Each layer is made again in the backward pass, as the second trunk's reference does: its float32 activations of
        # five layers do not fit beside two trainer states at the published widths.
        x, count = jax.checkpoint(layer, static_argnums=(2,))(x, p, r < 0)
        if r >= 0:
            counts.append(count)

    return _rms_norm(x, params["final_norm"], eps).reshape(b, 8, 8, -1), jnp.stack(counts)


def features(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> jax.Array:
    """The final-normed trunk output [B, 8, 8, hidden]: what both heads read."""
    return _trunk(params, planes, model, cast, grad_cast)[0]


def forward(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast):
    x, b = features(params, planes, model, cast, grad_cast), planes.shape[0]
    product = _product(cast, grad_cast)

    policy = product("brfh,hp->brfp", x, params["policy_w"][0, 0]) + params["policy_b"]
    logits = policy.reshape(b, -1)  # (square, plane) order
    v = jax.nn.relu(product("brfh,hc->brfc", x, params["value_w"][0, 0]) + params["value_b"]).reshape(b, -1)
    v = jax.nn.relu(product("bi,ij->bj", v, params["value_fc1_w"]) + params["value_fc1_b"])
    v = jnp.tanh(product("bi,ij->bj", v, params["value_fc2_w"]) + params["value_fc2_b"])
    return logits, v[:, 0]


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    logits, value = forward(params, batch["planes"], config["model"], cast_for(precision), grad_cast_for(precision))
    log_p = jax.nn.log_softmax(logits, axis=-1)
    policy_loss = -jnp.mean(jnp.sum(batch["policy_target"] * log_p, axis=-1))
    value_loss = jnp.mean((value - batch["value_target"]) ** 2)
    return policy_loss + config["train"]["value_weight"] * value_loss


_SLOTS: Dict[str, Any] = {}  # one compiled routing count a model, shared by every seed of a sweep


def expert_slots(params: Params, planes: jax.Array, model: Dict[str, Any]) -> jax.Array:
    """Every routed layer's slots an expert, in float32: what the balance update reads."""
    key = json.dumps(model, sort_keys=True)
    if key not in _SLOTS:
        _SLOTS[key] = jax.jit(lambda p, x: _trunk(p, x, model, cast_for("float32"), grad_cast_for("float32"))[1])
    return _SLOTS[key](params, planes)


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` updates on one batch, with
    ``grad(params, batch) -> (loss, gradients)`` of this module's
    ``loss``: AdamW (the first trunk's reference's) on every trained
    tensor, one at a time, each gradient dropped once used; and the
    balance rule on ``expert_bias``, from the routing of the parameters
    the step started with."""
    train, model = config["train"], config["model"]
    lr, wd = jnp.float32(train["learning_rate"]), jnp.float32(train["weight_decay"])
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    trained = [k for k in params if k != BUFFER]
    mu = {k: jnp.zeros_like(params[k]) for k in trained}
    nu = {k: jnp.zeros_like(params[k]) for k in trained}
    losses = []
    for t in range(1, steps + 1):
        value, g = grad(params, batch)
        losses.append(value)
        g = dict(g)
        slots = expert_slots(params, batch["planes"], model)
        for k in trained:
            params[k], mu[k], nu[k] = first_block._adamw(params[k], mu[k], nu[k], g.pop(k).astype(jnp.float32), jnp.float32(t), lr, wd)
        params[BUFFER] = balanced_bias(params[BUFFER], slots, model["load_balance_coeff"])
    return losses
