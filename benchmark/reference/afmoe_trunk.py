"""Plain reference for the afmoe block as a square-token trunk (Trinity-Mini's):
forward, loss, AdamW and the balance update.

Written from the published config.json of arcee-ai/Trinity-Mini
(``model_type`` afmoe) and, for what it does not say, the public afmoe
modelling code as ``configs/trinity-mini-trunk-train.json`` lists under
``assumed``. ``N`` is RMSNorm (eps 1e-5, statistics in float32), ``n`` the
normed input, 64 tokens a board::

    embed     x = (t W_in + b_in) * sqrt(hidden)                (mup_enabled; W_in is this repo's 19-plane embedding)
    layer     a = x + N_post_attn( Attn( N_in(x) ) )
              y = a + N_post_mlp( FFN( N_pre_mlp(a) ) )         (four norms a layer)
    Attn      q = n W_q [32 x 128];  k = n W_k [4 x 128];  v = n W_v [4 x 128];  g = n W_gate [32 x 128]
              q, k <- RMSNorm over head_dim, one gain each
              sliding layers: RoPE(theta 10000, rotate-half, all of head_dim) on the square index; full layers: none
              query head h attends key-value head h // 8, within a board, scores / sqrt(128)
              sliding layers: mask |i - j| < 2048, applied literally (all true at 64 tokens); softmax
              out = ( (P v) * sigmoid(g) ) W_o
    FFN dense (silu(n W_g) * (n W_u)) W_d, width 6144           (the leading layer)
    FFN MoE   s = sigmoid(n W_r) in R^128
              chosen = top-8 of (s + b), b = expert_bias, no gradient through b or the choice
              w_j = 2.826 * s[e_j] / (sum_j s[e_j] + 1e-20)     (over all 8 chosen, held or not)
              out = Shared(n) + sum over chosen e_j HELD HERE of w_j E_{e_j}(n);  Shared, E_e: SiLU-gated, width 1024
    balance   after a step, a routed layer's c_e = slots routed to expert e (all 128, held or not):
              d = 0.001 * sign(mean(c) - c);  b <- b + d - mean(d)
    out       N_final(y) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every routed layer, from
``first_held_expert``. The router keeps all its outputs and its top-8;
what the absent experts would have added is left out here as in the
program, and that partial result goes on to the next layer.

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch:
EVERY held expert is applied to EVERY token and the result masked by the
choice, one expert at a time (a scan whose body is recomputed in the
backward pass). It imports nothing of the program; the norm, RoPE, the
product in a precision and AdamW are the first trunk's reference's
(``reference/moe_trunk.py``), imported. Parameters carry the names of the
program's ``.npz`` checkpoint format, the layers of a kind stacked on a
leading axis; ``expert_bias`` is among them (a checkpoint carries it
too), has a zero gradient, and ``train_losses`` moves it by the balance
rule and never by AdamW.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16; the router's product,
the norms, the softmax and the sigmoids stay float32 in it, as in any fp8
recipe.
"""

from __future__ import annotations

import json
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import moe_trunk as first_block
from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = first_block.SQUARES
_rms_norm, _rope, _product = first_block._rms_norm, first_block._rope, first_block._product
BUFFER = "expert_bias"
_EVERY_LAYER = ("attn_norm", "wq", "wk", "wv", "wgate", "wo", "q_norm", "k_norm", "post_attn_norm", "moe_norm", "post_mlp_norm")
_DENSE_LAYER = ("dense_gate", "dense_up", "dense_down")
_ROUTED_LAYER = ("router_w", BUFFER, "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down")
#: A token's largest routing logit sits here (sigmoid 0.7) whatever the number of experts; the centre follows (below).
_FIRST_LOGIT = 0.85
#: The spread of a router's logits: its matrix at 3.0 / sqrt(hidden) on a normed stream whose constant coordinate takes ~5%.
_LOGIT_SPREAD = 2.85


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, conditioned as the first trunk's
    reference conditions its own (``reference/moe_trunk.py init_params``
    says why for each): matrices normal(0, 0.9^2 / fan_in), gains 1 + 0.1
    normal, biases 0.05 normal, the value head pinned alive (every plane
    on every square 4 sigma from the relu's corner, dense layers of one
    sign), because what it sees is nearly one vector on every square.

    The router is conditioned for this block's scores. With sigmoid
    scores renormalised over the chosen, a token's eight weights are
    nearly equal wherever the chosen logits are positive (s near 1 for
    all eight), and a bfloat16 rounding upstream that swaps the eighth
    and ninth of ``s + b`` then replaces an eighth of the token's routed
    output. A trained router of this kind is peaked instead: a few
    experts near 1, the rest near 0. So the logits are spread by ~2.9
    (the matrix is 3.3 times larger, as the first trunk's) AND centred
    below zero, where the largest of a token's logits over the experts
    is 0.85 (-6.7 for 128 experts): a token's first score is then ~0.7,
    its eighth ~0.1, its ninth within ~0.01 of that, and a swap exchanges
    two experts of a seventh of the first's weight. The router has no bias, so the centre comes
    from the stream itself: coordinate 0 of the embedding is the same
    constant on every token (``embed_w[:, 0] = 0``, ``embed_b[0] = 1``:
    sqrt(hidden) after the multiplier), the norms before the routers pass
    it on with gain 1, and row 0 of each ``router_w`` is the centre over
    what that coordinate reads there: sqrt(hidden) over the stream's
    rms, which is known from the draws' own scales (the embedding's
    mean square, plus one for every post-normed branch added so far).

    ``expert_bias`` is a few balance steps' worth (multiples of
    ``load_balance_coeff`` in -3..3, each layer's mean taken out), so that
    the choice is on ``s + b`` with a ``b`` that matters as much as it
    does after a few steps of training."""
    rng = np.random.default_rng([int(seed), 0x61666D])
    h, planes, hidden = model["hidden_size"], model["input_planes"], model["value_hidden"]
    layers, dense, head_dim = model["num_hidden_layers"], model["num_dense_layers"], model["head_dim"]
    routed, held, experts = layers - dense, model["num_experts"], model["num_routed_experts"]
    inner, kv_inner = model["num_attention_heads"] * head_dim, model["num_key_value_heads"] * head_dim
    w, dw, sw = model["moe_intermediate_size"], model["intermediate_size"], model["moe_intermediate_size"] * model["num_shared_experts"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(n: int) -> np.ndarray:
        return (0.05 * rng.standard_normal(n)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    params = {
        "embed_w": matrix(planes, h, fan_in=h), "embed_b": bias(h),
        "attn_norm": gain(layers, h),
        "wq": matrix(layers, h, inner, fan_in=h), "wk": matrix(layers, h, kv_inner, fan_in=h),
        "wv": matrix(layers, h, kv_inner, fan_in=h), "wgate": matrix(layers, h, inner, fan_in=h),
        "wo": matrix(layers, inner, h, fan_in=inner),
        "q_norm": gain(layers, head_dim), "k_norm": gain(layers, head_dim),
        "post_attn_norm": gain(layers, h), "moe_norm": gain(layers, h), "post_mlp_norm": gain(layers, h),
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
    # The constant coordinate and the routers' centre on it. A square has ~4 planes set (its piece, castling
    # rights, the side to move), each a row of embed_w of mean square 0.81 / h, on a bias of mean square 0.0025.
    params["embed_w"][:, 0], params["embed_b"][0], params["moe_norm"][:, 0] = 0.0, 1.0, 1.0
    embedded = h * (1.0 / h + (1.0 - 1.0 / h) * (4 * 0.81 / h + 0.0025))  # mean square of x = (t W_in + b_in) sqrt(h)
    centre = _FIRST_LOGIT - _LOGIT_SPREAD * NormalDist().inv_cdf(1.0 - 0.5 / experts)  # the largest of `experts` normal draws
    for r in range(routed):
        branches = 2 * (dense + r) + 1  # post-normed branches (mean square ~1 each) added before this router's norm
        params["router_w"][r, 0, :] = centre * np.sqrt(embedded + branches) / np.sqrt(h)
    steps = rng.integers(-3, 4, (routed, experts)).astype(np.float64) * model["load_balance_coeff"]
    params[BUFFER] = (steps - steps.mean(axis=-1, keepdims=True)).astype(np.float32)
    return params


def _gated(product, n: jax.Array, gate_w: jax.Array, up_w: jax.Array, down_w: jax.Array) -> jax.Array:
    return product("tw,wh->th", jax.nn.silu(product("th,hw->tw", n, gate_w)) * product("th,hw->tw", n, up_w), down_w)


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every routed
    layer's slots an expert [routed layers, experts] (all of them, held
    or not)."""
    heads, kv_heads, head_dim, eps = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"], model["rms_norm_eps"]
    top_k, first, held, b = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"], planes.shape[0]
    product = _product(cast, grad_cast)
    near = np.abs(np.arange(SQUARES)[:, None] - np.arange(SQUARES)[None, :]) < model["sliding_window"]

    def layer(x: jax.Array, p: Params, kind: str, dense: bool) -> Tuple[jax.Array, jax.Array]:
        n1 = _rms_norm(x, p["attn_norm"], eps)
        q = product("bsh,hd->bsd", n1, p["wq"]).reshape(b, SQUARES, heads, head_dim)
        k, v = (product("bsh,hd->bsd", n1, p[name]).reshape(b, SQUARES, kv_heads, head_dim) for name in ("wk", "wv"))
        gate = product("bsh,hd->bsd", n1, p["wgate"])
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
        if kind == "sliding_attention":
            q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
        k, v = (jnp.repeat(y, heads // kv_heads, axis=2) for y in (k, v))  # query head h attends key-value head h // 8
        scores = product("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
        if kind == "sliding_attention":
            scores = jnp.where(near, scores, -jnp.inf)
        mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, SQUARES, heads * head_dim)
        x = x + _rms_norm(product("bsd,dh->bsh", mixed * jax.nn.sigmoid(gate), p["wo"]), p["post_attn_norm"], eps)

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
        return x + _rms_norm(out, p["post_mlp_norm"], eps).reshape(b, SQUARES, -1), count

    x = (product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]) * np.sqrt(model["hidden_size"])
    counts = []
    for i, kind in enumerate(model["kept_layer_types"]):
        r = i - model["num_dense_layers"]
        p = {name: params[name][i] for name in _EVERY_LAYER}
        p.update({name: params[name][i if r < 0 else r] for name in (_DENSE_LAYER if r < 0 else _ROUTED_LAYER)})
        # Each layer is made again in the backward pass: at the published widths the float32 activations of five
        # layers of 4,096 tokens are 4.3 GiB, which the chip does not have left beside two trainer states.
        x, count = jax.checkpoint(layer, static_argnums=(2, 3))(x, p, kind, r < 0)
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


def balanced_bias(bias_: jax.Array, slots: jax.Array, rate: float) -> jax.Array:
    change = rate * jnp.sign(jnp.mean(slots, axis=-1, keepdims=True) - slots)
    return bias_ + change - jnp.mean(change, axis=-1, keepdims=True)


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` updates on one batch, with
    ``grad(params, batch) -> (loss, gradients)`` of this module's
    ``loss``: AdamW (Loshchilov & Hutter: decoupled weight decay; b1 0.9,
    b2 0.999, eps 1e-8) on every trained tensor, one at a time, each
    gradient dropped once used; and the balance rule on ``expert_bias``,
    from the routing of the parameters the step started with."""
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
