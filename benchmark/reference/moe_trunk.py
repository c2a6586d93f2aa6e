"""Plain reference for the sparse-expert square-token trunk: forward,
loss and AdamW.

Written from the published block of LLaDA-MoE-7B-A1B (inclusionAI,
config.json) and this repo's stated departures
(configs/lladamoe-trunk-train.json ``assumed``). The equations, with
``n = RMSNorm(x; g, eps)`` (statistics in float32)::

    tokens   t = planes.reshape(B, 64, 19)   (square = rank * 8 + file)
             x = t @ W_in + b_in
    attention q, k, v = n1 @ W_q, n1 @ W_k, n1 @ W_v, heads x head_dim
             q, k <- RMSNorm over head_dim (one gain each), then RoPE
             (theta, rotate-half, all of head_dim) on the square index
             h = x + concat(softmax(q k^T / sqrt(head_dim)) v) @ W_o   (no mask)
    router   p = softmax(n2 @ W_r); the experts_per_token largest p are
             the weights w_j of their experts, not renormalised
    experts  E_e(u) = (silu(u @ W_g[e]) * (u @ W_u[e])) @ W_d[e]
             x' = h + sum_j w_j E_{e_j}(n2)
    out      RMSNorm(x'; g_f) -> [B, 8, 8, hidden] -> a 1x1 policy
             convolution to 73 planes; a 1x1 value convolution to 4,
             relu, fc, relu, fc, tanh

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch:
EVERY expert is applied to EVERY token and the result masked by the
top-k choice, one expert at a time (a scan whose body is recomputed in
the backward pass, so that the temporaries stay under 1 GiB at the
published widths). It imports nothing of the program; parameters carry
the names of the program's public ``.npz`` checkpoint format, the layers
stacked on a leading axis.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16; the router's product,
the norms and the softmaxes stay float32 in it, as in any fp8 recipe.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = 64
_LAYER_TENSORS = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "moe_norm", "router_w",
                  "experts_gate", "experts_up", "experts_down")


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, conditioned so that every tensor
    has a gradient worth comparing: each a sum of terms that add, never
    what the bfloat16 program's rounding leaves of a sum that cancels.

    Matrices are normal(0, 0.9^2 / fan_in): 0.02 at the published hidden
    size, and activations of the same scale at any width. Norm gains are
    1 + 0.1 normal and biases 0.05 normal, so that none is a special
    point.

    The value head is pinned, because what it sees is nearly the same
    vector on every square of every position: most squares are empty and
    share one embedding, attention averages near-equal tokens, and at the
    published widths 91-95% of the squared norm of the final-normed
    features is their mean over squares and positions (PERF.md section 6,
    PR 29). ``x . value_w[:, c]`` is therefore one offset a plane, drawn
    once a seed, plus a variation several times smaller. With ``value_w``
    at the other matrices' scale the offsets read normal(0, 0.9^2) and the
    variation 0.07-0.5: a plane is alive on every square, dead on every
    square, or straddles the relu's corner, by the seed. Where none is
    alive ``value_fc1_w``'s gradient is what one barely-alive unit of 8192
    leaves (0.43 on the chip, seed 2700800011); where one straddles the
    corner the bfloat16 program's rounding flips its units' masks, that
    plane's gradients are off by 4-9%, and because a third of the trunk's
    gradient comes through the head every tensor reads 3x its usual
    (seed 2700034567: 0.016 overall). So: ``value_w`` is normal at
    0.2 / sqrt(hidden) (offsets of 0.2, variation 0.02-0.06) under a bias
    of 1 + 0.05 normal: every plane is alive on every square, 4 sigma and
    more from the corner, and a unit reads about 1. The two dense layers
    are of ONE sign (|normal|, the last layer's and its bias's sign drawn
    once a seed), scaled so that the 256 hidden units sit near 1 and the
    tanh's argument at 0.5 to 1.1. The pool's value targets are nearly
    all 0 (playouts cut at 120 plies are draws; 3-4% are decisive, 0-10
    of the 128 positions compared), so every position pulls the value the
    same way, and with layers of one sign the terms of every gradient of
    the head add.

    The router's matrix is 3.3 times larger, so that its logits spread by
    about 3: a trained router is peaked, and with the logits of a fresh
    one (spread 0.9) the eighth and ninth expert of a token weigh the
    same, a bfloat16 rounding swaps them in a few tokens of a hundred,
    and each swap replaces an eighth of that token's output: the
    comparison would then measure how many near-ties a seed has. With the
    peaked router a swap exchanges two experts of weight ~0.02 of the
    first's; every expert is still some token's first.

    The draws keep their order and count, so a seed's other tensors are
    what they were before the head was pinned."""
    rng = np.random.default_rng([int(seed), 0x6D6F65])
    h, e, w = model["hidden_size"], model["num_experts"], model["expert_intermediate_size"]
    layers, inner = model["num_hidden_layers"], model["num_attention_heads"] * model["head_dim"]
    planes, hidden = model["input_planes"], model["value_hidden"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(n: int) -> np.ndarray:
        return (0.05 * rng.standard_normal(n)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    return {
        "embed_w": matrix(planes, h, fan_in=h), "embed_b": bias(h),
        "attn_norm": gain(layers, h),
        "wq": matrix(layers, h, inner, fan_in=h), "wk": matrix(layers, h, inner, fan_in=h),
        "wv": matrix(layers, h, inner, fan_in=h), "wo": matrix(layers, inner, h, fan_in=inner),
        "q_norm": gain(layers, model["head_dim"]), "k_norm": gain(layers, model["head_dim"]),
        "moe_norm": gain(layers, h),
        "router_w": matrix(layers, h, e, fan_in=h, scale=3.0),
        "experts_gate": matrix(layers, e, h, w, fan_in=h), "experts_up": matrix(layers, e, h, w, fan_in=h),
        "experts_down": matrix(layers, e, w, h, fan_in=w),
        "final_norm": gain(h),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        # relu(value conv) is about 1 a unit: 256 of them times |normal| (mean 0.8) / 205 is a hidden unit near 1
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }


def _rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """[B, 64, heads, head_dim]: rotate-half RoPE, position = square index."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    angle = np.arange(SQUARES, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angle), np.cos(angle)], axis=-1), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(angle), np.sin(angle)], axis=-1), jnp.float32)[None, :, None, :]
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def _product(cast: Cast, grad_cast: Cast):
    """Every contraction of the model: operands and incoming gradient in the precision, the result float32."""
    def product(subscripts: str, left: jax.Array, right: jax.Array) -> jax.Array:
        return grad_cast(jnp.einsum(subscripts, cast(left), cast(right))).astype(jnp.float32)

    return product


def features(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> jax.Array:
    """The final-normed trunk output [B, 8, 8, hidden]: what both heads read."""
    heads, head_dim, eps = model["num_attention_heads"], model["head_dim"], model["rms_norm_eps"]
    top_k, b = model["num_experts_per_tok"], planes.shape[0]
    product = _product(cast, grad_cast)

    x = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    for i in range(model["num_hidden_layers"]):
        p = {name: params[name][i] for name in _LAYER_TENSORS}
        n1 = _rms_norm(x, p["attn_norm"], eps)
        q, k, v = (product("bsh,hd->bsd", n1, p[name]).reshape(b, SQUARES, heads, head_dim) for name in ("wq", "wk", "wv"))
        q = _rope(_rms_norm(q, p["q_norm"], eps), model["rope_theta"])
        k = _rope(_rms_norm(k, p["k_norm"], eps), model["rope_theta"])
        probs = jax.nn.softmax(product("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim), axis=-1)
        mixed = product("bhqk,bkhd->bqhd", probs, v).reshape(b, SQUARES, heads * head_dim)
        x = x + product("bsd,dh->bsh", mixed, p["wo"])

        n2 = _rms_norm(x, p["moe_norm"], eps).reshape(b * SQUARES, -1)
        route = jax.nn.softmax(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"), axis=-1)
        kth = jnp.sort(route, axis=-1)[:, -top_k][:, None]
        weights = jnp.where(route >= kth, route, 0.0)  # [tokens, experts], zero off the top k

        def one_expert(total, expert):
            w_gate, w_up, w_down, weight = expert
            act = jax.nn.silu(product("th,hw->tw", n2, w_gate)) * product("th,hw->tw", n2, w_up)
            return total + weight[:, None] * product("tw,wh->th", act, w_down), None

        routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(n2),
                                 (p["experts_gate"], p["experts_up"], p["experts_down"], weights.T))
        x = x + routed.reshape(b, SQUARES, -1)

    return _rms_norm(x, params["final_norm"], eps).reshape(b, 8, 8, -1)


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


@jax.jit
def _adamw(param, mu, nu, grad, t, lr, wd):
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = b1 * mu + (1 - b1) * grad
    nu = b2 * nu + (1 - b2) * grad * grad
    step = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t)) + eps)
    return param - lr * (step + wd * param), mu, nu


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` AdamW updates on one batch, with
    ``grad(params, batch) -> (loss, gradients)`` of this module's ``loss``
    (Loshchilov & Hutter: decoupled weight decay; b1 0.9, b2 0.999, eps
    1e-8). One tensor at a time, each gradient dropped once used: at the
    published widths the parameters, both moments and the gradients are
    1.56 GiB each."""
    train = config["train"]
    lr, wd = jnp.float32(train["learning_rate"]), jnp.float32(train["weight_decay"])
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses = []
    for t in range(1, steps + 1):
        value, g = grad(params, batch)
        losses.append(value)
        g = dict(g)
        for k in list(params):
            params[k], mu[k], nu[k] = _adamw(params[k], mu[k], nu[k], g.pop(k).astype(jnp.float32), jnp.float32(t), lr, wd)
    return losses
