"""Plain reference for the ouro block as a square-token trunk (Ouro-2.6B's):
a stack of layers run several times over the same weights, an exit at every
pass; forward, loss and AdamW.

Written from the published config.json of ByteDance/Ouro-2.6B (``model_type``
ouro) and, for what it does not say, the public ``ouro`` modelling code and
the model's report ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), as ``configs/ouro-2.6b-trunk-train.json`` lists under
``assumed``. ``N`` is RMSNorm with a plain gain (eps 1e-6, statistics in
float32), 64 tokens a board, L layers, T = ``total_ut_steps`` passes::

    embed    h_0 = t W_e + b_e                                  (this repo's 19-plane embedding; no scale)
    layer l  a = h + N2_l( Attn_l( N1_l(h) ) );   h' = a + N4_l( FFN_l( N3_l(a) ) )        (sandwich norms: four a layer; the weights of
                                                                layer l are the same at every pass)
    Attn     q = n W_q, k = n W_k, v = n W_v [16 x 128 each];  no bias, no qk-norm
             q, k <- rotate-half RoPE at theta 1e6 over all 128 columns, position = square index
             softmax(q k^T / sqrt(128)) v over a board's 64 squares (no mask), concat of the heads through W_o
    FFN      (silu(n W_g) * (n W_u)) W_d, width 5632
    loop     h_t = (Layer_{L-1} o ... o Layer_0)(h_{t-1}),  t = 1 .. T
    exits    f_t = N_f(h_t)                                     (ONE final gain for all t)
             policy_t = a 1x1 convolution of f_t to 73 planes;  value_t = a 1x1 convolution to 4, relu, fc, relu, fc, tanh      (one set of head weights)
             g_t = mean over the board's 64 squares of (f_t w_g + b_g);  lambda_t = sigmoid(g_t)        (ONE gate a board: the repo's departure)
    p        p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j), 1 < t < T;  p_T = prod_{j<T} (1 - lambda_j)
    loss     l_t = CE(policy_t, target) + value_weight (value_t - z)^2 a board
             L = mean over boards of [ sum_t p_t l_t - beta H(p) ],  H(p) = - sum_t p_t log p_t,  beta = exit_entropy_weight

``jax.numpy`` only, float32, no kernel and no scan: a Python loop over the
passes and the layers, each layer's application under ``jax.checkpoint`` (its
input is kept and the layer made again in the backward pass, so that 24 layer
passes of float32 temporaries are one's). It imports nothing of the program:
the exit distribution is the plain products above, where the program works in
logarithms; the norm, RoPE, the product in a precision and AdamW
(``train_losses``: no buffer rides beside these parameters) are the first
trunk's reference's, imported. ``model["misread"]`` (absent in every configuration;
``benchmark/sweep_misread.py`` and the tests set it) computes a plausible
misreading instead, which the comparison has to tell from the block:
``three_passes`` (T - 1 passes for T), ``last_pass_gradient`` (the passes
before the last under a stop-gradient: each weight's gradient from its last
use alone), ``remainder_lost`` (``p_T = lambda_T prod_{j<T} (1 - lambda_j)``:
the probabilities no longer sum to 1), ``no_entropy`` (beta 0),
``heads_without_final_norm`` (the heads and the gate on ``h_t``),
``no_middle_norms`` (``N2`` and ``N4`` left out: two norms a layer).

``init_params`` conditions as the older references do (``reference/moe_trunk.py
init_params`` says why for each): matrices normal(0, 0.9^2 / fan_in), gains 1
+ 0.1 normal, biases 0.05 normal, the value head pinned alive and of one sign;
the embedding at the scale of a branch (a sandwich-normed branch adds a vector
of rms about 1 whatever its weights' scale, so the stream starts there too:
entries 0.9, the bias sqrt(hidden) x 0.05 normal, as the eighth trunk's
reference); the gate's ``exit_gate_w`` at the other matrices' scale and its
bias 0.05 normal, so that a board's logit, the mean over 64 near-equal squares
of a unit-variance sum, lies within about +-1 of 0: every ``lambda`` between
0.25 and 0.75, every ``p_t`` over 0.01, no exit's loss out of the gradient.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import moe_trunk as first_block
from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = first_block.SQUARES
_rms_norm, _rope, _product, train_losses = first_block._rms_norm, first_block._rope, first_block._product, first_block.train_losses
_LAYER_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "post_attn_norm", "moe_norm", "dense_gate", "dense_up", "dense_down", "post_mlp_norm")


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, under the names of the program's checkpoint (module docstring)."""
    rng = np.random.default_rng([int(seed), 0x6F75726F])
    h, planes, hidden, layers = model["hidden_size"], model["input_planes"], model["value_hidden"], model["num_hidden_layers"]
    inner, w = model["num_attention_heads"] * model["head_dim"], model["intermediate_size"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(n: int) -> np.ndarray:
        return (0.05 * rng.standard_normal(n)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    return {
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": np.float32(np.sqrt(h)) * bias(h),
        "attn_norm": gain(layers, h), "post_attn_norm": gain(layers, h), "moe_norm": gain(layers, h), "post_mlp_norm": gain(layers, h),
        "wq": matrix(layers, h, inner, fan_in=h), "wk": matrix(layers, h, inner, fan_in=h), "wv": matrix(layers, h, inner, fan_in=h),
        "wo": matrix(layers, inner, h, fan_in=inner),
        "dense_gate": matrix(layers, h, w, fan_in=h), "dense_up": matrix(layers, h, w, fan_in=h), "dense_down": matrix(layers, w, h, fan_in=w),
        "final_norm": gain(h),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        # relu(value conv) is about 1 a unit: 256 of them times |normal| (mean 0.8) / 205 is a hidden unit near 1
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
        "exit_gate_w": matrix(h, 1, fan_in=h), "exit_gate_b": bias(1),
    }


def _layer(x: jax.Array, p: Params, model: Dict[str, Any], product) -> jax.Array:
    """One layer on the stream ``[B, 64, hidden]``: attention and the feed-forward, each between its two norms."""
    heads, head_dim, eps, theta = model["num_attention_heads"], model["head_dim"], model["rms_norm_eps"], float(model["rope_theta"])
    b, after = x.shape[0], (lambda y, gain: y) if model.get("misread") == "no_middle_norms" else (lambda y, gain: _rms_norm(y, gain, eps))
    n1 = _rms_norm(x, p["attn_norm"], eps)
    q, k, v = (product("bsh,hd->bsd", n1, p[name]).reshape(b, SQUARES, heads, head_dim) for name in ("wq", "wk", "wv"))
    q, k = _rope(q, theta), _rope(k, theta)
    probs = jax.nn.softmax(product("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim), axis=-1)
    mixed = product("bhqk,bkhd->bqhd", probs, v).reshape(b, SQUARES, heads * head_dim)
    a = x + after(product("bsd,dh->bsh", mixed, p["wo"]), p["post_attn_norm"])
    n3 = _rms_norm(a, p["moe_norm"], eps)
    act = jax.nn.silu(product("bsh,hw->bsw", n3, p["dense_gate"])) * product("bsh,hw->bsw", n3, p["dense_up"])
    return a + after(product("bsw,wh->bsh", act, p["dense_down"]), p["post_mlp_norm"])


def exits(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Every pass's policy logits ``[T, B, 4672]``, value ``[T, B]`` and gate logit ``[T, B]``."""
    misread, eps, b = model.get("misread"), model["rms_norm_eps"], planes.shape[0]
    product = _product(cast, grad_cast)
    layer = jax.checkpoint(lambda x, p: _layer(x, p, model, product))
    passes = model["total_ut_steps"] - (1 if misread == "three_passes" else 0)
    h = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    logits, values, gates = [], [], []
    for t in range(passes):
        if misread == "last_pass_gradient" and t == passes - 1:
            h = jax.lax.stop_gradient(h)
        for i in range(model["num_hidden_layers"]):
            weights = {name: params[name][i] for name in _LAYER_TENSORS}
            if misread == "last_pass_gradient" and t < passes - 1:
                weights = jax.lax.stop_gradient(weights)
            h = layer(h, weights)
        f = h if misread == "heads_without_final_norm" else _rms_norm(h, params["final_norm"], eps)
        x = f.reshape(b, 8, 8, -1)
        policy = product("brfh,hp->brfp", x, params["policy_w"][0, 0]) + params["policy_b"]
        v = jax.nn.relu(product("brfh,hc->brfc", x, params["value_w"][0, 0]) + params["value_b"]).reshape(b, -1)
        v = jax.nn.relu(product("bi,ij->bj", v, params["value_fc1_w"]) + params["value_fc1_b"])
        v = jnp.tanh(product("bi,ij->bj", v, params["value_fc2_w"]) + params["value_fc2_b"])
        logits.append(policy.reshape(b, -1))  # (square, plane) order
        values.append(v[:, 0])
        gates.append(jnp.mean(jnp.sum(f * params["exit_gate_w"][:, 0], axis=-1), axis=-1) + params["exit_gate_b"][0])  # float32: the gate is no product
    return jnp.stack(logits), jnp.stack(values), jnp.stack(gates)


def exit_distribution(gates: jax.Array, misread: Any = None) -> jax.Array:
    """Gate logits ``[T, B]`` -> the probability ``[T, B]`` that a board leaves at pass t: the plain products of the module docstring."""
    lam = jax.nn.sigmoid(gates)
    stay, probs = jnp.ones_like(lam[0]), []
    for t in range(gates.shape[0] - 1):
        probs.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    probs.append(lam[-1] * stay if misread == "remainder_lost" else stay)
    return jnp.stack(probs)


def forward(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """What is served: a board's first pass at which the cumulative exit probability reaches ``early_exit_threshold``, else its last."""
    logits, values, gates = exits(params, planes, model, cast, grad_cast)
    reached = jnp.cumsum(exit_distribution(gates), axis=0) >= model["early_exit_threshold"]
    chosen = jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), gates.shape[0] - 1)
    board = jnp.arange(planes.shape[0])
    return logits[chosen, board], values[chosen, board]


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    model, train = config["model"], config["train"]
    logits, values, gates = exits(params, batch["planes"], model, cast_for(precision), grad_cast_for(precision))
    policy = -jnp.sum(batch["policy_target"][None] * jax.nn.log_softmax(logits, axis=-1), axis=-1)  # [T, B]
    each = policy + train["value_weight"] * (values - batch["value_target"][None]) ** 2
    p = exit_distribution(gates, model.get("misread"))
    beta = 0.0 if model.get("misread") == "no_entropy" else train["exit_entropy_weight"]
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * each, axis=0) - beta * entropy)
