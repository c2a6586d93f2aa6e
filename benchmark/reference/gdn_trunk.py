"""Plain reference for the qwen3_next block as a square-token trunk
(Qwen3-Next-80B-A3B's): forward, loss, AdamW and the balance update.

Written from the published config.json of Qwen/Qwen3-Next-80B-A3B-Instruct
(``model_type`` qwen3_next) and, for what it does not say, the public
``qwen3_next`` modelling code in ``transformers`` and the Gated DeltaNet
paper (arXiv:2412.06464), as ``configs/qwen3-next-trunk-train.json`` lists
under ``assumed``. ``N`` is RMSNorm (eps 1e-6, statistics in float32) with a
ZERO-CENTRED gain, ``N(x; w) = x^ * (1 + w)``: the parameter is ``w``. ``n``
is the normed input, a board's 64 squares in index order, K key heads, V
value heads, d = 128 a head, r = V / K::

    embed    x = t W_in + b_in                                   (this repo's 19-plane embedding; no multiplier)
    layer i  a = x + Mixer_i(N_1(x));   y = a + MoE(N_2(a))      two norms a layer, no post-norms; Mixer_i as ``model["mixers"][i]``
    GDN      [q | k | v | z] a KEY head = n W_qkvz               the published column order: key head by key head, its q (d), k (d), its r
                                                                 value heads' v (r d) and z (r d);  W_ba likewise, a key head's b (r), a (r)
             [q | k | v] <- silu(conv([q | k | v]))              q, k, v flat side by side; ONE depthwise causal convolution, ``taps`` taps, no bias,
                                                                 nothing before square 0 (``gdn_conv`` [2 K d + V d, taps], the last tap the token's own)
             beta = sigmoid(b);  g = -exp(A_log[h]) * softplus(a + dt_bias[h])       [T, V] float32: ONE log-decay a value head and token
             q_h <- q_h / sqrt(|q_h|^2 + 1e-6) * d^-1/2;  k_h <- k_h / sqrt(|k_h|^2 + 1e-6);  value head h reads key head h // r
             value head h of board b, S [d, d] zero before square 0, square by square, LITERALLY:
               S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t
             out = ( N_d(o; PLAIN gain o_norm [d]) * silu(z) ) W_out                 the norm BEFORE the gate
    Attn     [q | gate] a head = n W_q                           the published order: head by head, its 256 query then its 256 gate columns
             k = n W_k, v = n W_v                                [KV heads x 256]
             q, k <- N over a head's 256 (zero-centred gains), then rotate-half RoPE on the FIRST ``rotary_dim`` 64 columns, theta 1e7,
             position = square index; query head h attends key-value head h // (H / KV); scores / sqrt(256); softmax; no mask on a board
             out = ( concat_h(P v) * sigmoid(gate) ) W_o
    MoE      p = softmax(n W_r) over all 512, float32;  chosen = top-10 of (p + b), b = ``expert_bias``: no gradient through b or the choice
             w_j = p[e_j] / (sum_j p[e_j] + 1e-20)                (norm_topk_prob; over all 10 chosen, held here or not)
             out = sigmoid(n w_s) * Shared(n) + sum over chosen e_j HELD HERE of w_j E_{e_j}(n)      Shared, E_e SiLU-gated, width 512
    balance  the share cells' rule (``reference/afmoe_trunk.py balanced_bias``): a DEPARTURE, the published model balances by an
             auxiliary loss, which is left out of the loss here as in the program
    out      N_final(y) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

The program computes a board as one chunk (a 64 x 64 unit-triangular solve
a value head, ``ops/board_delta.py``'s second form) and keeps its own column
orders (``families/gdn_trunk.py`` maps these parameters in and its gradients
back); nothing of either is here. ``model["misread"]`` (absent in every
configuration; ``benchmark/sweep_misread.py`` and the tests set it) computes
a plausible misreading instead, which the comparison has to tell from the
program: ``rate_times_1.5`` (``A_log`` x 1.5), ``key_head_mod`` (value head h
on key head ``h % K``), ``gate_sigmoid`` (the head norm's gate a sigmoid),
``gate_before_norm`` (the norm over the gated head), ``no_token_gate`` (the
shared expert ungated), ``rope_all`` (RoPE on all 256 columns),
``plain_gain`` (the q- and k-norms' gains read ``w`` in place of ``1 + w``).

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every layer, from ``first_held_expert``;
the mixers, the router, the shared expert and its gate are whole. What the
absent experts would have added is left out here as in the program, and the
shares of all chips with the gated shared expert counted once add up to the
layer (``tests/test_gdn_trunk.py``).

``jax.numpy`` only, float32, no kernel: the recurrence is a ``lax.scan``
over the squares, every held expert is applied to every token and masked by
the choice, every layer is made again in the backward pass. It imports
nothing of the program; the norm's statistics, the product in a precision,
the gated feed-forward, the causal convolution, the balance rule and AdamW
are the older trunks' references', imported.

``init_params`` conditions as the older references do (``reference/moe_trunk.py
init_params`` says why for each: matrices normal(0, 0.9^2 / fan_in), a peaked
router at 3.0 / sqrt(hidden), biases 0.05 normal, the value head pinned
alive, ``expert_bias`` a few balance steps' worth; the EMBEDDING at
sqrt(hidden) times the other matrices' scale, as the third trunk's
reference and for its reason: this block has no multiplier on the embedding
and no post-norms, and a stream that starts at the branches' scale is mostly
the sum of its branches, each of which doubles a relative error of its input
(a head's norm over 128 takes the scale of ``k . q`` out: read on the CPU at
the published head width, whatever the decay), so the comparison would read
how a seed's routing near-ties fall, not the program), with every zero-centred
``w`` 0.1 normal (a gain of 1 + 0.1 normal, as the plain gains), and the GDN
mixer's own tensors in the public layer's ranges, because the decay IS the
layer: ``A_log`` the log of a uniform draw in (0, 16), the head norm's
plain gain 1 + 0.1 normal, the taps uniform within 1 / sqrt(taps) with the
token's own tap moved to 1 + that (q, k and v are the token's projection
plus a mix of three earlier squares, not a sum that cancels). ONE of them
is conditioned: ``dt_bias`` is the inverse softplus of steps log-uniform in
[0.001, 0.1] (Mamba-2's range, as the sixth trunk's reference draws its
own), where the public reset has ones. At ones a step is ``softplus(1 + a)``
~ 1.3 under a rate up to 16: seven heads of eight keep under e^-2 of their
state a square and are memoryless, ``o_t = beta_t (k_t . q_t) v_t``, and the
head norm divides that by ``|k_t . q_t|``, which is near 0 on some square of
every board: the norm's gradient is ``1 / |k . q|`` there, one token's
gradient is then most of a tensor's, and the comparison reads how near a
seed's nearest zero lies, not the program (at the tiny size on the CPU:
all gradients as one vector 0.05, 0.10 and 3.0 on three seeds, ``dt_bias``'s
1.4; with the steps below 0.1 every head remembers, ``o_t`` is a sum over the
earlier squares, and the three read 0.03-0.05; on the chip at the published
widths 0.002-0.004). A fresh LEARNER starts at the
public reset (``models/trunk.py init_trunk_params``); the decay's
mathematics at fast rates is held by ``tests/test_board_delta.py`` against
the float64 recurrence.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16, q, k and v of the delta
rule among them (the program hands them to its core in bfloat16); the
router's product, the token gate's, the norms, g, the recurrence's decays,
the l2 norms, the softmaxes, the sigmoids and softplus stay float32 in it,
as in any fp8 recipe.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import afmoe_trunk as second_block
from benchmark.reference import kda_trunk as sixth_block
from benchmark.reference import moe_trunk as first_block
from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = first_block.SQUARES
_product, _gated, balanced_bias = first_block._product, second_block._gated, second_block.balanced_bias
_causal_conv = sixth_block._causal_conv  # depthwise along the squares, the last tap the token's own, nothing before square 0
BUFFER = "expert_bias"
L2_EPS = 1e-6
_GDN = ("gdn_qkvz", "gdn_ba", "gdn_conv", "gdn_dt_bias", "gdn_A_log", "gdn_o_norm", "gdn_out")
_ATTENTION = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
_ROUTED_LAYER = ("router_w", BUFFER, "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up", "shared_down", "shared_token_gate")


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, in the published column order
    (module docstring)."""
    rng = np.random.default_rng([int(seed), 0x67646E])
    h, planes, hidden, mixers = model["hidden_size"], model["input_planes"], model["value_hidden"], list(model["mixers"])
    layers, gdn, attn = len(mixers), mixers.count("gdn"), mixers.count("attention")
    heads, kv_heads, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    d, key_heads, value_heads, taps = model["linear_key_head_dim"], model["linear_num_key_heads"], model["linear_num_value_heads"], model["linear_conv_kernel_dim"]
    key, value = key_heads * d, value_heads * d
    held, experts, w, sw = model["num_experts"], model["num_routed_experts"], model["moe_intermediate_size"], model["shared_expert_intermediate_size"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int, centre: float = 1.0) -> np.ndarray:
        return (centre + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(n: int) -> np.ndarray:
        return (0.05 * rng.standard_normal(n)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    conv = rng.uniform(-1.0, 1.0, (gdn, 2 * key + value, taps)) / np.sqrt(taps)
    conv[..., -1] += 1.0
    steps = rng.integers(-3, 4, (layers, experts)).astype(np.float64) * model["load_balance_coeff"]
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (gdn, value_heads)))  # softplus(dt_bias): Mamba-2's range (module docstring)
    return {
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": np.float32(np.sqrt(h)) * bias(h),
        "attn_norm": gain(layers, h, centre=0.0), "moe_norm": gain(layers, h, centre=0.0),
        "gdn_qkvz": matrix(gdn, h, 2 * key + 2 * value, fan_in=h), "gdn_ba": matrix(gdn, h, 2 * value_heads, fan_in=h), "gdn_conv": conv.astype(np.float32),
        "gdn_dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32), "gdn_A_log": np.log(rng.uniform(0.0, 16.0, (gdn, value_heads))).astype(np.float32),
        "gdn_o_norm": gain(gdn, d), "gdn_out": matrix(gdn, value, h, fan_in=value),
        "wq": matrix(attn, h, 2 * heads * hd, fan_in=h), "wk": matrix(attn, h, kv_heads * hd, fan_in=h), "wv": matrix(attn, h, kv_heads * hd, fan_in=h),
        "q_norm": gain(attn, hd, centre=0.0), "k_norm": gain(attn, hd, centre=0.0), "wo": matrix(attn, heads * hd, h, fan_in=heads * hd),
        "router_w": matrix(layers, h, experts, fan_in=h, scale=3.0),
        BUFFER: (steps - steps.mean(axis=-1, keepdims=True)).astype(np.float32),
        "experts_gate": matrix(layers, held, h, w, fan_in=h), "experts_up": matrix(layers, held, h, w, fan_in=h),
        "experts_down": matrix(layers, held, w, h, fan_in=w),
        "shared_gate": matrix(layers, h, sw, fan_in=h), "shared_up": matrix(layers, h, sw, fan_in=h), "shared_down": matrix(layers, sw, h, fan_in=sw),
        "shared_token_gate": matrix(layers, h, 1, fan_in=h),
        "final_norm": gain(h, centre=0.0),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }


def _norm(x: jax.Array, w: jax.Array, eps: float, plain: bool = False) -> jax.Array:
    """RMSNorm with the zero-centred gain ``1 + w``, or (``plain``) with the gain ``w`` itself."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (w if plain else 1.0 + w)


def _delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array) -> jax.Array:
    """q, k, v [b, 64, V, d] (q and k already a value head's), g and beta [b, 64, V] -> o [b, 64, V, d]: the recurrence
    square by square, a state [d, d] a value head."""

    def square(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None, None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhcv,bhc->bhv", state, k_t, precision="highest"))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhcv,bhc->bhv", state, q_t, precision="highest")

    # Eight runs of eight squares, each run made again in the backward pass (and each square inside it), as the sixth trunk's
    # reference: kept a square for a whole board, a layer's states do not fit the chip beside ``correct``'s two trainer states.
    def run(state, squares):
        return jax.lax.scan(jax.checkpoint(square), state, squares)

    start = jnp.zeros((*q.shape[:1], *q.shape[2:], q.shape[-1]), jnp.float32)
    by_run = lambda y: jnp.moveaxis(y, 1, 0).reshape(8, SQUARES // 8, *y.shape[:1], *y.shape[2:])
    _, o = jax.lax.scan(jax.checkpoint(run), start, tuple(by_run(y) for y in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(SQUARES, *o.shape[2:]), 0, 1)


def _gdn(n1: jax.Array, p: Params, model: Dict[str, Any], product, cast: Cast) -> jax.Array:
    b, eps, misread = n1.shape[0], model["rms_norm_eps"], model.get("misread", "")
    d, key_heads, heads = model["linear_key_head_dim"], model["linear_num_key_heads"], model["linear_num_value_heads"]
    per = heads // key_heads
    qkvz = product("bsh,hd->bsd", n1, p["gdn_qkvz"]).reshape(b, SQUARES, key_heads, (2 + 2 * per) * d)  # a key head's q, k, its value heads' v, z
    ba = product("bsh,hd->bsd", n1, p["gdn_ba"]).reshape(b, SQUARES, key_heads, 2 * per)
    flat = lambda y: y.reshape(b, SQUARES, -1)
    q, k, v, z = flat(qkvz[..., :d]), flat(qkvz[..., d:2 * d]), flat(qkvz[..., 2 * d:(2 + per) * d]), flat(qkvz[..., (2 + per) * d:])
    beta, a = jax.nn.sigmoid(flat(ba[..., :per])), flat(ba[..., per:])
    mixed = cast(jax.nn.silu(_causal_conv(jnp.concatenate([q, k, v], axis=-1), p["gdn_conv"]))).astype(jnp.float32)
    q, k, v = (y.reshape(b, SQUARES, -1, d) for y in jnp.split(mixed, [key_heads * d, 2 * key_heads * d], axis=-1))
    rate = jnp.exp(p["gdn_A_log"] * (1.5 if misread == "rate_times_1.5" else 1.0))
    g = -rate * jax.nn.softplus(a + p["gdn_dt_bias"])
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / np.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    of = np.arange(heads) % key_heads if misread == "key_head_mod" else np.arange(heads) // per  # the key head a value head reads
    o = _delta_rule(q[:, :, of], k[:, :, of], v, g, beta)
    z = z.reshape(o.shape)
    gate = jax.nn.sigmoid(z) if misread == "gate_sigmoid" else jax.nn.silu(z)
    gated = _norm(o * gate, p["gdn_o_norm"], eps, plain=True) if misread == "gate_before_norm" else _norm(o, p["gdn_o_norm"], eps, plain=True) * gate
    return product("bsd,dh->bsh", flat(gated), p["gdn_out"])


def _rope_first(x: jax.Array, theta: float, rotary_dim: int) -> jax.Array:
    """[B, 64, heads, head_dim]: rotate-half RoPE inside the first ``rotary_dim`` columns, position = square index; the rest pass."""
    return jnp.concatenate([first_block._rope(x[..., :rotary_dim], theta), x[..., rotary_dim:]], axis=-1)


def _attention(n1: jax.Array, p: Params, model: Dict[str, Any], product) -> jax.Array:
    b, eps, misread = n1.shape[0], model["rms_norm_eps"], model.get("misread", "")
    heads, kv_heads, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    rotary = hd if misread == "rope_all" else model["rotary_dim"]
    qg = product("bsh,hd->bsd", n1, p["wq"]).reshape(b, SQUARES, heads, 2 * hd)  # a head's 256 query, then its 256 gate columns
    q, gate = qg[..., :hd], qg[..., hd:].reshape(b, SQUARES, heads * hd)
    k, v = (product("bsh,hd->bsd", n1, p[name]).reshape(b, SQUARES, kv_heads, hd) for name in ("wk", "wv"))
    plain = misread == "plain_gain"
    q = _rope_first(_norm(q, p["q_norm"], eps, plain), model["rope_theta"], rotary)
    k = _rope_first(_norm(k, p["k_norm"], eps, plain), model["rope_theta"], rotary)
    k, v = (jnp.repeat(y, heads // kv_heads, axis=2) for y in (k, v))  # query head h attends key-value head h // group
    probs = jax.nn.softmax(product("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd), axis=-1)
    mixed = product("bhqk,bkhd->bqhd", probs, v).reshape(b, SQUARES, heads * hd)
    return product("bsd,dh->bsh", mixed * jax.nn.sigmoid(gate), p["wo"])


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every layer's
    slots an expert [layers, experts] (all of them, held or not)."""
    eps, b, mixers = model["rms_norm_eps"], planes.shape[0], list(model["mixers"])
    top_k, first, held = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"]
    product = _product(cast, grad_cast)

    def layer(x: jax.Array, p: Params, mixer: str) -> Tuple[jax.Array, jax.Array]:
        n1 = _norm(x, p["attn_norm"], eps)
        x = x + (_gdn(n1, p, model, product, cast) if mixer == "gdn" else _attention(n1, p, model, product))
        n2 = _norm(x, p["moe_norm"], eps).reshape(b * SQUARES, -1)
        score = jax.nn.softmax(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"), axis=-1)
        chosen = score + jax.lax.stop_gradient(p[BUFFER])
        kth = jax.lax.stop_gradient(jnp.sort(chosen, axis=-1)[:, -top_k][:, None])
        picked = jnp.where(chosen >= kth, score, 0.0)  # [tokens, experts], zero off the top k
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        count = jnp.sum(chosen >= kth, axis=0).astype(jnp.float32)

        def one_expert(total, expert):
            w_gate, w_up, w_down, weight = expert
            return total + weight[:, None] * _gated(product, n2, w_gate, w_up, w_down), None

        shared = _gated(product, n2, p["shared_gate"], p["shared_up"], p["shared_down"])
        if model.get("misread", "") != "no_token_gate":
            shared = jax.nn.sigmoid(jnp.einsum("th,ho->to", n2, p["shared_token_gate"], precision="highest")) * shared
        out, _ = jax.lax.scan(jax.checkpoint(one_expert), shared, (
            p["experts_gate"], p["experts_up"], p["experts_down"], weights[:, first:first + held].T))
        return x + out.reshape(b, SQUARES, -1), count

    x = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    counts = []
    for i, mixer in enumerate(mixers):
        own = mixers[:i].count(mixer)  # a mixer's tensors are stacked over the layers of its kind
        p = {"attn_norm": params["attn_norm"][i], "moe_norm": params["moe_norm"][i]}
        p.update({name: params[name][own] for name in (_GDN if mixer == "gdn" else _ATTENTION)})
        p.update({name: params[name][i] for name in _ROUTED_LAYER})
        x, count = jax.checkpoint(layer, static_argnums=(2,))(x, p, mixer)
        counts.append(count)
    return _norm(x, params["final_norm"], eps).reshape(b, 8, 8, -1), jnp.stack(counts)


def features(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> jax.Array:
    """The final-normed trunk output [B, 8, 8, hidden]: what both heads read."""
    return _trunk(params, planes, model, cast, grad_cast)[0]


def forward(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast):
    x, b = features(params, planes, model, cast, grad_cast), planes.shape[0]
    product = _product(cast, grad_cast)
    policy = product("brfh,hp->brfp", x, params["policy_w"][0, 0]) + params["policy_b"]
    v = jax.nn.relu(product("brfh,hc->brfc", x, params["value_w"][0, 0]) + params["value_b"]).reshape(b, -1)
    v = jax.nn.relu(product("bi,ij->bj", v, params["value_fc1_w"]) + params["value_fc1_b"])
    v = jnp.tanh(product("bi,ij->bj", v, params["value_fc2_w"]) + params["value_fc2_b"])
    return policy.reshape(b, -1), v[:, 0]


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """Policy cross-entropy + value error: the repo's loss. The published model's auxiliary balance loss is left out."""
    logits, value = forward(params, batch["planes"], config["model"], cast_for(precision), grad_cast_for(precision))
    log_p = jax.nn.log_softmax(logits, axis=-1)
    policy_loss = -jnp.mean(jnp.sum(batch["policy_target"] * log_p, axis=-1))
    return policy_loss + config["train"]["value_weight"] * jnp.mean((value - batch["value_target"]) ** 2)


_SLOTS: Dict[str, Any] = {}  # one compiled routing count a model, shared by every seed of a sweep


def expert_slots(params: Params, planes: jax.Array, model: Dict[str, Any]) -> jax.Array:
    """Every layer's slots an expert, in float32: what the balance update reads."""
    key = json.dumps(model, sort_keys=True)
    if key not in _SLOTS:
        _SLOTS[key] = jax.jit(lambda p, x: _trunk(p, x, model, cast_for("float32"), grad_cast_for("float32"))[1])
    return _SLOTS[key](params, planes)


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` updates on one batch, with
    ``grad(params, batch) -> (loss, gradients)`` of this module's ``loss``:
    AdamW (the first trunk's reference's: decoupled weight decay on every
    trained tensor as it is held, so a zero-centred norm's ``w`` decays
    toward 0 and its gain toward 1) one tensor at a time, and the balance
    rule on ``expert_bias`` from the routing the step started with."""
    train, model = config["train"], config["model"]
    lr, wd = jnp.float32(train["learning_rate"]), jnp.float32(train["weight_decay"])
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    trained = [k for k in params if k != BUFFER]
    mu, nu = ({k: jnp.zeros_like(params[k]) for k in trained} for _ in range(2))
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
