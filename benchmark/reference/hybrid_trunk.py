"""Plain reference for the nemotron_h block as a square-token trunk
(Nemotron-Labs-TwoTower-30B-A3B's one declared tower): forward, loss,
AdamW and the balance update.

Written from the published config.json of
nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 (``model_type``
nemotron_h) and, for what it does not say, the Mamba-2 mixer as published
(Dao & Gu 2024, "Transformers are SSMs", the SSD layer with grouped B and
C, a depthwise convolution over ``[x | B | C]`` and a gated grouped
RMSNorm), as ``configs/nemotron-twotower-trunk-train.json`` lists under
``assumed``. A layer is ONE sublayer under ONE norm, of the kind the
pattern names. ``N`` is RMSNorm (eps 1e-5, statistics in float32), ``n``
the normed input, 64 tokens a board, boards never mix::

    embed    x = t W_in + b_in                                   (no scale; W_in is this repo's 19-plane embedding)
    layer i  x <- x + Mixer_kind(i)( N_i(x) )                    kind(i) = pattern[i]: M, E or *
    M        [z | xBC | dt] = n W_inproj                         [2688, 4096 + 6144 + 64];  xBC = [x | B | C] = 4096 + 8 x 128 + 8 x 128
             xBC <- silu( conv(xBC) ),  conv(u)[t] = b + sum_k w[:, k] u[t - 3 + k]    depthwise, 4 taps, nothing before square 0
             D_t = softplus(dt_t + dt_bias) [64 heads];  a = -exp(A_log) [64]
             head h (64 columns of x), group g = h // 8 (128 columns of B and of C), the squares t = 0..63 IN ORDER:
               S_t = exp(D_t a) S_{t-1} + D_t x_t B_t^T          S [64, 128], zero before square 0 of every board
               y_t = S_t C_t + D_skip[h] x_t
             y <- N_grouped( y * silu(z); gain [4096], 8 groups of 512 )
             out = y W_outproj                                   [4096, 2688]
    *        q = n W_q [32 x 128];  k = n W_k, v = n W_v [2 x 128];  RoPE (theta 10000, all 128 columns, rotate-half) on the square index;
             no qk-norm, no gate;  head h attends key-value head h // 16 within a board, no mask, scores / sqrt(128);  out = concat W_o
    E        s = sigmoid(n W_r) over all 128 experts, float32
             chosen = top-6 of (s + b),  b = ``expert_bias``, no gradient through b or the choice
             w_j = 2.5 * s[e_j] / (sum_j s[e_j] + 1e-20)         (norm_topk_prob; over all 6 chosen, held or not)
             E_e(u) = relu(u W_up[e])^2 W_down[e]                TWO products, no gate (mlp_hidden_act relu2)
             out = Shared(n) + sum over chosen e_j HELD HERE of w_j E_{e_j}(n);   Shared(u) = relu(u W_sup)^2 W_sdown, width 3712
    balance  after a step, a routed layer's c_e = slots routed to expert e (all 128, held or not):
             d = 0.001 * sign(mean(c) - c);  b <- b + d - mean(d)
    out      N_final(x) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

The mixer's core is the SEQUENTIAL RECURRENCE, a ``lax.scan`` over the 64
squares with a state ``[64, 128]`` a head, one head at a time: not the
dual (quadratic) form the program's kernels compute, so that the two do
not share a derivation.

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every routed layer, from
``first_held_expert``; what the absent experts would have added is left
out here as in the program.

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch: every
held expert is applied to every token and the result masked by the
choice, every layer made again in the backward pass (and every head of a
mixer). It imports nothing of the program; the norm, RoPE, the product in
a precision and AdamW are the first trunk's reference's, the balance rule
the second's, imported. Parameters carry the names of the program's
``.npz`` checkpoint format, the layers of a kind stacked on a leading
axis (``conv_w`` is the published ``conv1d.weight`` without its middle
axis of one); ``expert_bias`` is among them, has a zero gradient, and
``train_losses`` moves it by the balance rule and never by AdamW.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16, the convolution's and
the recurrence's (x, B, C) among them; the router's product, the norms,
the softmax, the sigmoid, softplus and the decay stay float32 in it, as
in any fp8 recipe.
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
_rms_norm, _rope, _product, balanced_bias = first_block._rms_norm, first_block._rope, first_block._product, second_block.balanced_bias
BUFFER = "expert_bias"
_BY_KIND = {
    "M": ("mamba_in", "conv_w", "conv_b", "dt_bias", "A_log", "D_skip", "mamba_norm", "mamba_out"),
    "*": ("wq", "wk", "wv", "wo"),
    "E": ("router_w", BUFFER, "experts_up", "experts_down", "shared_up", "shared_down"),
}
#: A token's largest routing logit sits here (sigmoid 0.7) whatever the number of experts; the centre follows (below).
_FIRST_LOGIT = 0.85
#: The spread of a router's logits: its matrix at 3.0 / sqrt(hidden) on a normed stream whose constant coordinate takes ~5%.
_LOGIT_SPREAD = 2.85
#: The mean square a branch adds to a coordinate of the stream, with the branches' last matrices at ``_OUT`` (below): read on the
#: CPU at the published widths, 8 boards, 0.2-0.3 a layer of any kind (at 0.9 they add 0.8-1.0 each: a squared ReLU and a gated
#: norm pass on more than a softmax over near-equal keys and a SiLU gate do).
_BRANCH = 0.25
#: The scale of a branch's LAST matrix (``mamba_out``, ``wo``, ``experts_down``, ``shared_down``) beside the other matrices' 0.9.
_OUT = 0.45
#: Mamba-2's initial steps: log-uniform in [time_step_min, time_step_max], at least time_step_floor.
_TIME_STEP = (0.001, 0.1, 1e-4)


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, conditioned as the third trunk's
    reference conditions its own (``reference/mla_trunk.py init_params``
    says why for each): matrices normal(0, 0.9^2 / fan_in), gains 1 + 0.1
    normal, biases 0.05 normal, the value head pinned alive,
    ``expert_bias`` a few balance steps' worth, the embedding at
    sqrt(hidden) times the other matrices' scale with a constant
    coordinate 0, and on it a router that is peaked and centred below
    zero (a token's largest logit 0.85).

    One thing is conditioned for this block alone: a branch's last
    matrix is drawn at half the other matrices' scale (``_OUT``). At 0.9
    every layer adds 0.8-1.0 to a coordinate's mean square beside the
    embedding's 11 (the third trunk's branches add 0.25), so by the
    second router a fifth of the stream is branches' output, rounded in
    bfloat16, and the routers' logits carry that rounding: on the chip
    the program then swaps a token's sixth and seventh expert often
    enough that ``router_w``, whose columns for ABSENT experts are sums
    of few signed terms, read 0.14 where every other tensor read 0.003
    (seed 2914700123, PERF.md section 6, PR 41). The published
    initialisation scales these matrices down as well
    (``rescale_prenorm_residual``: by 1 / sqrt(2 x layers)).

    The mixer's own tensors start where Mamba-2's do, because that is
    where its gradients are those of a mixer that remembers: the decay
    rates ``exp(A_log)`` uniform in [1, 16], the steps
    ``softplus(dt_bias)`` log-uniform in [0.001, 0.1] (``dt_bias`` their
    inverse softplus), the direct term ``D_skip`` a gain (1 + 0.1
    normal), the convolution's four taps normal(0, 0.9^2 / 4) under a
    bias of 0.05 normal."""
    rng = np.random.default_rng([int(seed), 0x6E656D])
    h, planes, hidden = model["hidden_size"], model["input_planes"], model["value_hidden"]
    pattern, head_dim = model["pattern"], model["head_dim"]
    m, routed, attn = pattern.count("M"), pattern.count("E"), pattern.count("*")
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    ssm_heads, taps = model["mamba_num_heads"], model["conv_kernel"]
    inner, state = ssm_heads * model["mamba_head_dim"], model["n_groups"] * model["ssm_state_size"]
    held, experts = model["num_experts"], model["num_routed_experts"]
    w, sw = model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(*shape: int, scale: float = 0.05) -> np.ndarray:
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    low, high, floor = _TIME_STEP
    steps = np.maximum(np.exp(rng.uniform(np.log(low), np.log(high), (m, ssm_heads))), floor)
    params = {
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": bias(h, scale=0.05 * np.sqrt(h)),
        "layer_norm": gain(len(pattern), h),
        "mamba_in": matrix(m, h, 2 * inner + 2 * state + ssm_heads, fan_in=h),
        "conv_w": matrix(m, inner + 2 * state, taps, fan_in=taps), "conv_b": bias(m, inner + 2 * state),
        "dt_bias": (steps + np.log(-np.expm1(-steps))).astype(np.float32),
        "A_log": np.log(rng.uniform(1.0, 16.0, (m, ssm_heads))).astype(np.float32),
        "D_skip": gain(m, ssm_heads), "mamba_norm": gain(m, inner), "mamba_out": matrix(m, inner, h, fan_in=inner, scale=_OUT),
        "wq": matrix(attn, h, heads * head_dim, fan_in=h), "wk": matrix(attn, h, kv_heads * head_dim, fan_in=h),
        "wv": matrix(attn, h, kv_heads * head_dim, fan_in=h), "wo": matrix(attn, heads * head_dim, h, fan_in=heads * head_dim, scale=_OUT),
        "router_w": matrix(routed, h, experts, fan_in=h, scale=3.0),
        "experts_up": matrix(routed, held, h, w, fan_in=h), "experts_down": matrix(routed, held, w, h, fan_in=w, scale=_OUT),
        "shared_up": matrix(routed, h, sw, fan_in=h), "shared_down": matrix(routed, sw, h, fan_in=sw, scale=_OUT),
        "final_norm": gain(h),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }
    # The constant coordinate and the routers' centre on it, as the third trunk's reference: every layer before a router is one branch.
    routers = [i for i, kind in enumerate(pattern) if kind == "E"]
    params["embed_w"][:, 0], params["embed_b"][0] = 0.0, np.sqrt(h)
    params["layer_norm"][routers, 0] = 1.0
    embedded = h * (1.0 / h + (1.0 - 1.0 / h) * (4 * 0.81 / h + 0.0025))  # mean square of a coordinate of x = t W_in + b_in
    centre = _FIRST_LOGIT - _LOGIT_SPREAD * NormalDist().inv_cdf(1.0 - 0.5 / experts)  # the largest of `experts` normal draws
    for r, i in enumerate(routers):
        params["router_w"][r, 0, :] = centre * np.sqrt(embedded + _BRANCH * i) / np.sqrt(h)
    moves = rng.integers(-3, 4, (routed, experts)).astype(np.float64) * model["load_balance_coeff"]
    params[BUFFER] = (moves - moves.mean(axis=-1, keepdims=True)).astype(np.float32)
    return params


def _recurrence(x: jax.Array, b: jax.Array, c: jax.Array, step: jax.Array, rate: jax.Array, skip: jax.Array) -> jax.Array:
    """x [B, 64, H, P], b and c [B, 64, G, N], step [B, 64, H], rate and
    skip [H] -> y [B, 64, H, P]: one head at a time (each made again in
    the backward pass: a head's 64 states of a chunk of boards are what a
    step of the scan keeps), its squares in order."""
    heads, per_group = x.shape[2], x.shape[2] // b.shape[2]

    def head(args):
        x_h, step_h, rate_h, skip_h, group = args  # [B, 64, P], [B, 64], (), (), ()
        b_g, c_g = (jax.lax.dynamic_index_in_dim(y, group, axis=2, keepdims=False) for y in (b, c))  # [B, 64, N]

        def square(state, now):
            x_t, b_t, c_t, d_t = now  # [B, P], [B, N], [B, N], [B]
            state = jnp.exp(d_t * rate_h)[:, None, None] * state + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.sum(state * c_t[:, None, :], axis=-1) + skip_h * x_t

        start = jnp.zeros((x_h.shape[0], x_h.shape[-1], b_g.shape[-1]), jnp.float32)
        _, y = jax.lax.scan(square, start, tuple(jnp.moveaxis(y, 1, 0) for y in (x_h, b_g, c_g, step_h)))
        return jnp.moveaxis(y, 0, 1)

    ys = jax.lax.map(jax.checkpoint(head), (jnp.moveaxis(x, 2, 0), jnp.moveaxis(step, 2, 0), rate, skip, jnp.arange(heads) // per_group))
    return jnp.moveaxis(ys, 0, 2)


def _ungated(product, n: jax.Array, up_w: jax.Array, down_w: jax.Array) -> jax.Array:
    return product("tw,wh->th", jnp.square(jax.nn.relu(product("th,hw->tw", n, up_w))), down_w)


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every routed
    layer's slots an expert [routed layers, experts] (all of them, held
    or not)."""
    eps, theta, b = model["rms_norm_eps"], model["rope_theta"], planes.shape[0]
    heads, kv_heads, head_dim = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    ssm_heads, groups, taps = model["mamba_num_heads"], model["n_groups"], model["conv_kernel"]
    inner, state = ssm_heads * model["mamba_head_dim"], groups * model["ssm_state_size"]
    top_k, first, held = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"]
    product = _product(cast, grad_cast)

    def mixer(n: jax.Array, p: Params) -> jax.Array:
        proj = product("bsh,hd->bsd", n, p["mamba_in"])
        z, xbc, dt = proj[..., :inner], proj[..., inner:2 * inner + 2 * state], proj[..., 2 * inner + 2 * state:]
        padded, taps_w = jnp.pad(cast(xbc), ((0, 0), (taps - 1, 0), (0, 0))), cast(p["conv_w"])
        conv = sum((padded[:, k:k + SQUARES] * taps_w[:, k]).astype(jnp.float32) for k in range(taps))
        xbc = cast(jax.nn.silu(grad_cast(conv) + p["conv_b"])).astype(jnp.float32)  # the recurrence's operands in the precision
        x, b_, c_ = xbc[..., :inner], xbc[..., inner:inner + state], xbc[..., inner + state:]
        y = _recurrence(x.reshape(b, SQUARES, ssm_heads, -1), b_.reshape(b, SQUARES, groups, -1), c_.reshape(b, SQUARES, groups, -1),
                        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]), p["D_skip"])
        y = grad_cast(y.reshape(b, SQUARES, inner)) * jax.nn.silu(z)
        y = _rms_norm(y.reshape(b, SQUARES, groups, -1), p["mamba_norm"].reshape(groups, -1), eps).reshape(b, SQUARES, inner)
        return product("bsd,dh->bsh", y, p["mamba_out"])

    def attention(n: jax.Array, p: Params) -> jax.Array:
        q = product("bsh,hd->bsd", n, p["wq"]).reshape(b, SQUARES, heads, head_dim)
        k, v = (product("bsh,hd->bsd", n, p[name]).reshape(b, SQUARES, kv_heads, head_dim) for name in ("wk", "wv"))
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = (jnp.repeat(y, heads // kv_heads, axis=2) for y in (k, v))  # query head h attends key-value head h // 16
        scores = product("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
        mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, SQUARES, heads * head_dim)
        return product("bsd,dh->bsh", mixed, p["wo"])

    def routed(n: jax.Array, p: Params) -> Tuple[jax.Array, jax.Array]:
        n2 = n.reshape(b * SQUARES, -1)
        score = jax.nn.sigmoid(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"))
        chosen = score + jax.lax.stop_gradient(p[BUFFER])
        kth = jax.lax.stop_gradient(jnp.sort(chosen, axis=-1)[:, -top_k][:, None])
        picked = jnp.where(chosen >= kth, score, 0.0)  # [tokens, experts], zero off the top k
        weights = model["route_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

        def one_expert(total, expert):
            w_up, w_down, weight = expert
            return total + weight[:, None] * _ungated(product, n2, w_up, w_down), None

        shared = _ungated(product, n2, p["shared_up"], p["shared_down"])
        out, _ = jax.lax.scan(jax.checkpoint(one_expert), shared, (p["experts_up"], p["experts_down"], weights[:, first:first + held].T))
        return out.reshape(b, SQUARES, -1), jnp.sum(chosen >= kth, axis=0).astype(jnp.float32)

    def layer(x: jax.Array, p: Params, kind: str) -> Tuple[jax.Array, jax.Array]:
        n = _rms_norm(x, p["layer_norm"], eps)
        if kind == "E":
            out, count = routed(n, p)
            return x + out, count
        return x + (mixer(n, p) if kind == "M" else attention(n, p)), jnp.zeros((0,), jnp.float32)

    x = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    counts, seen = [], dict.fromkeys(_BY_KIND, 0)
    for i, kind in enumerate(model["pattern"]):
        p = {name: params[name][seen[kind]] for name in _BY_KIND[kind]}
        p["layer_norm"] = params["layer_norm"][i]
        seen[kind] += 1
        # Each layer is made again in the backward pass, as the other trunks' references do.
        x, count = jax.checkpoint(layer, static_argnums=(2,))(x, p, kind)
        if kind == "E":
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
