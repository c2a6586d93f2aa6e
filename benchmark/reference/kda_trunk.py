"""Plain reference for the kimi_linear block as a square-token trunk
(Kimi-Linear-48B-A3B's): forward, loss, AdamW and the balance update.

Written from the published config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct (``model_type`` kimi_linear) and,
for what it does not say, the Kimi Linear report (arXiv:2510.26692) and the
public ``KimiDeltaAttention`` layer, as ``configs/kimi-linear-trunk-train.json``
lists under ``assumed``. ``N`` is RMSNorm (eps 1e-5, statistics in
float32), ``n`` the normed input, a board's 64 squares in index order::

    embed    x = t W_in + b_in                                   (this repo's 19-plane embedding)
    layer i  a = x + Mixer_i(N_in(x));   y = a + FFN_i(N_post(a))      two norms a layer, no post-norms
             Mixer_i = KDA or MLA as ``model["mixers"][i]`` says; FFN_i dense (SiLU-gated) in the leading layer, else routed
    KDA      H heads HELD, d a head, P = H d
             q, k, v = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v))     conv depthwise along the squares, ``taps`` taps,
                                                                  causal, no bias, nothing before square 0 (``kda_conv`` [3 P, taps]:
                                                                  q's, k's and v's channels in turn, the last tap the token's own)
             q_h <- q_h / sqrt(|q_h|^2 + 1e-6) * d^-1/2 ;  k_h <- k_h / sqrt(|k_h|^2 + 1e-6)
             g = -exp(A_log[h]) * softplus((n W_fa) W_fb + dt_bias)               [T, H, d]: a log-decay a CHANNEL;  alpha = exp(g)
             beta = sigmoid(n W_b)                                                [T, H]
             head h of board b, S [d, d] zero before square 0, square by square, LITERALLY:
               S <- Diag(alpha_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t
             out = ( N_d(o; gain o_norm [d]) * sigmoid((n W_ga) W_gb) ) W_o
    MLA      the third block's latent attention (``reference/mla_trunk.py``: no q latent, a 512-wide key-value latent under its
             norm, 128 + 64 score columns over 128-wide values, ONE 64-wide shared key part), the 64 columns NOT rotated
             (``mla_use_nope``); scores / sqrt(192); no mask on a board
    routed   the third block's: sigmoid scores over all the experts, the choice of 8 on score + expert_bias, weights
             renormalised over all 8 and scaled by 2.446; one SiLU-gated shared expert beside the HELD experts; its balance rule
    out      N_final(y) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

``u`` is the delta rule: what the state would answer for ``k_t`` after this
square's decay is taken from the value before it is written, ``S_t = (I -
beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``. The program
computes a board as one chunk (a 64 x 64 unit-triangular solve a head,
``ops/board_delta.py``); nothing of that form is here. ``model["misread"]``
(absent in every configuration; ``benchmark/sweep_misread.py`` and the
tests set it) computes a plausible misreading instead, which the
comparison has to tell from the program: ``decay_after`` (the decay
applied after the rank-one correction), ``unit_beta`` (beta fixed at 1) or
``gate_before_norm`` (the fourth block's order: the head norm over the
gated head).

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every routed layer, from
``first_held_expert``, and ``num_attention_heads`` / ``kda_num_heads`` of
each mixer's published heads: ``W_q``, ``W_k``, ``W_v``, ``W_fb``,
``W_gb``, ``W_b``, ``wq``, ``wkv_b`` have the held heads' columns, ``W_o``
and ``wo`` their rows. Both mixers are sums over heads (a KDA head's state,
norm and gate are its own; the latent is made once and every head reads
it), so what the absent heads and experts would have added is left out
here as in the program, and the shares of all chips add up to the layer
(``tests/test_kda_trunk.py``).

``jax.numpy`` only, float32, no kernel: the recurrence is a ``lax.scan``
over the squares, every held expert is applied to every token and masked
by the choice, every layer is made again in the backward pass. It imports
nothing of the program; the latent attention's pieces, the router, the
heads' conditioning and AdamW are the third trunk's reference's, imported.

``init_params`` is the third trunk's (its docstring says what is
conditioned and why: matrices normal(0, 0.9^2 / fan_in), gains 1 + 0.1
normal, a peaked router centred on a constant coordinate, the embedding at
sqrt(hidden) times the other matrices' scale, the value head pinned alive)
with the latent's tensors cut to the latent layers, and the KDA mixer's
beside them: its matrices as the others; ``kda_A_log`` and ``kda_dt_bias``
in the layer's own published ranges (rates uniform in [1, 16], steps
log-uniform in [0.001, 0.1]), because the decay IS the layer and a
conditioned one (every channel at alpha 0.9, say) would hide a kernel that
loses a fast channel; ``kda_conv`` uniform within 1 / sqrt(taps) with the
token's own tap moved to 1 + that, so that q, k and v are the token's
projection plus a mix of three earlier squares, not a sum that cancels.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16, q, k and v of the delta
rule among them (the program hands them to its core in bfloat16); the
router's product, the norms, the decays, the softmax and the sigmoids stay
float32 in it, as in any fp8 recipe.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mla_trunk as third_block
from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = third_block.SQUARES
_rms_norm, _product, _gated, balanced_bias = third_block._rms_norm, third_block._product, third_block._gated, third_block.balanced_bias
BUFFER = third_block.BUFFER
L2_EPS = 1e-6
_LATENT = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
_KDA = ("kda_q", "kda_k", "kda_v", "kda_conv", "kda_fa", "kda_fb", "kda_dt_bias", "kda_A_log", "kda_beta", "kda_ga", "kda_gb", "kda_o_norm",
        "kda_out")
_DENSE_LAYER, _ROUTED_LAYER = third_block._DENSE_LAYER, third_block._ROUTED_LAYER


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed (module docstring): the third
    trunk's reference's, the latent's cut to the latent layers, and the
    KDA mixers'."""
    mixers = list(model["mixers"])
    params = third_block.init_params(seed, model)
    for name in _LATENT:
        params[name] = np.ascontiguousarray(params[name][:mixers.count("latent")])
    rng = np.random.default_rng([int(seed), 0x6B6461])
    n, h, heads, d, taps = mixers.count("kda"), model["hidden_size"], model["kda_num_heads"], model["kda_head_dim"], model["short_conv_kernel_size"]
    p = heads * d
    matrix = lambda *shape: (rng.standard_normal(shape, dtype=np.float32) * np.float32(0.9 / np.sqrt(shape[-2])))
    conv = rng.uniform(-1.0, 1.0, (n, 3 * p, taps)) / np.sqrt(taps)
    conv[..., -1] += 1.0
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (n, p)))
    params.update({
        "kda_q": matrix(n, h, p), "kda_k": matrix(n, h, p), "kda_v": matrix(n, h, p), "kda_conv": conv.astype(np.float32),
        "kda_fa": matrix(n, h, d), "kda_fb": matrix(n, d, p), "kda_dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
        "kda_A_log": np.log(rng.uniform(1.0, 16.0, (n, heads))).astype(np.float32), "kda_beta": matrix(n, h, heads),
        "kda_ga": matrix(n, h, d), "kda_gb": matrix(n, d, p), "kda_o_norm": (1.0 + 0.1 * rng.standard_normal((n, d))).astype(np.float32),
        "kda_out": matrix(n, p, h),
    })
    return params


def _causal_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """``u`` [b, 64, channels], ``w`` [channels, taps]: ``out[t] = sum_k w[:, k] u[t - (taps - 1) + k]``, nothing before square 0."""
    taps = w.shape[-1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, k] * padded[:, k:k + SQUARES] for k in range(taps))


def _delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, misread: str) -> jax.Array:
    """q, k, v, g [b, 64, H, d], beta [b, 64, H] -> o [b, 64, H, d]: the recurrence square by square, a state [d, d] a head."""
    if misread == "unit_beta":
        beta = jnp.ones_like(beta)

    def square(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        alpha = jnp.exp(g_t)[..., :, None]
        if misread != "decay_after":
            state = alpha * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhcv,bhc->bhv", state, k_t, precision="highest"))
        state = state + k_t[..., :, None] * u[..., None, :]
        if misread == "decay_after":
            state = alpha * state
        return state, jnp.einsum("bhcv,bhc->bhv", state, q_t, precision="highest")

    # Eight runs of eight squares, each run made again in the backward pass (and each square inside it): what is kept is a state a
    # run and, while one run is differentiated, a state a square of it. Kept a square for a whole board, the three states of a
    # square are 6 GiB a layer at 32 boards of the published widths, and one of them 2 GiB.
    def run(state, squares):
        return jax.lax.scan(jax.checkpoint(square), state, squares)

    start = jnp.zeros((*q.shape[:1], *q.shape[2:], q.shape[-1]), jnp.float32)
    by_run = lambda y: jnp.moveaxis(y, 1, 0).reshape(8, SQUARES // 8, *y.shape[:1], *y.shape[2:])
    _, o = jax.lax.scan(jax.checkpoint(run), start, tuple(by_run(y) for y in (q, k, v, g, beta)))
    o = o.reshape(SQUARES, *o.shape[2:])
    return jnp.moveaxis(o, 0, 1)


def _kda(n1: jax.Array, p: Params, model: Dict[str, Any], product, cast: Cast) -> jax.Array:
    b, heads, d, eps = n1.shape[0], model["kda_num_heads"], model["kda_head_dim"], model["rms_norm_eps"]
    inner = heads * d
    by_head = lambda y: y.reshape(b, SQUARES, heads, d)
    projected = jnp.concatenate([product("bsh,hd->bsd", n1, p[name]) for name in ("kda_q", "kda_k", "kda_v")], axis=-1)
    q, k, v = (by_head(cast(y).astype(jnp.float32)) for y in jnp.split(jax.nn.silu(_causal_conv(projected, p["kda_conv"])), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / np.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    step = jax.nn.softplus(product("bsr,rd->bsd", product("bsh,hr->bsr", n1, p["kda_fa"]), p["kda_fb"]) + p["kda_dt_bias"])
    g = by_head(step) * -jnp.exp(p["kda_A_log"])[:, None]
    beta = jax.nn.sigmoid(product("bsh,hd->bsd", n1, p["kda_beta"]))
    misread = model.get("misread", "")
    o = _delta_rule(q, k, v, g, beta, misread)
    gate = by_head(jax.nn.sigmoid(product("bsr,rd->bsd", product("bsh,hr->bsr", n1, p["kda_ga"]), p["kda_gb"])))
    gated = _rms_norm(o * gate, p["kda_o_norm"], eps) if misread == "gate_before_norm" else _rms_norm(o, p["kda_o_norm"], eps) * gate
    return product("bsd,dh->bsh", gated.reshape(b, SQUARES, inner), p["kda_out"])


def _latent(n1: jax.Array, p: Params, model: Dict[str, Any], product) -> jax.Array:
    """The third block's latent attention, a head's 64 further score columns rotated or, ``mla_use_nope``, as they are."""
    b, heads, eps = n1.shape[0], model["num_attention_heads"], model["rms_norm_eps"]
    rank, nope, rope = model["kv_lora_rank"], model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    turned = (lambda y: y) if model["mla_use_nope"] else (lambda y: third_block._rope_pairs(y, model["rope_theta"]))
    q = product("bsh,hd->bsd", n1, p["wq"]).reshape(b, SQUARES, heads, nope + rope)
    ckv = product("bsh,hd->bsd", n1, p["wkv_a"])
    c, k_pe = _rms_norm(ckv[..., :rank], p["kv_norm"], eps), ckv[..., None, rank:]
    kv = product("bsr,rd->bsd", c, p["wkv_b"]).reshape(b, SQUARES, heads, -1)
    q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(turned(k_pe), (b, SQUARES, heads, rope))], axis=-1)
    scores = product("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
    mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:]).reshape(b, SQUARES, -1)
    return product("bsd,dh->bsh", mixed, p["wo"])


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every routed
    layer's slots an expert [routed layers, experts] (all of them, held
    or not)."""
    eps, b, mixers = model["rms_norm_eps"], planes.shape[0], list(model["mixers"])
    top_k, first, held = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"]
    product = _product(cast, grad_cast)

    def layer(x: jax.Array, p: Params, mixer: str, dense: bool) -> Tuple[jax.Array, jax.Array]:
        n1 = _rms_norm(x, p["attn_norm"], eps)
        x = x + (_kda(n1, p, model, product, cast) if mixer == "kda" else _latent(n1, p, model, product))
        n2 = _rms_norm(x, p["moe_norm"], eps).reshape(b * SQUARES, -1)
        if dense:
            out, count = _gated(product, n2, p["dense_gate"], p["dense_up"], p["dense_down"]), jnp.zeros((0,), jnp.float32)
        else:  # the third block's routed feed-forward, as its reference writes it
            score = jax.nn.sigmoid(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"))
            chosen = score + jax.lax.stop_gradient(p[BUFFER])
            kth = jax.lax.stop_gradient(jnp.sort(chosen, axis=-1)[:, -top_k][:, None])
            picked = jnp.where(chosen >= kth, score, 0.0)
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
    for i, mixer in enumerate(mixers):
        r = i - model["num_dense_layers"]
        own = mixers[:i].count(mixer)  # a mixer's tensors are stacked over the layers of its kind
        p = {"attn_norm": params["attn_norm"][i], "moe_norm": params["moe_norm"][i]}
        p.update({name: params[name][own] for name in (_KDA if mixer == "kda" else _LATENT)})
        p.update({name: params[name][i if r < 0 else r] for name in (_DENSE_LAYER if r < 0 else _ROUTED_LAYER)})
        x, count = jax.checkpoint(layer, static_argnums=(2, 3))(x, p, mixer, r < 0)
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
    v = jax.nn.relu(product("brfh,hc->brfc", x, params["value_w"][0, 0]) + params["value_b"]).reshape(b, -1)
    v = jax.nn.relu(product("bi,ij->bj", v, params["value_fc1_w"]) + params["value_fc1_b"])
    v = jnp.tanh(product("bi,ij->bj", v, params["value_fc2_w"]) + params["value_fc2_b"])
    return policy.reshape(b, -1), v[:, 0]


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    logits, value = forward(params, batch["planes"], config["model"], cast_for(precision), grad_cast_for(precision))
    log_p = jax.nn.log_softmax(logits, axis=-1)
    policy_loss = -jnp.mean(jnp.sum(batch["policy_target"] * log_p, axis=-1))
    return policy_loss + config["train"]["value_weight"] * jnp.mean((value - batch["value_target"]) ** 2)


_SLOTS: Dict[str, Any] = {}  # one compiled routing count a model, shared by every seed of a sweep


def expert_slots(params: Params, planes: jax.Array, model: Dict[str, Any]) -> jax.Array:
    """Every routed layer's slots an expert, in float32: what the balance update reads."""
    key = json.dumps(model, sort_keys=True)
    if key not in _SLOTS:
        _SLOTS[key] = jax.jit(lambda p, x: _trunk(p, x, model, cast_for("float32"), grad_cast_for("float32"))[1])
    return _SLOTS[key](params, planes)


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The third trunk's reference's (AdamW on every trained tensor, the
    balance rule on ``expert_bias`` from the routing the step started
    with), counting the slots with this module's trunk."""
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
            params[k], mu[k], nu[k] = third_block.first_block._adamw(params[k], mu[k], nu[k], g.pop(k).astype(jnp.float32), jnp.float32(t), lr, wd)
        params[BUFFER] = balanced_bias(params[BUFFER], slots, model["load_balance_coeff"])
    return losses
