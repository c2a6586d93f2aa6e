"""Plain reference for the zaya block as a square-token trunk (ZAYA1-8B's):
forward, loss, AdamW and the balance update.

Written from the published config.json of Zyphra/ZAYA1-8B (``model_type``
zaya) and, for what it does not say, the family's two public papers
("Compressed Convolutional Attention", arXiv:2510.04476: **[CCA]**; the
ZAYA1 technical report, arXiv:2511.17127: **[ZAYA1]**) as
``configs/zaya1-trunk-train.json`` lists under ``assumed``, each entry
with its basis. ``N`` is RMSNorm (eps 1e-5, statistics in float32), ``n``
the normed input, 64 tokens a board, boards never mix; ``t - 1`` is the
previous square of the same board and is zero before square 0::

    embed    x = t W_in + b_in                                   (W_in is this repo's 19-plane embedding)
    layer i  x <- x + Attn(N_a(x));   x <- x + MoE(N_m(x))
    Attn(n)  q~ = n W_q [2048, 8 x 128];  k~ = n W_k [2048, 2 x 128]
             v  = [ n_t W_v1 | n_{t-1} W_v2 ]                    W_v1, W_v2 [2048, 2 x 64]: each key-value head's first 64 columns from
                                                                 the token, its last 64 from the previous square (the value shift,
                                                                 made HERE on the normed input)
             a  = conv0([q~ | k~])   a[t] = b0 + sum_k w0[:, k] * x[t - (T0 - 1) + k]       depthwise over the 1280 columns, T0 = 2 taps
             c  = conv1(a)           c[t, g] = b1[g] + sum_k a[t - (T1 - 1) + k, g] W1[g, k]  a head at a time (10 groups of 128 columns:
                                                                 8 query heads, 2 key heads), T1 = 2 taps, each a [128, 128] matrix
             m_q[h] = ( q~[h] + k~[h // 4] ) / 2;   m_k[g] = ( mean over the group's 4 heads of q~ + k~[g] ) / 2
             q = c_q + m_q;   k = c_k + m_k
             q[h] <- q[h] / rms(q[h]);   k[g] <- temp[g] * k[g] / rms(k[g])                  rms over the head's 128 columns
             RoPE (theta 5e6, rotate-half) on the FIRST 64 columns of each head, the last 64 pass
             head h attends key-value head h // 4 within a board, no mask, scores / sqrt(128), softmax
             out = concat_h(mixed) W_o                           [1024, 2048]
    MoE(n)   r = n W_rd + b_rd                                   [2048, 256]
             h = gelu(r W_r1 + b_r1);  h = gelu(h W_r2 + b_r2)   [256, 256] each, gelu by erf
             s = softmax(h W_r3) over 16;   e = argmax(s + expert_bias), no gradient through the bias or the choice
             out = s[e] * E_e(n) if e is HELD HERE, else 0;   E_e(u) = ( silu(u W_gate[e]) * (u W_up[e]) ) W_down[e]
    balance  after a step, a layer's c_e = tokens routed to expert e (all 16, held or not):
             d = 0.001 * sign(mean(c) - c);  b <- b + d - mean(d)
    out      N_final(x) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

Both convolutions are explicit sums over taps of shifted copies, the
q-k mean is written out head by head, partial RoPE is a slice, a
rotation and a concatenation, and the value shift moves the INPUT of its
projection: the program moves the product's rows, convolves through one
function and rotates by tables inside its kernels, so the two do not
share a derivation.

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every layer, from
``first_held_expert``. The router keeps all its outputs and its top-1; a
token whose expert is absent gets nothing from the layer's feed-forward,
here as in the program (no shared expert stands behind it).

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch: every
held expert is applied to every token and the result masked by the
choice, every layer made again in the backward pass. It imports nothing
of the program; the norm, RoPE, the product in a precision and AdamW are
the first trunk's reference's, the balance rule the second's, imported. Parameters carry the names of the
program's ``.npz`` checkpoint format, the layers stacked on a leading
axis (``conv0_w`` [layers, 1280, taps] and ``conv1_w`` [layers, 10, taps,
128 in, 128 out]: a published ``Conv1d`` weight ``[out, in / groups,
taps]`` with its axes moved, the last tap the token's own);
``expert_bias`` is among them, has a zero gradient, and ``train_losses``
moves it by the balance rule and never by AdamW.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16, conv1's among them;
the router's MLP, the norms, the softmaxes, conv0 and ``temp`` stay
float32 in it, as in any fp8 recipe.
"""

from __future__ import annotations

import json
import math
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
_ATTENTION = ("attn_norm", "wq", "wk", "wv1", "wv2", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "temp", "wo")
_ROUTER = ("router_down", "router_down_b", "router_w1", "router_w1_b", "router_w2", "router_w2_b", "router_w3")
_LAYER = (*_ATTENTION, "moe_norm", *_ROUTER, BUFFER, "experts_gate", "experts_up", "experts_down")
#: The scale of a branch's LAST matrix (``wo``, ``experts_down``) beside the other matrices' 0.9, as the fourth trunk's reference.
_OUT = 0.45
#: The planes of this repo's encoding that say which piece stands on a square (``models/az_encoding.py``: six own, six the opponent's).
_PIECE_PLANES = 12
#: What a token's piece plane reads in the router's down-projection (its other columns read ``_READ_REST`` a unit of the normed stream),
#: the two thresholds between which the router's first layer clamps that reading, and the slope of the clamp.
_READ, _READ_REST, _CLAMP, _SLOPE = 4.0, 0.005, (1.0, 2.0), 4.0
#: The spread of a router's logits, and the margin, in logits, by which every kind of square's chosen logit leads its second.
_LOGIT_SPREAD, _MARGIN = 3.0, 1.0


def _gelu(z: np.ndarray) -> np.ndarray:
    return 0.5 * z * (1.0 + np.vectorize(math.erf)(z / math.sqrt(2.0)))


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, conditioned as the fourth trunk's
    reference conditions its own (``reference/hybrid_trunk.py
    init_params`` and the first's say why for each): matrices normal(0,
    0.9^2 / fan_in), a branch's last matrix at half that scale, gains 1 +
    0.1 normal, biases 0.05 normal, the value head pinned alive, the
    embedding at sqrt(hidden) times the other matrices' scale,
    ``expert_bias`` a few balance steps' worth.

    What is conditioned for this block alone:

    The mix starts near the pass it is initialised to, with every tap
    alive: ``conv0_w`` 1 + 0.1 normal at the token's own tap and 0.3
    normal at the earlier ones, ``conv1_w`` the identity at the token's
    own tap plus normal(0, 0.25^2 / head_dim) on every tap (0.022 at 128
    columns), so that both taps of both convolutions carry gradient and a
    pair of exchanged taps is another function; ``temp`` 1 + 0.1 normal.

    **A peaked router with a stated margin.** At one expert a token a
    near-tie that a bfloat16 rounding flips swaps the token's WHOLE
    feed-forward, in every layer after it too; a random MLP over the
    normed stream has its first and second logit within the rounding of
    each other in ~1% of the tokens whatever its scale (both scale
    alike), and 50-odd swapped tokens a layer of 8,192 would be most of
    what the comparison reads of the experts' tensors. So the router
    chooses on what is discrete in a token: ``router_down``'s first 12
    columns read the token's piece plane out of the normed stream (the
    pseudo-inverse of the embedding's 19 planes and its bias, divided by
    the norm's gain: ``_READ`` = 4 on a square with that piece, 0 on any
    other, whatever the other planes say), its other columns read the
    whole stream at ``_READ_REST`` (they have a gradient like any, and
    move a logit by thousandths). What is not discrete in that reading
    (the norm's divisor follows the castling planes, +-10%; the branches
    before the router leak +-0.1 into every column) is CLAMPED by the
    MLP's first two layers, which are otherwise random: units 2p and 2p +
    1 of the first layer read piece p's column at slope 4 under the
    thresholds 1 and 2 and enter the second layer with opposite signs,
    so that it sees ``gelu(4 (r - 1)) - gelu(4 (r - 2))``: 4 for every
    reading of 2.6 and more, 0 for every reading under 0.3, to the fourth
    decimal (GELU is the identity or zero out there). A square's hidden
    code is then its kind's, a random vector a kind under a bias of unit
    spread (an empty square reads the bias alone);
    ``router_w3`` at a logit spread of ``_LOGIT_SPREAD``. A layer's
    ``router_w3`` is then changed by the least that gives each of the 13
    kinds of square (empty, or one of 12 pieces), on the embedding
    alone, a chosen logit that leads its second by ``_MARGIN`` = 1.0
    logit (the kinds' hidden vectors are independent, so a pseudo-inverse
    lifts exactly those logits and no other), after the empty square's
    expert, which takes half a layer's tokens, has been made a held one
    in the even layers and an absent one in the odd (two columns change
    places): favourites held and absent alike. What the rest of the
    stream still moves a logit by (the ``_READ_REST`` columns and the
    random part of the first layer) and what bfloat16 moves it by are
    hundredths, so every token keeps its kind's choice with the margin
    (PERF.md section 6, PR 43, has the readings at width). The routing
    this makes is lumpy by design (an empty square's expert takes half a
    layer's tokens); the program's own initialisation, which the cell's
    window runs, has nothing of it."""
    rng = np.random.default_rng([int(seed), 0x7A617961])
    h, planes, hidden = model["hidden_size"], model["input_planes"], model["value_hidden"]
    layers, hd = model["num_hidden_layers"], model["head_dim"]
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    inner, kv_inner, mixed = heads * hd, kv_heads * hd, (heads + kv_heads) * hd
    t0, t1, rh = model["cca_time0"], model["cca_time1"], model["router_hidden_size"]
    held, experts, first, w = model["num_experts"], model["num_routed_experts"], model["first_held_expert"], model["moe_intermediate_size"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(*shape: int, scale: float = 0.05) -> np.ndarray:
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    own_tap = lambda taps: (np.arange(taps) == taps - 1).astype(np.float32)
    params = {
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": bias(h, scale=0.05 * np.sqrt(h)),
        "attn_norm": gain(layers, h),
        "wq": matrix(layers, h, inner, fan_in=h), "wk": matrix(layers, h, kv_inner, fan_in=h),
        "wv1": matrix(layers, h, kv_inner // 2, fan_in=h), "wv2": matrix(layers, h, kv_inner // 2, fan_in=h),
        "conv0_w": (own_tap(t0) * gain(layers, mixed, t0) + (1.0 - own_tap(t0)) * bias(layers, mixed, t0, scale=0.3)).astype(np.float32),
        "conv0_b": bias(layers, mixed),
        "conv1_w": (own_tap(t1)[:, None, None] * np.eye(hd, dtype=np.float32) + matrix(layers, heads + kv_heads, t1, hd, hd, fan_in=hd, scale=0.25)),
        "conv1_b": bias(layers, mixed),
        "temp": gain(layers, kv_heads),
        "wo": matrix(layers, inner, h, fan_in=inner, scale=_OUT),
        "moe_norm": gain(layers, h),
        "router_down": matrix(layers, h, rh, fan_in=h, scale=_READ_REST), "router_down_b": bias(layers, rh),
        "router_w1": matrix(layers, rh, rh, fan_in=rh, scale=0.3), "router_w1_b": bias(layers, rh, scale=1.0),
        "router_w2": matrix(layers, rh, rh, fan_in=rh, scale=1.4), "router_w2_b": bias(layers, rh, scale=1.0),
        "router_w3": np.zeros((layers, rh, experts), np.float32),
        "experts_gate": matrix(layers, held, h, w, fan_in=h), "experts_up": matrix(layers, held, h, w, fan_in=h),
        "experts_down": matrix(layers, held, w, h, fan_in=w, scale=_OUT),
        "final_norm": gain(h),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }
    # The router: the piece planes read out of the normed stream, then a random MLP whose last layer is drawn to the margin.
    embedding = np.concatenate([params["embed_w"], params["embed_b"][None]]).astype(np.float64)  # a stream is a combination of these rows
    read = np.linalg.pinv(embedding)[:, :_PIECE_PLANES]  # [hidden, 12]: row p of ``embedding`` reads 1 in column p, every other row 0
    # A square's stream on the embedding alone: its piece's plane (none on an empty square), the all-ones plane, the bias.
    kinds = np.concatenate([np.zeros((1, planes)), np.eye(planes)[:_PIECE_PLANES]])
    kinds[:, planes - 1] = 1.0
    streams = kinds @ embedding[:planes] + embedding[planes]
    scale = np.sqrt(np.mean(streams ** 2, axis=-1)).mean()  # the norm divides a token's stream by its root mean square
    if rh < 2 * _PIECE_PLANES:
        raise ValueError(f"a router MLP of {rh} units has no two units a piece plane for the clamp")
    pieces, low, high = np.arange(_PIECE_PLANES), 2 * np.arange(_PIECE_PLANES), 2 * np.arange(_PIECE_PLANES) + 1
    for i in range(layers):
        g = params["moe_norm"][i].astype(np.float64)
        params["router_down"][i, :, :_PIECE_PLANES] = (_READ * scale * read / g[:, None]).astype(np.float32)
        # The clamp: units 2p and 2p + 1 read piece p's column at the slope, one threshold each, and go on with opposite signs.
        params["router_w1"][i, pieces, low] = params["router_w1"][i, pieces, high] = _SLOPE
        params["router_w1_b"][i, low], params["router_w1_b"][i, high] = -_SLOPE * _CLAMP[0], -_SLOPE * _CLAMP[1]
        code = (rng.standard_normal((_PIECE_PLANES, rh)) * 1.5 / (_SLOPE * (_CLAMP[1] - _CLAMP[0]))).astype(np.float32)
        params["router_w2"][i, low], params["router_w2"][i, high] = code, -code
        normed = streams / np.sqrt(np.mean(streams ** 2, axis=-1, keepdims=True) + model["rms_norm_eps"]) * g
        r = normed @ params["router_down"][i].astype(np.float64) + params["router_down_b"][i]
        for name in ("router_w1", "router_w2"):
            r = _gelu(r @ params[name][i].astype(np.float64) + params[f"{name}_b"][i])
        last = rng.standard_normal((rh, experts)) * (_LOGIT_SPREAD / np.sqrt(np.mean(r ** 2) * rh))
        # The empty square's expert (half a layer's tokens) is held in the even layers and absent in the odd: two columns change places.
        favourite = int(np.argmax(r[0] @ last))
        if ((favourite - first) % experts < held) != (i % 2 == 0):
            other = (favourite + experts // 2) % experts if 2 * held == experts else int((first + held) % experts if i % 2 else first)
            last[:, [favourite, other]] = last[:, [other, favourite]]
        # The margin, made and not waited for: the 13 kinds' hidden vectors are independent, so the least change of the matrix that
        # lifts each kind's chosen logit to ``_MARGIN`` over its second, and moves no other logit of any kind, is a pseudo-inverse away.
        logits = r @ last
        ranked = np.sort(logits, axis=-1)
        lift = np.zeros_like(logits)
        lift[np.arange(len(r)), np.argmax(logits, axis=-1)] = np.maximum(0.0, _MARGIN - (ranked[:, -1] - ranked[:, -2]))
        last = last + np.linalg.pinv(r) @ lift
        params["router_w3"][i] = last.astype(np.float32)
    moves = rng.integers(-3, 4, (layers, experts)).astype(np.float64) * model["load_balance_coeff"]
    params[BUFFER] = (moves - moves.mean(axis=-1, keepdims=True)).astype(np.float32)
    return params


def _shifted(u: jax.Array, squares: int) -> jax.Array:
    """``u[t - squares]`` along a board's squares [B, 64, ...], zero before square 0."""
    if squares == 0:
        return u
    return jnp.pad(u[:, :-squares], ((0, 0), (squares, 0)) + ((0, 0),) * (u.ndim - 2))


def _unit(x: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every layer's
    tokens an expert [layers, experts] (all of them, held or not)."""
    eps, theta, b = model["rms_norm_eps"], model["rope_theta"], planes.shape[0]
    heads, kv_heads, hd, rotary = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"], model["rotary_dim"]
    group, half = heads // kv_heads, hd // 2
    t0, t1 = model["cca_time0"], model["cca_time1"]
    first, held = model["first_held_expert"], model["num_experts"]
    product = _product(cast, grad_cast)

    def part_rope(x: jax.Array) -> jax.Array:  # [B, 64, heads, head_dim]: the first ``rotary`` columns turn, the rest pass
        return jnp.concatenate([_rope(x[..., :rotary], theta), x[..., rotary:]], axis=-1)

    def attention(n: jax.Array, p: Params) -> jax.Array:
        q_raw = product("bsh,hd->bsd", n, p["wq"]).reshape(b, SQUARES, heads, hd)
        k_raw = product("bsh,hd->bsd", n, p["wk"]).reshape(b, SQUARES, kv_heads, hd)
        v1 = product("bsh,hd->bsd", n, p["wv1"]).reshape(b, SQUARES, kv_heads, half)
        v2 = product("bsh,hd->bsd", _shifted(n, 1), p["wv2"]).reshape(b, SQUARES, kv_heads, half)  # the shift on the normed input
        v = jnp.concatenate([v1, v2], axis=-1)
        x = jnp.concatenate([q_raw, k_raw], axis=2)  # [B, 64, heads + kv_heads, head_dim]: the 1280 columns side by side
        w0, b0 = p["conv0_w"].reshape(heads + kv_heads, hd, t0), p["conv0_b"].reshape(heads + kv_heads, hd)
        a = b0 + sum(_shifted(x, t0 - 1 - k) * w0[..., k] for k in range(t0))
        c = p["conv1_b"].reshape(heads + kv_heads, hd) + sum(product("bsgi,gio->bsgo", _shifted(a, t1 - 1 - k), p["conv1_w"][:, k]) for k in range(t1))
        m_q = jnp.stack([(q_raw[:, :, h] + k_raw[:, :, h // group]) / 2 for h in range(heads)], axis=2)
        m_k = jnp.stack([(jnp.mean(q_raw[:, :, g * group:(g + 1) * group], axis=2) + k_raw[:, :, g]) / 2 for g in range(kv_heads)], axis=2)
        q = part_rope(_unit(c[:, :, :heads] + m_q, eps))
        k = part_rope(_unit(c[:, :, heads:] + m_k, eps) * p["temp"][:, None])
        k, v = (jnp.repeat(y, group, axis=2) for y in (k, v))  # query head h attends key-value head h // group
        scores = product("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, SQUARES, heads * hd)
        return product("bsd,dh->bsh", mixed, p["wo"])

    def routed(n: jax.Array, p: Params) -> Tuple[jax.Array, jax.Array]:
        n2 = n.reshape(b * SQUARES, -1)
        dense = lambda u, name: jnp.einsum("ti,io->to", u, p[name], precision="highest")
        r = dense(n2, "router_down") + p["router_down_b"]
        for name in ("router_w1", "router_w2"):
            r = jax.nn.gelu(dense(r, name) + p[f"{name}_b"], approximate=False)
        score = jax.nn.softmax(dense(r, "router_w3"), axis=-1)
        chosen = score + jax.lax.stop_gradient(p[BUFFER])
        top = jax.lax.stop_gradient(jnp.max(chosen, axis=-1, keepdims=True))
        weights = jnp.where(chosen >= top, score, 0.0)  # [tokens, experts], zero off the one chosen; not renormalised (one term)

        def one_expert(total, expert):
            w_gate, w_up, w_down, weight = expert
            act = jax.nn.silu(product("th,hw->tw", n2, w_gate)) * product("th,hw->tw", n2, w_up)
            return total + weight[:, None] * product("tw,wh->th", act, w_down), None

        out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(n2),
                              (p["experts_gate"], p["experts_up"], p["experts_down"], weights[:, first:first + held].T))
        return out.reshape(b, SQUARES, -1), jnp.sum(chosen >= top, axis=0).astype(jnp.float32)

    def layer(x: jax.Array, p: Params) -> Tuple[jax.Array, jax.Array]:
        x = x + attention(_rms_norm(x, p["attn_norm"], eps), p)
        out, count = routed(_rms_norm(x, p["moe_norm"], eps), p)
        return x + out, count

    x = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    counts = []
    for i in range(model["num_hidden_layers"]):
        # Each layer is made again in the backward pass, as the other trunks' references do.
        x, count = jax.checkpoint(layer)(x, {name: params[name][i] for name in _LAYER})
        counts.append(count)
    return _rms_norm(x, params["final_norm"], eps).reshape(b, 8, 8, -1), jnp.stack(counts)


def features(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> jax.Array:
    """The final-normed trunk output [B, 8, 8, hidden]: what both heads read."""
    return _trunk(params, planes, model, cast, grad_cast)[0]


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """The other trunks' references' heads and loss on this trunk's features."""
    cast, grad_cast = cast_for(precision), grad_cast_for(precision)
    x, b = features(params, batch["planes"], config["model"], cast, grad_cast), batch["planes"].shape[0]
    product = _product(cast, grad_cast)
    logits = (product("brfh,hp->brfp", x, params["policy_w"][0, 0]) + params["policy_b"]).reshape(b, -1)  # (square, plane) order
    v = jax.nn.relu(product("brfh,hc->brfc", x, params["value_w"][0, 0]) + params["value_b"]).reshape(b, -1)
    v = jax.nn.relu(product("bi,ij->bj", v, params["value_fc1_w"]) + params["value_fc1_b"])
    value = jnp.tanh(product("bi,ij->bj", v, params["value_fc2_w"]) + params["value_fc2_b"])[:, 0]
    policy_loss = -jnp.mean(jnp.sum(batch["policy_target"] * jax.nn.log_softmax(logits, axis=-1), axis=-1))
    return policy_loss + config["train"]["value_weight"] * jnp.mean((value - batch["value_target"]) ** 2)


_SLOTS: Dict[str, Any] = {}  # one compiled routing count a model, shared by every seed of a sweep


def expert_slots(params: Params, planes: jax.Array, model: Dict[str, Any]) -> jax.Array:
    """Every layer's tokens an expert, in float32: what the balance update reads."""
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
