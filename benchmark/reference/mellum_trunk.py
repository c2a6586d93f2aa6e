"""Plain reference for the mellum block as a square-token trunk
(Mellum2-12B-A2.5B's): forward, loss, AdamW and the balance update.

Written from the published config.json of JetBrains/Mellum2-12B-A2.5B-Instruct
(``model_type`` mellum) and, for what it does not say, the Qwen3-MoE family's
block whose keys it carries, as ``configs/mellum2-trunk-train.json`` lists
under ``assumed``. ``N`` is RMSNorm with a plain gain (eps 1e-6, statistics
in float32), ``n`` the normed input, 64 tokens a board::

    embed    x = t W_in + b_in                                  (this repo's 19-plane embedding; no scale)
    layer i  a = x + Attn_kind(i)( N_in(x) );   y = a + MoE( N_post(a) )        (two norms a layer, no post-norms)
    Attn     q = n W_q [32 x 128];  k = n W_k, v = n W_v [4 x 128];  no bias, no gate
             q, k <- RMSNorm over head_dim, one gain each
             q, k <- RoPE by the layer's kind (``layer_types``, ``rope_parameters``), rotate-half over all of head_dim,
             position = square index, pair j of head_dim / 2:
               sliding_attention (rope_type default):  f_j = theta^(-2j / head_dim);  cos, sin of position x f_j
               full_attention (rope_type yarn):
                 c(b) = head_dim ln(original_max_position_embeddings / (2 pi b)) / (2 ln theta)
                 lo = max(floor(c(beta_fast)), 0);  hi = min(ceil(c(beta_slow)), head_dim - 1)         (18 and 35 as published)
                 r_j = clip((j - lo) / (hi - lo), 0, 1);   f_j = (1 - r_j) theta^(-2j / head_dim) + r_j theta^(-2j / head_dim) / factor
                 cos, sin <- attention_factor x cos, sin: on the query AND on the key (scores x attention_factor^2)
             query head h attends key-value head h // 8, within a board, scores / sqrt(128), softmax
             sliding layers: mask |i - j| < sliding_window, applied literally (1024: all true at 64 tokens); no causal mask
             out = concat_h( P_h v ) W_o
    MoE      p = softmax(n W_r) over the 64 experts
             chosen = top-8 of (p + b), b = expert_bias, no gradient through b or the choice
             w_j = p[e_j] / (sum over the 8 chosen of p[e_j] + 1e-20)          (norm_topk_prob; over ALL chosen, held here or not)
             out = sum over chosen e_j HELD HERE of w_j E_{e_j}(n);  E_e SiLU-gated, width 896; no shared expert, no dense layer
    balance  after a step, a layer's c_e = slots routed to expert e (all 64, held or not):
             d = 0.001 * sign(mean(c) - c);  b <- b + d - mean(d)
    out      N_final(y) -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu, fc, tanh

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every layer, from ``first_held_expert``.
The router keeps all its outputs and its top-8; what the absent experts
would have added is left out here as in the program, and that partial result
goes on to the next layer.

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch: EVERY
held expert is applied to EVERY token and the result masked by the choice,
one expert at a time. It imports nothing of the program: the YaRN arithmetic
below is its own (``_rope_table``), the norm, the product in a precision,
AdamW, the gated feed-forward and the balance rule are the older trunks'
references', imported. ``model["misread"]`` (absent in every configuration;
``benchmark/sweep_misread.py`` and the tests set it) computes a plausible
misreading instead, which the comparison has to tell from the block:
``plain_full_layer`` (the full layer turned by the sliding layers' table),
``no_attention_factor``, ``factor_once`` (on the query alone: scores x 1.277,
not 1.631), ``ramp_swapped`` (``lo`` and ``hi`` from ``beta_slow`` and
``beta_fast``: the fast pairs interpolated), ``not_renormalised``,
``renormalised_over_held`` (a token's held weights add up to 1),
``kv_head_mod`` (query head h on key-value head ``h % 4``).

``init_params`` conditions as the older references do (``reference/moe_trunk.py
init_params`` says why for each: matrices normal(0, 0.9^2 / fan_in), a peaked
router at 3.0 / sqrt(hidden), gains 1 + 0.1 normal, biases 0.05 normal, the
value head pinned alive, ``expert_bias`` a few balance steps' worth; the
EMBEDDING at sqrt(hidden) times the other matrices' scale, as the third and
seventh trunks' references and for their reason: this block has no
multiplier on the embedding and no post-norms, and a stream that starts at
the branches' scale is mostly the sum of its branches).

**The router's columns.** A board's tokens are of a few kinds: six squares
of ten are empty, and what an empty square's token holds is what EVERY token
holds, the embedding's bias and the plane of ones (with a board's castling
planes beside them). Under the embedding's scale that constant part is most
of every token, so every token ranks the 64 experts nearly alike, and which
eight are everybody's is drawn with the seed: read on the chip at width with
the columns as drawn, some expert took all 8,192 tokens of a comparison in
every layer, the 8 held of 64 took 0.3-2.4% of a layer's slots where even
routing gives 12.5%, on one seed 191 of 65,536, and that layer's
``router_w`` gradient had a norm of 0.0006 where another seed's had 0.29
(PR 56; PERF.md section 7 "After PR 49" (a) and "After PR 53" are the same
defect in the sixth and seventh trunks' comparisons: a reference router
gradient that is nearly nothing refuses whatever PR draws the seed). So the
columns of each layer's ``router_w`` are PERMUTED, after the draw: the
constant token (the bias plus the ones plane's row, under that layer's norm
gain) ranks the experts, and the held ones take every other rank inside its
top-k from the second (1, 3, 5, 7 of 0-63 at top-8: the second, fourth, sixth
and eighth weights, ~0.18 down to ~0.05 renormalised) and then the ranks just
past the cut, one after another (8, 9, 10, 11). A token's own part of a
logit moves an expert about five ranks either way, so every one of the 8
held experts is chosen by a tenth or more of a comparison's tokens, at
weights that differ by the rank, and about half of a layer's chosen slots
are held: a fault in ONE held expert's group offset or tile is several per
cent of ``experts_*``'s gradient, and weights renormalised over the held
alone are still about twice the block's. (The first placement, 2, 10, 18,
... 58, put one held expert inside the top-8 and the others so far past the
cut that all but two took under 1% of the tokens: REVIEW of PR 56.)
A permutation of the columns of a normal
matrix is a draw of the same matrix; nothing of the mathematics is touched,
and the token-specific part of a logit (std ~0.9 of ~3.1) still moves single
tokens in and out.

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16; the router's product,
the norms, the tables and the softmaxes stay float32 in it, as in any fp8
recipe.
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
_rms_norm, _product, _gated, balanced_bias = first_block._rms_norm, first_block._product, second_block._gated, second_block.balanced_bias
BUFFER = "expert_bias"
_LAYER = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "moe_norm", "router_w", BUFFER, "experts_gate", "experts_up", "experts_down")


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, under the names of the program's checkpoint (module docstring)."""
    rng = np.random.default_rng([int(seed), 0x6D656C])
    h, planes, hidden, layers, hd = model["hidden_size"], model["input_planes"], model["value_hidden"], model["num_hidden_layers"], model["head_dim"]
    inner, kv_inner = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    held, experts, w = model["num_experts"], model["num_routed_experts"], model["moe_intermediate_size"]

    def matrix(*shape: int, fan_in: int, scale: float = 0.9) -> np.ndarray:
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale / np.sqrt(fan_in)))

    def gain(*shape: int) -> np.ndarray:
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    def bias(n: int) -> np.ndarray:
        return (0.05 * rng.standard_normal(n)).astype(np.float32)

    sign = np.float32(rng.choice([-1.0, 1.0]))
    steps = rng.integers(-3, 4, (layers, experts)).astype(np.float64) * model["load_balance_coeff"]
    params = {
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": np.float32(np.sqrt(h)) * bias(h),
        "attn_norm": gain(layers, h), "moe_norm": gain(layers, h),
        "wq": matrix(layers, h, inner, fan_in=h), "wk": matrix(layers, h, kv_inner, fan_in=h), "wv": matrix(layers, h, kv_inner, fan_in=h),
        "q_norm": gain(layers, hd), "k_norm": gain(layers, hd), "wo": matrix(layers, inner, h, fan_in=inner),
        "router_w": matrix(layers, h, experts, fan_in=h, scale=3.0),
        BUFFER: (steps - steps.mean(axis=-1, keepdims=True)).astype(np.float32),
        "experts_gate": matrix(layers, held, h, w, fan_in=h), "experts_up": matrix(layers, held, h, w, fan_in=h),
        "experts_down": matrix(layers, held, w, h, fan_in=w),
        "final_norm": gain(h),
        "policy_w": matrix(1, 1, h, model["policy_planes"], fan_in=h), "policy_b": bias(model["policy_planes"]),
        "value_w": matrix(1, 1, h, 4, fan_in=h, scale=0.2), "value_b": np.float32(1.0) + bias(4),
        "value_fc1_w": np.abs(matrix(4 * SQUARES, hidden, fan_in=1, scale=1.0 / 205.0)), "value_fc1_b": bias(hidden),
        "value_fc2_w": sign * np.abs(matrix(hidden, 1, fan_in=1, scale=0.375 / hidden)),
        "value_fc2_b": (sign * rng.uniform(0.3, 0.7, 1)).astype(np.float32),
    }
    # The held experts' places in the constant token's ranking (module docstring, "The router's columns").
    constant = params["embed_b"].astype(np.float64) + params["embed_w"][planes - 1].astype(np.float64)  # the encoding's last plane is all ones
    top_k = model["num_experts_per_tok"]
    ranks = np.asarray([*range(1, top_k, 2), *range(top_k, experts), *range(0, top_k, 2)][:held])  # an uncut layer's are all of them
    first = model["first_held_expert"]
    for layer in range(layers):
        ranking = np.argsort(-((constant * params["moe_norm"][layer]) @ params["router_w"][layer].astype(np.float64)), kind="stable")
        here = ranking[ranks].tolist()  # the columns the held experts take, the most favoured first
        others = sorted(set(range(experts)) - set(here))
        columns = np.asarray([*others[:first], *here, *others[first:]])
        params["router_w"][layer] = params["router_w"][layer][:, columns]
    return params


def _rope_table(rope: Dict[str, Any], head_dim: int, misread: str = "") -> Tuple[np.ndarray, np.ndarray, float]:
    """A layer kind's ``rope_parameters`` -> cos and sin ``[64, head_dim / 2]`` of position x frequency (float64) and the
    factor both carry (module docstring); position = square index."""
    half, theta = head_dim // 2, float(rope["rope_theta"])
    pair = np.arange(half, dtype=np.float64)
    frequency, scale = theta ** (-2.0 * pair / head_dim), 1.0
    if rope["rope_type"] == "yarn":
        correction = lambda turns: head_dim * math.log(rope["original_max_position_embeddings"] / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))
        fast, slow = (rope["beta_slow"], rope["beta_fast"]) if misread == "ramp_swapped" else (rope["beta_fast"], rope["beta_slow"])
        lo, hi = max(math.floor(correction(fast)), 0), min(math.ceil(correction(slow)), head_dim - 1)
        ramp = np.clip((pair - lo) / (hi - lo if hi != lo else 0.001), 0.0, 1.0)
        frequency = (1.0 - ramp) * frequency + ramp * frequency / rope["factor"]
        scale = 1.0 if misread == "no_attention_factor" else float(rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is neither default nor yarn")
    angle = np.arange(SQUARES, dtype=np.float64)[:, None] * frequency[None, :]
    return np.cos(angle), np.sin(angle), scale


def _rope(x: jax.Array, cos: np.ndarray, sin: np.ndarray, scale: float) -> jax.Array:
    """[B, 64, heads, head_dim]: rotate-half, ``(x cos + rotate_half(x) sin) * scale``."""
    half = x.shape[-1] // 2
    cos, sin = (jnp.asarray(np.concatenate([y, y], axis=-1) * scale, jnp.float32)[None, :, None, :] for y in (cos, sin))
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def _trunk(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed trunk output [B, 8, 8, hidden] and every layer's slots an expert [layers, experts] (all of them, held or not)."""
    heads, kv_heads, head_dim, eps = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"], model["rms_norm_eps"]
    top_k, first, held, b, misread = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"], planes.shape[0], model.get("misread", "")
    product = _product(cast, grad_cast)
    near = np.abs(np.arange(SQUARES)[:, None] - np.arange(SQUARES)[None, :]) < model["sliding_window"]
    group = heads // kv_heads
    of = np.arange(heads) % kv_heads if misread == "kv_head_mod" else np.arange(heads) // group  # the key-value head a query head attends
    is_held = np.zeros(model["num_routed_experts"], bool)
    is_held[first:first + held] = True

    def layer(x: jax.Array, p: Params, kind: str) -> Tuple[jax.Array, jax.Array]:
        n1 = _rms_norm(x, p["attn_norm"], eps)
        q = product("bsh,hd->bsd", n1, p["wq"]).reshape(b, SQUARES, heads, head_dim)
        k, v = (product("bsh,hd->bsd", n1, p[name]).reshape(b, SQUARES, kv_heads, head_dim) for name in ("wk", "wv"))
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
        turned_as = "sliding_attention" if misread == "plain_full_layer" else kind
        cos, sin, scale = _rope_table(model["rope_parameters"][turned_as], head_dim, misread)
        q, k = _rope(q, cos, sin, scale), _rope(k, cos, sin, 1.0 if misread == "factor_once" else scale)
        k, v = k[:, :, of], v[:, :, of]
        scores = product("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
        if kind == "sliding_attention":
            scores = jnp.where(near, scores, -jnp.inf)
        mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, SQUARES, heads * head_dim)
        x = x + product("bsd,dh->bsh", mixed, p["wo"])

        n2 = _rms_norm(x, p["moe_norm"], eps).reshape(b * SQUARES, -1)
        score = jax.nn.softmax(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"), axis=-1)
        chosen = score + jax.lax.stop_gradient(p[BUFFER])
        kth = jax.lax.stop_gradient(jnp.sort(chosen, axis=-1)[:, -top_k][:, None])
        picked = jnp.where(chosen >= kth, score, 0.0)  # [tokens, experts], zero off the top k
        over = jnp.where(is_held, picked, 0.0) if misread == "renormalised_over_held" else picked
        weights = picked if misread == "not_renormalised" else picked / (jnp.sum(over, axis=-1, keepdims=True) + 1e-20)
        count = jnp.sum(chosen >= kth, axis=0).astype(jnp.float32)

        def one_expert(total, expert):
            w_gate, w_up, w_down, weight = expert
            return total + weight[:, None] * _gated(product, n2, w_gate, w_up, w_down), None

        out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(n2), (
            p["experts_gate"], p["experts_up"], p["experts_down"], weights[:, first:first + held].T))
        return x + out.reshape(b, SQUARES, -1), count

    x = product("bsp,ph->bsh", planes.reshape(b, SQUARES, -1), params["embed_w"]) + params["embed_b"]
    counts = []
    for i, kind in enumerate(model["kept_layer_types"]):
        # Each layer is made again in the backward pass, as the older trunks' references': the float32 activations of four layers do
        # not fit the chip beside ``correct``'s two trainer states.
        x, count = jax.checkpoint(layer, static_argnums=(2,))(x, {name: params[name][i] for name in _LAYER}, kind)
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
    return policy.reshape(b, -1), v[:, 0]  # the logits in (square, plane) order


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    """Policy cross-entropy + value error: the repo's loss (the config names no auxiliary loss)."""
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
    AdamW (the first trunk's reference's) on every trained tensor, one at a
    time, and the balance rule on ``expert_bias`` from the routing the step
    started with."""
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
