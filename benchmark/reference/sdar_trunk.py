"""Plain reference for the sdar_moe block as a square-token trunk
(SDAR-30B-A3B-Chat's) TRAINED BY BLOCK DIFFUSION over a board: the two-stream
forward, the three-term loss, AdamW and the balance update.

Written from the published config.json of JetLM/SDAR-30B-A3B-Chat
(``model_type`` sdar_moe) and, for what it does not say, the Qwen3-MoE
family's decoder layer whose keys it carries and the SDAR / BD3-LM papers'
training (arXiv:2510.06303, arXiv:2503.09573), as
``configs/sdar-30b-a3b-trunk-train.json`` lists under ``assumed``. ``N`` is
RMSNorm with a plain gain (eps 1e-6, statistics in float32), ``n`` the normed
input. A board's 64 squares in the trunk's order are the sequence; ``L`` =
``block_length``, ``blk(s) = s // L``, 64 / L blocks::

    noise    (a batch carries it) a level t_b in [t_min, 1] a board and block (``block_level`` [B, 64 / L]); a mask m_s in {0, 1} a square
             (``square_masked`` [B, 64]), drawn with probability t_blk(s)
    streams  two copies of every board through ONE set of weights, 128 tokens a board:
             clean   x^c_s = t_s W_in + b_in                              (this repo's 19-plane embedding; no scale)
             noised  x^n_s = t~_s W_in + b_in + m_s e_mask                t~_s = t_s with its 12 piece planes zeroed where m_s = 1 (the 7
                                                                          board-wide planes stay); e_mask [hidden] learned (``mask_embed``)
    layer    on both streams alike:  a = x + Attn( N_in(x) );   y = a + MoE( N_post(a) )              (two norms a layer, no post-norms)
    Attn     q = n W_q [32 x 128];  k = n W_k, v = n W_v [4 x 128];  no bias, no gate
             q, k <- RMSNorm over head_dim, one gain each;  q, k <- RoPE, rotate-half over all of head_dim at theta, no scaling,
             position = SQUARE index in BOTH copies (a noised square and its clean twin turn alike)
             query head h attends key-value head h // 8;  scores / sqrt(128);  ONE softmax, float32, over the ALLOWED keys (the mask below is
             a literal [128, 128] boolean built from blk, -inf where not allowed: a key that is not allowed has probability exactly 0):
               a clean query i sees the clean keys j with blk(j) <= blk(i); never a noised key
               a noised query i sees the noised keys j with blk(j) = blk(i) AND the clean keys j with blk(j) < blk(i); never a clean key of
               its own or a later block, never a noised key of another block
             out = concat_h( P_h v ) W_o;  no sink, no window
    MoE      a token of either stream:  p = softmax(n W_r) over the 128 experts
             chosen = top-8 of (p + b), b = expert_bias, no gradient through b or the choice
             w_j = p[e_j] / (sum over the 8 chosen of p[e_j] + 1e-20)          (norm_topk_prob; over ALL chosen, held here or not)
             out = sum over chosen e_j HELD HERE of w_j E_{e_j}(n);  E_e SiLU-gated, width 768; no shared expert, no dense layer
    balance  after a step, a layer's c_e = slots routed to expert e (all 128, held or not; both streams' tokens):
             d = rate * sign(mean(c) - c);  b <- b + d - mean(d)
    out      N_final on both streams. The CLEAN stream -> a 1x1 policy convolution to 73 planes; a 1x1 value convolution to 4, relu, fc, relu,
             fc, tanh (as every trunk's one copy). The NOISED stream -> the denoiser:  z_s = N_final(x^n_s) W_d + b_d  in R^13, a square's
             class: 0 empty, 1 + p the piece plane p of the 12
    loss     policy_loss + value_weight x value_loss + denoise_weight x denoise_loss,
             denoise_loss = (1 / (boards x 64)) sum_boards sum_s m_s (1 / t_blk(s)) CE(z_s, class_s)
    served   (``features``, no noise) the clean stream alone under its rule: what the training forward's clean stream is, exactly

The share (guide section 4): this chip holds ``num_experts`` of the
``num_routed_experts`` experts of every layer, from ``first_held_expert``.
The router keeps all its outputs and its top-8; what the absent experts
would have added is left out here as in the program, and that partial result
goes on to the next layer.

``jax.numpy`` only, float32, no kernel, no sorting and no dispatch: EVERY
held expert is applied to EVERY token and the result masked by the choice,
one expert at a time. It imports nothing of the program: the mask, the noised
copy and the square's class below are its own, the norm, the product in a
precision, RoPE, AdamW, the gated feed-forward and the balance rule are the
older trunks' references', imported. ``model["misread"]`` (absent in every
configuration; the tests set it) computes a plausible misreading instead,
which the comparison has to tell from the block: ``noised_sees_own_clean``
(a noised query also sees the clean keys of its OWN block: the answer leaks),
``clean_unmasked`` (the clean copy bidirectional, as every other trunk's one
copy), ``no_level_weight`` (1 / t dropped: a plain masked cross-entropy),
``positions_shifted`` (the noised copy turned by positions 64-127, as a
sequence laid end to end would be).

``init_params`` conditions as the eighth trunk's reference does
(``reference/mellum_trunk.py`` says why for each: matrices normal(0, 0.9^2 /
fan_in), gains 1 + 0.1 normal, biases 0.05 normal, the value head pinned
alive, ``expert_bias`` a few balance steps' worth, the EMBEDDING at
sqrt(hidden) times the other matrices' scale; a peaked router at 3.0 /
sqrt(hidden) whose columns are PERMUTED after the draw so that the 8 held
experts are among every layer's favourites: at ranks 1, 3, 5, 7 inside the
constant token's top-8 and 8, 9, 10, 11 just past it. 8 of 128 as drawn take
a sixteenth of a layer's slots in the mean and, a board's tokens being of a
few kinds, by the seed anything from none to all: PERF.md section 7 "After
PR 41" (f)). ``mask_embed`` is drawn as a row of the embedding is (it stands
where a piece's row stood), the denoiser as the policy head.

**The 1 / t weight.** Both sides get the same noise, so the weight itself
is not at issue; what could be is ONE square carrying the gradient: a block
at t = 0.001 is masked on a square in a thousand and that square then weighs
1000 where a comparison's 4,096 masked squares weigh ~7 in the mean. At the
cell's t_min the sum of the weights of 8,192 squares is 8,192 +- 240 and the
largest single weight of a comparison a few hundred (once in a few seeds, a
few per cent of the term): the denoiser's gradient stays a sum of thousands
of terms, and no reading of the sweep stood apart for it (the
configuration's ``correct.reason``).

The control (``precision`` one step down) rounds the operands of every
product that the configuration states as bfloat16; the router's product,
the norms, the table, the mask and the softmaxes stay float32 in it, as in
any fp8 recipe.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import afmoe_trunk as second_block
from benchmark.reference import moe_trunk as first_block
from benchmark.reference.precision import Cast, cast_for, grad_cast_for

Params = Dict[str, Any]

SQUARES = first_block.SQUARES
PIECE_PLANES = 12  # of the 19 planes a square: own and opponent's P N B R Q K; the other 7 are board-wide
_rms_norm, _product, _gated, balanced_bias = first_block._rms_norm, first_block._product, second_block._gated, second_block.balanced_bias
BUFFER = "expert_bias"
_LAYER = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "moe_norm", "router_w", BUFFER, "experts_gate", "experts_up", "experts_down")


def init_params(seed: int, model: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Float32 parameters from the seed, under the names of the program's checkpoint (module docstring)."""
    rng = np.random.default_rng([int(seed), 0x73646172])
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
        "embed_w": matrix(planes, h, fan_in=1), "embed_b": np.float32(np.sqrt(h)) * bias(h), "mask_embed": matrix(h, fan_in=1),
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
        "denoise_w": matrix(h, 1 + PIECE_PLANES, fan_in=h), "denoise_b": bias(1 + PIECE_PLANES),
    }
    # The held experts' places in the constant token's ranking (module docstring; ``reference/mellum_trunk.py`` "The router's columns").
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


def allowed(block_length: int, streams: int, misread: str = "") -> np.ndarray:
    """The mask, a literal boolean ``[query, key]`` over a board's ``64 x streams`` tokens (token ``s`` the clean square ``s``, ``64 + s``
    the noised one), built from ``blk`` rule by rule (module docstring)."""
    blk = lambda token: (token % SQUARES) // block_length
    noised = lambda token: token >= SQUARES
    mask = np.zeros((SQUARES * streams, SQUARES * streams), bool)
    for i in range(SQUARES * streams):
        for j in range(SQUARES * streams):
            if not noised(i):
                mask[i, j] = not noised(j) and (blk(j) <= blk(i) or misread == "clean_unmasked")
            elif noised(j):
                mask[i, j] = blk(j) == blk(i)
            else:
                mask[i, j] = blk(j) < blk(i) or (misread == "noised_sees_own_clean" and blk(j) == blk(i))
    return mask


def _rope(x: jax.Array, theta: float, positions: np.ndarray) -> jax.Array:
    """[B, tokens, heads, head_dim]: rotate-half RoPE over all of head_dim, a token turned by ITS position."""
    half = x.shape[-1] // 2
    angle = positions.astype(np.float64)[:, None] / theta ** (np.arange(half, dtype=np.float64) / half)[None, :]
    cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, axis=-1), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def _trunk(params: Params, tokens: jax.Array, marked: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """``tokens`` [B, T, 19], T = 64 (the clean copy alone) or 128 (the clean, then the noised copy) and ``marked`` [B, T] (1 where a token
    takes the mask embedding) -> the final-normed streams [B, T, hidden] and every layer's slots an expert [layers, experts] (all of them,
    held or not, both streams' tokens)."""
    heads, kv_heads, head_dim, eps = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"], model["rms_norm_eps"]
    top_k, first, held, misread = model["num_experts_per_tok"], model["first_held_expert"], model["num_experts"], model.get("misread", "")
    b, t = tokens.shape[:2]
    product = _product(cast, grad_cast)
    mask = allowed(model["block_length"], t // SQUARES, misread)
    positions = np.arange(t) if misread == "positions_shifted" else np.arange(t) % SQUARES  # the square index in both copies
    of = np.arange(heads) // (heads // kv_heads)  # the key-value head a query head attends
    is_held = np.zeros(model["num_routed_experts"], bool)
    is_held[first:first + held] = True

    def layer(x: jax.Array, p: Params) -> Tuple[jax.Array, jax.Array]:
        n1 = _rms_norm(x, p["attn_norm"], eps)
        q = product("bsh,hd->bsd", n1, p["wq"]).reshape(b, t, heads, head_dim)
        k, v = (product("bsh,hd->bsd", n1, p[name]).reshape(b, t, kv_heads, head_dim) for name in ("wk", "wv"))
        q, k = _rope(_rms_norm(q, p["q_norm"], eps), model["rope_theta"], positions), _rope(_rms_norm(k, p["k_norm"], eps), model["rope_theta"], positions)
        k, v = k[:, :, of], v[:, :, of]
        scores = jnp.where(mask, product("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim), -jnp.inf)
        mixed = product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, t, heads * head_dim)
        x = x + product("bsd,dh->bsh", mixed, p["wo"])

        n2 = _rms_norm(x, p["moe_norm"], eps).reshape(b * t, -1)
        score = jax.nn.softmax(jnp.einsum("th,he->te", n2, p["router_w"], precision="highest"), axis=-1)
        chosen = score + jax.lax.stop_gradient(p[BUFFER])
        kth = jax.lax.stop_gradient(jnp.sort(chosen, axis=-1)[:, -top_k][:, None])
        picked = jnp.where(chosen >= kth, score, 0.0)  # [tokens, experts], zero off the top k
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        count = jnp.sum(chosen >= kth, axis=0).astype(jnp.float32)

        def one_expert(total, expert):
            w_gate, w_up, w_down, weight = expert
            return total + weight[:, None] * _gated(product, n2, w_gate, w_up, w_down), None

        out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(n2), (
            p["experts_gate"], p["experts_up"], p["experts_down"], weights[:, first:first + held].T))
        return x + out.reshape(b, t, -1), count

    x = product("bsp,ph->bsh", tokens, params["embed_w"]) + params["embed_b"] + marked[:, :, None] * params["mask_embed"]
    counts = []
    for i in range(model["num_hidden_layers"]):
        # Each layer is made again in the backward pass, as the older trunks' references': the float32 activations of the layers do
        # not fit the chip beside ``correct``'s two trainer states.
        x, count = jax.checkpoint(layer)(x, {name: params[name][i] for name in _LAYER})
        counts.append(count)
    return _rms_norm(x, params["final_norm"], eps), jnp.stack(counts)


def _copies(planes: jax.Array, square_masked: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A batch's planes and mask -> the 128 tokens a board (the clean copy, then the noised one: a masked square's 12 piece planes zeroed,
    its 7 board-wide planes as they are) and which of them take the mask embedding."""
    b = planes.shape[0]
    clean, m = planes.reshape(b, SQUARES, -1), square_masked.astype(jnp.float32)
    is_piece = (np.arange(clean.shape[-1]) < PIECE_PLANES).astype(np.float32)
    noised = clean * (1.0 - m[:, :, None] * is_piece)
    return jnp.concatenate([clean, noised], axis=1), jnp.concatenate([jnp.zeros_like(m), m], axis=1)


def streams(params: Params, planes: jax.Array, square_masked: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> Tuple[jax.Array, jax.Array]:
    """The final-normed clean and noised streams, [B, 64, hidden] each."""
    x = _trunk(params, *_copies(planes, square_masked), model, cast, grad_cast)[0]
    return x[:, :SQUARES], x[:, SQUARES:]


def features(params: Params, planes: jax.Array, model: Dict[str, Any], cast: Cast, grad_cast: Cast) -> jax.Array:
    """What is served: the clean stream alone under its block-causal rule, final-normed, [B, 8, 8, hidden]."""
    b = planes.shape[0]
    return _trunk(params, planes.reshape(b, SQUARES, -1), jnp.zeros((b, SQUARES), jnp.float32), model, cast, grad_cast)[0].reshape(b, 8, 8, -1)


def heads(params: Params, x: jax.Array, cast: Cast, grad_cast: Cast):
    """The policy logits in (square, plane) order and the value off the clean stream's features [B, 8, 8, hidden]."""
    b, product = x.shape[0], _product(cast, grad_cast)
    policy = product("brfh,hp->brfp", x, params["policy_w"][0, 0]) + params["policy_b"]
    v = jax.nn.relu(product("brfh,hc->brfc", x, params["value_w"][0, 0]) + params["value_b"]).reshape(b, -1)
    v = jax.nn.relu(product("bi,ij->bj", v, params["value_fc1_w"]) + params["value_fc1_b"])
    v = jnp.tanh(product("bi,ij->bj", v, params["value_fc2_w"]) + params["value_fc2_b"])
    return policy.reshape(b, -1), v[:, 0]


def square_class(planes: jax.Array) -> jax.Array:
    """[B, 64] int: 0 an empty square, 1 + p the piece plane p that is set."""
    pieces = planes.reshape(planes.shape[0], SQUARES, -1)[..., :PIECE_PLANES]
    return jnp.where(jnp.max(pieces, axis=-1) > 0, 1 + jnp.argmax(pieces, axis=-1), 0)


def loss_terms(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """policy_loss, value_loss, denoise_loss (module docstring)."""
    model, cast, grad_cast = config["model"], cast_for(precision), grad_cast_for(precision)
    clean, noised = streams(params, batch["planes"], batch["square_masked"], model, cast, grad_cast)
    b = clean.shape[0]
    logits, value = heads(params, clean.reshape(b, 8, 8, -1), cast, grad_cast)
    policy_loss = -jnp.mean(jnp.sum(batch["policy_target"] * jax.nn.log_softmax(logits, axis=-1), axis=-1))
    value_loss = jnp.mean((value - batch["value_target"]) ** 2)
    z = _product(cast, grad_cast)("bsh,hc->bsc", noised, params["denoise_w"]) + params["denoise_b"]
    cross_entropy = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1), square_class(batch["planes"])[..., None], axis=-1)[..., 0]
    level = jnp.repeat(batch["block_level"], model["block_length"], axis=1)  # a square's level is its block's
    weight = 1.0 if model.get("misread", "") == "no_level_weight" else 1.0 / level
    return policy_loss, value_loss, jnp.sum(batch["square_masked"] * weight * cross_entropy) / (b * SQUARES)


def loss(params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], precision: str = "float32") -> jax.Array:
    policy_loss, value_loss, denoise_loss = loss_terms(params, batch, config, precision)
    return policy_loss + config["train"]["value_weight"] * value_loss + config["train"]["denoise_weight"] * denoise_loss


_SLOTS: Dict[str, Any] = {}  # one compiled routing count a model, shared by every seed of a sweep


def expert_slots(params: Params, batch: Dict[str, jax.Array], model: Dict[str, Any]) -> jax.Array:
    """Every layer's slots an expert over BOTH streams' tokens, in float32: what the balance update reads."""
    key = json.dumps(model, sort_keys=True)
    if key not in _SLOTS:
        _SLOTS[key] = jax.jit(lambda p, planes, masked: _trunk(p, *_copies(planes, masked), model, cast_for("float32"), grad_cast_for("float32"))[1])
    return _SLOTS[key](params, batch["planes"], batch["square_masked"])


def train_losses(grad: Any, params: Params, batch: Dict[str, jax.Array], config: Dict[str, Any], steps: int) -> List[jax.Array]:
    """The loss before each of ``steps`` updates on one batch (its noise held fixed, as the batch carries it), with
    ``grad(params, batch) -> (loss, gradients)`` of this module's ``loss``: AdamW (the first trunk's reference's) on every trained
    tensor, one at a time, and the balance rule on ``expert_bias`` from the routing the step started with."""
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
        slots = expert_slots(params, batch, model)
        for k in trained:
            params[k], mu[k], nu[k] = first_block._adamw(params[k], mu[k], nu[k], g.pop(k).astype(jnp.float32), jnp.float32(t), lr, wd)
        params[BUFFER] = balanced_bias(params[BUFFER], slots, model["load_balance_coeff"])
    return losses
