"""Operations and bytes the latent attention core needs for one training
step, from shapes and the configuration's stated precision alone,
whatever implements the core.

The core is what lies between the projections and the output projection
of a latent-attention layer: for every board and head the scores of 64
queries on 64 keys over ``qk_nope_head_dim + qk_rope_head_dim`` columns
(RoPE on the second part, one RoPE key for all heads), the softmax and
the mix of ``v_head_dim``-wide values. Products, a (board, head):
forward two (64 x 64 x score width, 64 x 64 x value width); gradient
five (the scores again, the probabilities' cotangent and the values'
gradient over the value width, the queries' and the keys' gradients over
the score width). The least HBM traffic, in the precision the
configuration states (q's two parts and both parts of k float32 as the
projections write them, v and the mix bfloat16): forward, q ``[T, heads x
(nope + rope)]``, k_nope ``[T, heads x nope]``, k_pe ``[T, rope]`` read
and the mix ``[T, heads x value]`` written, v read, each once; gradient,
the same operands read again, the mix's cotangent read, dq, dk_nope,
dk_pe and dv written, each once. No scores, no copy of ``k_pe`` a head,
no norm (the latent's norm is outside the core), nothing made again but
the scores.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
F32, BF16 = 4, 2


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    score = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    value = model["v_head_dim"]
    per_head = 2 * SQUARES * SQUARES * ((score + value) + (score + 2 * value + 2 * score))  # forward two products, gradient five
    return float(batch * model["num_attention_heads"] * per_head)


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    heads, nope, rope, value = model["num_attention_heads"], model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    tokens = batch * SQUARES
    scores_side = (heads * (nope + rope) + heads * nope + rope) * F32  # q, k_nope, k_pe a token
    values_side = heads * value * BF16  # v, or the mix, a token
    forward = scores_side + 2 * values_side  # v read, the mix written
    gradient = scores_side + 2 * values_side + scores_side + values_side  # operands and the mix's cotangent read; dq, dk_nope, dk_pe, dv written
    return float(tokens * (forward + gradient))


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = model["num_hidden_layers"]
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
