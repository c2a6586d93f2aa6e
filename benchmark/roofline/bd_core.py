"""Operations and bytes the block-masked attention core needs for one
training step under block diffusion, from shapes and the configuration's
stated precision alone, whatever implements the core.

A board is two copies of its 64 squares, a clean and a noised one, 128
tokens, in ``64 / block_length`` blocks. The core is what lies between the
projections and the output projection of an attention layer whose
``num_attention_heads`` query heads share ``num_key_value_heads`` key-value
heads of ``head_dim``: for every board and query head the scores, the
softmax and the mix of the ALLOWED (query, key) pairs alone: a clean query
on the clean keys of its own and earlier blocks, a noised query on the
noised keys of its own block and the clean keys of earlier blocks. With B =
``64 / block_length`` blocks of L squares that is ``L^2 B (B + 1) / 2`` pairs
a copy (2,176 of a clean copy's 4,096 and 2,176 of a noised copy's 8,192 at
L = 4); a pair that the mask forbids is no work the mathematics asks for,
whatever tile an implementation computes it in. Products, an allowed pair:
forward two (its score and its part of the mix, ``head_dim`` multiply-adds
each); gradient five (the score again, the probability's cotangent, the
value's, the query's and the key's gradients). The qk-norm and RoPE are
elementwise over operands counted once and add no product and no byte. The
least HBM traffic, in the precision the configuration states (q and k
float32 as the projections write them, v, the mix and its cotangent
bfloat16), a token of EITHER copy: forward, q ``[heads x head_dim]`` and k,
v ``[kv_heads x head_dim]`` read and the mix written, each once: a copy's k
and v once for all the query heads of a group and for BOTH copies' queries;
gradient, the same three read again with the mix's cotangent, dq, dk
(float32) and dv (bfloat16) written, each once, dk and dv of the clean copy
summed over the group and over both copies' queries before they are
written. No scores, no mask, no repeated key, nothing made again but the
scores.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
COPIES = 2
F32, BF16 = 4, 2


def attention_layers(model: Dict[str, Any]) -> int:
    return model["num_hidden_layers"]


def allowed_pairs(model: Dict[str, Any]) -> int:
    """The (query, key) pairs of ONE board and query head that the mask allows, both copies' queries."""
    length = model["block_length"]
    blocks = SQUARES // length
    return COPIES * length * length * blocks * (blocks + 1) // 2


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    per_head = 2 * allowed_pairs(model) * model["head_dim"] * (2 + 5)  # forward two products, gradient five
    return float(batch * model["num_attention_heads"] * per_head)


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    queries, keys = model["num_attention_heads"] * model["head_dim"], model["num_key_value_heads"] * model["head_dim"]
    forward = queries * F32 + keys * F32 + keys * BF16 + queries * BF16  # q, k, v read; the mix written
    gradient = forward + queries * F32 + keys * F32 + keys * BF16  # the same three and the mix's cotangent read; dq, dk, dv written
    return float(batch * COPIES * SQUARES * (forward + gradient))


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = attention_layers(model)
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
