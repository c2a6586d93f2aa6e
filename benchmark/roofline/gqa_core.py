"""Operations and bytes the grouped-query attention core needs for one
training step, from shapes and the configuration's stated precision alone,
whatever implements the core.

The core is what lies between the projections and the output projection of
an attention layer whose ``num_attention_heads`` query heads share
``num_key_value_heads`` key-value heads of ``head_dim``: for every board and
query head the scores of 64 queries on 64 keys over ``head_dim`` columns,
the softmax and the mix of ``head_dim``-wide values. Products, a (board,
query head): forward two (64 x 64 x head_dim each: scores, mix); gradient
five (the scores again, the probabilities' cotangent, the values', the
queries' and the keys' gradients). The qk-norm and RoPE (whichever table a
layer's kind turns by) are elementwise over operands counted once, and add
no product and no byte. The least HBM traffic, in the precision the
configuration states (q and k float32 as the projections write them, v, the
mix and its cotangent bfloat16): forward, q ``[T, heads x head_dim]`` and k,
v ``[T, kv_heads x head_dim]`` read and the mix written, each once: a
key-value head's k and v once for ALL the query heads of its group;
gradient, the same three read again with the mix's cotangent, dq, dk
(float32) and dv (bfloat16) written, each once, dk and dv summed over a
group before they are written. No scores, no repeated key, nothing made
again but the scores.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
F32, BF16 = 4, 2


def attention_layers(model: Dict[str, Any]) -> int:
    return len(model["kept_layer_types"])


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    per_head = 2 * SQUARES * SQUARES * model["head_dim"] * (2 + 5)  # forward two products, gradient five
    return float(batch * model["num_attention_heads"] * per_head)


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    queries, keys = model["num_attention_heads"] * model["head_dim"], model["num_key_value_heads"] * model["head_dim"]
    forward = queries * F32 + keys * F32 + keys * BF16 + queries * BF16  # q, k, v read; the mix written
    gradient = forward + queries * F32 + keys * F32 + keys * BF16  # the same three and the mix's cotangent read; dq, dk, dv written
    return float(batch * SQUARES * (forward + gradient))


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = attention_layers(model)
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
