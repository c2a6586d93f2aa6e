"""Operations and bytes the core of a Kimi Delta Attention mixer needs for
one training step, from shapes and the configuration's stated precision
alone, whatever implements the core.

The core is what lies between the convolution and the gated head norm of a
KDA layer: for every board and every held head (``kda_head_dim`` = d
columns of q, k, v and of the log-decay g, one beta a square) the
recurrence ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t
k_t v_t^T``, ``o_t = S_t^T q_t`` over the 64 squares. A board is one chunk
from a zero state, so the least work is the chunk form's: products, a
board and head, forward the two decayed score tables ``K K^T`` and ``Q
K^T`` (64 x 64 x d each), the triangular solve applied to ``beta V`` and
``Mq U`` (64 x 64 x d each): four; gradient, the two tables again, the two
applications again and their transposes (``dO U^T``, ``Mq^T dO``, the
transposed solve, ``W U^T``: four), and the four products that take the
two tables' cotangents to q and k (from each table, one to its left and
one to its right operand): fourteen of 64 x 64 x d a step. The solve's own
64 x 64 x 64 products, the l2 norms, the cumulative sum and the
exponentials are not counted: they are neither ``d``-wide products nor HBM
traffic. The least HBM traffic, in the precision the configuration states
(q, k, v, o and their cotangents bfloat16, g, beta and their cotangents
float32): forward q, k, v, g and beta read, o written, each once;
gradient the same operands and o's cotangent read, dq, dk, dv, dg and
dbeta written, each once. No ``[64, 64]`` table, no state, nothing made
again but the tables and the solve.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
F32, BF16 = 4, 2


def kda_layers(model: Dict[str, Any]) -> int:
    return list(model["mixers"]).count("kda")


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    heads, d = model["kda_num_heads"], model["kda_head_dim"]
    a_product = 2 * SQUARES * SQUARES * d
    return float(batch * heads * (4 + 10) * a_product)


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    heads, d = model["kda_num_heads"], model["kda_head_dim"]
    operands = heads * (3 * d * BF16 + d * F32 + F32)  # q, k, v, g and beta, a token
    result = heads * d * BF16  # o, or its cotangent, a token
    forward = operands + result
    gradient = operands + result + operands  # operands and do read; dq, dk, dv, dg, dbeta written
    return float(batch * SQUARES * (forward + gradient))


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = kda_layers(model)
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
