"""Operations and bytes the core of a Gated DeltaNet mixer needs for one
training step, from shapes and the configuration's stated precision alone,
whatever implements the core.

The core is what lies between the convolution and the gated head norm of a
GDN layer: for every board and every VALUE head (``linear_value_head_dim`` =
d columns of v and o, one log-decay g and one beta a square; value head h
reads key head ``h // r``, r = value heads / key heads, whose d columns of q
and k are shared) the recurrence ``S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t -
exp(g_t) S_{t-1}^T k_t)^T``, ``o_t = S_t^T q_t`` over the 64 squares. A board
is one chunk from a zero state, so the least work is the chunk form's, its
two score tables made once a KEY head for all of its value heads (the decay
is a scalar a square: it multiplies a table, it is in no product). Products
of 64 x 64 x d, a board, counted one by one:

    forward,  a key head     q k^T;  k k^T                                                            2
    forward,  a value head   the triangular solve applied to beta V (``T (beta V)``);  Mq U            2
    gradient, a key head     q k^T and k k^T again (nothing ``[64, 64]`` is kept in HBM)               2
    gradient, a value head   dO U^T;  Mq^T dO;  the transposed solve applied to it (``T^T dU``);
                             its product with U^T (the cotangent of the solve's matrix)               4
    gradient, a key head     the two tables' cotangents (summed over the key head's value heads) to
                             their operands: to q; to k from q's table; to k, left and right, from
                             its own                                                                  4

``(8 + 6 r)`` a key head and board: 20 at two value heads a key head, 10 a
value head (the sixth trunk's core, one decay a channel and q, k a head,
counts 14). The solve's own 64 x 64 x 64 products, the l2 norms, the sums of
g over spans of squares and the exponentials are not counted: they are
neither ``d``-wide products nor HBM traffic, and a core that forward-
substitutes needs none of the first. The least HBM traffic, in the precision
the configuration states (q, k, v, o and their cotangents bfloat16; g, beta
and their cotangents float32): forward q and k read once a KEY head, v, g
and beta read and o written once a value head; gradient the same operands
and o's cotangent read, dq and dk written once a key head, dv, dg and dbeta
once a value head. No ``[64, 64]`` table, no state, no q or k repeated a
value head, no decay broadcast over channels, nothing kept between the two
passes.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
F32, BF16 = 4, 2


def gdn_layers(model: Dict[str, Any]) -> int:
    return list(model["mixers"]).count("gdn")


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    key_heads, per, d = model["linear_num_key_heads"], model["linear_num_value_heads"] // model["linear_num_key_heads"], model["linear_value_head_dim"]
    a_product = 2 * SQUARES * SQUARES * d
    return float(batch * key_heads * (8 + 6 * per) * a_product)


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    key_heads, heads, d = model["linear_num_key_heads"], model["linear_num_value_heads"], model["linear_value_head_dim"]
    operands = 2 * key_heads * d * BF16 + heads * (d * BF16 + 2 * F32)  # q and k a key head; v, g and beta a value head: a token
    result = heads * d * BF16  # o, or its cotangent, a token
    forward = operands + result
    gradient = operands + result + operands  # operands and do read; dq, dk, dv, dg, dbeta written
    return float(batch * SQUARES * (forward + gradient))


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = gdn_layers(model)
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
