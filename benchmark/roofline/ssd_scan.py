"""Operations and bytes the core of a Mamba-2 mixer's scan needs for one
training step, from shapes and the configuration's stated precision
alone, whatever implements the core.

The core is what lies between the convolution and the gated norm of an
``M`` layer: for every board, and every head h of ``mamba_num_heads``
(``mamba_head_dim`` columns of x) with its group's ``ssm_state_size``
columns of B and of C, the recurrence ``S_t = exp(D_t a) S_{t-1} + D_t
x_t B_t^T``, ``y_t = S_t C_t + D_skip x_t`` over the 64 squares. A board
is one chunk, so the least work is the dual form's: products, a board,
forward ``C B^T`` once a group (64 x 64 x N) and ``W X`` a head (64 x 64
x P); gradient ``C B^T`` again and the two gradients to B and C a group
(three of 64 x 64 x N), and ``W^T dY`` and ``dY X^T`` a head (two of 64
x 64 x P). The decay (a cumulative sum, 64 x 64 exponentials a head) is
not counted: it is neither a product nor HBM traffic. The least HBM
traffic, in the precision the configuration states (x, B, C, y and their
cotangents bfloat16, the step D float32): forward, x ``[T, heads x P]``,
B and C ``[T, groups x N]`` and the step's ``heads`` columns read, y
written, each once; gradient, the same operands and y's cotangent read,
dx, dB, dC and d(step) written, each once. No ``[64, 64]`` table, no
state, nothing made again but ``C B^T`` and the decay.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
F32, BF16 = 4, 2


def scan_layers(model: Dict[str, Any]) -> int:
    return model["pattern"].count("M")


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    heads, groups, p, n = model["mamba_num_heads"], model["n_groups"], model["mamba_head_dim"], model["ssm_state_size"]
    a_product = 2 * SQUARES * SQUARES
    forward = groups * a_product * n + heads * a_product * p
    gradient = groups * 3 * a_product * n + heads * 2 * a_product * p
    return float(batch * (forward + gradient))


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    heads, groups, p, n = model["mamba_num_heads"], model["n_groups"], model["mamba_head_dim"], model["ssm_state_size"]
    operands = heads * p * BF16 + 2 * groups * n * BF16 + heads * F32  # x, B, C, the step, a token
    result = heads * p * BF16  # y, or its cotangent, a token
    forward = operands + result
    gradient = operands + result + operands  # operands and dy read; dx, dB, dC, d(step) written
    return float(batch * SQUARES * (forward + gradient))


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = scan_layers(model)
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
