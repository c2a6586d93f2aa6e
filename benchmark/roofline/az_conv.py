"""Operations and bytes the AlphaZero tower's matrix work needs for one
training step, from shapes alone.

A k x k 'same' convolution over the 8x8 board multiplies only where a
tap falls on the board: 484 of the 576 (square, tap) pairs for 3x3, as
XLA's own count does. Training runs each convolution three times
(forward, gradient to its input, gradient to its kernel); the stem's
input is data, so it runs twice. Recomputation is not counted.
"""

from __future__ import annotations

from typing import Dict

BOARD = 8


def board_taps(k: int) -> int:
    """(output square, tap) pairs of a k x k 'same' convolution that fall on the board."""
    pad = k // 2
    per_axis = sum(BOARD - abs(d) for d in range(-pad, pad + 1))
    return per_axis * per_axis


def conv_flops(batch: int, k: int, cin: int, cout: int, passes: int = 3) -> int:
    return 2 * batch * board_taps(k) * cin * cout * passes


def dense_flops(batch: int, cin: int, cout: int, passes: int = 3) -> int:
    return 2 * batch * cin * cout * passes


def step_flops(model: Dict[str, int], batch: int) -> int:
    """Multiply-adds x 2 of every convolution and matrix product of one step."""
    c = model["channels"]
    total = conv_flops(batch, 3, model["input_planes"], c, passes=2)
    total += 2 * model["blocks"] * conv_flops(batch, 3, c, c)
    total += conv_flops(batch, 1, c, model["policy_planes"])
    total += conv_flops(batch, 1, c, 4)
    total += dense_flops(batch, 4 * BOARD * BOARD, model["value_hidden"])
    total += dense_flops(batch, model["value_hidden"], 1)
    return total


def step_bytes(model: Dict[str, int], batch: int) -> int:
    """Least HBM traffic of the tower: each 3x3 convolution reads its input
    and writes its output once per pass in bfloat16, and reads or writes
    its float32 kernel; no reuse between layers is assumed."""
    c = model["channels"]
    act = batch * BOARD * BOARD * c * 2
    kernel = 9 * c * c * 4
    per_conv = 3 * (2 * act + kernel)
    return (2 * model["blocks"] + 1) * per_conv


def least_seconds(model: Dict[str, int], batch: int, peaks: Dict[str, float]) -> Dict[str, float]:
    compute = step_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = step_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
