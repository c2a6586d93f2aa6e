"""Operations and bytes the routed experts' three grouped matrix
products need for one training step, from shapes alone.

Every (token, slot) pair is one row: ``slots = positions x 64 squares x
experts_per_token``. Each row goes through the gate and the up product
(hidden -> expert width) and the down product (expert width -> hidden),
and training runs each product three times (forward, gradient to its
rows, gradient to its weights). Rows of padding to a tile and anything
recomputed in the backward pass are NOT counted, so the share of the
roofline cannot read over 100%. The least HBM traffic: each pass of each
product reads or writes every expert's weights once in bfloat16 and
reads its rows in and writes its rows out once in bfloat16.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
PRODUCTS = 3  # gate, up, down: each hidden x expert width multiply-adds a row
PASSES = 3  # forward, gradient to the rows, gradient to the weights


def slots(model: Dict[str, Any], batch: int) -> int:
    return batch * SQUARES * model["num_experts_per_tok"]


def step_flops(model: Dict[str, Any], batch: int) -> int:
    per_row = 2 * model["hidden_size"] * model["expert_intermediate_size"]
    return slots(model, batch) * per_row * PRODUCTS * PASSES * model["num_hidden_layers"]


def step_bytes(model: Dict[str, Any], batch: int) -> int:
    hidden, width = model["hidden_size"], model["expert_intermediate_size"]
    weights = model["num_experts"] * hidden * width * 2
    rows = slots(model, batch) * (hidden + width) * 2
    return (weights + rows) * PRODUCTS * PASSES * model["num_hidden_layers"]


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    compute = step_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = step_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
