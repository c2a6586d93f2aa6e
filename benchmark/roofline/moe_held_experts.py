"""Operations and bytes the HELD routed experts' three grouped matrix
products need for one training step, from shapes alone.

One chip of an expert-parallel deployment holds ``num_experts`` of the
``num_routed_experts`` experts of every routed layer. Every (token,
slot) pair is one row of some expert, ``positions x 64 squares x
experts_per_token`` a routed layer; under even routing the held experts
receive ``num_experts / num_routed_experts`` of them. That count is the
EXPECTATION: a step's own count is the program's step counter
``held_slots`` (``models/trunk.py trunk_forward_counted``: the slots that
fell on the held experts, summed over the layers), which no reducer can
read until a runner hands step metrics over (PERF.md section 7); where
the routing is uneven, check this count by it. Each row goes through the
gate and the up product (hidden -> expert width) and the down product
(expert width -> hidden), and training runs each product three times
(forward, gradient to its rows, gradient to its weights). Rows of padding
to a tile, the rows of absent experts (the program moves them and the
products skip them) and anything made again in the backward pass are NOT
counted. The least HBM traffic: each pass of each product reads or writes
the held experts' weights once in bfloat16 and reads its rows in and
writes its rows out once in bfloat16.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
PRODUCTS = 3  # gate, up, down: each hidden x expert width multiply-adds a row
PASSES = 3  # forward, gradient to the rows, gradient to the weights


def routed_layers(model: Dict[str, Any]) -> int:
    return model["num_hidden_layers"] - model["num_dense_layers"]


def held_slots(model: Dict[str, Any], batch: int) -> float:
    """Rows the held experts of ONE routed layer receive under even routing."""
    return batch * SQUARES * model["num_experts_per_tok"] * model["num_experts"] / model["num_routed_experts"]


def step_flops(model: Dict[str, Any], batch: int) -> float:
    per_row = 2 * model["hidden_size"] * model["moe_intermediate_size"]
    return held_slots(model, batch) * per_row * PRODUCTS * PASSES * routed_layers(model)


def step_bytes(model: Dict[str, Any], batch: int) -> float:
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["num_experts"] * hidden * width * 2
    rows = held_slots(model, batch) * (hidden + width) * 2
    return (weights + rows) * PRODUCTS * PASSES * routed_layers(model)


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    compute = step_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = step_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
