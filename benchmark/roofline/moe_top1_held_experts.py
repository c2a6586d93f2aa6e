"""Operations and bytes the HELD routed experts' three grouped matrix
products need for one training step, from shapes alone: the count of
``roofline/moe_held_experts.py`` in a block whose every layer is routed
and chooses ONE expert a token.

One chip of an expert-parallel deployment holds ``num_experts`` of the
``num_routed_experts`` experts of every layer (8 of 16: two chips share a
layer). Every token is one row of ONE expert, ``positions x 64 squares``
a layer; under even routing the held experts receive ``num_experts /
num_routed_experts`` of them (the EXPECTATION of the program's step
counter ``held_slots``, which ``moe_held_slots`` reports beside this).
Each row goes through the gate and the up product (hidden -> expert
width) and the down product (expert width -> hidden): three products;
training runs each three times (forward, gradient to its rows, gradient
to its weights). Rows of padding to a tile, the rows of absent experts
and anything made again in the backward pass are NOT counted. The least
HBM traffic: each pass of each product reads or writes the held experts'
weights once in bfloat16 and reads its rows in and writes its rows out
once in bfloat16.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
PRODUCTS = 3  # gate, up, down: each hidden x expert width multiply-adds a row
PASSES = 3  # forward, gradient to the rows, gradient to the weights


def routed_layers(model: Dict[str, Any]) -> int:
    return model["num_hidden_layers"]  # no dense layer: every layer chooses


def held_slots(model: Dict[str, Any], batch: int) -> float:
    """Rows the held experts of ONE layer receive under even routing."""
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
