"""Bytes the NNUE feature transformer needs for one training step, from
shapes and the number of ACTIVE (unmasked) feature rows alone.

Forward reads each active row of the table once (1024 weight columns and
8 PSQT columns, float32) and writes the two accumulators of each
position. Backward reads those accumulators' gradients and adds them
into each active row of the gradient table: one read and one write of
the row. No reuse between positions is assumed; padding slots cost
nothing. Its additions (one per element moved) are far under the
machine's balance, so the bound is memory.
"""

from __future__ import annotations

from typing import Dict


def row_bytes(model: Dict[str, int]) -> int:
    return (model["l1"] + model["num_buckets"]) * 4


def step_bytes(model: Dict[str, int], batch: int, active_rows: float) -> float:
    """``active_rows``: active (position, perspective, slot) triples in the batch."""
    row = row_bytes(model)
    forward = active_rows * row + batch * 2 * row
    backward = batch * 2 * row + 2 * active_rows * row
    return forward + backward


def step_flops(model: Dict[str, int], active_rows: float) -> float:
    return 2 * active_rows * (model["l1"] + model["num_buckets"])


def least_seconds(model: Dict[str, int], batch: int, active_rows: float, peaks: Dict[str, float]) -> Dict[str, float]:
    memory = step_bytes(model, batch, active_rows) / peaks["hbm_bytes_per_s"]
    compute = step_flops(model, active_rows) / peaks["bf16_flops_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
