"""What the program recorded of its own steps.

Both trainers hand every step's ``metrics`` (device scalars: ``loss`` and
the counters of ``doc/observability.md`` "Training and compilation") to
the program's step recorder (``fishnet_tpu/train/step_metrics.py``), which
keeps each trainer's last 64 steps on the device until someone reads.
The cell's trainer is the first of the process to step (the comparison
that decides ``correct`` makes and steps another, later; ``scopes.py``
makes a third that never steps), so the reducers read that one, once a
run: the reading is kept in ``ctx``. When a traced run's reducers run, the
ring holds the traced steps and, before them, the tail of the window. A
program without the recorder (the parent of the PR that added it) gives
None, and the metrics are left out of the line.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional


def steps(ctx: Dict[str, Any]) -> Optional[List[Dict[str, float]]]:
    """The metrics of the steps the cell's trainer's ring holds, oldest
    first, fetched once a run; None where nothing recorded a step."""
    if "step_counters" not in ctx:
        ctx["step_counters"] = _read()
    return ctx["step_counters"]


def _read() -> Optional[List[Dict[str, float]]]:
    try:
        from fishnet_tpu.train import step_metrics
    except ImportError:
        return None
    record = step_metrics.STEPS.first_stepped()
    if record is None:
        return None
    started = time.monotonic()
    reading = record.read()
    print(
        f"step counters: trainer {reading.trainer}, steps {reading.first}-{reading.steps - 1} of {reading.steps} "
        f"read in {1e3 * (time.monotonic() - started):.3f} ms; keys {' '.join(sorted(reading.metrics[-1]))}"
    )
    return reading.metrics


def values(ctx: Dict[str, Any], key: str) -> Optional[List[float]]:
    """``key`` of every step read, or None where the steps do not carry it
    (another family, another block of the trunk)."""
    held = steps(ctx)
    if not held or any(key not in step for step in held):
        return None
    return [step[key] for step in held]
