"""What the program recorded of its own start-up.

The trainers record two spans in the program's span recorder
(``fishnet_tpu/train/startup.py``): ``train_init`` round ``init`` and
``train_first_step`` round the first ``.step`` of a trainer instance,
each with the compile seconds and persistent-cache misses that fell
inside it. The cell's trainer is the first the process makes (the
comparison that decides ``correct`` makes another, later), so the
``setup_*`` reducers read the first span of each stage. A program that
records no such span (the parent of the PR that added them) gives None,
and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

STAGES = ("train_init", "train_first_step")


def first_span(stage: str) -> Optional[Dict[str, Any]]:
    """The earliest span of ``stage`` this process recorded, or None."""
    from fishnet_tpu.telemetry.spans import RECORDER

    # spans() is oldest first
    return next((span for span in RECORDER.spans() if span["stage"] == stage), None)


def span_seconds(stage: str) -> Optional[float]:
    span = first_span(stage)
    return None if span is None else span["dur_ms"] / 1e3
