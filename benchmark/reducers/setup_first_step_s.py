"""Seconds of the program's own ``train_first_step`` span: the first
``.step`` of the cell's trainer, the step program traced, lowered,
compiled or loaded, and dispatched (benchmark/startup.py)."""

from benchmark import startup


def reduce(ctx):
    return startup.span_seconds("train_first_step")
