"""Seconds of the program's own ``train_init`` span: the cell's
``trainer.init``, its program traced, lowered, compiled or loaded, and
run (benchmark/startup.py)."""

from benchmark import startup


def reduce(ctx):
    return startup.span_seconds("train_init")
