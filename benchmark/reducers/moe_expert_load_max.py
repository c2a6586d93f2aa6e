"""Largest ``expert_load_max`` (the most (token, slot) rows any expert of
any layer received in a step) over the steps the program's step recorder
holds (benchmark/step_counters.py): the window's tail and the traced steps
that follow it. A collapsing router shows here. None where no step carries
the key."""

from benchmark import step_counters


def reduce(ctx):
    loads = step_counters.values(ctx, "expert_load_max")
    return None if loads is None else max(loads)
