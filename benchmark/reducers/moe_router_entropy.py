"""Mean of the step counter ``router_entropy`` (nats; mean over tokens and
layers of the entropy of the router's distribution, ``log(experts)`` when
uniform) over the steps the program's step recorder holds
(benchmark/step_counters.py): the window's tail and the traced steps that
follow it. None where no step carries the key."""

import statistics

from benchmark import step_counters


def reduce(ctx):
    entropy = step_counters.values(ctx, "router_entropy")
    return None if entropy is None else statistics.fmean(entropy)
