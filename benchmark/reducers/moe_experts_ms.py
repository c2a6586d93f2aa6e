"""Device ms a traced step in the routed experts of the sparse-expert
trunk: every operation under a scope ``layerNN.experts`` of
``models/trunk.py`` (the three grouped products, the casts of their
weights, silu x up), forward and ``transpose(...)`` paths both, summed
over ``benchmark/scopes.py``'s ``split(ctx).by_path``. None where the
program has no such scope."""

import re

from benchmark import scopes

PARTS = ("experts",)


def part_ms(ctx, parts):
    """ms a step under the scopes ``layerNN.<part>`` for ``parts``, or
    None without a trace or where no path names one."""
    found = scopes.split(ctx)
    if found is None:
        return None
    named = re.compile(r"(^|/)layer\d+\.(%s)$" % "|".join(parts))
    times = [ms for path, ms in found.by_path.items() if named.search(path)]
    return sum(times) if times else None


def reduce(ctx):
    return part_ms(ctx, PARTS)
