"""Mean of the step counter ``held_slots``: the (token, slot) rows that
fell on the experts a share holds, summed over the routed layers, a step.
Read from the program's step recorder (benchmark/step_counters.py) over
the steps its ring holds: the window's tail and the traced steps that
follow it. The line before the result gives min / median / max, since a
share's rate follows these rows. None where no step carries the key."""

import statistics

from benchmark import step_counters


def reduce(ctx):
    held = step_counters.values(ctx, "held_slots")
    if held is None:
        return None
    print(f"held_slots over {len(held)} steps: min {min(held):.0f} median {statistics.median(held):.0f} max {max(held):.0f}")
    return statistics.fmean(held)
