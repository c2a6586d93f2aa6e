"""Mean of the step counter ``moved_rows``: the rows each row move of the
step's routed layers covers, summed over the layers (every slot where all
experts are held: routed layers x batch x 64 x experts a token; a share's
held counts rounded up to the moves' blocks). Read from the program's step
recorder (benchmark/step_counters.py) over the steps its ring holds: the
window's tail and the traced steps that follow it. None where no step
carries the key."""

import statistics

from benchmark import step_counters


def reduce(ctx):
    moved = step_counters.values(ctx, "moved_rows")
    return None if moved is None else statistics.fmean(moved)
