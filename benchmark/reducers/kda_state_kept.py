"""Mean of the step counter ``kda_state_kept`` (the mean decay ``alpha =
exp(g)`` a square of the Kimi Delta Attention mixers' states, over tokens,
heads, channels and mixers: 1 a state that never forgets, 0 one that holds
nothing) over the steps the program's step recorder holds
(benchmark/step_counters.py): the window's tail and the traced steps that
follow it. The line before the result gives ``kda_beta`` beside it. None
where no step carries the key."""

import statistics

from benchmark import step_counters


def reduce(ctx):
    kept = step_counters.values(ctx, "kda_state_kept")
    if kept is None:
        return None
    beta = step_counters.values(ctx, "kda_beta")
    print(f"kda_state_kept over {len(kept)} steps: min {min(kept):.4f} max {max(kept):.4f}"
          + ("" if beta is None else f"; kda_beta mean {statistics.fmean(beta):.4f}"))
    return statistics.fmean(kept)
