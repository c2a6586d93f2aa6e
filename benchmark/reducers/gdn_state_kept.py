"""Mean of the step counter ``gdn_state_kept`` (the mean decay ``exp(g)`` a
square of the Gated DeltaNet mixers' states, over tokens, value heads and
mixers: 1 a state that never forgets, 0 one that holds nothing) over the
steps the program's step recorder holds (benchmark/step_counters.py): the
window's tail and the traced steps that follow it. The line before the
result gives ``gdn_beta`` and ``shared_gate_mean`` beside it. None where no
step carries the key."""

import statistics

from benchmark import step_counters


def reduce(ctx):
    kept = step_counters.values(ctx, "gdn_state_kept")
    if kept is None:
        return None
    beside = {name: step_counters.values(ctx, name) for name in ("gdn_beta", "shared_gate_mean")}
    print(f"gdn_state_kept over {len(kept)} steps: min {min(kept):.4f} max {max(kept):.4f}"
          + "".join(f"; {name} mean {statistics.fmean(values):.4f}" for name, values in beside.items() if values is not None))
    return statistics.fmean(kept)
