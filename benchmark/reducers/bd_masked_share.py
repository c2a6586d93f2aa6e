"""The share of a step's squares that its batch's noise masked: the mean of
the step counter ``masked_squares`` (``train/az_trainer.py _loss``: the
squares masked in the batch) over ``batch x 64``, read from the program's
step recorder (benchmark/step_counters.py) over the steps its ring holds.
Under levels uniform on [t_min, 1] it is 0.5 and a step's own varies by what
8,192 draws give: the cell's steadiness beside ``moe_held_slots``, and the
denoising loss's. The line before the result gives min / median / max of the
share and the mean level. None where no step carries the key (another
family; a program without block diffusion)."""

import statistics

from benchmark import step_counters

SQUARES = 64


def reduce(ctx):
    masked = step_counters.values(ctx, "masked_squares")
    if masked is None:
        return None
    shares = [count / (ctx["batch"] * SQUARES) for count in masked]
    level = step_counters.values(ctx, "noise_level_mean") or [float("nan")]
    print(f"masked share over {len(shares)} steps: min {min(shares):.4f} median {statistics.median(shares):.4f} max {max(shares):.4f}; "
          f"mean noise level {statistics.fmean(level):.4f}")
    return statistics.fmean(shares)
