"""The mean pass a looped trunk's boards are expected to leave at: the mean
over the steps the program's step recorder holds (benchmark/step_counters.py)
of the step counter ``exit_step_mean`` (``train/az_trainer.py
_expected_exit_terms``: ``sum_t t p_t``, mean over the boards, in [1,
``total_ut_steps``]). A steadiness counter, as ``bd_masked_share`` is: 1.875
where every gate sits at a half over four passes; a gate that collapses onto
the first pass reads 1 and onto the last ``total_ut_steps``, and the entropy
term is there to keep it from either. The line before the result gives min /
median / max of it, and the means of ``exit_entropy``, ``loss_first_pass``,
``loss_last_pass`` and ``loop_update_rms`` over the same steps. None where no
step carries the key (another family; a trunk that is not looped)."""

import statistics

from benchmark import step_counters


def reduce(ctx):
    means = step_counters.values(ctx, "exit_step_mean")
    if means is None:
        return None
    beside = {key: statistics.fmean(step_counters.values(ctx, key) or [float("nan")]) for key in ("exit_entropy", "loss_first_pass", "loss_last_pass", "loop_update_rms")}
    print(f"exit step mean over {len(means)} steps: min {min(means):.4f} median {statistics.median(means):.4f} max {max(means):.4f}; "
          + ", ".join(f"{key} {value:.4f}" for key, value in beside.items()))
    return statistics.fmean(means)
