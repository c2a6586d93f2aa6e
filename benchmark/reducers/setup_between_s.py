"""Seconds from the first ``train_init``'s end to the first
``train_first_step``'s start: the caller's time, here the settle and the
position pool (benchmark/startup_programs.py)."""

from benchmark import startup_programs


def reduce(ctx):
    return startup_programs.metric(ctx, "setup_between_s")
