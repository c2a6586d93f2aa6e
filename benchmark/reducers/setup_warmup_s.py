"""Seconds from the first ``train_first_step``'s end to the window's start
(``process_boot``'s start + ``setup_s``): the warm-up steps' execution
(benchmark/startup_programs.py)."""

from benchmark import startup_programs


def reduce(ctx):
    return startup_programs.metric(ctx, "setup_warmup_s")
