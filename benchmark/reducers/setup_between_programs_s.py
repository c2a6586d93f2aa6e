"""Seconds of ``setup_between_s`` that went into bringing programs up: the
four phases of every ``program_up`` span that ended in that interval, on
any thread, and the growth of ``small``'s seconds over it (the settle's
forward-only program, the pool's; benchmark/startup_programs.py)."""

from benchmark import startup_programs


def reduce(ctx):
    return startup_programs.metric(ctx, "setup_between_programs_s")
