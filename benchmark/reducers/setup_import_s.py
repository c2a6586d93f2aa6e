"""Seconds from the package's first import to the first ``train_init``'s
start: the ``program_import`` span (the program's own imports, up to the
cell's trainer's construction) and the gap after it
(benchmark/startup_programs.py)."""

from benchmark import startup_programs


def reduce(ctx):
    return startup_programs.metric(ctx, "setup_import_s")
