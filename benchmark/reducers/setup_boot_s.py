"""Seconds of the program's ``process_boot`` span: the process's start to
the package's first import, which is the interpreter, the benchmark's and
JAX's imports and the device client's start (benchmark/startup_programs.py)."""

from benchmark import startup_programs


def reduce(ctx):
    return startup_programs.metric(ctx, "setup_boot_s")
