"""``program_up`` spans with ``cache: miss`` that ended before the window's
start, on any thread: every program of start-up the persistent compile
cache did not hold, the settle's and the pool's too, where
``setup_cache_misses`` reads the two trainer spans alone. A warm run reads
0 (benchmark/startup_programs.py)."""

from benchmark import startup_programs


def reduce(ctx):
    return startup_programs.metric(ctx, "setup_programs_missed")
