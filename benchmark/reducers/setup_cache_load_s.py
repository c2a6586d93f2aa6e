"""Seconds the persistent compile cache took to hand back programs
(``cache_load_s``) inside the program's first ``train_init`` and first
``train_first_step`` spans (benchmark/startup.py): what a warm start pays
for the size of its executables. None where a span is missing."""

from benchmark import startup


def reduce(ctx):
    spans = [startup.first_span(stage) for stage in startup.STAGES]
    if None in spans:
        return None
    return sum(span["cache_load_s"] for span in spans)
