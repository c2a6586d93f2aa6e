"""Programs the persistent compile cache did not hold, inside the
program's ``train_init`` and ``train_first_step`` spans (their
``cache_misses`` fields; benchmark/startup.py). A warm run reads 0."""

from benchmark import startup


def reduce(ctx):
    spans = [startup.first_span(stage) for stage in startup.STAGES]
    if None in spans:
        return None
    return sum(span["cache_misses"] for span in spans)
