"""Seconds of Python tracing and lowering (``trace_lower_s``) inside the
program's first ``train_init`` and first ``train_first_step`` spans
(benchmark/startup.py): paid at every start, compile cache or none. None
where a span is missing."""

from benchmark import startup


def reduce(ctx):
    spans = [startup.first_span(stage) for stage in startup.STAGES]
    if None in spans:
        return None
    return sum(span["trace_lower_s"] for span in spans)
