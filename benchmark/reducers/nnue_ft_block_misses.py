"""Sum of the step counter ``ft_block_misses`` (active feature indices
outside their pair's block of the table: the entries the table gradient
drops) over the steps the program's step recorder holds
(benchmark/step_counters.py): the window's tail and the traced steps that
follow it. 0 while the batches keep the index contract. None where no step
carries the key."""

from benchmark import step_counters


def reduce(ctx):
    misses = step_counters.values(ctx, "ft_block_misses")
    return None if misses is None else sum(misses)
