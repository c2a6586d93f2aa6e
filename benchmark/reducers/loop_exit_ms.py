"""Device ms a traced step in a looped trunk's exits: the scopes
``final_norm``, ``policy_head``, ``value_head`` and ``exit_gate`` under
``forward`` (``models/trunk.py trunk_forward_counted``: one call each on all
``loop_steps`` passes' streams) and ``exit`` under ``loss`` (the exit
distribution, the entropy and the expectation over the exits,
``train/az_trainer.py _expected_exit_terms``), forward and ``transpose(...)``
paths both, summed over ``benchmark/scopes.py``'s ``split(ctx).by_path``: what
``loop_steps`` exits cost where every other trunk pays one. None without a
trace or where the program has no ``exit_gate`` scope (a trunk that is not
looped, whose ``final_norm`` and heads are not an exit's)."""

import re

from benchmark import scopes

_EXITS = re.compile(r"(^|/)(final_norm|policy_head|value_head|exit_gate|exit)$")
_GATE = re.compile(r"(^|/)exit_gate$")


def reduce(ctx):
    found = scopes.split(ctx)
    if found is None or not any(_GATE.search(path) for path in found.by_path):
        return None
    return sum(ms for path, ms in found.by_path.items() if _EXITS.search(path))
