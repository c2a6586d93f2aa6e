"""Device ms a traced step in operations that carry none of the program's
scopes (benchmark/scopes.py): copies the compiler added, and the whole
step where a refactor or a program cached before the scopes lost the
names. The tripwire of the other three."""

from benchmark import scopes


def reduce(ctx):
    return scopes.phase_ms(ctx, "unscoped")
