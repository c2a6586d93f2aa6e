"""Concreteness checks for code that is sometimes traced.

``is_concrete(x)`` is the sanctioned guard for host-only fast paths
inside functions that may run under ``jax.jit``: the static checker
(fishnet_tpu.analysis R2) exempts ``if is_concrete(x):`` subtrees from
the host-sync rules, because such a branch executes at trace time on the
Python value and can never observe a traced array's contents.

This replaces the deprecated ``isinstance(x, jax.core.Tracer)`` pattern
(flagged by R3): ``jax.core.Tracer`` is slated for removal from the
public namespace, while ``jax.core.is_concrete`` is the supported
concreteness predicate of the installed JAX (0.9.0).
"""

from __future__ import annotations

import numpy as np

__all__ = ["is_concrete"]


def is_concrete(x) -> bool:
    """True when ``x`` is host-inspectable NOW: a numpy array/scalar, a
    Python number, or a committed ``jax.Array`` — anything but a tracer.

    Cheap and import-light: jax is only consulted for values that could
    actually be traced.
    """
    if x is None or isinstance(
        x, (np.ndarray, np.generic, bool, int, float, complex, list, tuple)
    ):
        return True
    import jax

    return bool(jax.core.is_concrete(x))
