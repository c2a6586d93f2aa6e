"""How a reference computes: in float32 at ``highest`` matmul precision
when it is the yardstick, or in a lower precision when it stands in the
program's place as the control (the step a later PR would be tempted by)."""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

Cast = Callable[[jax.Array], jax.Array]


def cast_for(precision: str) -> Cast:
    """What a reference applies to every operand of a contraction."""
    if precision == "float32":
        return lambda x: x.astype(jnp.float32)
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16)
    if precision == "float8_e4m3fn":
        # Per-tensor scaled e4m3 with a straight-through gradient, carried
        # in bfloat16: what an fp8 training recipe does to each operand
        # of a forward contraction.
        def fp8(x: jax.Array) -> jax.Array:
            x = x.astype(jnp.bfloat16)
            return x + jax.lax.stop_gradient(_quantise(x, jnp.float8_e4m3fn, 448.0) - x)

        return fp8
    raise ValueError(f"no reference arithmetic for precision {precision!r}")


def _quantise(x: jax.Array, dtype: Any, largest: float) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30) / largest
    return ((x.astype(jnp.float32) / scale).astype(dtype).astype(jnp.float32) * scale).astype(x.dtype)


@jax.custom_vjp
def _e5m2_gradient(x: jax.Array) -> jax.Array:
    return x


def _e5m2_forward(x):
    return x, None


def _e5m2_backward(_, g):
    return (_quantise(g, jnp.float8_e5m2, 57344.0),)


_e5m2_gradient.defvjp(_e5m2_forward, _e5m2_backward)


def grad_cast_for(precision: str) -> Cast:
    """What a reference applies to the output of a contraction so that its
    incoming gradient, the operand of both backward contractions, is in
    the precision too: identity, but e5m2 (the fp8 recipes' gradient
    format) on the way back for the fp8 control. A bfloat16 output
    already carries a bfloat16 gradient."""
    return _e5m2_gradient if precision == "float8_e4m3fn" else (lambda x: x)
