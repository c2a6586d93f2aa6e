"""The policy and value heads every ``az_forward`` network ends in.

Pulled out of ``models/az.py`` unchanged so that the conv tower and the
sparse-expert trunk (``models/trunk.py``) share them: features
[B, 8, 8, C] in bfloat16 -> policy logits [B, 64 * planes] and a tanh
value [B], both float32.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, jax.Array]


def conv2d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    out = jax.lax.conv_general_dilated(
        x,
        w.astype(x.dtype),
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out + b.astype(x.dtype)


def policy_value_heads(params: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("policy_head"):
        pol = conv2d(x, params["policy_w"], params["policy_b"])
        policy_logits = pol.reshape(pol.shape[0], -1).astype(jnp.float32)
    # NHWC reshape order = square-major within plane-minor; reorder to the
    # square*73+plane indexing of az_encoding.move_to_index.
    # pol[b, r, f, p] -> index (r*8+f)*73 + p: reshape already yields
    # b, (r*8+f)*planes + p, which is exactly that. (No permute needed.)

    with jax.named_scope("value_head"):
        v = jax.nn.relu(conv2d(x, params["value_w"], params["value_b"]))
        v = v.reshape(v.shape[0], -1)
        v = jax.nn.relu(v @ params["value_fc1_w"].astype(v.dtype) + params["value_fc1_b"].astype(v.dtype))
        v = jnp.tanh(v @ params["value_fc2_w"].astype(v.dtype) + params["value_fc2_b"].astype(v.dtype))
        value = v[:, 0].astype(jnp.float32)
    return policy_logits, value
