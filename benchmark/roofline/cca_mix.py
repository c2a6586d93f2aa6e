"""Operations and bytes the convolutional mixing of compressed attention
needs for one training step, from shapes and the configuration's stated
precision alone, whatever implements the mix.

The mix is what lies between the joined query-key projection and the
attention core of a layer: for every token the ``(heads + kv_heads) x
head_dim`` columns ``[q~ | k~]`` pass a depthwise convolution of
``cca_time0`` taps along the squares, a convolution of ``cca_time1``
taps a head at a time (each tap a ``[head_dim, head_dim]`` matrix of its
head) and take the q-k mean. The products are conv1's alone: forward one
``[head_dim] x [head_dim, head_dim]`` a token, head and tap; the gradient
two (to its input and to its weights). conv0's multiply-adds, the means
and the shifts are neither products nor HBM traffic and are not counted.
The least HBM traffic, in the precision the configuration states
(``[q~ | k~]``, q, k and their cotangents float32, as the projection
writes and the core reads them): forward, ``[q~ | k~]`` read and q and k
written, each once; gradient, ``[q~ | k~]`` and the cotangents of q and k
read and the cotangent of ``[q~ | k~]`` written, each once. The weights
(0.33 M a layer) and their gradients are read and written once a step
and are counted too. Nothing is made again but conv0's result, which
costs no traffic. The move of the shifted value halves under the same
scope (``[tokens, kv_heads x head_dim]`` read in float32 and written in
bfloat16, and back) IS counted: it is the scope's, and no form of the
mix does without it.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64
F32, BF16 = 4, 2


def columns(model: Dict[str, Any]) -> int:
    return (model["num_attention_heads"] + model["num_key_value_heads"]) * model["head_dim"]


def layer_flops(model: Dict[str, Any], batch: int) -> float:
    heads, hd, taps = model["num_attention_heads"] + model["num_key_value_heads"], model["head_dim"], model["cca_time1"]
    a_product = 2 * hd * hd * heads * taps  # a token
    return float(batch * SQUARES * 3 * a_product)  # forward, gradient to the input, gradient to the weights


def layer_bytes(model: Dict[str, Any], batch: int) -> float:
    mixed, values = columns(model), model["num_key_value_heads"] * model["head_dim"]
    heads, hd = model["num_attention_heads"] + model["num_key_value_heads"], model["head_dim"]
    weights = (mixed * model["cca_time0"] + mixed + heads * model["cca_time1"] * hd * hd + mixed) * F32
    forward = 2 * mixed * F32 + values * (F32 + BF16)
    gradient = 3 * mixed * F32 + values * (F32 + BF16)  # [q~ | k~] and the cotangents of q | k read, the cotangent of [q~ | k~] written
    return float(batch * SQUARES * (forward + gradient) + 3 * weights)  # weights read forward and backward, their gradient written


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> Dict[str, Any]:
    layers = model["num_hidden_layers"]
    compute = layers * layer_flops(model, batch) / peaks["bf16_flops_per_s"]
    memory = layers * layer_bytes(model, batch) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, "least_s": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
