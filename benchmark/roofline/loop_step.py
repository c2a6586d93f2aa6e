"""The matrix work of one training step of a looped trunk, from shapes alone,
whatever implements it.

A looped trunk (``ouro_trunk``) runs its ``num_hidden_layers`` layers
``total_ut_steps`` times over the same weights on ``batch x 64`` tokens a
pass. Counted, a step:

* the layers' matrix parameters (a layer: ``W_q``, ``W_k``, ``W_v``, ``W_o``,
  each ``hidden x heads x head_dim``, and the gated feed-forward's three of
  ``hidden x intermediate_size``; the norms' gains are no matrix), three
  products a weight and use (forward, the input's gradient, the weight's
  gradient), two operations a multiply-add: ``3 x 2 x tokens x passes x
  parameters``;
* the attention core, a board, head and pass: seven products of ``64 x 64 x
  head_dim`` multiply-adds (forward the scores and the mix; gradient the
  scores again, the probabilities' cotangent, the values', the queries' and
  the keys' gradients: ``roofline/gqa_core.py``'s count at a group of one);
* the heads and the embedding, whose products run once a pass and a step
  respectively: the policy's ``hidden x policy_planes`` and the value head's
  ``hidden x 4`` a token and pass, its ``256 x value_hidden`` and
  ``value_hidden x 1`` a board and pass, the embedding's ``input_planes x
  hidden`` a token; three products each as the layers'.

NOTHING remade is counted: a program that recomputes a pass in its backward
does more work than this and its share of the peak reads lower for it. The
elementwise work (norms, RoPE, softmax, the gated activation, the exit gate's
sum, AdamW) is no matrix work and adds nothing: a share of the bfloat16 peak
made from this count cannot pass 100%.
"""

from __future__ import annotations

from typing import Any, Dict

SQUARES = 64


def layer_matrix_parameters(model: Dict[str, Any]) -> int:
    inner = model["num_attention_heads"] * model["head_dim"]
    return 4 * model["hidden_size"] * inner + 3 * model["hidden_size"] * model["intermediate_size"]


def core_flops(model: Dict[str, Any], batch: int) -> float:
    """The attention cores of one pass over one layer: seven products a board and head."""
    return float(batch * model["num_attention_heads"] * 7 * 2 * SQUARES * SQUARES * model["head_dim"])


def head_matrix_parameters(model: Dict[str, Any]) -> Dict[str, int]:
    """Multiply-adds of the heads' products: a token's (policy, value convolution) and a board's (the value head's two dense layers)."""
    return {"token": model["hidden_size"] * (model["policy_planes"] + 4), "board": 4 * SQUARES * model["value_hidden"] + model["value_hidden"]}


def step_flops(model: Dict[str, Any], batch: int) -> Dict[str, float]:
    tokens, passes, layers = batch * SQUARES, model["total_ut_steps"], model["num_hidden_layers"]
    heads = head_matrix_parameters(model)
    parts = {
        "layers": 3.0 * 2.0 * tokens * passes * layers * layer_matrix_parameters(model),
        "cores": passes * layers * core_flops(model, batch),
        "heads": 3.0 * 2.0 * passes * (tokens * heads["token"] + batch * heads["board"]),
        "embed": 3.0 * 2.0 * tokens * model["input_planes"] * model["hidden_size"],
    }
    return {**parts, "all": sum(parts.values())}


def least_seconds(model: Dict[str, Any], batch: int, peaks: Dict[str, float]) -> float:
    return step_flops(model, batch)["all"] / peaks["bf16_flops_per_s"]
