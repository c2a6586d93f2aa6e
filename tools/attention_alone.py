"""The attention core's plain kernel pair, or its block-masked one (``fishnet_tpu/ops/board_attention.py``), timed ALONE on a cell's shape.

    python3 tools/attention_alone.py [--boards 256 --heads 32 --kv-heads 4 --d 128] [--block-length L --streams S] [--calls 10] [--seed 0]
                                     [--against other/tree/fishnet_tpu/ops/board_attention.py]

prints one JSON line: the device, the shape, and the host clock's median, least
and most over ``--calls`` calls (after one warm call, each ended by
``block_until_ready``) of the two programs a step holds of the pair, and of
the gradient kernel by itself::

    forward_ms                ``board_attention``: the normed form, both gains, RoPE over all of a head
    forward_and_gradient_ms   ``value_and_grad`` of a weighted sum of ``mixed``: both kernels
    gradient_ms               the gradient kernel alone, on the inputs (the pair keeps nothing else)

The defaults are ``mellum_trunk_train_b256``'s shape (and
``afmoe_trunk_train_b256``'s): 256 boards x 32 query heads over 4 key-value
heads of 128 columns, q and k float32, v bfloat16, from ``--seed``.
``--block-length L`` times ``board_attention_blocks`` and its gradient instead,
under the block mask of ``L`` squares with ``--streams`` copies of a board
along the rows (1: the clean copy alone, what is served; 2: block-diffusion
training); ``sdar_trunk_train_b128``'s shape is ``--boards 128 --heads 32
--kv-heads 4 --d 128 --block-length 4 --streams 2``.
``--against`` loads ANOTHER tree's ``board_attention.py`` by its path, times it
the same way in the same process (``against``) and gives the largest
difference of ``mixed`` and the five gradients between the two trees
(``largest_difference``, 0.0 where they are equal bit for bit; ``scale`` is
each output's own largest value): a parent unpacked beside the change (``git
archive``) is read so. A host clock reads ~1 ms over a kernel's device time:
compare two trees, not a time with a floor. A time is a device time only where
``device`` names a TPU; off it the kernels run under the Pallas interpreter and
the numbers say how fast that is, which nobody needs
(``tests/test_board_attention.py`` runs a tiny shape so, for the tool's sake).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO = Path(__file__).resolve().parent.parent

PROGRAMS = ("forward_ms", "forward_and_gradient_ms", "gradient_ms")
OUTPUTS = ("mixed", "dq", "dk", "dv", "dg_q", "dg_k")
THETA, EPS = 10000.0, 1e-6


def operands(boards: int, heads: int, kv_heads: int, d: int, seed: int, streams: int = 1):
    """q, k float32 and v bfloat16 as the projections write them (``streams`` copies of a board along the rows), and the two gains near 1."""
    rng = np.random.default_rng([seed, heads, kv_heads, d])
    normal = lambda width, dtype, scale=1.0: jnp.asarray(scale * rng.standard_normal((boards, 64 * streams, width), np.float32), dtype)
    gain = lambda: jnp.asarray(1.0 + 0.1 * rng.standard_normal(d), jnp.float32)
    return normal(heads * d, jnp.float32, 1.5), normal(kv_heads * d, jnp.float32, 1.5), normal(kv_heads * d, jnp.bfloat16), gain(), gain()


def load(path: Path):
    """A tree's ``board_attention.py`` by its path, as a module of its own."""
    spec = importlib.util.spec_from_file_location("board_attention_against", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def measure(module, ops, weight, interpret: bool, calls: int, masked: dict):
    """The three programs of ``module``'s pair on ``ops`` (``masked``: the block-masked pair's ``block_length`` and ``streams``, or nothing):
    what each made (float32 on the host) and its times."""
    core = lambda *a: module.board_attention(*a, THETA, EPS, interpret, **masked)
    forward = jax.jit(core)
    both = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(core(*a).astype(jnp.float32) * weight), argnums=tuple(range(5))))
    gradient = jax.jit(lambda do, *a: jax.vjp(core, *a)[1](do))  # the forward it also names has no reader: the gradient kernel alone is left
    cotangent = weight.astype(jnp.bfloat16)  # ``mixed``'s cotangent as ``both`` hands it on
    times, made = {}, {}
    for name, fn, args in zip(PROGRAMS, (forward, both, gradient), (ops, ops, (cotangent, *ops))):
        made[name] = jax.block_until_ready(fn(*args))  # the warm call
        taken = []
        for _ in range(calls):
            start = time.perf_counter()
            jax.block_until_ready(fn(*args))
            taken.append((time.perf_counter() - start) * 1e3)
        times[name] = {"median": float(np.median(taken)), "min": min(taken), "max": max(taken)}
    values = dict(zip(OUTPUTS, (made["forward_ms"], *made["gradient_ms"])))
    return {name: np.asarray(value, np.float32) for name, value in values.items()}, times


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--boards", type=int, default=256)
    parser.add_argument("--heads", type=int, default=32, help="query heads")
    parser.add_argument("--kv-heads", type=int, default=4, help="key-value heads: a grid step is one of them and its heads / kv-heads query heads")
    parser.add_argument("--d", type=int, default=128, help="columns a head")
    parser.add_argument("--block-length", type=int, help="squares a block: the block-masked pair (board_attention_blocks, _grad) in the plain pair's place")
    parser.add_argument("--streams", type=int, default=1, help="copies of a board along the rows under --block-length: 1 the clean one alone, 2 with its noised one")
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--against", type=Path, help="another tree's board_attention.py: timed the same way, and its six outputs compared")
    args = parser.parse_args(argv)
    sys.path.insert(0, str(REPO))
    from fishnet_tpu.ops import board_attention as module

    if args.streams != 1 and args.block_length is None:
        parser.error("--streams is the block-masked pair's: give --block-length")
    masked = {} if args.block_length is None else {"block_length": args.block_length, "streams": args.streams}
    interpret = jax.default_backend() != "tpu"
    ops = operands(args.boards, args.heads, args.kv_heads, args.d, args.seed, args.streams)
    weight = jnp.asarray(np.random.default_rng(args.seed).standard_normal(ops[0].shape, np.float32))
    out = {"device": jax.devices()[0].device_kind, "interpret": interpret, "boards": args.boards, "heads": args.heads, "kv_heads": args.kv_heads,
           "d": args.d, **masked, "calls": args.calls, "seed": args.seed}
    values, times = measure(module, ops, weight, interpret, args.calls, masked)
    out.update(times)
    out["finite"] = bool(all(np.isfinite(value).all() for value in values.values()))
    out["scale"] = {name: float(np.abs(value).max()) for name, value in values.items()}
    if args.against:
        other_values, out["against"] = measure(load(args.against), ops, weight, interpret, args.calls, masked)
        out["largest_difference"] = {name: float(np.abs(value - other_values[name]).max()) for name, value in values.items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
