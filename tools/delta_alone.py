"""The delta rule's kernel pair (``fishnet_tpu/ops/board_delta.py``) timed ALONE on a cell's shape.

    python3 tools/delta_alone.py --form gdn|kda [--boards 128 --heads 16 --per 2 --d 128] [--calls 10] [--seed 0]
                                 [--against other/tree/fishnet_tpu/ops/board_delta.py]

prints one JSON line: the device, the shape, and the host clock's median, least
and most over ``--calls`` calls (after one warm call, each ended by
``block_until_ready``) of the three programs a step holds of the pair, and of
the gradient kernel by itself::

    forward_ms                the primal: ``board_delta`` writes o alone
    forward_kept_ms           the differentiated forward: o and the kept arrays
    forward_and_gradient_ms   ``value_and_grad`` of a weighted sum of o: both kernels
    gradient_ms               ``board_delta_grad`` alone, on the kept arrays the differentiated forward wrote

The defaults are the two cells' shapes: ``gdn`` 128 boards x 16 key heads x 2
value heads a key head (``gdn_trunk_train_b128``), ``kda`` 128 boards x 16
heads (``kda_trunk_train_b128``), heads of 128 columns. The operands come from
``--seed`` at a fresh learner's decays (an odd seed: the comparison's slow
ones, steps under 0.1). ``--against`` loads ANOTHER tree's ``board_delta.py``
by its path, times it the same way in the same process (``against``) and says
of o, every kept array and the five gradients whether the two trees' are
equal bit for bit, else the largest difference (``bit_equal``): a parent
unpacked beside the change (``git archive``) is read so. A time is a device
time only where ``device`` names a TPU; off it the kernels run under the
Pallas interpreter and the numbers say how fast that is, which nobody needs
(``tests/test_board_delta.py`` runs a tiny shape so, for the tool's sake).
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

PROGRAMS = ("forward_ms", "forward_kept_ms", "forward_and_gradient_ms", "gradient_ms")
GRADIENTS = ("dq", "dk", "dv", "dg", "dbeta")


def operands(form: str, boards: int, heads: int, per: int, d: int, seed: int):
    """q, k, v bfloat16, the log-decay and beta float32, in ``board_delta``'s shapes of ``form``."""
    value_heads = heads * per if form == "gdn" else heads
    rng = np.random.default_rng([seed, value_heads, d])
    key, value, by_head = (boards, 64, heads * d), (boards, 64, value_heads * d), (boards, 64, value_heads)
    slow = lambda shape: np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
    if form == "gdn":  # a decay a head and token: rates in (0, 16) on steps of softplus(1 + 0.3 a)
        rate = rng.uniform(0.0, 16.0, value_heads)
        step = slow(by_head) if seed % 2 else np.log1p(np.exp(1.0 + 0.3 * rng.standard_normal(by_head)))
    else:  # a decay a channel
        rate, step = np.repeat(rng.uniform(1.0, 16.0, heads), d), slow(key)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16) for shape in (key, key, value))
    return q, k, v, jnp.asarray(-rate * step, jnp.float32), jnp.asarray(rng.uniform(0.05, 0.95, by_head), jnp.float32)


def load(path: Path):
    """A tree's ``board_delta.py`` by its path, as a module of its own."""
    spec = importlib.util.spec_from_file_location("board_delta_against", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def measure(module, ops, weight, interpret: bool, calls: int):
    """The four programs of ``module``'s pair on ``ops``: what each made (float32 on the host) and its times."""
    forward = jax.jit(lambda *a: module.board_delta(*a, interpret))
    kept = jax.jit(lambda *a: module._board_delta_fwd(*a, interpret))
    both = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(module.board_delta(*a, interpret).astype(jnp.float32) * weight), argnums=tuple(range(5))))
    gradient = jax.jit(lambda residuals, do: module._board_delta_bwd(interpret, residuals, do))
    times, made = {}, {}
    for name, fn in zip(PROGRAMS, (forward, kept, both, gradient)):
        args = ops if name != "gradient_ms" else (made["forward_kept_ms"][1], weight.astype(jnp.bfloat16))  # o's cotangent as ``both`` hands it on
        made[name] = jax.block_until_ready(fn(*args))  # the warm call
        taken = []
        for _ in range(calls):
            start = time.perf_counter()
            jax.block_until_ready(fn(*args))
            taken.append((time.perf_counter() - start) * 1e3)
        times[name] = {"median": float(np.median(taken)), "min": min(taken), "max": max(taken)}
    o_kept, (*_, tables) = made["forward_kept_ms"]
    values = {"o": made["forward_ms"], "o_kept": o_kept, **{f"kept{n}": x for n, x in enumerate(tables)}, **dict(zip(GRADIENTS, made["forward_and_gradient_ms"][1]))}
    return {name: np.asarray(value, np.float32) for name, value in values.items()}, times


def same(name: str, a, b):
    """True where the two trees' arrays are equal bit for bit, else the largest difference."""
    if name == "kept2":  # the first form's Mq: the upper 64 lanes of a head's tile are never written
        a, b = (x.reshape(*x.shape[:2], -1, 128)[..., :64] for x in (a, b))
    return True if np.array_equal(a, b) else float(np.abs(a - b).max())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--form", required=True, choices=("gdn", "kda"), help="gdn: a decay a head (the second form); kda: a decay a channel")
    parser.add_argument("--boards", type=int, default=128)
    parser.add_argument("--heads", type=int, default=16, help="heads (kda) or key heads (gdn)")
    parser.add_argument("--per", type=int, default=2, help="value heads a key head (gdn)")
    parser.add_argument("--d", type=int, default=128, help="columns a head")
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--against", type=Path, help="another tree's board_delta.py: timed the same way, and compared bit for bit")
    args = parser.parse_args(argv)
    sys.path.insert(0, str(REPO))
    from fishnet_tpu.ops import board_delta as module

    interpret = jax.default_backend() != "tpu"
    ops = operands(args.form, args.boards, args.heads, args.per, args.d, args.seed)
    weight = jnp.asarray(np.random.default_rng(args.seed).standard_normal(ops[2].shape), jnp.float32)
    out = {"device": jax.devices()[0].device_kind, "interpret": interpret, "form": args.form, "boards": args.boards, "heads": args.heads,
           "per": args.per if args.form == "gdn" else None, "d": args.d, "calls": args.calls, "seed": args.seed}
    values, times = measure(module, ops, weight, interpret, args.calls)
    out.update(times)
    out["finite"] = bool(all(np.isfinite(value).all() for name, value in values.items() if name != "kept2"))
    if args.against:
        other_values, out["against"] = measure(load(args.against), ops, weight, interpret, args.calls)
        out["bit_equal"] = {name: same(name, value, other_values[name]) for name, value in values.items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
