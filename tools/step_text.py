"""The tiny lowered step program that a step pin hashes, as text.

    python3 tools/step_text.py --block llada|afmoe|mla|hybrid|cca|kda|gdn|mellum|sdar|ouro [--no-ids] > text

``tests/test_hybrid_trunk.py PARENT_STEP_SHA256`` and
``tests/test_cca_trunk.py CCA_STEP_SHA256`` hold the sha256 of what this
prints without ``--no-ids``: ``jax.jit(AzTrainer(cfg)._step).lower(state,
batch).as_text()`` of a block's tiny net (``tests/trunk_tiny.py BLOCKS``),
which carries no debug locations and no scope names. A pin that fails
says two hashes; to see what moved, dump the parent's and the change's
text with ``--no-ids`` (the SSA numbers replaced, and the counters jax
appends to a function's name, ``@closed_call_280``, ``@_where_203``, so
that an operation that moved or one helper more is one hunk and not a
renumbering of everything after it) and ``diff`` them::

    git archive <parent> | tar -x -C /some/dir
    (cd /some/dir && python3 tools/step_text.py --block mla --no-ids) > parent.txt
    python3 tools/step_text.py --block mla --no-ids > change.txt
    diff parent.txt change.txt

Runs on the CPU (it lowers, it compiles nothing), under the tests'
platform: eight virtual devices, as ``tests/conftest.py`` sets them.
"""

from __future__ import annotations

import argparse
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_SSA_ID = re.compile(r"%\d+")
_FUNCTION_COUNTER = re.compile(r"(@[A-Za-z_][\w.]*?)_\d+\b")

#: What a failing pin says beside its two hashes.
HOW_TO_SEE_WHAT_MOVED = (
    "the tiny lowered step of {block!r} is not its parent's. To see what moved, unpack the parent (git archive <parent> | tar -x -C <dir>) "
    "and diff `python3 tools/step_text.py --block {block} --no-ids` of both trees; a PR that MEANT to change this program reads the "
    "new sha256 on its own tree and says so beside the pin")


def without_ids(text: str) -> str:
    """``text`` with every ``%123`` as ``%`` and every function's ``@name_123`` as ``@name``: the numbers that an operation or a helper more renumbers."""
    return _FUNCTION_COUNTER.sub(r"\1", _SSA_ID.sub("%", text))


def lowered_step_text(cfg, batch, ids: bool = True) -> str:
    """The lowered text of ``AzTrainer(cfg)``'s step on ``batch`` (arrays or their shapes); without ``ids`` every ``%123`` reads ``%`` and every ``@name_123`` ``@name``."""
    import jax

    from fishnet_tpu.train.az_trainer import AzTrainer

    trainer = AzTrainer(cfg)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    text = jax.jit(trainer._step).lower(state, jax.eval_shape(lambda: batch)).as_text()
    return text if ids else without_ids(text)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--block", required=True, choices=("llada", "afmoe", "mla", "hybrid", "cca", "kda", "gdn", "mellum", "sdar", "ouro"))
    parser.add_argument("--no-ids", action="store_true", help="replace the SSA numbers and the counters on function names: a diff then shows the operations that moved")
    args = parser.parse_args(argv)
    sys.path[:0] = [str(REPO), str(REPO / "tests")]
    import conftest  # noqa: F401  the tests' platform, set before jax is imported
    from trunk_tiny import BLOCKS

    cfg, batch_of = BLOCKS[args.block]
    sys.stdout.write(lowered_step_text(cfg, batch_of(1), ids=not args.no_ids))
    return 0


if __name__ == "__main__":
    sys.exit(main())
