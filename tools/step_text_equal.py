"""Are two compiled step programs the same program but for where their kernels were traced?

    python3 tools/step_text_equal.py <parent's text> <change's text>

Takes two texts of a compiled module (``compiled.as_text()``, or what
``tools/step_recorder_cost.py --text-out`` wrote), drops the metadata
(``benchmark/scopes.py without_metadata``) and compares them in two
parts: everything but the Pallas kernels' serialized Mosaic modules, as
text; and each kernel's module, parsed and printed without its debug
locations. A Mosaic module keeps the file names and lines of its trace's
call stack, so an edit that moves a line of ``models/trunk.py`` changes
the text, and its sha256, of a program that runs the same instructions.
Exits 0 where both parts are equal, 1 where not, and names what differs.
Runs anywhere (it compiles nothing). It reads the modules through
``jax._src``'s MLIR bindings, which no release promises to keep:
``tests/test_trunk_tpu_compile.py`` runs it on a two-kernel program.
"""

from __future__ import annotations

import base64
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

_BODY = re.compile(r'("body":\s*")([A-Za-z0-9+/=]+)')
_NAME = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")


def kernels(text: str):
    """(instruction name, Mosaic module without debug locations) of every kernel call of a module's text."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    found = []
    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        for line in text.splitlines():
            body = _BODY.search(line)
            if body and 'custom_call_target="tpu_custom_call"' in line:
                module = ir.Module.parse(base64.b64decode(body.group(2)))
                found.append((_NAME.match(line).group(1), module.operation.get_asm(enable_debug_info=False)))
    return found


def main(argv) -> int:
    from benchmark import scopes

    if len(argv) != 2:
        sys.stderr.write(__doc__)
        return 2
    parent, change = (scopes.without_metadata(Path(path).read_text()) for path in argv)
    rest_equal = _BODY.sub(r"\1", parent) == _BODY.sub(r"\1", change)
    ours, theirs = kernels(parent), kernels(change)
    differ = [a for (a, x), (b, y) in zip(ours, theirs) if (a, x) != (b, y)]
    print(f"text without metadata: {'equal' if parent == change else 'differs'}; without the kernels' modules: "
          f"{'equal' if rest_equal else 'differs'}; kernels {len(ours)} and {len(theirs)}, modules that differ without "
          f"debug locations: {differ or 'none'}")
    return 0 if rest_equal and len(ours) == len(theirs) and not differ else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
