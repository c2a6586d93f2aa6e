"""Device ms a traced step under the program's ``denoise`` scopes: the
denoiser's logits off the noised stream (``forward`` / ``denoise``,
``models/trunk.py trunk_forward_counted``) and the third term of the loss,
the 1 / t weighted cross-entropy on the masked squares (``loss`` /
``denoise``, ``train/az_trainer.py _loss``), forward and ``transpose(...)``
paths both, summed over ``benchmark/scopes.py``'s ``split(ctx).by_path``. None
without a trace or where the program has no such scope."""

import re

from benchmark import scopes

_DENOISE = re.compile(r"(^|/)denoise$")


def reduce(ctx):
    found = scopes.split(ctx)
    if found is None:
        return None
    times = [ms for path, ms in found.by_path.items() if _DENOISE.search(path)]
    return sum(times) if times else None
