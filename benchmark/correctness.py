"""How ``correct`` is decided: the program against the plain reference.

On ``config["correct"]["batch"]`` seeded pool positions, at the
configuration's full widths, with parameters the reference makes from the
seed: the program's loss and gradients (``jax.grad`` of the trainer's own
loss) against the reference's in float32 at ``highest`` matmul precision,
and the fall of the loss over three optimizer steps on one batch. The
positions are compared ``correct.chunk`` at a time and the gradients
averaged, so that the count can be larger than one program holds. With
``control=True`` the reference in the configuration's
``control_precision`` stands in the program's place: that comparison has
to come out not correct.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Tuple

import numpy as np

STEPS = 3
#: A tensor of fewer elements (a head's bias) has a gradient that is a sum
#: of a few cancelling terms: its relative error swings further from seed
#: to seed than a kernel's. Such tensors have a maximum, and a limit, of
#: their own.
SMALL = 64
#: Every run compares these, and ``judge`` wants a limit for each.
COMPARED = ("loss_rel_diff", "grad_rel_l2_all", "grad_rel_l2_max", "grad_rel_l2_small_max", "steps_drop_rel_diff")


class Checker:
    """Holds the jitted reference, control and program functions of one
    configuration, so that many seeds share one compilation. The trainer
    is the program's, built as the cell's is; where the configuration
    gives ``correct.steps_learning_rate`` its optimizer steps at that
    rate (the configuration file says why)."""

    def __init__(self, family: Any, reference: Any, config: Dict[str, Any]) -> None:
        import jax

        config = copy.deepcopy(config)
        config["train"]["learning_rate"] = config["correct"].get("steps_learning_rate", config["train"]["learning_rate"])
        self.family, self.reference, self.config = family, reference, config
        self.trainer = family.make_trainer(config)
        self._reference_grad = jax.jit(jax.value_and_grad(lambda p, b: reference.loss(p, b, config)))
        control = config["control_precision"]
        self._control_grad = jax.jit(jax.value_and_grad(lambda p, b: reference.loss(p, b, config, control)))
        self._program_grad = family.loss_and_grads(self.trainer)

    @staticmethod
    def _mean(grad: Any, params: Dict[str, Any], chunks: List[Dict[str, Any]]) -> Tuple[float, Dict[str, np.ndarray]]:
        """Loss and gradients of the mean over equal chunks, summed in float64 on the host."""
        loss, total = 0.0, {}
        for chunk in chunks:
            value, grads = grad(params, chunk)
            loss += float(value) / len(chunks)
            for name, g in grads.items():
                total[name] = total.get(name, 0.0) + np.asarray(g, np.float64) / len(chunks)
        return loss, total

    def compare(self, pool: Dict[str, np.ndarray], seed: int, control: bool = False) -> Dict[str, Any]:
        """The numbers compared (see ``judge`` for their limits)."""
        import jax
        import jax.numpy as jnp

        config, reference = self.config, self.reference
        rng = np.random.default_rng([int(seed), 0x636865636B])
        n_pool = len(next(iter(pool.values())))
        n, size = config["correct"]["batch"], config["correct"].get("chunk", config["correct"]["batch"])
        if n % size:
            raise ValueError("correct.batch has to be a multiple of correct.chunk")
        idx = rng.integers(0, n_pool, n)
        chunks = [
            {k: jnp.asarray(v) for k, v in self.family.build_batch(pool, idx[i:i + size]).items()}
            for i in range(0, n, size)
        ]
        batch = chunks[0]  # the optimizer steps run on this one
        params = {k: jnp.asarray(v) for k, v in reference.init_params(seed, config["model"]).items()}

        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = self._mean(self._reference_grad, params, chunks)
            ref_steps = [float(x) for x in reference.train_losses(self._reference_grad, params, batch, config, STEPS)]
        if control:
            loss, grads = self._mean(self._control_grad, params, chunks)
            steps = [float(x) for x in reference.train_losses(self._control_grad, params, batch, config, STEPS)]
        else:
            loss, grads = self._mean(self._program_grad, params, chunks)
            state = self.family.state_from_params(self.trainer, params)
            steps = []
            for _ in range(STEPS):
                state, metrics = self.trainer.step(state, batch)
                steps.append(float(metrics["loss"]))

        per_tensor, diff_sq, ref_sq = {}, 0.0, 0.0
        for name, ref in ref_grads.items():
            got = grads[name]
            diff_sq += float(np.sum((got - ref) ** 2))
            ref_sq += float(np.sum(ref ** 2))
            err = float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-300))
            per_tensor[name] = err if np.isfinite(err) else float("inf")
        counted = {k: v for k, v in per_tensor.items() if ref_grads[k].size >= SMALL}
        small = [v for k, v in per_tensor.items() if k not in counted]
        worst_name = max(counted, key=counted.get)
        overall = float(np.sqrt(diff_sq / max(ref_sq, 1e-300)))
        ref_drop = ref_steps[0] - ref_steps[-1]
        drop_diff = abs((steps[0] - steps[-1]) - ref_drop) / max(abs(ref_drop), 1e-300)
        return {
            "loss_rel_diff": abs(loss - ref_loss) / max(abs(ref_loss), 1e-300),
            "grad_rel_l2_all": overall if np.isfinite(overall) else float("inf"),
            "grad_rel_l2_max": counted[worst_name],
            "grad_rel_l2_small_max": max(small, default=0.0),
            "steps_drop_rel_diff": float(drop_diff) if np.isfinite(drop_diff) else float("inf"),
            "_worst_tensor": worst_name,
            "_ref_loss": ref_loss,
            "_steps": steps,
            "_ref_steps": ref_steps,
            "_per_tensor": per_tensor,
            **{f"grad_rel_l2.{name}": err for name, err in per_tensor.items()},
        }


def judge(numbers: Dict[str, Any], config: Dict[str, Any]) -> Tuple[bool, str]:
    """Whether every number compared is within its limit, and a line that
    shows each beside it. The configuration gives a limit for each of
    ``COMPARED`` and for any single tensor it names
    (``grad_rel_l2.<tensor>``); none may be left out or null."""
    limits = config["correct"]["limits"]
    unset = [name for name in COMPARED if limits.get(name) is None] + [k for k, v in limits.items() if v is None]
    if unset:
        raise ValueError(f"no limit for {sorted(set(unset))}: set the configuration's correct.limits from readings")
    ok, parts = True, []
    for name, limit in limits.items():
        value = numbers[name]
        within = bool(np.isfinite(value)) and value <= limit
        ok = ok and within
        parts.append(f"{name}={value:.6g} (limit {limit}){'' if within else ' EXCEEDED'}")
    ref_steps = numbers["_ref_steps"]
    parts.append(f"worst tensor {numbers['_worst_tensor']}, reference loss {numbers['_ref_loss']:.6g}, "
                 f"over the steps {ref_steps[0]:.6g} -> {ref_steps[-1]:.6g}")
    return ok, "; ".join(parts)
