"""A temp copy of the benchmark with a tiny cell added as NEW files and
entries only: what a later PR is allowed to do."""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Dict

REPO = Path(__file__).resolve().parents[2]

TINY_TRAFFIC = {
    "name": "tiny_pool", "generator": "playout_pool", "pool_positions": 96, "max_plies": 40,
    "skip_first": 6, "start": "startpos", "sampling": "uniform with replacement",
    "prefetch_batches": 2, "az_dirichlet_alpha": 0.3, "nnue_score_noise_cp": 50.0,
}

TINY_CONFIGS: Dict[str, Dict[str, Any]] = {
    "az-6x64-tiny": {
        "family": "az", "source": "test preset: the repo's 6x64 toy tower",
        "model": {"channels": 64, "blocks": 6, "value_hidden": 128, "policy_planes": 73, "input_planes": 19},
        "train": {"batch": 8, "optimizer": "adamw", "learning_rate": 0.002, "weight_decay": 0.0001, "value_weight": 1.0},
        "precision": "bfloat16", "control_precision": "float8_e4m3fn", "assumed": [], "reduced": [],
        # CPU readings at this size over 8 seeds (test_control_fails_and_program_passes prints them): the
        # program reads policy_w <= 0.0048, all <= 0.058, max <= 0.13, small <= 0.066, steps <= 0.029, loss
        # <= 0.0003; the fp8 control reads policy_w >= 0.038 and all >= 0.105.
        "correct": {"batch": 8, "steps_learning_rate": 2e-5, "limits": {"grad_rel_l2.policy_w": 0.015, "loss_rel_diff": 0.001,
                                           "grad_rel_l2_all": 0.085, "grad_rel_l2_max": 0.27,
                                           "grad_rel_l2_small_max": 0.15, "steps_drop_rel_diff": 0.1}},
    },
    "nnue-tiny": {
        "family": "nnue", "source": "test preset: full feature set, narrow stacks",
        "model": {"num_features": 22528, "max_active": 32, "l1": 64, "l2": 15, "l3": 32, "num_buckets": 8},
        "train": {"batch": 8, "optimizer": "adam", "learning_rate": 0.0008, "wdl_lambda": 0.75},
        "precision": "float32", "control_precision": "bfloat16", "assumed": [], "reduced": [],
        # CPU readings at this size over 8 seeds (float32 products are float32 here): the program reads
        # <= 5e-7 on every gradient number and 7e-6 on the steps; the bfloat16 control >= 3e-3 and 1.1e-3.
        "correct": {"batch": 8, "limits": {"loss_rel_diff": 1e-5, "grad_rel_l2_all": 3e-4, "grad_rel_l2_max": 3e-4,
                                           "grad_rel_l2_small_max": 1e-4, "steps_drop_rel_diff": 1e-4}},
    },
}


def tiny_checkout(tmp: Path) -> Path:
    """Copy BENCHMARK.json and benchmark/ into ``tmp`` and add, as new
    files and appended entries, a traffic mix, two configurations, two
    cells and one layer metric (an entry and its reducer)."""
    shutil.copy2(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", tmp / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp / "benchmark"
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    (bench / "traffic" / "tiny_pool.json").write_text(json.dumps(TINY_TRAFFIC))
    for name, config in TINY_CONFIGS.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps({"name": name, **config}))
        spec["configs"].append({"name": name, "source": config["source"], "file": f"benchmark/configs/{name}.json",
                                "reduced": [], "why": "test"})
        cell = name.replace("-", "_") + "_cell"
        (bench / "workloads" / f"{cell}.json").write_text(
            json.dumps({"name": cell, "runner": "train_step", "warmup_steps": 2, "trace_steps": 2}))
        spec["workloads"].append({"name": cell, "config": name, "traffic": "tiny_pool", "chips": 1, "why": "test"})
    (bench / "reducers" / "steps_in_window.py").write_text(
        '"""Steps completed in the window (a counter, for the test)."""\n\n\ndef reduce(ctx):\n    return ctx["steps"]\n')
    metric = {"name": "steps_in_window", "unit": "steps", "better": "higher", "source": "program_counter",
              "layer": "trainer step", "moves": "train_pos_per_s", "workloads": ["az_6x64_tiny_cell"]}
    spec["per_layer"].append(metric)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
