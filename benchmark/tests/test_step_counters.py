"""The reducers over the program's step recorder and the start-up spans'
fields (``benchmark/step_counters.py``, ``benchmark/startup.py``), on the
CPU: each after a traced run of a tiny cell of its family, in a temp copy
that lists the tiny cells under every one of the seven metrics, so that a
reducer that returns None shows as a metric the line leaves out."""

from __future__ import annotations

import json
import math
import time

import pytest

import helpers
import test_moe_trunk
from benchmark import step_counters
from benchmark.registry import Registry

REPO = helpers.REPO
COUNTERS = ("moe_held_slots", "moe_moved_rows", "moe_expert_load_max", "moe_router_entropy", "nnue_ft_block_misses")
SPAN_FIELDS = ("setup_trace_lower_s", "setup_cache_load_s")
TINY_CELLS = ("az_6x64_tiny_cell", "nnue_tiny_cell", "moe_trunk_tiny_cell")
# what each family's steps carry: the reducers of the other keys return None
REPORTED = {
    "az_6x64_tiny_cell": set(),
    "nnue_tiny_cell": {"nnue_ft_block_misses"},
    "moe_trunk_tiny_cell": {"moe_moved_rows", "moe_expert_load_max", "moe_router_entropy"},  # every expert held: no held_slots
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = test_moe_trunk.tiny_moe_checkout(tmp_path_factory.mktemp("checkout"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for metric in spec["per_layer"]:
        if metric["name"] in COUNTERS + SPAN_FIELDS:
            metric["workloads"] = sorted(set(metric["workloads"]) | set(TINY_CELLS))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(root)


@pytest.fixture()
def fresh_recorder(monkeypatch):
    """The process's step recorder, empty: trainers of earlier tests that
    the collector has not reached yet would otherwise come first."""
    from fishnet_tpu.telemetry.registry import MetricsRegistry
    from fishnet_tpu.train import step_metrics

    recorder = step_metrics.StepRecorder(MetricsRegistry())
    monkeypatch.setattr(step_metrics, "STEPS", recorder)
    return recorder


def test_the_seven_entries_are_declared_with_their_cells():
    registry = Registry(REPO)
    entries = {m["name"]: m for m in registry.spec["per_layer"]}
    assert [m["name"] for m in registry.spec["per_layer"]][-7:] == list(COUNTERS + SPAN_FIELDS)
    trunks = ["moe_trunk_train_b512", "afmoe_trunk_train_b256", "mla_trunk_train_b256"]
    assert entries["moe_held_slots"]["workloads"] == trunks[1:]  # the two that hold a share
    for name in ("moe_moved_rows", "moe_expert_load_max", "moe_router_entropy"):
        assert entries[name]["workloads"] == trunks
    assert entries["nnue_ft_block_misses"]["workloads"] == ["nnue_train_b16384"]
    for name in COUNTERS:
        assert (entries[name]["source"], entries[name]["moves"]) == ("program_counter", "train_pos_per_s")
    for name in SPAN_FIELDS:
        assert (entries[name]["source"], entries[name]["moves"]) == ("program_span", "setup_s")
        assert entries[name]["workloads"] == [w["name"] for w in registry.spec["workloads"]]
    layers = {m["layer"] for m in registry.spec["per_layer"][:-7]}
    assert {entries[name]["layer"] for name in COUNTERS + SPAN_FIELDS} <= layers  # no new layer name


@pytest.mark.parametrize("cell_name", TINY_CELLS)
def test_reducers_after_a_traced_run(tiny, fresh_recorder, cell_name, capsys):
    import jax

    cell = tiny.workload(cell_name)
    runner = tiny.module("runners", cell["runner"])
    traced = runner.run(tiny, cell, 2**31 + 23, 0.5, True, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert traced["correct"] is True and traced["failed"] == 0
    metrics = {name: m["value"] for name, m in traced["metrics"].items()}
    assert set(metrics) & set(COUNTERS) == REPORTED[cell_name]
    assert set(SPAN_FIELDS) <= set(metrics)
    # the cell's trainer is the first the run made (the comparison's stepped later, scopes' never)
    first = "nnue-0" if cell_name.startswith("nnue") else "az-0"
    assert f"step counters: trainer {first}, steps " in out
    assert 0 < metrics["setup_trace_lower_s"] < 600 and 0 <= metrics["setup_cache_load_s"] < 600
    if cell_name == "nnue_tiny_cell":
        assert metrics["nnue_ft_block_misses"] == 0  # Board.nnue_features keeps the index contract
    if cell_name == "moe_trunk_tiny_cell":
        model = tiny.config("moe-trunk-tiny")["model"]
        slots = model["num_hidden_layers"] * 8 * 64 * model["num_experts_per_tok"]  # layers x batch x squares x experts a token
        assert metrics["moe_moved_rows"] == slots == tiny.module("roofline", "moe_experts").slots(model, 8) * model["num_hidden_layers"]
        assert slots / model["num_experts"] / model["num_hidden_layers"] <= metrics["moe_expert_load_max"] <= 8 * 64
        assert 0 < metrics["moe_router_entropy"] <= math.log(model["num_experts"]) + 1e-6


def test_an_empty_recorder_gives_none(tiny, fresh_recorder, monkeypatch):
    """No trainer has stepped and no span was recorded (and, on the parent
    of the PR that added it, no recorder at all): every reducer returns
    None and raises nothing."""
    import sys

    from fishnet_tpu.telemetry import spans

    monkeypatch.setattr(spans, "RECORDER", spans.SpanRecorder())
    for name in COUNTERS + SPAN_FIELDS:
        assert tiny.module("reducers", name).reduce({}) is None
    from fishnet_tpu.train.az_trainer import AzTrainer  # a trainer that never stepped changes nothing

    idle = AzTrainer()
    assert [r.trainer for r in fresh_recorder.records()] == ["az-0"] and idle is not None
    assert tiny.module("reducers", "moe_moved_rows").reduce({}) is None
    import fishnet_tpu.train

    monkeypatch.delattr(fishnet_tpu.train, "step_metrics")  # the parent: the import fails
    monkeypatch.setitem(sys.modules, "fishnet_tpu.train.step_metrics", None)
    for name in COUNTERS:
        assert tiny.module("reducers", name).reduce({}) is None


def test_a_reading_is_fetched_once_a_run(tiny, fresh_recorder):
    """The helper keeps what it read in ``ctx``: seven reducers, one transfer."""
    import jax.numpy as jnp

    record = fresh_recorder.attach("az")
    for step in range(3):
        record.run(lambda state, batch: (state, {"moved_rows": jnp.float32(512 + step), "loss": jnp.float32(1.0)}), None, None)
    ctx = {}
    assert tiny.module("reducers", "moe_moved_rows").reduce(ctx) == 513.0
    record.run(lambda state, batch: (state, {"moved_rows": jnp.float32(9999.0)}), None, None)
    assert tiny.module("reducers", "moe_moved_rows").reduce(ctx) == 513.0  # the run's one reading
    assert tiny.module("reducers", "moe_held_slots").reduce(ctx) is None
    assert step_counters.values({}, "moved_rows") == [512.0, 513.0, 514.0, 9999.0]
    assert step_counters.values({}, "loss") is None  # a key one step lacks is not averaged over the rest
