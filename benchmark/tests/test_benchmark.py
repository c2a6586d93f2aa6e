"""The benchmark's own tests: CPU, tiny sizes (``pytest benchmark/tests``)."""

from __future__ import annotations

import gzip
import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

import helpers
from benchmark import correctness, positions, tracelib
from benchmark.registry import BenchmarkError, Registry

REPO = helpers.REPO
DATA = Path(__file__).resolve().parent / "data"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return Registry(helpers.tiny_checkout(tmp_path_factory.mktemp("checkout")))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "nnue_trace.json.gz", "rt") as fh:
        return tracelib.Trace.from_json(json.load(fh))


# -- BENCHMARK.json against the contract ---------------------------------------


def test_benchmark_json_names_units_and_files():
    registry = Registry(REPO)
    spec = registry.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[section]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (section, entry["name"]) not in names
            names.add((section, entry["name"]))
    for metric in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
        assert (REPO / "benchmark" / "reducers" / f"{metric['name']}.py").is_file()
    for metric in spec["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1 and metric["source"] in ("host_clock", "device_trace")
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in end_to_end
    cells = {w["name"] for w in spec["workloads"]}
    for metric in spec["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["moves"] in end_to_end and set(metric.get("workloads", [])) <= cells
    pairs = set()
    for cell in spec["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200 and (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        full = registry.workload(cell["name"])
        assert (REPO / "benchmark" / "runners" / f"{full['runner']}.py").is_file()
        assert registry.traffic(cell["traffic"])["pool_positions"] > 0
    for config in spec["configs"]:
        assert config["file"].startswith("benchmark/") and len(config["source"]) <= 200
        on_file = registry.config(config["name"])
        assert on_file["source"] == config["source"] and on_file["reduced"] == config["reduced"]
        limits = on_file["correct"]["limits"]
        assert set(correctness.COMPARED) <= set(limits) and all(v > 0 for v in limits.values())  # none left unjudged
    assert (REPO / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_unknown_device_kind_is_an_error():
    registry = Registry(REPO)
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(BenchmarkError):
        registry.peaks("cpu")
    with pytest.raises(BenchmarkError):
        registry.peaks("source")


# -- operation and byte functions against hand counts ---------------------------


def test_az_conv_flops_hand_count():
    az = Registry(REPO).module("roofline", "az_conv")
    assert az.board_taps(3) == 484 and az.board_taps(1) == 64  # 22 x 22 of 24 x 24
    # one 3x3 256->256 convolution, one row, forward only: 2 * 484 * 256 * 256
    assert az.conv_flops(1, 3, 256, 256, passes=1) == 63_438_848
    model = Registry(REPO).config("az-256x19-train")["model"]
    forward_row = (2 * 484 * 19 * 256 + 38 * 63_438_848 + 2 * 64 * 256 * 73 + 2 * 64 * 256 * 4
                   + 2 * 256 * 256 + 2 * 256)
    assert forward_row == 2_418_039_296  # 2.42 GFLOP a row forward, not the padded 2.87
    stem_once = 2 * 484 * 19 * 256
    assert az.step_flops(model, 4096) == 4096 * (3 * forward_row - stem_once)
    # XLA's own count for the compiled step at batch 4096 was 29.77 TFLOP (PERF.md section 5)
    assert abs(az.step_flops(model, 4096) / 29.77e12 - 1) < 0.01
    least = az.least_seconds(model, 4096, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "compute" and abs(least["compute_s"] - az.step_flops(model, 4096) / 197e12) < 1e-12


def test_nnue_ft_bytes_hand_count():
    ft = Registry(REPO).module("roofline", "nnue_ft")
    model = {"l1": 1024, "num_buckets": 8}
    assert ft.row_bytes(model) == 4128
    # one position, 3 active rows in all: forward 3 rows + 2 accumulators, backward 2 + 2 x 3 rows
    assert ft.step_bytes(model, 1, 3) == 4128 * (3 + 2 + 2 + 6)
    least = ft.least_seconds(model, 16384, 16384 * 50, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory"


# -- the reduction, on a recorded chip trace -----------------------------------


def test_hlo_kinds_reads_fusions():
    text = (DATA / "hlo_sample.txt").read_text()
    kinds = tracelib.hlo_kinds(text)
    assert "scatter" in kinds["fusion.7"] and "gather" in kinds["fusion"]
    assert "gather" not in kinds["broadcast_multiply_fusion"]
    assert "reduce" in kinds["fusion.17"]


def test_union_and_percentile():
    assert tracelib.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tracelib.union_ns([]) == 0
    assert tracelib.percentile([1, 2, 3, 4, 5], 50) == 3
    assert tracelib.percentile(list(range(101)), 90) == 90


def test_recorded_trace_reductions(recorded):
    """Three steps of nnue_train_b16384 recorded on a v5e (my chip run, PR 23)."""
    steps = tracelib.step_modules(recorded)
    assert len(steps) == 3 and steps[0][0].startswith("jit__step")
    lo, hi = tracelib.window(recorded)
    busy = tracelib.busy_ns(recorded, (lo, hi))
    assert 0.99 < busy / (hi - lo) <= 1.0
    top = tracelib.top_ops(recorded, 3)
    assert "scatter" in top[0][0] and "f32[22528,1024]" in top[0][0]
    assert abs(sum(s for _n, s in tracelib.top_ops(recorded, 1000)) - busy / 1e9) < 1e-3
    gaps = dict(tracelib.idle_gaps(recorded))
    assert abs(sum(gaps.values()) - (hi - lo - busy) / 1e9) < 1e-9
    assert set(gaps) <= set(tracelib.HOST_SPANS) | {"between_spans"}


def test_reducers_on_recorded_trace(recorded):
    registry = Registry(REPO)
    config = registry.config("nnue-sfnnv5-train")
    pool = {"indices": np.where(np.arange(32)[None, None, :] < 25, 7, 22528) * np.ones((4, 2, 1), np.int64)}
    ctx = {"registry": registry, "config": config, "trace": recorded, "batch": 16384, "pool": pool,
           "device_kind": "TPU v5 lite"}
    step_ms = registry.module("reducers", "step_device_ms").reduce(ctx)
    assert 90 < step_ms < 100
    idle = registry.module("reducers", "device_idle").reduce(ctx)
    assert 0 <= idle < 1
    share = registry.module("reducers", "nnue_ft_roofline").reduce(ctx)
    # 50 active rows a position: 16384 * (3 * 50 + 4) * 4128 B = 10.4 GB, 12.7 ms at 819 GB/s
    least_ms = 16384 * (3 * 50 + 4) * 4128 / 819e9 * 1e3
    ft_ms = tracelib.kind_time_ns(recorded, ("gather", "scatter")) / 1e6 / 3
    assert abs(share - 100 * least_ms / ft_ms) < 1.0 and 10 < share < 25  # ft_ms also holds the small gathers
    # a reducer that finds nothing to read returns nothing
    assert registry.module("reducers", "az_conv_roofline").reduce(ctx) is None
    assert registry.module("reducers", "step_device_ms").reduce({**ctx, "trace": None}) is None


def test_feed_reducers_read_the_harness_span():
    registry = Registry(REPO)
    ctx = {"spans_s": {"feed_wait": [0.001] * 9 + [0.011]}}
    assert abs(registry.module("reducers", "feed_wait_ms").reduce(ctx) - 2.0) < 1e-9
    assert abs(registry.module("reducers", "feed_stall_p90_ms").reduce(ctx) - 2.0) < 1e-9
    assert registry.module("reducers", "feed_wait_ms").reduce({"spans_s": {"feed_wait": []}}) is None
    ctx = {"batch": 8, "steps": 10, "window_s": 2.0, "setup_s": 3.5, "step_intervals_s": [0.2] * 10,
           "memory_peak_bytes": 5 * 2**30}
    assert registry.module("reducers", "train_pos_per_s").reduce(ctx) == 40.0
    assert abs(registry.module("reducers", "step_ms_p90").reduce(ctx) - 200.0) < 1e-9
    assert registry.module("reducers", "setup_s").reduce(ctx) == 3.5
    assert registry.module("reducers", "peak_hbm_gib").reduce(ctx) == 5.0


# -- data-driven: new files and entries, no edit --------------------------------


def test_new_files_are_picked_up_without_an_edit(tiny):
    cell = tiny.workload("az_6x64_tiny_cell")
    assert cell["runner"] == "train_step" and cell["config"] == "az-6x64-tiny"
    assert tiny.config("az-6x64-tiny")["model"]["channels"] == 64
    assert tiny.traffic("tiny_pool")["pool_positions"] == 96
    names = [m["name"] for m in tiny.metrics("per_layer", "az_6x64_tiny_cell")]
    assert "steps_in_window" in names and "nnue_ft_roofline" not in names
    assert "steps_in_window" not in [m["name"] for m in tiny.metrics("per_layer", "nnue_tiny_cell")]
    for path in (REPO / "benchmark").rglob("*"):  # nothing that was there was edited
        if path.is_file() and "__pycache__" not in path.parts:
            assert (tiny.dir / path.relative_to(REPO / "benchmark")).read_bytes() == path.read_bytes()
    with pytest.raises(BenchmarkError):
        tiny.workload("no_such_cell")


@pytest.mark.parametrize("cell_name", ["az_6x64_tiny_cell", "nnue_tiny_cell"])
def test_runner_end_to_end(tiny, cell_name, capsys):
    """Batch 8 on the 6x64 tower (and a narrow NNUE), both kinds of run."""
    import jax

    cell = tiny.workload(cell_name)
    runner = tiny.module("runners", cell["runner"])
    results = {}
    for trace in (False, True):
        results[trace] = runner.run(tiny, cell, 2**31 + 11, 0.5, trace, time.monotonic(), jax.devices())
    out = capsys.readouterr().out
    assert "compilations inside the window 0" in out and "limit" in out
    plain, traced = results[False], results[True]
    assert set(plain) == {"correct", "attempted", "failed", "metrics", "device"}
    assert plain["correct"] is True and plain["failed"] == 0 and plain["attempted"] > 2
    assert set(plain["metrics"]) == {"train_pos_per_s", "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in plain["metrics"].values())
    assert plain["device"]["platform"] == "cpu"  # named for what it is; run.py refuses it
    # only the metric that lists this cell, through the reducer the temp copy added
    assert set(traced["metrics"]) == ({"steps_in_window"} if cell_name.startswith("az") else set())
    json.dumps(traced)


def test_positions_come_from_the_seed(tiny):
    config = tiny.config("nnue-tiny")
    family = tiny.module("families", "nnue")
    traffic = tiny.traffic("tiny_pool")
    a = positions.playout_pool(traffic, 2**31 + 5, family)
    b = positions.playout_pool(traffic, 2**31 + 5, family)
    c = positions.playout_pool(traffic, 6, family)
    assert all(np.array_equal(a[k], b[k]) for k in a) and not np.array_equal(a["indices"], c["indices"])
    assert len({row.tobytes() for row in a["indices"]}) == 96  # distinct positions
    batch = family.build_batch(a, np.arange(8))
    assert batch["indices"].shape == (8, 2, 32) and batch["indices"].dtype == np.int32
    assert config["model"]["num_features"] == 22528


def test_az_batches_are_dense_and_normalised(tiny):
    family = tiny.module("families", "az")
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 3, family)
    batch = family.build_batch(pool, np.array([0, 5, 5, 95]))
    assert batch["planes"].shape == (4, 8, 8, 19) and batch["policy_target"].shape == (4, 4672)
    assert np.allclose(batch["policy_target"].sum(axis=1), 1.0, atol=1e-5)
    assert np.array_equal((batch["policy_target"] > 0).sum(axis=1), pool["legal"][[0, 5, 5, 95]].sum(axis=1))
    assert np.all(np.abs(batch["value_target"]) <= 1.0)


# -- the comparison that decides `correct`, and its control ---------------------


@pytest.mark.parametrize("config_name", ["az-6x64-tiny", "nnue-tiny"])
def test_control_fails_and_program_passes(tiny, config_name):
    """The reference in the next precision down, in the program's place,
    has to come out not correct; the program has to pass."""
    config = tiny.config(config_name)
    family = tiny.module("families", config["family"])
    reference = tiny.module("reference", config["family"])
    checker = correctness.Checker(family, reference, config)
    for seed in (11, 2**31 + 12, 13):
        pool = positions.playout_pool(tiny.traffic("tiny_pool"), seed, family)
        sound = checker.compare(pool, seed)
        control = checker.compare(pool, seed, control=True)
        print(config_name, seed, {k: v for k, v in sound.items() if k != "_per_tensor"},
              {k: v for k, v in control.items() if k != "_per_tensor"})
        assert correctness.judge(sound, config)[0], correctness.judge(sound, config)[1]
        assert not correctness.judge(control, config)[0], correctness.judge(control, config)[1]


class _FrozenTrainer:
    """The program with its optimizer left out: steps that change nothing."""

    def __init__(self, trainer):
        self._trainer = trainer

    def __getattr__(self, name):
        return getattr(self._trainer, name)

    def step(self, state, batch):
        import jax
        import jax.numpy as jnp

        _stepped, metrics = self._trainer.step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics


@pytest.mark.parametrize("config_name,tensor", [("az-6x64-tiny", "res3_w1"), ("az-6x64-tiny", "value_fc2_b"),
                                                ("nnue-tiny", "ft_w"), ("nnue-tiny", "out_b")])
def test_left_out_mathematics_fails(tiny, config_name, tensor):
    """A gradient tensor that is zeroed or scaled, and an optimizer that
    does not update, each come out not correct: every number has a limit."""
    config = tiny.config(config_name)
    family = tiny.module("families", config["family"])
    reference = tiny.module("reference", config["family"])
    checker = correctness.Checker(family, reference, config)
    pool = positions.playout_pool(tiny.traffic("tiny_pool"), 21, family)
    program_grad = checker._program_grad
    for factor in (0.0, 1.5):
        def corrupted(params, batch, factor=factor):
            loss, grads = program_grad(params, batch)
            return loss, {**grads, tensor: factor * grads[tensor]}

        checker._program_grad = corrupted
        ok, line = correctness.judge(checker.compare(pool, 21), config)
        assert not ok and "EXCEEDED" in line, line
    checker._program_grad = program_grad
    checker.trainer = _FrozenTrainer(checker.trainer)
    ok, line = correctness.judge(checker.compare(pool, 21), config)
    assert not ok and "steps_drop_rel_diff" in line.split("EXCEEDED")[0].rsplit(";", 1)[-1], line
