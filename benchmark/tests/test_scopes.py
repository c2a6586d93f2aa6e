"""benchmark/scopes.py and the seven metrics that read the program's own
names: CPU, recorded data (``pytest benchmark/tests``)."""

from __future__ import annotations

import gzip
import json
import time
from pathlib import Path

import pytest

import helpers
from benchmark import scopes, startup, tracelib
from benchmark.registry import Registry

REPO = helpers.REPO
DATA = Path(__file__).resolve().parent / "data"
PHASE_METRICS = {phase: f"step_{phase}_ms" for phase in scopes.PHASES}

# Real ``op_name``s: of the compiled NNUE and AZ steps (JAX 0.9.0), and of the program before it had scopes.
OP_NAMES = [
    ("jit(_step)/jvp(forward)/ft_gather/jit(_take)/gather", "forward", "jvp(forward)/ft_gather"),
    ("jit(_step)/transpose(jvp(forward))/ft_gather/jit(_take)/scatter-add", "backward", "transpose(jvp(forward))/ft_gather"),
    ("jit(_step)/jvp(forward)/stacks/bi,koi->bko/dot_general", "forward", "jvp(forward)/stacks"),
    ("jit(_step)/jvp(forward)/ft_gather/jit(_take)", "forward", "jvp(forward)/ft_gather"),
    ("jit(_step)/jvp(forward)/mul", "forward", "jvp(forward)"),
    ("jit(_step)/jvp(loss)/jit(log_softmax)/reduce_max", "forward", "jvp(loss)"),
    ("jit(_step)/transpose(jvp(loss))/mul;jit(_step)/transpose(jvp(loss))/broadcast_in_dim", "backward", "transpose(jvp(loss))"),
    ("jit(_step)/transpose(jvp(forward))/block07/conv_general_dilated", "backward", "transpose(jvp(forward))/block07"),
    ("jit(_step)/optimizer/jit(_where)/select_n", "optimizer", "optimizer"),
    ("jit(_step)/optimizer/add;jit(_step)/transpose(jvp(forward))/block07/conv_general_dilated", "optimizer", "optimizer"),
    ("jit(_step)/add;jit(_step)/jvp(forward)/stem/max", "forward", "jvp(forward)/stem"),
    # JAX lowers jit(relu) once for every block: its instructions carry the first caller's scope; the direct one is right
    ("jit(_step)/jvp(forward)/stem/jit(relu)/max;jit(_step)/jvp(forward)/block03/gt", "forward", "jvp(forward)/block03"),
    ("jit(_step)/jvp(forward)/stem/jit(relu)/max", "forward", "jvp(forward)/stem"),
    ("jit(_step)/forward_pass/add", "unscoped", "forward_pass"),  # never a prefix match
    ("jit(_step)/jvp(reforward)/add", "unscoped", "jvp(reforward)"),
    ("jit(_step)/transpose(jvp(jit(_take)))/scatter-add", "unscoped", "transpose(jvp(jit(_take)))"),  # before the scopes
    ("jit(_step)/jvp()/reduce_sum", "unscoped", "jvp()"),
    ("jit(_step)/add", "unscoped", "(no scope)"),
    ("reduce_sum", "unscoped", "(no scope)"),
    ("state.opt_state[0].mu[\\'ft_w\\']", "unscoped", "(no scope)"),
    ("", "unscoped", "(no scope)"),
]


@pytest.mark.parametrize("op_name,phase,path", OP_NAMES)
def test_phase_of(op_name, phase, path):
    assert scopes.phase_of(op_name) == (phase, path)


def test_a_joined_name_holds_a_set_of_phases():
    assert scopes.phases_of(OP_NAMES[9][0]) == {"optimizer", "backward"}
    assert scopes.phases_of(OP_NAMES[6][0]) == {"backward"}
    assert scopes.phases_of("jit(_step)/add") == set()


def _trace(name):
    with gzip.open(DATA / name, "rt") as fh:
        return tracelib.Trace.from_json(json.load(fh))


@pytest.fixture(scope="module")
def recorded():
    """Three steps of nnue_train_b16384 with the scopes in (my chip run, PR 24)."""
    return _trace("nnue_scoped_trace.json.gz")


@pytest.fixture(scope="module")
def scoped_text():
    with gzip.open(DATA / "nnue_step_scoped.hlo.txt.gz", "rt") as fh:
        return fh.read()


def _reduce(registry, ctx):
    return {phase: registry.module("reducers", name).reduce(ctx) for phase, name in PHASE_METRICS.items()}


def test_phase_reducers_sum_to_the_operations_total(recorded, scoped_text, capsys):
    """The NNUE step's compiled text and three traced steps of it, both
    from one run on a v5e (my chip run, PR 24)."""
    registry = Registry(REPO)
    ctx = {"trace": recorded, "step_hlo_text": scoped_text}
    values = _reduce(registry, ctx)
    total_ms = sum(
        o.dur_ns for _n, start, dur in tracelib.step_modules(recorded) for o in tracelib.ops_in(recorded, (start, start + dur))
    ) / 1e6 / 3
    assert sum(values.values()) == pytest.approx(total_ms, rel=1e-9)
    assert total_ms == pytest.approx(registry.module("reducers", "step_device_ms").reduce(ctx), rel=1e-3)
    assert 30 < values["forward"] < 40 and 54 < values["backward"] < 62
    assert 0.3 < values["optimizer"] < 4 and values["unscoped"] < 0.01 * total_ms
    found = ctx["scopes_split"]
    assert sum(found.by_path.values()) == pytest.approx(total_ms, rel=1e-9)
    assert found.by_path["transpose(jvp(forward))/ft_gather"] > found.by_path["jvp(forward)/ft_gather"] > 20
    assert all("+" in held for held in found.mixed_ms)
    out = capsys.readouterr().out
    assert out.count("scopes: 9") == 1  # the four reducers share one split, printed once
    assert "transpose(jvp(forward))/ft_gather" in out and "carries no scope" not in out


def test_the_parents_trace_joins_the_scoped_text(scoped_text):
    """Scopes are metadata: PR 23's recording of the program before it had
    any joins this PR's text in every operation, name and shape."""
    found = scopes.split_trace(_trace("nnue_trace.json.gz"), scoped_text)
    assert found is not None and found.steps == 3 and found.by_phase["unscoped"] < 0.5


def test_a_program_without_scopes_is_all_unscoped(recorded, scoped_text, capsys):
    values = _reduce(Registry(REPO), {"trace": recorded, "step_hlo_text": scopes.without_metadata(scoped_text)})
    assert values["forward"] == values["backward"] == values["optimizer"] == 0.0 and values["unscoped"] > 90
    assert "the compiled program carries no scope" in capsys.readouterr().out


@pytest.mark.parametrize("wrong", ["shape", "name"])
def test_an_operation_that_does_not_join_gives_no_number(recorded, scoped_text, wrong, capsys):
    text = (scoped_text.replace("%fusion.7 = f32[22528,1024]", "%fusion.7 = f32[22528,1023]") if wrong == "shape"
            else scoped_text.replace("%fusion.7 ", "%fusion.7777 "))
    assert text != scoped_text
    values = _reduce(Registry(REPO), {"trace": recorded, "step_hlo_text": text})
    assert set(values.values()) == {None}
    out = capsys.readouterr().out
    assert "fusion.7 f32[22528,1024]" in out and "no phase metric is reported" in out
    assert _reduce(Registry(REPO), {"trace": None}) == dict.fromkeys(scopes.PHASES)


def test_self_time_takes_nested_operations_out():
    ops = [tracelib.Op("while", "", 0.0, 100.0), tracelib.Op("body.1", "", 10.0, 30.0),
           tracelib.Op("body.2", "", 50.0, 40.0), tracelib.Op("after", "", 100.0, 5.0)]
    assert scopes._self_ns(ops) == [30.0, 30.0, 40.0, 5.0]


def test_setup_reducers_read_the_programs_spans(tmp_path):
    """A tiny cell run on the CPU: the three start-up metrics come from
    the spans the trainer recorded, and the phase metrics find no device
    trace and stay out."""
    import jax

    checkout = helpers.tiny_checkout(tmp_path)
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    for metric in spec["per_layer"]:
        if metric["name"].startswith(("setup_", "step_")):
            metric["workloads"].append("nnue_tiny_cell")
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    tiny = Registry(checkout)
    cell = tiny.workload("nnue_tiny_cell")
    t0 = time.monotonic()
    result = tiny.module("runners", cell["runner"]).run(tiny, cell, 5, 0.3, True, t0, jax.devices())
    metrics = result["metrics"]
    assert set(metrics) == {"setup_init_s", "setup_first_step_s", "setup_cache_misses"}
    # the reducers read the FIRST span of the process (in a benchmark run, the cell's trainer; here maybe an earlier test's)
    assert metrics["setup_first_step_s"]["value"] == startup.span_seconds("train_first_step") > 0
    assert metrics["setup_init_s"]["value"] == startup.span_seconds("train_init") > 0
    assert metrics["setup_cache_misses"]["value"] >= 0
    from fishnet_tpu.telemetry.spans import RECORDER

    mine = [span for span in RECORDER.spans() if span["stage"] == "train_first_step" and span["t"] >= t0]
    assert mine[0]["trainer"] == "nnue" and mine[0]["trace_lower_s"] > 0  # this run's own trainer recorded its span
    assert startup.first_span("no_such_stage") is None and startup.span_seconds("no_such_stage") is None
