"""The six start-up reducers (``benchmark/startup_programs.py``) held to
a recorded list of spans: what a settled cell's warm start left in the
program's span recorder, times rounded."""

from __future__ import annotations

import importlib

import pytest

import helpers
from benchmark import startup_programs
from benchmark.registry import Registry

METRICS = startup_programs.METRICS
SETUP_S = 46.7


def program(name, t, phases, cache="hit", thread="MainThread", **fields):
    trace_s, lower_s, cache_load_s, compile_s = phases
    return {"stage": "program_up", "t": t, "dur_ms": 1e3 * (sum(phases) + 0.05), "thread": thread, "name": name, "trace_s": trace_s,
            "lower_s": lower_s, "cache_load_s": cache_load_s, "compile_s": compile_s, "cache": cache,
            "traced": [[name, 1, trace_s]], "small": [0, 0.0], **fields}


def recorded():
    """Oldest first, as ``RECORDER.spans()`` gives them: the process started at 100.0 on the recorder's clock."""
    return [
        {"stage": "process_boot", "t": 100.0, "dur_ms": 9800.0, "thread": "MainThread"},
        {"stage": "program_import", "t": 109.8, "dur_ms": 4000.0, "thread": "MainThread"},
        {"stage": "train_init", "t": 113.9, "dur_ms": 3200.0, "thread": "MainThread", "trainer": "az", "compile_s": 0.0, "cache_load_s": 0.9,
         "trace_lower_s": 1.8, "cache_misses": 0, "small_at_start": [3, 0.01], "small_at_end": [40, 0.11], "trace_id": "1.1", "span_id": "1.1"},
        program("_init", 113.95, (0.9, 0.9, 0.9, 0.0), trace_id="1.1", span_id="1.2", parent_id="1.1"),  # inside train_init: not between
        program("<lambda>", 117.5, (4.0, 0.6, 1.2, 0.0)),  # the settle's forward-only program
        program("balanced_bias", 123.4, (0.2, 0.1, 0.05, 0.0)),
        program("encode", 125.0, (0.1, 0.1, 0.0, 0.15), cache="miss", thread="feed"),  # another thread's counts too
        {"stage": "train_first_step", "t": 134.4, "dur_ms": 11700.0, "thread": "MainThread", "trainer": "az", "compile_s": 0.0,
         "cache_load_s": 1.4, "trace_lower_s": 10.1, "cache_misses": 0, "small_at_start": [90, 0.36], "small_at_end": [90, 0.36],
         "trace_id": "1.3", "span_id": "1.3"},
        program("_step", 134.45, (8.0, 2.1, 1.4, 0.0), trace_id="1.3", span_id="1.4", parent_id="1.3"),
        program("loss_and_grads", 190.0, (3.0, 1.0, 0.0, 40.0), cache="miss"),  # correct's, after the window: not start-up's
        {"stage": "train_init", "t": 180.0, "dur_ms": 900.0, "thread": "MainThread", "trainer": "az", "compile_s": 0.0, "cache_load_s": 0.1,
         "trace_lower_s": 0.2, "cache_misses": 0, "small_at_start": [200, 0.9], "small_at_end": [210, 1.0]},  # correct's trainer: not the first
    ]


def reduced(spans):
    ctx = {"setup_s": SETUP_S, "startup_programs": startup_programs.read(spans, SETUP_S)}
    return {name: importlib.import_module(f"benchmark.reducers.{name}").reduce(ctx) for name in METRICS}


def test_the_six_entries_are_declared_last_with_every_cell():
    spec = Registry(helpers.REPO).spec
    cells = [cell["name"] for cell in spec["workloads"]]
    entries = spec["per_layer"][-6:]
    assert tuple(entry["name"] for entry in entries) == METRICS
    for entry in entries:
        counter = entry["name"] == "setup_programs_missed"
        assert entry == {"name": entry["name"], "unit": "count" if counter else "s", "better": "lower", "layer": "start-up",
                         "source": "program_counter" if counter else "program_span", "moves": "setup_s", "workloads": cells}


def test_the_partition_adds_up_to_setup_s(capsys):
    values = reduced(recorded())
    assert values == {
        "setup_boot_s": pytest.approx(9.8), "setup_import_s": pytest.approx(4.1), "setup_between_s": pytest.approx(17.3),
        # <lambda> 5.8 + balanced_bias 0.35 + the feed thread's 0.35 + small's growth 0.25
        "setup_between_programs_s": pytest.approx(6.75), "setup_warmup_s": pytest.approx(0.6), "setup_programs_missed": 1}
    init_s, first_step_s = 3.2, 11.7  # setup_init_s and setup_first_step_s: the two spans' durations
    six = values["setup_boot_s"] + values["setup_import_s"] + init_s + values["setup_between_s"] + first_step_s + values["setup_warmup_s"]
    assert six == pytest.approx(SETUP_S, abs=1e-6)
    assert values["setup_between_programs_s"] <= values["setup_between_s"]
    partition, programs = capsys.readouterr().out.splitlines()
    assert partition == ("start-up: boot 9.8 | import 4.1 | init 3.2 | between 17.3 (programs 6.8) | first step 11.7 | warm-up 0.6"
                         " = 46.7 of setup_s 46.7")
    assert programs.startswith("start-up programs: _step 11.50 s (trace 8.00 lower 2.10 cache_load 1.40 compile 0.00, hit); <lambda> 5.80 s (")
    assert "loss_and_grads" not in programs and programs.endswith("traced in _step: _step x1 8.00")


def test_a_program_after_the_windows_start_is_not_counted():
    spans = recorded()
    assert reduced(spans)["setup_programs_missed"] == 1  # the feed thread's, not correct's
    spans[6]["cache"] = "hit"
    assert reduced(spans)["setup_programs_missed"] == 0
    late = program("late", 100.0 + SETUP_S - 0.2, (0.1, 0.1, 0.0, 0.1), cache="miss")  # ends 0.15 s after the window's start
    assert reduced(spans + [late])["setup_programs_missed"] == 0
    late["t"] -= 0.2
    assert reduced(spans + [late])["setup_programs_missed"] == 1
    # a program that ended inside train_first_step is not between's
    assert reduced(spans)["setup_between_programs_s"] == reduced(spans + [late])["setup_between_programs_s"]


@pytest.mark.parametrize("missing, left_out", [
    ("process_boot", {"setup_boot_s", "setup_warmup_s", "setup_programs_missed"}),  # a platform without /proc
    ("program_import", {"setup_import_s"}),
    ("train_init", {"setup_import_s", "setup_between_s", "setup_between_programs_s"}),
    ("train_first_step", {"setup_between_s", "setup_between_programs_s", "setup_warmup_s", "setup_programs_missed"}),
])
def test_a_missing_span_gives_none(missing, left_out, capsys):
    values = reduced([span for span in recorded() if span["stage"] != missing])
    assert {name for name, value in values.items() if value is None} == left_out
    assert capsys.readouterr().out == ""  # no partition to print


def test_the_parents_spans_give_none_and_do_not_raise(capsys):
    """The parent of the PR that added them records the two trainer spans
    with their four fields alone: ``setup_between_s`` is theirs to give,
    the other five are left out of the line."""
    old = [{key: value for key, value in span.items() if not key.startswith("small_")}
           for span in recorded() if span["stage"] in ("train_init", "train_first_step")]
    values = reduced(old)
    assert {name for name, value in values.items() if value is not None} == {"setup_between_s"}
    assert reduced([]) == dict.fromkeys(METRICS) and capsys.readouterr().out == ""


def test_the_reading_is_made_once_a_run(monkeypatch, capsys):
    from fishnet_tpu.telemetry.spans import RECORDER

    calls = []
    monkeypatch.setattr(RECORDER, "spans", lambda: calls.append(1) or recorded())
    ctx = {"setup_s": SETUP_S}
    values = [importlib.import_module(f"benchmark.reducers.{name}").reduce(ctx) for name in METRICS]
    assert None not in values and len(calls) == 1 and len(capsys.readouterr().out.splitlines()) == 2
