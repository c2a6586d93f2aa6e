"""Perf-regression sentinel (telemetry/regress.py, ISSUE 15): the
checked-in bench artifacts must judge clean (exit 0, >=10 tracked
series — the acceptance floor), a doctored artifact must gate (exit 1),
a missing/empty root exits 2, and the direction/zero/true judging rules
plus the legacy-wrapper tail recovery are pinned as units."""

import json
import os
import shutil

import pytest

from fishnet_tpu.telemetry import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARTIFACT_PREFIXES = ("BENCH_", "MULTICHIP_", "CLUSTER_", "MCTS_")


def _copy_artifacts(dst: str) -> int:
    n = 0
    for fname in sorted(os.listdir(REPO)):
        if fname.endswith(".json") and fname.startswith(ARTIFACT_PREFIXES):
            shutil.copy(os.path.join(REPO, fname), os.path.join(dst, fname))
            n += 1
    return n


# -- the acceptance run over the checked-in artifacts -------------------------


def test_checked_in_artifacts_judge_clean(capsys):
    """The repo's own artifact history must not gate: the sentinel over
    the 15 checked-in BENCH/MULTICHIP/CLUSTER/MCTS runs exits 0 and
    tracks at least 10 series (the ISSUE acceptance floor)."""
    rc = regress.main(["--root", REPO, "--no-write"])
    assert rc == 0
    report = regress.build_report(REPO)
    assert report["artifacts_ingested"] >= 15
    assert report["series_tracked"] >= 10
    assert report["status"] == "ok"
    assert report["gated_regressions"] == []
    # The table printer names every gated metric family prefix.
    out = capsys.readouterr().out
    assert "series" in out


def test_checked_in_report_matches_repo_copy():
    """REGRESS_r01.json in the repo is a real run of this tool over
    these artifacts — same format tag and a clean status."""
    with open(os.path.join(REPO, "REGRESS_r01.json")) as fp:
        checked_in = json.load(fp)
    assert checked_in["format"] == "fishnet-regress/1"
    assert checked_in["status"] == "ok"
    assert checked_in["series_tracked"] >= 10


def test_doctored_artifact_gates(tmp_path):
    """Halving the latest MCTS warm visits/s (a gate-severity
    up-direction series with a 20% band) must flip the report to
    regression and the CLI to exit 1."""
    root = str(tmp_path)
    assert _copy_artifacts(root) >= 12  # 15 before PR 21 deleted three
    latest = os.path.join(root, "MCTS_r02.json")
    with open(os.path.join(root, "MCTS_r01.json")) as fp:
        doc = json.load(fp)
    doc["value"] = doc["value"] * 0.5
    with open(latest, "w") as fp:
        json.dump(doc, fp)

    report = regress.build_report(root)
    assert report["status"] == "regression"
    assert any("mcts" in m.lower() for m in report["gated_regressions"])
    rc = regress.main(["--root", root, "--no-write"])
    assert rc == 1


def test_watch_severity_does_not_gate(tmp_path):
    """A watch-severity regression is reported but never gates: halve
    a MULTICHIP watch metric (steps_per_s) while keeping its gate
    parity bits intact — status stays ok, exit stays 0."""
    root = str(tmp_path)
    _copy_artifacts(root)
    with open(os.path.join(root, "MULTICHIP_r06.json")) as fp:
        doc = json.load(fp)
    doc["value"] = doc["value"] * 0.5
    with open(os.path.join(root, "MULTICHIP_r07.json"), "w") as fp:
        json.dump(doc, fp)
    report = regress.build_report(root)
    assert report["status"] == "ok"
    assert any(
        "steps_per_s" in m for m in report["regressions"]
    ), report["regressions"]


def test_report_written_with_next_run_number(tmp_path):
    root = str(tmp_path)
    _copy_artifacts(root)
    rc = regress.main(["--root", root])
    assert rc == 0
    assert os.path.exists(os.path.join(root, "REGRESS_r01.json"))
    # Next invocation numbers past the existing report.
    assert regress._next_out_path(root).endswith("REGRESS_r02.json")


def test_missing_and_empty_roots_exit_2(tmp_path):
    assert regress.main(["--root", str(tmp_path / "nope")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert regress.main(["--root", str(empty), "--no-write"]) == 2


# -- judging rules ------------------------------------------------------------


def _series(spec, points):
    s = regress._Series(spec=spec)
    for run, val in points.items():
        s.points[run] = (val, f"{spec.prefix}_{run}.json")
    return s


def test_judge_directions():
    up = regress.Spec("X", "m", "value", "up", 0.10, "gate")
    down = regress.Spec("X", "m", "value", "down", 0.10, "gate")
    zero = regress.Spec("X", "m", "value", "zero", 0.0, "gate")
    true = regress.Spec("X", "m", "value", "true", 0.0, "gate")

    assert regress._judge(_series(up, {"r01": 100, "r02": 95}))[
        "verdict"] == "ok"  # -5% within 10% band
    assert regress._judge(_series(up, {"r01": 100, "r02": 80}))[
        "verdict"] == "regression"
    assert regress._judge(_series(down, {"r01": 100, "r02": 120}))[
        "verdict"] == "regression"
    assert regress._judge(_series(down, {"r01": 100, "r02": 105}))[
        "verdict"] == "ok"
    assert regress._judge(_series(zero, {"r01": 0.0}))["verdict"] == "ok"
    assert regress._judge(_series(zero, {"r01": 2.0}))[
        "verdict"] == "regression"
    assert regress._judge(_series(true, {"r01": 1.0}))["verdict"] == "ok"
    assert regress._judge(_series(true, {"r01": 0.0}))[
        "verdict"] == "regression"
    assert regress._judge(_series(up, {"r01": 100}))[
        "verdict"] == "single-point"


def test_judge_compares_latest_to_nearest_prior():
    """Only the newest step is judged: an old regression between r01
    and r02 must not flag once r03 recovers."""
    up = regress.Spec("X", "m", "value", "up", 0.10, "gate")
    row = regress._judge(_series(up, {"r01": 100, "r02": 50, "r03": 51}))
    assert row["verdict"] == "ok"
    assert row["prior_run"] == "r02"


def test_resolve_dotted_paths_lists_and_bools():
    doc = {"a": {"b": 3.5}, "lost": [1, 2], "ok": True}
    assert regress._resolve(doc, "a.b") == 3.5
    assert regress._resolve(doc, "lost") == 2.0  # lists -> len
    assert regress._resolve(doc, "ok") == 1.0
    assert regress._resolve(doc, "a.missing") is None


def test_legacy_wrapper_tail_recovery():
    """BENCH_r03..r05 are legacy wrappers (parsed=null, front-truncated
    JSON in "tail"): ingest must still recover the regexable headline
    series from them."""
    store, log = regress.ingest(REPO)
    legacy = [a for a in log if a["file"] == "BENCH_r03.json"]
    assert legacy and legacy[0]["legacy"]
    recovered = [
        key for key, s in store.items()
        if "r03" in s.points and key.startswith("BENCH/legacy_")
    ]
    assert recovered, "no series recovered from the legacy tail"
    # Legacy recovery is watch-severity only: a noisy regexed tail must
    # never gate CI.
    assert all(
        store[k].spec.severity == "watch" for k in store
        if k.startswith("BENCH/legacy_")
    )
