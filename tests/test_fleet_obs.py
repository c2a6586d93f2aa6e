"""Fleet observability plane (doc/observability.md "Fleet
observability"): histogram quantile summaries, build-info families,
the exporter scrape-vs-shutdown race, cross-process trace stitching
(reassignment joins, fenced late submits, zero orphans), the SLO
burn-rate engine, and the FleetAggregator's federation + staleness
semantics. ``make fleet-obs-smoke`` additionally runs the ``slow``
tests here: real supervised processes under a SIGKILL with the
aggregator scraping throughout."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

from fishnet_tpu.telemetry import registry as reg
from fishnet_tpu.telemetry.critical_path import group_traces, orphan_spans
from fishnet_tpu.telemetry.exporter import MetricsExporter
from fishnet_tpu.telemetry.fleet import FleetAggregator, port_dir_targets
from fishnet_tpu.telemetry.registry import (
    MetricFamily,
    MetricsRegistry,
    Sample,
    histogram_quantiles,
    percentile,
    quantile_from_buckets,
)
from fishnet_tpu.telemetry.slo import SLO, Selector, SLOEngine, default_slos
from fishnet_tpu.telemetry.stitch import (
    attribute_fleet_trace,
    fleet_report,
    is_global_trace_id,
    stitch,
    tag_actor_spans,
)
from fishnet_tpu.telemetry.trace_export import (
    chrome_trace,
    validate_chrome_trace,
)
from fishnet_tpu.telemetry.tracing import trace_id_for_batch

_REPO_ROOT = Path(__file__).resolve().parents[1]


def _get(url: str, timeout: float = 3.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        assert resp.status == 200
        return resp.read()


# ---------------------------------------------------------------------------
# Quantile summaries (registry.py)
# ---------------------------------------------------------------------------


def test_percentile_shared_definition():
    assert percentile([], 99) is None
    assert percentile([5.0], 50) == 5.0
    vals = list(range(1, 101))
    # Nearest-rank over (n-1)-scaled index: see registry.percentile.
    assert percentile(vals, 50) == 51
    assert percentile(vals, 99) == 99


def test_quantile_from_buckets_interpolates_and_clamps():
    bounds = [0.1, 1.0, 10.0]
    # 10 obs <= 0.1, 10 more in (0.1, 1.0], none beyond.
    assert quantile_from_buckets(bounds, [10, 20, 20], 20, 0.5) == 0.1
    mid = quantile_from_buckets(bounds, [10, 20, 20], 20, 0.75)
    assert 0.1 < mid <= 1.0
    # Observations past the last finite bound clamp to it.
    assert quantile_from_buckets(bounds, [0, 0, 0], 5, 0.99) == 10.0
    assert quantile_from_buckets(bounds, [], 0, 0.5) is None


def test_render_json_carries_histogram_quantiles():
    registry = MetricsRegistry()
    hist = registry.histogram(
        "test_fleet_seconds", "h", buckets=(0.1, 1.0, 10.0),
        labelnames=("endpoint",),
    )
    for _ in range(10):
        hist.observe(0.05, endpoint="a")
    for _ in range(10):
        hist.observe(5.0, endpoint="a")
    doc = registry.render_json()
    entry = doc["metrics"]["test_fleet_seconds"]
    rows = {
        r["labels"]["endpoint"]: r for r in entry["quantiles"]
    }
    assert rows["a"]["count"] == 20
    assert rows["a"]["p50"] <= 1.0 < rows["a"]["p99"] <= 10.0
    # Families without observations expose no quantile rows.
    fam = MetricFamily("empty_seconds", "histogram", "h")
    assert histogram_quantiles(fam) == []


# ---------------------------------------------------------------------------
# Build info + start time (exporter.py)
# ---------------------------------------------------------------------------


def test_every_exporter_serves_build_info_and_start_time():
    registry = MetricsRegistry()
    exporter = MetricsExporter(port=0, registry=registry)
    try:
        text = _get(exporter.url + "/metrics").decode()
    finally:
        exporter.close()
    assert "# TYPE fishnet_build_info gauge" in text
    assert 'fishnet_build_info{' in text
    for label in ("version=", "abi=", "jax="):
        assert label in text
    assert "fishnet_proc_start_time_seconds" in text
    start = [
        line for line in text.splitlines()
        if line.startswith("fishnet_proc_start_time_seconds")
    ][0]
    assert 0 < float(start.split()[-1]) <= time.time()


def test_exporter_close_refuses_scrapes_instead_of_racing():
    registry = MetricsRegistry()
    exporter = MetricsExporter(port=0, registry=registry)
    url = exporter.url
    errors = []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                _get(url + "/metrics", timeout=1.0)
            except AssertionError:
                pass  # 503 while closing: the refusal path
            except Exception as exc:  # noqa: BLE001
                if not isinstance(exc, (OSError, urllib.error.URLError)):
                    errors.append(exc)
                return

    import urllib.error

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    exporter.close()  # must not deadlock against in-flight scrapes
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    assert not errors
    # close() drained the registry's scrape path too.
    registry.scrape_barrier()


# ---------------------------------------------------------------------------
# Cross-process trace stitching (stitch.py)
# ---------------------------------------------------------------------------


def _span(stage, t, dur_ms, tid=None, sid=None, parent=None, **fields):
    s = {"stage": stage, "t": t, "dur_ms": dur_ms, "thread": "w0"}
    if tid is not None:
        s["trace_id"] = tid
    if sid is not None:
        s["span_id"] = sid
    if parent is not None:
        s["parent_id"] = parent
    s.update(fields)
    return s


def test_global_trace_id_is_the_batch_digest_shape():
    tid = trace_id_for_batch("workunit-1")
    assert is_global_trace_id(tid)
    assert not is_global_trace_id("3.7")  # step trace: tid.counter
    assert not is_global_trace_id("ABCDEF0123456789")  # uppercase


def test_tag_actor_spans_namespaces_and_rebases():
    tid = trace_id_for_batch("B")
    spans = [
        _span("acquire", 1.0, 100.0, tid=tid, sid=tid),
        _span("pack", 2.0, 5.0, tid="3.7", sid="3.8", parent="3.7",
              links=[["3.7", "3.9"]]),
    ]
    out = tag_actor_spans("A@1", "PROC0", spans, epoch_offset=1000.0)
    assert out[0]["t"] == 1001.0 and out[0]["proc"] == "PROC0"
    assert out[0]["trace_id"] == tid  # global: the join key survives
    assert out[0]["span_id"] == f"A@1/{tid}"
    assert out[1]["trace_id"] == "A@1/3.7"  # step trace: namespaced
    assert out[1]["links"] == [["A@1/3.7", "A@1/3.9"]]
    assert spans[0]["t"] == 1.0  # inputs untouched


def _two_proc_dump(fenced_submit=False):
    """Synthetic two-process span dumps for one reassigned work unit:
    PROC0 acquires and dies; PROC1 re-acquires after the server's
    reassignment sweep and completes. With ``fenced_submit`` PROC0
    also submits late (partition, not death) and is fenced."""
    tid = trace_id_for_batch("game42")
    a = [
        _span("acquire", 10.0, 50.0, tid=tid, sid=tid),
        _span("schedule", 10.1, 5.0, tid=tid, sid="1.1", parent=tid),
        _span("queue_wait", 10.15, 200.0, tid=tid, sid="1.2", parent="1.1"),
    ]
    if fenced_submit:
        a.append(
            _span("submit", 13.5, 40.0, tid=tid, sid="1.3", parent=tid)
        )
    b = [
        _span("acquire", 12.5, 60.0, tid=tid, sid=tid),
        _span("schedule", 12.6, 4.0, tid=tid, sid="2.1", parent=tid),
        _span("queue_wait", 12.65, 150.0, tid=tid, sid="2.2", parent="2.1"),
        _span("submit", 13.0, 30.0, tid=tid, sid="2.3", parent=tid),
    ]
    return tid, [
        {"proc": "PROC0", "actor": "PROC0@100", "spans": a,
         "epoch_offset": 0.0},
        {"proc": "PROC1", "actor": "PROC1@200", "spans": b,
         "epoch_offset": 0.0},
    ]


def test_stitch_joins_reassigned_unit_into_one_tree():
    tid, incs = _two_proc_dump()
    report = stitch(incs)
    assert report["traces"] == 1
    assert report["cross_proc"] == [tid]
    assert report["reassignments"] == 1 and report["fenced"] == 0
    spans = [s for s in report["spans"] if s.get("trace_id") == tid]
    reassign = [s for s in spans if s["stage"] == "reassignment"]
    assert len(reassign) == 1
    r = reassign[0]
    assert r["from_actor"] == "PROC0@100" and r["to_actor"] == "PROC1@200"
    # Explicit link to where the dead actor went dark.
    assert [tid, "PROC0@100/1.2"] in r["links"]
    # The successor's root is parented under the reassignment span,
    # which is parented under the primary root: ONE tree.
    b_root = next(s for s in spans if s["span_id"] == f"PROC1@200/{tid}")
    assert b_root["parent_id"] == r["span_id"]
    assert r["parent_id"] == f"PROC0@100/{tid}"
    roots = [s for s in spans if s.get("parent_id") is None]
    assert len(roots) == 1 and roots[0]["span_id"] == f"PROC0@100/{tid}"
    # Zero orphans through the single-process grouper too.
    for trace in group_traces(report["spans"]).values():
        assert orphan_spans(trace) == []


def test_stitch_marks_fenced_late_submit():
    tid, incs = _two_proc_dump(fenced_submit=True)
    report = stitch(incs)
    assert report["fenced"] == 1
    spans = [s for s in report["spans"] if s.get("trace_id") == tid]
    r = next(s for s in spans if s["stage"] == "reassignment")
    late = next(s for s in spans if s["span_id"] == "PROC0@100/1.3")
    assert late.get("fenced") is True
    assert [tid, "PROC0@100/1.3"] in r["links"]
    assert r["fenced"] is True
    for trace in group_traces(report["spans"]).values():
        assert orphan_spans(trace) == []


def test_stitch_keeps_step_traces_per_process():
    # Identical process-local step trace ids must NOT merge.
    a = [_span("pack", 1.0, 5.0, tid="3.1", sid="3.2", parent="3.1")]
    b = [_span("pack", 1.0, 5.0, tid="3.1", sid="3.2", parent="3.1")]
    report = stitch([
        {"proc": "P0", "actor": "P0@1", "spans": a, "epoch_offset": 0.0},
        {"proc": "P1", "actor": "P1@2", "spans": b, "epoch_offset": 0.0},
    ])
    tids = {s["trace_id"] for s in report["spans"]}
    assert tids == {"P0@1/3.1", "P1@2/3.1"}


def test_fleet_attribution_sums_to_wall_with_reassignment():
    tid, incs = _two_proc_dump()
    report = stitch(incs)
    spans = [s for s in report["spans"] if s.get("trace_id") == tid]
    attr = attribute_fleet_trace(spans)
    total = sum(
        attr[c] for c in (
            "acquire", "schedule", "queue_wait", "compute", "submit",
            "reassignment", "other",
        )
    )
    assert attr["wall_ms"] > 0
    assert abs(total - attr["wall_ms"]) < 1e-6
    assert attr["reassignment"] > 0
    assert attr["coverage"] > 0.9
    # Per-proc attribution names both processes.
    assert set(attr["per_proc"]) == {"PROC0", "PROC1"}

    fleet = fleet_report(report["spans"])
    assert fleet["traces"] == 1
    assert fleet["reassignment_ms"] > 0
    assert set(fleet["per_proc"]) == {"PROC0", "PROC1"}


def test_fleet_chrome_export_one_track_group_per_proc():
    _, incs = _two_proc_dump()
    trace = chrome_trace(stitch(incs)["spans"])
    validate_chrome_trace(trace)
    proc_meta = {
        ev["args"]["name"] for ev in trace["traceEvents"]
        if ev["ph"] == "M" and ev["name"] == "process_name"
    }
    assert proc_meta == {"PROC0", "PROC1"}
    pids = {
        ev["pid"] for ev in trace["traceEvents"] if ev["ph"] == "X"
    }
    assert len(pids) == 2
    # The reassignment link renders as a cross-track flow arrow.
    assert any(ev["ph"] == "s" for ev in trace["traceEvents"])


# ---------------------------------------------------------------------------
# SLO burn-rate engine (slo.py)
# ---------------------------------------------------------------------------


def _counter_fams(total, bad):
    fam = MetricFamily("req_total", "counter", "h")
    fam.samples.append(Sample("req_total", total, {"outcome": "ok"}))
    fam.samples.append(Sample("req_total", bad, {"outcome": "error"}))
    return {"req_total": fam}


def _ratio_slo(objective=0.9):
    return SLO(
        name="t", description="d", objective=objective,
        total=Selector("req_total"),
        bad=Selector("req_total", {"outcome": "error"}),
    )


def test_ratio_slo_burn_rates_multi_window():
    eng = SLOEngine([_ratio_slo(0.9)], windows=(60.0, 300.0))
    t0 = 1000.0
    eng.observe(_counter_fams(100, 0), now=t0)
    # 100 more requests, 20 bad, inside the short window: 20% bad over
    # a 10% budget = burn 2.0 on BOTH windows (same delta).
    eng.observe(_counter_fams(180, 20), now=t0 + 30)
    rows = eng.evaluate(now=t0 + 30)
    assert rows[0]["windows"]["60s"] == pytest.approx(2.0)
    assert rows[0]["status"] == "breach"
    # A later clean minute: the short window calms first.
    eng.observe(_counter_fams(1180, 20), now=t0 + 120)
    rows = eng.evaluate(now=t0 + 120)
    assert rows[0]["windows"]["60s"] == 0.0
    assert rows[0]["windows"]["300s"] > 0.0


def test_slo_no_traffic_is_not_burning():
    eng = SLOEngine([_ratio_slo()], windows=(60.0,))
    eng.observe(_counter_fams(50, 5), now=0.0)
    eng.observe(_counter_fams(50, 5), now=30.0)
    rows = eng.evaluate(now=30.0)
    assert rows[0]["windows"]["60s"] == 0.0
    assert rows[0]["status"] == "ok"


def test_latency_slo_counts_good_from_snapped_bucket():
    fam = MetricFamily("lat_seconds", "histogram", "h")

    def snap(le, v):
        return Sample("lat_seconds_bucket", v, {"le": le})

    def fams(under, total):
        f = MetricFamily("lat_seconds", "histogram", "h")
        f.samples = [
            snap("1", under), snap("2.5", under), snap("+Inf", total),
            Sample("lat_seconds_count", total, {}),
            Sample("lat_seconds_sum", 0.0, {}),
        ]
        return {"lat_seconds": f}

    slo = SLO(
        name="lat", description="d", objective=0.9,
        total=Selector("lat_seconds"), threshold_s=2.0,
    )
    good, total, snapped = slo.good_total(fams(80, 100))
    assert (good, total) == (80.0, 100.0)
    assert snapped == 2.5  # 2.0 snapped up to the 2.5 bound
    eng = SLOEngine([slo], windows=(60.0,))
    eng.observe(fams(80, 100), now=0.0)
    eng.observe(fams(160, 200), now=30.0)  # 20% over-threshold
    rows = eng.evaluate(now=30.0)
    assert rows[0]["windows"]["60s"] == pytest.approx(2.0)
    assert rows[0]["snapped_bound_s"] == 2.5


def test_slo_families_exposition_shape():
    eng = SLOEngine([_ratio_slo()], windows=(60.0,))
    eng.observe(_counter_fams(10, 0), now=0.0)
    fams = {f.name: f for f in eng.families(now=0.0)}
    burn = fams["fishnet_slo_burn_rate"].samples
    assert burn[0].labels == {"slo": "t", "window": "60s"}
    assert fams["fishnet_slo_status"].samples[0].value == 0.0


def test_default_slos_reference_live_family_names():
    names = {s.name for s in default_slos()}
    assert {"move_latency", "analysis_ttfa", "api_success"} <= names
    for slo in default_slos():
        assert slo.total.family.startswith("fishnet_")


# ---------------------------------------------------------------------------
# FleetAggregator federation + staleness
# ---------------------------------------------------------------------------


def _proc_exporter(reqs_ok: int):
    registry = MetricsRegistry()
    counter = registry.counter(
        "fishnet_api_requests_total", "h", labelnames=("endpoint", "outcome")
    )
    for _ in range(reqs_ok):
        counter.inc(endpoint="acquire", outcome="ok")
    return MetricsExporter(port=0, registry=registry)


def test_aggregator_federates_with_proc_labels_and_meta():
    e0, e1 = _proc_exporter(3), _proc_exporter(5)
    agg = FleetAggregator(
        targets={"PROC0": e0.url, "PROC1": e1.url}
    )
    try:
        agg.poll_once()
        fams = agg.federated_families()
        samples = fams["fishnet_api_requests_total"].samples
        by_proc = {
            s.labels["proc"]: s.value for s in samples
            if s.labels.get("endpoint") == "acquire"
        }
        assert by_proc == {"PROC0": 3.0, "PROC1": 5.0}
        ups = {
            s.labels["proc"]: s.value
            for s in fams["fishnet_fleet_proc_up"].samples
        }
        assert ups == {"PROC0": 1.0, "PROC1": 1.0}
        # Build info federates per proc too (satellite 1 contract).
        info = fams["fishnet_build_info"].samples
        assert {s.labels["proc"] for s in info} == {"PROC0", "PROC1"}
        # SLO families ride the same exposition.
        assert "fishnet_slo_burn_rate" in fams
    finally:
        agg.close()
        e0.close()
        e1.close()


def test_aggregator_keeps_dead_proc_series_marked_stale():
    e0, e1 = _proc_exporter(3), _proc_exporter(5)
    agg = FleetAggregator(targets={"PROC0": e0.url, "PROC1": e1.url})
    try:
        agg.poll_once()
        e1.close()  # SIGKILL-shaped: the target stops answering
        agg.poll_once()  # must not raise
        fams = agg.federated_families()
        ups = {
            s.labels["proc"]: s.value
            for s in fams["fishnet_fleet_proc_up"].samples
        }
        assert ups == {"PROC0": 1.0, "PROC1": 0.0}
        # The dead proc's last-known series are STILL exported.
        by_proc = {
            s.labels["proc"]: s.value
            for s in fams["fishnet_api_requests_total"].samples
            if s.labels.get("endpoint") == "acquire"
        }
        assert by_proc["PROC1"] == 5.0
        errs = {
            s.labels["proc"]: s.value
            for s in fams["fishnet_fleet_scrape_errors_total"].samples
        }
        assert errs["PROC1"] >= 1.0
        doc = agg.fleet_doc()
        assert doc["procs"]["PROC1"]["up"] is False
        assert doc["procs"]["PROC1"]["last_error"]
    finally:
        agg.close()
        e0.close()


def test_aggregator_serves_fleet_routes():
    e0 = _proc_exporter(2)
    agg = FleetAggregator(targets={"PROC0": e0.url})
    srv = agg.serve(0)
    try:
        agg.poll_once()
        doc = json.loads(_get(srv.url + "/fleet"))
        assert doc["procs"]["PROC0"]["up"] is True
        slo_doc = json.loads(_get(srv.url + "/fleet/slo"))
        assert {row["slo"] for row in slo_doc["slo"]} == {
            s.name for s in default_slos()
        }
        trace = json.loads(_get(srv.url + "/fleet/trace"))
        validate_chrome_trace(trace)
        # The federated exposition includes the proc-labeled series.
        text = _get(srv.url + "/metrics").decode()
        assert 'proc="PROC0"' in text
        assert "fishnet_fleet_proc_up" in text
        assert "fishnet_slo_burn_rate" in text
    finally:
        agg.close()
        e0.close()


def test_port_dir_discovery_follows_rewrites(tmp_path):
    e0 = _proc_exporter(1)
    (tmp_path / "PROC0.port").write_text(f"{e0.port}\n")
    (tmp_path / "junk.port").write_text("not-a-port\n")
    resolve = port_dir_targets(str(tmp_path))
    assert resolve() == {"PROC0": f"http://127.0.0.1:{e0.port}"}
    agg = FleetAggregator(targets_fn=resolve)
    try:
        agg.poll_once()
        assert agg.fleet_doc()["procs"]["PROC0"]["up"] is True
        # Port file disappears (child died, file cleaned): stale, kept.
        (tmp_path / "PROC0.port").unlink()
        agg.poll_once()
        doc = agg.fleet_doc()
        assert doc["procs"]["PROC0"]["up"] is False
    finally:
        agg.close()
        e0.close()


def test_journal_recovers_spans_lost_to_sigkill(tmp_path):
    """The write-ahead journal closes the scrape race: a span recorded
    AFTER the aggregator's last scrape of a process that is then
    SIGKILLed must still reach the stitcher via the journal tail, and
    a span present in BOTH the scrape and the journal must not
    double-count."""
    from fishnet_tpu.telemetry.spans import SpanRecorder
    from fishnet_tpu.telemetry.tracing import batch_root

    journal = tmp_path / "PROC0.journal.jsonl"
    rec = SpanRecorder()
    rec.journal_to(str(journal))
    t0 = time.monotonic()
    rec.record("acquire", t0, trace=batch_root("doomed-unit"), batch="doomed-unit")
    # Step traces stay ring-only: never journaled.
    from fishnet_tpu.telemetry.tracing import new_trace

    rec.record("pack", t0, trace=new_trace())
    rec.journal_close()
    lines = journal.read_text().strip().splitlines()
    header = json.loads(lines[0])
    assert header["format"].startswith("fishnet-spans-journal/")
    assert header["pid"] == os.getpid()
    recs = [json.loads(ln) for ln in lines[1:]]
    assert [r["stage"] for r in recs] == ["acquire"]
    # Journal record is byte-identical in content to the /spans shape,
    # so the incarnation dedup collapses scrape+journal duplicates.
    scraped = [s for s in rec.spans() if s["stage"] == "acquire"]
    assert recs[0] == scraped[0]

    agg = FleetAggregator(targets={}, journal_dir=str(tmp_path))
    try:
        agg.poll_once()
        st = agg.stitched()
        acq = [s for s in st["spans"] if s["stage"] == "acquire"]
        assert len(acq) == 1
        assert acq[0]["proc"] == "PROC0"
        assert acq[0]["actor"] == f"PROC0@{os.getpid()}"
        doc = agg.fleet_doc()
        # Journal-only proc: known (archived), never scraped, not up.
        assert doc["procs"]["PROC0"]["up"] is False
    finally:
        agg.close()


# ---------------------------------------------------------------------------
# Churn + supervised fleet (slow; `make fleet-obs-smoke`)
# ---------------------------------------------------------------------------

_CHILD = """
import sys, time
from fishnet_tpu import telemetry
exporter = telemetry.start_exporter(0)
with open(sys.argv[1] + ".tmp", "w") as fp:
    fp.write(str(exporter.port))
import os
os.replace(sys.argv[1] + ".tmp", sys.argv[1])
time.sleep(120)
"""


@pytest.mark.slow
def test_scrape_loop_survives_sigkill_restart_churn(tmp_path):
    """Satellite 3 regression: the aggregator polls in a tight loop
    while a real exporter process is SIGKILLed and restarted 10x. The
    aggregator must never crash, must flip up/stale each death, and
    must key a fresh incarnation per pid."""
    port_file = tmp_path / "CHURN.port"
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        f"{_REPO_ROOT}{os.pathsep}{env['PYTHONPATH']}"
        if env.get("PYTHONPATH") else str(_REPO_ROOT)
    )
    env.setdefault("JAX_PLATFORMS", "cpu")

    def spawn():
        return subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(port_file)],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    agg = FleetAggregator(
        targets_fn=port_dir_targets(str(tmp_path)), poll_interval=0.05
    ).start()
    pids = []
    try:
        for _ in range(10):
            child = spawn()
            pids.append(child.pid)
            deadline = time.time() + 20
            while not port_file.exists() and time.time() < deadline:
                time.sleep(0.05)
            assert port_file.exists(), "child never wrote its port file"
            time.sleep(0.3)  # let a few scrapes land
            os.kill(child.pid, signal.SIGKILL)
            child.wait(timeout=10)
            port_file.unlink(missing_ok=True)
            time.sleep(0.15)
        # Aggregator thread is alive and the state is coherent.
        doc = agg.fleet_doc()
        st = doc["procs"]["CHURN"]
        assert st["up"] is False
        assert st["scrapes"] >= 5
        # Each restart was a distinct incarnation (distinct pid).
        assert len(st["pids"]) >= 5
        assert set(st["pids"]) <= set(pids)
    finally:
        agg.close()


@pytest.mark.slow
@pytest.mark.anyio
async def test_supervised_fleet_observed_through_a_kill(tmp_path):
    """The tentpole end-to-end: 3 supervised client processes with one
    SIGKILL mid-run; the fleet aggregator (discovering via the
    supervisor's port files) must federate all 3 procs, mark the
    killed one stale while it is down, archive enough spans to stitch,
    evaluate SLOs from federated series, and export a valid fleet
    Perfetto trace."""
    from fake_server import FakeLichess, FakeServer

    from fishnet_tpu.cluster.supervisor import FleetSupervisor, ProcSpec

    lichess = FakeLichess(require_key=False)
    lichess.auto_refill = 6
    lichess.refill_move_every = 4
    lichess.reassign_after = 1.5
    specs = [
        ProcSpec(name="PROC0", fault_spec="seed=3;proc.kill:nth=10:crash"),
        ProcSpec(name="PROC1"),
        ProcSpec(name="PROC2"),
    ]
    stale_seen = False
    async with FakeServer(lichess) as server:
        supervisor = FleetSupervisor(
            server.endpoint,
            specs,
            workdir=str(tmp_path),
            tick_seconds=0.2,
            drain_deadline=4.0,
        )
        await supervisor.start()
        agg = FleetAggregator(
            targets_fn=port_dir_targets(str(tmp_path)),
            poll_interval=0.25,
            journal_dir=str(tmp_path),
        ).start()
        try:
            import asyncio

            t0 = time.monotonic()
            while time.monotonic() - t0 < 14.0:
                await asyncio.sleep(0.25)
                kinds = [k for _, _, k in supervisor.events]
                if "kill" in kinds and not stale_seen:
                    # Probe the live aggregator state during the stale
                    # window (before the supervisor respawns).
                    doc = agg.fleet_doc()
                    downs = [
                        n for n, st in doc["procs"].items() if not st["up"]
                    ]
                    if "PROC0" in downs:
                        fams = agg.federated_families()
                        procs_in_series = {
                            s.labels.get("proc")
                            for s in fams[
                                "fishnet_api_requests_total"
                            ].samples
                        }
                        assert "PROC0" in procs_in_series
                        stale_seen = True
                if stale_seen and "restart" in kinds and (
                    time.monotonic() - t0 > 8.0
                ):
                    break
            agg.poll_once()
            doc = agg.fleet_doc()
        finally:
            agg.close()
            await supervisor.kill_all()

    assert stale_seen, "never observed PROC0 stale during its kill window"
    assert set(doc["procs"]) == {"PROC0", "PROC1", "PROC2"}
    assert all(st["scrapes"] >= 1 for st in doc["procs"].values())
    # The killed proc restarted under a fresh pid: >= 2 incarnations.
    assert len(doc["procs"]["PROC0"]["pids"]) >= 2
    assert doc["stitch"]["traces"] >= 1
    assert doc["slo"], "SLO evaluation missing"
    assert doc["critical_path"]["traces"] >= 1


# ---------------------------------------------------------------------------
# Shared-plane MCTS families federate (doc/search.md)
# ---------------------------------------------------------------------------


def test_mcts_tree_families_federate_with_proc_labels():
    """The MCTS tree-side families ride the standard exposition: a proc
    that ran an MctsPool federates them through the FleetAggregator
    with proc labels intact, next to every other family."""
    import numpy as np

    from fishnet_tpu.models.az_encoding import POLICY_SIZE
    from fishnet_tpu.search.mcts import MctsConfig, MctsPool

    class _InstantEval:
        def warmup(self, cap):
            pass

        def evaluate(self, planes_u8, n, keys=None):
            return (
                np.zeros((n, POLICY_SIZE), np.float32),
                np.zeros(n, np.float32),
            )

        def close(self):
            pass

    start = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
    pool = MctsPool(
        {}, MctsConfig(batch_capacity=32), evaluator=_InstantEval()
    )
    sids = [pool.submit(start, [], 20) for _ in range(2)]
    while pool.active() > 0:
        pool.step()
    for sid in sids:
        pool.harvest(sid)
    pool.close()

    exporter = MetricsExporter(port=0, registry=reg.REGISTRY)
    agg = FleetAggregator(targets={"PROC0": exporter.url})
    try:
        agg.poll_once()
        fams = agg.federated_families()
        for name in (
            "fishnet_mcts_visits_total",
            "fishnet_mcts_collisions_total",
            "fishnet_mcts_subtree_reuse_total",
            "fishnet_mcts_batch_fill_ratio",
            "fishnet_mcts_trees_active",
        ):
            assert name in fams, name
            assert fams[name].samples
            assert all(
                s.labels.get("proc") == "PROC0" for s in fams[name].samples
            )
        visits = sum(
            s.value for s in fams["fishnet_mcts_visits_total"].samples
        )
        assert visits >= 40
    finally:
        agg.close()
        exporter.close()


# ---------------------------------------------------------------------------
# Journal robustness + the --profiles console panel (ISSUE 15)
# ---------------------------------------------------------------------------


def test_journal_tolerates_torn_partial_tail(tmp_path):
    """A crash mid-write leaves a newline-less torn tail: the reader
    must consume only complete lines, leave the cursor before the torn
    one, and — once the line is completed — deliver that span exactly
    once on the next poll."""
    from fishnet_tpu.telemetry.spans import SpanRecorder
    from fishnet_tpu.telemetry.tracing import batch_root

    journal = tmp_path / "PROC0.journal.jsonl"
    rec = SpanRecorder()
    rec.journal_to(str(journal))
    rec.record(
        "acquire", time.monotonic(), trace=batch_root("unit-a"),
        batch="unit-a",
    )
    rec.journal_close()
    full = journal.read_bytes()
    lines = full.splitlines(keepends=True)
    torn = lines[-1]
    journal.write_bytes(b"".join(lines[:-1]) + torn[: len(torn) // 2])

    agg = FleetAggregator(targets={}, journal_dir=str(tmp_path))
    try:
        agg.poll_once()  # must not raise, must not consume the torn tail
        spans = agg.stitched()["spans"]
        assert [s for s in spans if s["stage"] == "acquire"] == []
        # The writer completes the line: the span arrives, exactly once.
        journal.write_bytes(full)
        agg.poll_once()
        spans = agg.stitched()["spans"]
        assert len([s for s in spans if s["stage"] == "acquire"]) == 1
    finally:
        agg.close()


def test_journal_truncation_between_polls_resets_cursor(tmp_path):
    """Rotation/truncation regression: when the journal shrinks below
    the aggregator's cursor (logrotate, crash-dump rewrite), the reader
    must restart from offset 0 instead of seeking past EOF and reading
    nothing forever."""
    from fishnet_tpu.telemetry.spans import SpanRecorder
    from fishnet_tpu.telemetry.tracing import batch_root

    journal = tmp_path / "PROC0.journal.jsonl"
    rec = SpanRecorder()
    rec.journal_to(str(journal))
    for i in range(3):
        rec.record(
            "acquire", time.monotonic(), trace=batch_root(f"unit-{i}"),
            batch=f"unit-{i}",
        )
    rec.journal_close()

    agg = FleetAggregator(targets={}, journal_dir=str(tmp_path))
    try:
        agg.poll_once()
        spans = agg.stitched()["spans"]
        assert len([s for s in spans if s["stage"] == "acquire"]) == 3

        # The journal restarts smaller than the old cursor.
        journal.unlink()
        rec2 = SpanRecorder()
        rec2.journal_to(str(journal))
        rec2.record(
            "acquire", time.monotonic(), trace=batch_root("unit-x"),
            batch="unit-x",
        )
        rec2.journal_close()
        assert journal.stat().st_size < agg._journal_offsets[str(journal)]

        agg.poll_once()
        spans = agg.stitched()["spans"]
        batches = {
            s.get("batch") for s in spans if s["stage"] == "acquire"
        }
        assert "unit-x" in batches, batches
    finally:
        agg.close()


def test_poll_collects_profiles_and_console_renders_hot_stacks():
    """--profiles: each poll also scrapes /profile per up-target; the
    console appends the top-5 hottest-stacks panel, and a 503 (plane
    off) renders as "profiling off", never as a scrape error."""
    from fishnet_tpu.telemetry import profiler
    from fishnet_tpu.telemetry.fleet import render_console

    e0 = _proc_exporter(1)
    agg = FleetAggregator(targets={"PROC0": e0.url}, profiles=True)
    try:
        agg.poll_once()
        assert agg.fleet_doc()["procs"]["PROC0"]["up"] is True
        frame = render_console(agg, profiles=True)
        assert "HOT STACKS" in frame
        assert "profiling off" in frame

        prof = profiler.start(hz=200)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and prof.samples < 5:
            time.sleep(0.02)
        agg.poll_once()
        frame = render_console(agg, profiles=True)
        assert "samples @" in frame
        assert "profiling off" not in frame
        # Without the flag the panel never renders.
        assert "HOT STACKS" not in render_console(agg)
    finally:
        profiler.stop()
        agg.close()
        e0.close()
