"""Causal batch tracing (doc/observability.md "Causal tracing"): trace
contexts and ids, the fishnet-spans/2 record fields and dump locations,
trace-context propagation across the coalescer's pack/decode worker
handoffs (fused multi-owner fan-in included) — direct on the pipeline
and end-to-end through gated smokes, sync (FISHNET_NO_ASYNC=1) and
async — plus the critical-path analyzer (span-tree reconstruction,
orphan detection, wall-time attribution summing to the window), the
Chrome/Perfetto exporter with cross-thread flow arrows, and the
critical-path report's key contract. `make trace-smoke` runs this file."""

import json
import os
import threading
import time

import numpy as np
import pytest

from fishnet_tpu import telemetry
from fishnet_tpu.telemetry import critical_path as cp
from fishnet_tpu.telemetry import tracing
from fishnet_tpu.telemetry.spans import FORMAT, RECORDER, SpanRecorder
from fishnet_tpu.telemetry.trace_export import (
    chrome_trace,
    main as export_main,
    read_spans,
    validate_chrome_trace,
)
from fishnet_tpu.search.service import (
    _AsyncDispatchPipeline,
    _CoalesceTicket,
    _FusedValues,
)
from tests.test_async_dispatch import _SMOKE_FENS, _SlowValues, _smoke_run


@pytest.fixture
def tel_enabled():
    telemetry.enable()
    try:
        yield
    finally:
        telemetry.disable()


def _spans_since(t0):
    # spans() rounds t to 6 decimals — allow the round-down.
    return [s for s in RECORDER.spans() if s["t"] >= t0 - 1e-4]


# -- trace contexts and ids ---------------------------------------------------


def test_trace_context_chaining():
    root = tracing.new_trace()
    assert root.span_id == root.trace_id and root.parent_id is None
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id
    grandchild = child.child()
    assert grandchild.parent_id == child.span_id
    assert grandchild.trace_id == root.trace_id


def test_batch_trace_ids_deterministic():
    # Any stage knowing the batch id derives the same tree — no
    # registry: root span_id == trace_id, children parent to it.
    tid = tracing.trace_id_for_batch("wk0001")
    assert tid == tracing.trace_id_for_batch("wk0001")
    assert tid != tracing.trace_id_for_batch("wk0002")
    root = tracing.batch_root("wk0001")
    assert root.trace_id == root.span_id == tid and root.parent_id is None
    c1, c2 = tracing.batch_child("wk0001"), tracing.batch_child("wk0001")
    assert c1.trace_id == c2.trace_id == tid
    assert c1.parent_id == c2.parent_id == tid
    assert c1.span_id != c2.span_id


def test_span_ids_unique_across_threads():
    ids, lock = set(), threading.Lock()

    def mint():
        mine = {tracing.next_span_id() for _ in range(200)}
        with lock:
            ids.update(mine)

    threads = [threading.Thread(target=mint) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ids) == 4 * 200


def test_links_for():
    ctxs = [tracing.new_trace() for _ in range(3)]
    links = tracing.links_for(ctxs)
    assert links == [(c.trace_id, c.span_id) for c in ctxs]


# -- fishnet-spans/2: record fields + dump locations --------------------------


def test_record_carries_trace_fields(tel_enabled):
    t0 = time.monotonic()
    root = tracing.new_trace()
    child = root.child()
    RECORDER.record("pack", t0, trace=root, group=0)
    RECORDER.record(
        "device_step", t0, trace=child,
        links=[("aaaa", "bbbb")], group=0,
    )
    spans = _spans_since(t0)
    by_stage = {s["stage"]: s for s in spans}
    pk = by_stage["pack"]
    assert pk["trace_id"] == pk["span_id"] == root.trace_id
    assert "parent_id" not in pk  # root: field omitted, not null
    ds = by_stage["device_step"]
    assert ds["trace_id"] == root.trace_id
    assert ds["parent_id"] == root.span_id
    assert ds["links"] == [["aaaa", "bbbb"]]


def test_dump_header_is_v2_and_spans_dir(tmp_path, monkeypatch):
    rec = SpanRecorder(capacity=8)
    # FISHNET_SPANS_DIR steers the per-pid dump file; the dir need not
    # pre-exist (dump() creates it).
    monkeypatch.delenv("FISHNET_SPANS_FILE", raising=False)
    monkeypatch.setenv("FISHNET_SPANS_DIR", str(tmp_path / "spans"))
    path = rec.default_path()
    assert path == str(
        tmp_path / "spans" / f"fishnet-spans-{os.getpid()}.jsonl"
    )
    rec.record("pack", time.monotonic(), trace=tracing.new_trace(), n=1)
    written = rec.dump(reason="test")
    assert written == path and os.path.exists(path)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["format"] == FORMAT == "fishnet-spans/2"
    assert lines[1]["trace_id"] == lines[1]["span_id"]
    # FISHNET_SPANS_FILE wins outright.
    monkeypatch.setenv("FISHNET_SPANS_FILE", str(tmp_path / "exact.jsonl"))
    assert rec.default_path() == str(tmp_path / "exact.jsonl")


# -- span-tree reconstruction + critical-path attribution ---------------------


def _mk(stage, t, dur_ms, trace_id=None, span_id=None, parent_id=None,
        thread="t", **extra):
    s = {"stage": stage, "t": t, "dur_ms": dur_ms, "thread": thread}
    if trace_id:
        s["trace_id"] = trace_id
        s["span_id"] = span_id
        if parent_id:
            s["parent_id"] = parent_id
    s.update(extra)
    return s


def _synthetic_step_trace(base=100.0, tid="T1"):
    """A realistic async step trace: pack -> device_step ->
    dispatch_issue -> dispatch_wait -> wire_decode -> postprocess."""
    return [
        _mk("pack", base, 10.0, tid, tid),
        _mk("device_step", base + 0.010, 2.0, tid, "d", tid),
        _mk("dispatch_issue", base + 0.013, 2.0, tid, "i", "d",
            thread="dispatch-pack"),
        _mk("dispatch_wait", base + 0.015, 15.0, tid, "w", "i",
            thread="dispatch-decode"),
        _mk("wire_decode", base + 0.016, 15.0, tid, "wd", "w"),
        _mk("postprocess", base + 0.031, 4.0, tid, "pp", "wd"),
    ]


def test_critical_path_chain_follows_parents():
    spans = _synthetic_step_trace()
    chain = cp.critical_path(spans)
    assert [s["stage"] for s in chain] == [
        "pack", "device_step", "dispatch_issue", "dispatch_wait",
        "wire_decode", "postprocess",
    ]


def test_critical_path_group_traces_reattach_fan_in_links():
    # A fused dispatch shared by two step traces: parented under T1,
    # linked to T2 — group_traces re-attaches a copy under T2's link.
    spans = [
        _mk("pack", 0.0, 1.0, "T1", "T1"),
        _mk("pack", 0.0, 1.0, "T2", "T2"),
        _mk("device_step", 0.001, 1.0, "T1", "d1", "T1"),
        _mk("device_step", 0.001, 1.0, "T2", "d2", "T2"),
        _mk("dispatch_issue", 0.002, 1.0, "T1", "i", "d1",
            links=[["T2", "d2"]]),
    ]
    traces = cp.group_traces(spans)
    assert set(traces) == {"T1", "T2"}
    t2_issue = [s for s in traces["T2"] if s["stage"] == "dispatch_issue"]
    assert len(t2_issue) == 1
    assert t2_issue[0]["parent_id"] == "d2"
    assert "links" not in t2_issue[0]
    assert cp.orphan_spans(spans) == []


def test_critical_path_detects_orphans():
    spans = [
        _mk("pack", 0.0, 1.0, "T1", "T1"),
        _mk("device_step", 0.001, 1.0, "T1", "d", "missing-parent"),
    ]
    orphans = cp.orphan_spans(spans)
    assert len(orphans) == 1 and orphans[0]["stage"] == "device_step"


def test_fleet_joiner_adopts_orphans_from_killed_process():
    """The fleet stitcher (telemetry/stitch.py) feeds the SAME orphan
    detector: after joining a killed-and-reassigned unit whose dead
    actor lost a parent span to a missed scrape, the stitched output
    must be orphan-free — lost parents are adopted under the trace
    root and counted, never dropped."""
    from fishnet_tpu.telemetry.stitch import stitch
    from fishnet_tpu.telemetry.tracing import trace_id_for_batch

    tid = trace_id_for_batch("orphan-unit")
    # Dead actor: the batch root was never scraped (SIGKILL between
    # scrapes), leaving its child dangling.
    dead = [
        _mk("queue_wait", 1.0, 100.0, tid, "1.2", "lost-parent"),
    ]
    survivor = [
        _mk("acquire", 2.0, 50.0, tid, tid),
        _mk("submit", 2.2, 30.0, tid, "2.1", tid),
    ]
    report = stitch([
        {"proc": "P0", "actor": "P0@1", "spans": dead, "epoch_offset": 0.0},
        {"proc": "P1", "actor": "P1@2", "spans": survivor,
         "epoch_offset": 0.0},
    ])
    assert report["orphans_adopted"] >= 1
    assert report["reassignments"] == 1
    for trace in cp.group_traces(report["spans"]).values():
        assert cp.orphan_spans(trace) == []


def test_critical_path_attribution_sums_to_wall():
    attr = cp.attribute_trace(_synthetic_step_trace(), fixed_transport_ms=5.0)
    wall = attr["wall_ms"]
    assert wall == pytest.approx(35.0, abs=1e-6)
    total = sum(attr[c] for c in cp.COMPONENTS)
    assert total == pytest.approx(wall, rel=1e-9)
    # pack = pack + device_step; transport = issue span + 5 ms fixed
    # slice of the in-flight interval; the rest of [issue end, wait
    # end] is device compute; wire_decode's tail past the in-flight
    # interval is decode_wait; the device_step->issue gap is queueing.
    assert attr["pack"] == pytest.approx(12.0, abs=1e-6)
    assert attr["transport"] == pytest.approx(7.0, abs=1e-6)
    assert attr["device_compute"] == pytest.approx(10.0, abs=1e-6)
    assert attr["decode_wait"] == pytest.approx(1.0, abs=1e-6)
    assert attr["submit"] == pytest.approx(4.0, abs=1e-6)
    assert attr["queue_wait"] == pytest.approx(1.0, abs=1e-6)
    assert attr["other"] == pytest.approx(0.0, abs=1e-6)
    assert attr["coverage"] == pytest.approx(1.0, abs=1e-6)


def test_critical_path_report_aggregates_step_traces():
    spans = (
        _synthetic_step_trace(base=100.0, tid="T1")
        + _synthetic_step_trace(base=200.0, tid="T2")
    )
    rep = cp.report(spans, fixed_transport_ms=5.0, skip_warmup=False)
    assert rep["traces"] == 2
    assert rep["wall_ms"] == pytest.approx(35.0, abs=1e-3)
    assert rep["pack_ms"] == pytest.approx(12.0, abs=1e-3)
    assert rep["transport_ms"] == pytest.approx(7.0, abs=1e-3)
    assert rep["compute_ms"] == pytest.approx(10.0, abs=1e-3)
    assert rep["coverage"] >= 0.99
    # Empty input: zeroed shape, never a crash.
    empty = cp.report([])
    assert empty["traces"] == 0 and empty["wall_ms"] == 0.0


@pytest.mark.parametrize(
    "recorded",
    [lambda: SpanRecorder().spans(), _synthetic_step_trace],
    ids=["empty_recorder", "one_trace"],
)
def test_critical_path_report_key_contract(recorded):
    """One key a component (``device_compute`` reports as
    ``compute_ms``) plus the three totals, whatever was recorded, so
    a reader may index the dict without ``.get``."""
    spans = recorded()
    rep = cp.report(spans, fixed_transport_ms=5.0, skip_warmup=False)
    per_component = {
        "compute_ms" if c == "device_compute" else f"{c}_ms"
        for c in cp.COMPONENTS
    }
    assert set(rep) == per_component | {"wall_ms", "coverage", "traces"}
    assert rep["traces"] == (1 if spans else 0)


def _dispatch(seq, issued, done, wait_from=None):
    """One dispatch's span pair: issued at ``issued``, materialized at
    ``done`` (the wait span starts at ``wait_from``, default half-way)."""
    wait_from = (issued + done) / 2 if wait_from is None else wait_from
    return [
        {"stage": "dispatch_issue", "t": issued, "dur_ms": 1.0, "seq": seq},
        {"stage": "dispatch_wait", "t": wait_from,
         "dur_ms": (done - wait_from) * 1e3, "seq": seq},
    ]


@pytest.mark.parametrize(
    "spans, paired, busy_s, dual_s, ratio",
    [
        # Two dispatches one after the other: never two in flight.
        (_dispatch(0, 10.0, 10.1) + _dispatch(1, 10.2, 10.3),
         2, 0.2, 0.0, 0.0),
        # Two coincident dispatches: two in flight the whole time.
        (_dispatch(0, 10.0, 10.4) + _dispatch(1, 10.0, 10.4),
         2, 0.4, 0.4, 1.0),
        # The second issued half-way through the first.
        (_dispatch(0, 10.0, 10.2) + _dispatch(1, 10.1, 10.3),
         2, 0.3, 0.1, 0.3333),
        # An issue whose wait was never recorded (ring overwrite, a
        # crash between the two) is not a dispatch interval.
        (_dispatch(0, 10.0, 10.2) + _dispatch(1, 10.1, 10.3)[:1],
         1, 0.2, 0.0, 0.0),
        # A wait that ends before its issue starts (a reused seq after
        # a service rebuild) is dropped, not swept as negative time.
        (_dispatch(0, 10.0, 10.2) + _dispatch(1, 10.1, 9.9, wait_from=9.8),
         1, 0.2, 0.0, 0.0),
    ],
    ids=["disjoint", "coincident", "half", "unpaired_issue", "wait_before_issue"],
)
def test_dispatch_overlap_from_spans(spans, paired, busy_s, dual_s, ratio):
    other = [{"stage": "pack", "t": 10.0, "dur_ms": 500.0}]  # ignored
    assert cp.dispatch_overlap(spans + other) == {
        "dispatches_paired": paired,
        "busy_s": busy_s,
        "dual_s": dual_s,
        "overlap_ratio": ratio,
    }


def test_critical_path_batch_report():
    tid = tracing.trace_id_for_batch("wkA")
    spans = [
        _mk("acquire", 0.0, 50.0, tid, tid),
        _mk("schedule", 0.051, 2.0, tid, "s", tid),
        _mk("queue_wait", 0.053, 200.0, tid, "q", tid),
        _mk("submit", 0.300, 40.0, tid, "sub", tid),
    ]
    rep = cp.batch_report(spans)
    assert rep["batches"] == 1
    assert rep["queue_wait_ms"] == pytest.approx(200.0, abs=1e-6)
    assert rep["submit_ms"] == pytest.approx(40.0, abs=1e-6)
    assert rep["schedule_ms"] == pytest.approx(52.0, abs=1e-6)
    assert rep["wall_ms"] == pytest.approx(340.0, abs=1e-3)


# -- Chrome/Perfetto export ---------------------------------------------------


def test_chrome_trace_export_structure_and_flow_arrows():
    trace = chrome_trace(_synthetic_step_trace())
    validate_chrome_trace(trace)
    events = trace["traceEvents"]
    x = [e for e in events if e["ph"] == "X"]
    m = [e for e in events if e["ph"] == "M"]
    assert len(x) == 6
    # One track per recording thread.
    assert {e["args"]["name"] for e in m} == {
        "t", "dispatch-pack", "dispatch-decode",
    }
    # Cross-thread causal edges render as s/f flow pairs: driver ->
    # pack worker, pack -> decode worker, decode -> driver.
    starts = [e for e in events if e["ph"] == "s"]
    finishes = [e for e in events if e["ph"] == "f"]
    assert len(starts) == len(finishes) == 3
    assert all(e["bp"] == "e" for e in finishes)
    # Same-thread parent links (pack -> device_step) emit NO arrow.
    ids = {e["id"] for e in starts}
    assert len(ids) == 3


def test_chrome_trace_export_validation_rejects_malformed():
    trace = chrome_trace(_synthetic_step_trace())
    bad = json.loads(json.dumps(trace))
    bad["traceEvents"][1].pop("tid", None)
    with pytest.raises(ValueError):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError):
        validate_chrome_trace({"nope": []})
    # A dangling flow start must fail, not render as a broken arrow.
    dangling = json.loads(json.dumps(trace))
    dangling["traceEvents"] = [
        e for e in dangling["traceEvents"] if e["ph"] != "f"
    ]
    with pytest.raises(ValueError):
        validate_chrome_trace(dangling)


def test_trace_export_cli_roundtrip(tmp_path, capsys):
    # Two dumps of the same ring (overlapping contents, one header
    # each): read_spans must skip headers and de-duplicate.
    spans = _synthetic_step_trace()
    dump = tmp_path / "fishnet-spans-1.jsonl"
    with open(dump, "w") as fp:
        for seq in (1, 2):
            fp.write(json.dumps({
                "format": FORMAT, "seq": seq, "reason": "test",
                "pid": 1, "dumped_at": 0.0, "monotonic_to_epoch": 0.0,
                "spans": len(spans),
            }) + "\n")
            for s in spans:
                fp.write(json.dumps(s) + "\n")
    assert len(read_spans([str(dump)])) == len(spans)
    out = tmp_path / "trace.json"
    assert export_main([str(dump), "-o", str(out)]) == 0
    trace = json.loads(out.read_text())
    validate_chrome_trace(trace)
    assert sum(1 for e in trace["traceEvents"] if e["ph"] == "X") == len(spans)


# -- propagation across the pack/decode worker handoff (direct) ---------------


class _StubCoalescer:
    def _execute(self, tickets, defer_cost=False):
        for tk in tickets:
            tk.done.set()


class _StubSvc:
    def __init__(self):
        self._coalescer = _StubCoalescer()


def test_handoff_propagation_fused_multi_owner(tel_enabled):
    """The tentpole invariant, pinned directly on the pipeline: one
    fused dispatch owned by TWO step traces. dispatch_issue parents
    under the FIRST owner's device_step context and links the second;
    dispatch_wait (decode worker, a second thread handoff) chains under
    dispatch_issue in the same trace, links preserved."""
    d1 = tracing.new_trace().child()  # two owners' device_step contexts
    d2 = tracing.new_trace().child()
    t0 = time.monotonic()
    pipe = _AsyncDispatchPipeline(_StubSvc())
    try:
        tks = [
            _CoalesceTicket(0, 1, 4, trace=d1),
            _CoalesceTicket(1, 1, 4, trace=d2),
        ]
        tks[0].values = _FusedValues(np.zeros(8, np.int32))
        assert pipe.submit(tks)
        for tk in tks:
            assert tk.done.wait(5) and tk.error is None
        deadline = time.monotonic() + 5
        while (
            "dispatch_wait" not in {s["stage"] for s in _spans_since(t0)}
            and time.monotonic() < deadline
        ):
            time.sleep(0.005)
    finally:
        pipe.close()
    by_stage = {s["stage"]: s for s in _spans_since(t0)}
    issue, wait = by_stage["dispatch_issue"], by_stage["dispatch_wait"]
    assert issue["trace_id"] == d1.trace_id
    assert issue["parent_id"] == d1.span_id
    assert issue["links"] == [[d2.trace_id, d2.span_id]]
    assert issue["thread"] == "dispatch-pack"
    assert wait["trace_id"] == d1.trace_id  # identical across the handoff
    assert wait["parent_id"] == issue["span_id"]
    assert wait["links"] == issue["links"]
    assert wait["thread"] == "dispatch-decode"
    # Reconstructed: both owners' traces see the shared spans, orphan-free.
    spans = [
        s for s in _spans_since(t0)
        if s.get("trace_id") in (d1.trace_id, d2.trace_id)
    ]
    traces = cp.group_traces(spans)
    assert {s["stage"] for s in traces[d2.trace_id]} >= {
        "dispatch_issue", "dispatch_wait",
    }


def test_decode_queue_depth_gauge_direct():
    pipe = _AsyncDispatchPipeline(_StubSvc())
    try:
        assert pipe.decode_queue_depth() == 0
    finally:
        pipe.close()
    from fishnet_tpu.search.service import _COUNTER_METRICS

    name, kind, _ = _COUNTER_METRICS["decode_queue"]
    assert name == "fishnet_decode_queue_depth" and kind == "gauge"


# -- end-to-end gated smokes --------------------------------------------------


def _slow_mutate(svc):
    # Transport-like materialization latencies (test_async_dispatch's
    # overlap idiom) so in-flight intervals are visible in the trees.
    orig_seg = svc._dispatch_segmented
    orig_solo = svc._dispatch_eval

    def slow_segmented(tickets):
        orig_seg(tickets)
        fv = tickets[0].values
        fv._arr = _SlowValues(fv._arr, 0.02)

    def slow_solo(group, n, rows):
        values, acct = orig_solo(group, n, rows)
        return _SlowValues(values, 0.02), acct

    svc._dispatch_segmented = slow_segmented
    svc._dispatch_eval = slow_solo


def _step_traces(spans):
    return {
        tid: sp for tid, sp in cp.group_traces(spans).items()
        if any(s["stage"] == "pack" for s in sp)
    }


def test_trace_smoke_async(monkeypatch, tel_enabled):
    """Acceptance smoke, async path: every eval microbatch yields a
    complete span tree (zero orphans) spanning the driver -> pack ->
    decode thread handoffs, the Chrome export validates with flow
    arrows, and critical-path attribution covers >= 95% of steady-state
    per-batch wall time."""
    from fishnet_tpu.nnue.weights import NnueWeights

    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "2")
    t0 = time.monotonic()
    _, _, meta = _smoke_run(
        NnueWeights.random(seed=7), fens=_SMOKE_FENS[:4], nodes=150,
        mutate=_slow_mutate,
    )
    assert meta["async"]
    spans = _spans_since(t0)
    stages = {s["stage"] for s in spans}
    assert stages >= {
        "pack", "device_step", "dispatch_issue", "dispatch_wait",
        "wire_decode", "postprocess",
    }
    traced = [s for s in spans if "trace_id" in s]
    assert cp.orphan_spans(traced) == [], "orphan spans in a gated run"
    step = _step_traces(traced)
    assert len(step) > 3
    for tid, sp in step.items():
        roots = [s for s in sp if s["stage"] == "pack"]
        assert len(roots) == 1 and roots[0]["span_id"] == tid
        assert {s["stage"] for s in sp} >= {
            "pack", "device_step", "wire_decode", "postprocess",
        }
    # The async handoff spans land in >= 3 distinct threads per fused
    # trace: driver, dispatch-pack, dispatch-decode.
    threads = {
        s["thread"] for sp in step.values() for s in sp
        if s["stage"] in ("device_step", "dispatch_issue", "dispatch_wait")
    }
    assert {"dispatch-pack", "dispatch-decode"} <= threads
    # Critical-path attribution: >= 95% of steady-state wall attributed.
    rep = cp.report(traced)
    assert rep["traces"] > 0
    assert rep["coverage"] >= 0.95, rep
    total = sum(
        rep[k] for k in (
            "queue_wait_ms", "pack_ms", "transport_ms", "compute_ms",
            "decode_wait_ms", "submit_ms", "other_ms",
        )
    )
    assert total == pytest.approx(rep["wall_ms"], rel=0.05)
    # Perfetto export of the same spans: valid, with handoff arrows.
    trace = chrome_trace(spans)
    validate_chrome_trace(trace)
    assert any(e["ph"] == "s" for e in trace["traceEvents"])


def test_trace_smoke_sync(monkeypatch, tel_enabled):
    """FISHNET_NO_ASYNC=1: the same complete-tree and coverage
    guarantees hold on the inline synchronous flush (no
    dispatch_issue/dispatch_wait spans, no worker threads)."""
    from fishnet_tpu.nnue.weights import NnueWeights

    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "2")
    monkeypatch.setenv("FISHNET_NO_ASYNC", "1")
    t0 = time.monotonic()
    _, _, meta = _smoke_run(
        NnueWeights.random(seed=7), fens=_SMOKE_FENS[:4], nodes=150,
    )
    assert not meta["async"]
    traced = [s for s in _spans_since(t0) if "trace_id" in s]
    assert cp.orphan_spans(traced) == []
    step = _step_traces(traced)
    assert len(step) > 3
    for tid, sp in step.items():
        assert {s["stage"] for s in sp} >= {
            "pack", "device_step", "wire_decode", "postprocess",
        }
    rep = cp.report(traced)
    assert rep["traces"] > 0 and rep["coverage"] >= 0.95, rep


def test_trace_smoke_decode_queue_counter(monkeypatch):
    """The output-side backlog gauge rides counters() on both paths."""
    from fishnet_tpu.nnue.weights import NnueWeights

    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "2")
    _, counters, meta = _smoke_run(
        NnueWeights.random(seed=3), fens=_SMOKE_FENS[:2], nodes=100,
    )
    assert meta["async"] and counters["decode_queue"] >= 0
    monkeypatch.setenv("FISHNET_NO_ASYNC", "1")
    _, counters, _ = _smoke_run(
        NnueWeights.random(seed=3), fens=_SMOKE_FENS[:2], nodes=100,
    )
    assert counters["decode_queue"] == 0


# -- critical-path report over the recorder ----------------------------------


def test_critical_path_report_over_recorder(tel_enabled):
    rep = cp.report(RECORDER.spans(), fixed_transport_ms=5.0)
    assert set(rep) >= {"wall_ms", "coverage", "traces", "compute_ms"}
