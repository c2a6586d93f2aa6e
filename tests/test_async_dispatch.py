"""Double-buffered async dispatch (the PR 6 tentpole): sync-vs-async
bit-identical analyses across the psqt_path rungs, ping-pong donation
correctness (never more than DEPTH dispatches in flight, staging slots
never reused while unmaterialized), failure semantics under async
(``service.device_step`` faults still degrade the ladder and reach the
owning driver), deterministic wire-diet planner units (cross-segment
eval-dedup + anchor placement), and an overlap smoke proving
transport/compute overlap actually happens (overlap_ratio > 0, the
dispatch_issue/dispatch_wait span families recorded)."""

import asyncio
import os
import threading
import time

import numpy as np
import pytest

from fishnet_tpu.chess.core import NativeCoreError
from fishnet_tpu.nnue import spec
from fishnet_tpu.nnue.weights import NnueWeights
from fishnet_tpu.ops.ft_gather import plan_segment_dedup
from fishnet_tpu.resilience import accounting, faults
from fishnet_tpu.resilience.supervisor import ServiceSupervisor
from fishnet_tpu.search.service import (
    SearchService,
    _AsyncDispatchPipeline,
    _CoalesceTicket,
    _FusedValues,
)
from fishnet_tpu.utils.logger import Logger


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    faults.clear()
    accounting.clear()


# -- harness (test_coalesce's gated smoke, parameterized) ---------------------


_SMOKE_FENS = [
    "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
    "r1bqkbnr/pppp1ppp/2n5/4p3/4P3/5N2/PPPP1PPP/RNBQKB1R w KQkq - 2 3",
    "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
    "4rrk1/pp1n3p/3q2pQ/2p1pb2/2PP4/2P3N1/P2B2PP/4RRK1 b - - 7 19",
    "r3r1k1/2p2ppp/p1p1bn2/8/1q2P3/2NPQN2/PPP3PP/R4RK1 b - - 2 15",
    "2rq1rk1/1p3ppp/p2p1n2/2bPp3/4P1b1/2N2N2/PPQ1BPPP/R1B2RK1 w - - 0 12",
    "r1bqk2r/ppp2ppp/2np1n2/2b1p3/2B1P3/2PP1N2/PP3PPP/RNBQK2R w KQkq - 0 6",
    "r2q1rk1/ppp2ppp/2npbn2/2b1p3/4P3/2PP1NN1/PPB2PPP/R1BQ1RK1 w - - 6 9",
]


class _GatedService(SearchService):
    """SearchService whose driver parks after warmup until the gate
    opens — every smoke submission lands in ONE drain pass, making the
    whole schedule a deterministic function of the submission sequence
    (test_coalesce's discipline; with bit-identical eval values the
    async and sync runs then walk the exact same search trees)."""

    def __init__(self, *args, **kwargs):
        self.gate = threading.Event()
        super().__init__(*args, **kwargs)

    def warmup(self):
        super().warmup()
        self.gate.wait()


def _smoke_run(weights, fens=None, nodes=200, psqt_path=None, mutate=None):
    # Default workload sized for tier-1 wall clock: 6 positions x 200
    # nodes still drives multi-group coalesced traffic through every
    # entry kind while a full smoke stays well under 10 s on one core.
    fens = _SMOKE_FENS[:6] if fens is None else fens
    from fishnet_tpu.search import eval_cache

    # Cold-start the process eval cache: back-to-back runs of the same
    # FENs would otherwise whole-batch-skip dispatches and skew the
    # eval_steps/overlap comparisons (analyses stay bit-identical).
    eval_cache.reset_cache()
    svc = _GatedService(
        weights=weights, pool_slots=8, batch_capacity=256,
        tt_bytes=8 << 20, backend="jax", pipeline_depth=4,
        driver_threads=1, psqt_path=psqt_path,
    )
    try:
        # Pin speculation so TT insertions are schedule-deterministic.
        svc.set_prefetch(0, adaptive=False)
        if mutate is not None:
            mutate(svc)

        async def go():
            tasks = [
                asyncio.ensure_future(svc.search(fen, [], nodes=nodes))
                for fen in fens
            ]
            await asyncio.sleep(0.3)  # let every submission queue
            svc.gate.set()
            return await asyncio.gather(*tasks)

        results = asyncio.run(go())
        analyses = [
            (
                r.best_move, r.depth, r.nodes,
                tuple(
                    (l.multipv, l.depth, l.is_mate, l.value, tuple(l.pv))
                    for l in r.lines
                ),
            )
            for r in results
        ]
        meta = {
            "async": svc._async_pipe is not None,
            "overlap_ratio": (
                svc._async_pipe.overlap_ratio()
                if svc._async_pipe is not None else 0.0
            ),
        }
        return analyses, svc.counters(), meta
    finally:
        svc.gate.set()  # never leave the driver parked on a failure
        svc.close()


# -- sync vs async bit-identical analyses (all rungs) -------------------------


@pytest.mark.parametrize("rung", ["xla", "host-material"])
def test_async_parity_smoke(rung, monkeypatch):
    """The tentpole invariant: the async double-buffered pipeline is a
    pure scheduling change — analyses are bit-identical to the
    synchronous inline flush (FISHNET_NO_ASYNC=1), per rung."""
    weights = NnueWeights.random(seed=7)
    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "4")
    a, ca, ma = _smoke_run(weights, psqt_path=rung)
    assert ma["async"], "async pipeline should be on by default"
    monkeypatch.setenv("FISHNET_NO_ASYNC", "1")
    b, cb, mb = _smoke_run(weights, psqt_path=rung)
    assert not mb["async"]
    assert a == b, "async dispatch changed analysis output"
    assert ca["eval_steps"] == cb["eval_steps"]


def test_async_parity_smoke_fused(monkeypatch):
    """The fused rung (Pallas interpreter off-TPU — hence the reduced
    workload) walks the same trees sync and async."""
    weights = NnueWeights.random(seed=7)
    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "2")
    kw = dict(fens=_SMOKE_FENS[:4], nodes=120, psqt_path="fused")
    a, _, ma = _smoke_run(weights, **kw)
    assert ma["async"]
    monkeypatch.setenv("FISHNET_NO_ASYNC", "1")
    b, _, mb = _smoke_run(weights, **kw)
    assert not mb["async"]
    assert a == b, "async dispatch changed analysis output (fused rung)"


def test_no_async_env_disables_pipeline(monkeypatch):
    monkeypatch.setenv("FISHNET_NO_ASYNC", "1")
    svc = SearchService(
        weights=NnueWeights.random(seed=3), pool_slots=8,
        batch_capacity=256, tt_bytes=4 << 20, backend="jax",
        pipeline_depth=4, driver_threads=1,
    )
    try:
        assert svc._coalescer is not None
        assert svc._async_pipe is None
    finally:
        svc.close()


def test_single_group_service_builds_no_pipeline():
    # No coalescer (one group) -> nothing to pipeline behind.
    svc = SearchService(
        weights=NnueWeights.random(seed=3), pool_slots=8,
        batch_capacity=64, tt_bytes=4 << 20, backend="jax",
    )
    try:
        assert svc._coalescer is None
        assert svc._async_pipe is None
    finally:
        svc.close()


# -- ping-pong donation correctness -------------------------------------------


class _Blocker:
    """An array-like whose materialization blocks until released —
    stands in for an in-flight device dispatch."""

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()

    def __array__(self, dtype=None, copy=None):
        self.entered.set()
        self.release.wait(10)
        return np.zeros(4, np.int32)


class _StubCoalescer:
    def __init__(self):
        self._lock = threading.Lock()
        self.executed = []

    def _execute(self, tickets, defer_cost=False):
        with self._lock:
            self.executed.append(tickets)
        for tk in tickets:
            tk.done.set()


class _StubSvc:
    def __init__(self):
        self._coalescer = _StubCoalescer()


def test_ping_pong_depth_bounds_inflight_dispatches():
    """Dispatch N+2 must not stage until dispatch N has materialized:
    its staging slot (N % DEPTH) still belongs to an in-flight wire."""
    svc = _StubSvc()
    pipe = _AsyncDispatchPipeline(svc)
    blockers = [_Blocker() for _ in range(3)]
    tks = []

    def n_exec():
        with svc._coalescer._lock:
            return len(svc._coalescer.executed)

    def wait_exec(n, timeout=5.0):
        deadline = time.monotonic() + timeout
        while n_exec() < n and time.monotonic() < deadline:
            time.sleep(0.005)
        return n_exec()

    try:
        for b in blockers:
            tk = _CoalesceTicket(0, 1, 4)
            tk.values = _FusedValues(b)
            tks.append(tk)
            assert pipe.submit([tk])
        assert wait_exec(2) == 2
        assert blockers[0].entered.wait(5)
        time.sleep(0.2)  # every chance for the pack worker to misbehave
        assert n_exec() == 2, "third dispatch staged while two in flight"
        assert pipe.inflight() == 2
        blockers[0].release.set()  # dispatch 0 materializes, slot 0 frees
        assert wait_exec(3) == 3
        blockers[1].release.set()
        blockers[2].release.set()
        for tk in tks:
            assert tk.done.wait(5)
            assert tk.error is None
    finally:
        for b in blockers:
            b.release.set()
        pipe.close()


def test_submit_after_close_reports_down():
    """A downed pipeline refuses batches (the coalescer then runs its
    inline synchronous flush, so shutdown never strands a ticket)."""
    pipe = _AsyncDispatchPipeline(_StubSvc())
    pipe.close()
    assert not pipe.submit([_CoalesceTicket(0, 1, 4)])


# -- failure semantics under async --------------------------------------------


@pytest.mark.anyio
async def test_device_step_fault_under_async_degrades_ladder():
    """The ``service.device_step`` fault site still fires on the driver
    thread with the async pipeline up: the error reaches the owner, the
    service reads dead, and the supervisor degrades one rung."""
    weights = NnueWeights.random(seed=21)

    def builder(rung):
        return SearchService(
            weights=weights, pool_slots=8, batch_capacity=256,
            tt_bytes=8 << 20, backend="jax", psqt_path=rung,
            pipeline_depth=4, driver_threads=1,
        )

    sup = ServiceSupervisor(
        builder, start_rung="xla", degrade_after=1, logger=Logger()
    )
    fresh = "rnbqkb1r/pppppppp/5n2/8/3P4/8/PPP1PPPP/RNBQKBNR w KQkq - 1 2"
    svc = sup.build()
    try:
        assert svc._async_pipe is not None
        faults.install("service.device_step:nth=1:crash")
        with pytest.raises(NativeCoreError):
            await svc.search(fresh, [], depth=3)
        faults.clear()
        assert not svc.is_alive()
    finally:
        svc.close()
    svc2 = sup.build()
    try:
        assert sup.rung == "host-material"  # degraded below "xla"
        r = await svc2.search(fresh, [], depth=2)
        assert r.best_move is not None
    finally:
        svc2.close()


# -- cross-segment eval-dedup planner (deterministic units) -------------------


def _pers_code(aid, is_delta, swap=0):
    return -(2 + ((aid << 2) | (2 if is_delta else 0) | swap))


def _payload(pid):
    rng = np.random.default_rng(1000 + pid)
    return rng.integers(0, spec.NUM_FEATURES, (4, 2, 8)).astype(np.uint16)


def _delta_payload(pid):
    rng = np.random.default_rng(2000 + pid)
    row = np.full((1, 2, 8), spec.NUM_FEATURES, np.uint16)
    row[0, :, :2] = rng.integers(0, spec.NUM_FEATURES, (2, 2))
    row[0, :, 4] = spec.DELTA_BASE + rng.integers(0, spec.NUM_FEATURES, (2,))
    row[0, :, 5:] = spec.DELTA_BASE + spec.NUM_FEATURES
    return row


def _dedup_seg(plan, size=8):
    """One segment's planner inputs from an entry plan. Items:
    ("full", payload) plain full; ("store", aid, payload) full anchor
    seed; ("pers", aid, payload) persistent anchor delta;
    ("inbatch", ref) in-batch delta. Equal payload ids produce
    byte-identical feature blocks."""
    parent = np.full(size, -1, np.int32)
    buckets = np.zeros(size, np.int32)
    offsets = np.zeros(size, np.int32)
    chunks, rows = [], 0
    for i, item in enumerate(plan):
        offsets[i] = rows
        kind = item[0]
        if kind == "full":
            parent[i] = -1
            chunks.append(_payload(item[1]))
            rows += 4
        elif kind == "store":
            parent[i] = _pers_code(item[1], False)
            chunks.append(_payload(item[2]))
            rows += 4
        elif kind == "pers":
            parent[i] = _pers_code(item[1], True)
            chunks.append(_delta_payload(item[2]))
            rows += 1
        else:  # in-batch delta
            parent[i] = item[1] << 1
            chunks.append(_delta_payload(99))
            rows += 1
    packed = (
        np.concatenate(chunks)
        if chunks else np.zeros((0, 2, 8), np.uint16)
    )
    return parent, buckets, offsets, packed, len(plan)


def _plan_args(*segs):
    return (
        [s[0] for s in segs],  # parents
        [s[1] for s in segs],  # buckets
        [s[2] for s in segs],  # offsets
        [s[4] for s in segs],  # ns
        [s[3] for s in segs],  # packed
    )


def test_dedup_planner_drops_cross_segment_duplicate():
    s0 = _dedup_seg([("full", 1), ("full", 2)])
    s1 = _dedup_seg([("full", 3), ("full", 2), ("full", 4)])
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], [1]]
    assert refs == [[], [0]]  # most recent preceding kept anchor
    assert pairs == [(1, 1, 0, 1)]  # value restored from the original


def test_dedup_planner_keeps_consumed_fulls():
    # Segment 1's duplicate full anchors an in-batch delta: dropping it
    # would orphan the chain, so it must be kept.
    s0 = _dedup_seg([("full", 2)])
    s1 = _dedup_seg([("full", 3), ("full", 2), ("inbatch", 1)])
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], []] and pairs == []


def test_dedup_planner_never_drops_first_entry():
    # Every group batch STARTS with an anchor (wire invariant): entry 0
    # stays even when it duplicates an earlier segment's entry.
    s0 = _dedup_seg([("full", 2)])
    s1 = _dedup_seg([("full", 2), ("full", 5)])
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], []] and pairs == []


def test_dedup_planner_never_drops_persistent_entries():
    # A persistent-store entry seeds the anchor table: not removable
    # even when its feature block matches an earlier full.
    s0 = _dedup_seg([("full", 7)])
    s1 = _dedup_seg([("full", 3), ("store", 1, 7)])
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], []] and pairs == []


def test_dedup_planner_matches_store_originals():
    # ...but a plain full DUPLICATING a store's block is droppable.
    s0 = _dedup_seg([("store", 0, 7)])
    s1 = _dedup_seg([("full", 8), ("full", 7)])
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], [1]]
    assert refs == [[], [0]]
    assert pairs == [(1, 1, 0, 0)]


def test_dedup_planner_bucket_distinguishes():
    s0 = _dedup_seg([("full", 2)])
    s1 = _dedup_seg([("full", 3), ("full", 2)])
    s1[1][1] = 5  # same rows, different layer-stack bucket
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], []] and pairs == []


def test_dedup_planner_refs_skip_dropped_anchors():
    # Two duplicates in a row: the second's ref must point at the last
    # KEPT anchor, not at the first duplicate (which is gone).
    s0 = _dedup_seg([("full", 2)])
    s1 = _dedup_seg([("full", 5), ("full", 2), ("full", 2)])
    drops, refs, pairs = plan_segment_dedup(*_plan_args(s0, s1))
    assert drops == [[], [1, 2]]
    assert refs == [[], [0, 0]]
    assert pairs == [(1, 1, 0, 0), (1, 2, 0, 0)]


def test_dedup_planner_is_deterministic():
    s0 = _dedup_seg([("full", 1), ("full", 2), ("inbatch", 0)])
    s1 = _dedup_seg([("full", 2), ("full", 1), ("full", 2)])
    first = plan_segment_dedup(*_plan_args(s0, s1))
    second = plan_segment_dedup(*_plan_args(s0, s1))
    assert first == second


# -- dedup staging end-to-end (values bit-identical, garbage restored) --------


def test_segmented_dedup_restores_values_bit_identical():
    """Staging a fused dispatch with dedup ON yields values
    bit-identical to dedup OFF: the duplicate ships as a one-row
    sentinel delta, computes garbage on device, and _FusedValues
    restores its true value from the original at materialize time."""
    weights = NnueWeights.random(seed=5)
    svc = SearchService(
        weights=weights, pool_slots=8, batch_capacity=256,
        tt_bytes=4 << 20, backend="jax", pipeline_depth=4,
        driver_threads=1, psqt_path="xla",
    )
    try:
        svc.warmup()  # serialize vs the driver's own warmup dispatches
        rng = np.random.default_rng(3)
        size = svc._eval_sizes[0]

        def fill(g, plan):
            rows = 0
            for i, item in enumerate(plan):
                svc._offset_buf[g][i] = rows
                if item[0] == "full":
                    svc._parent_buf[g][i] = -1
                    svc._packed_buf[g][rows : rows + 4] = _payload(item[1])
                    rows += 4
                else:  # in-batch delta
                    svc._parent_buf[g][i] = item[1] << 1
                    svc._packed_buf[g][rows : rows + 1] = _delta_payload(99)
                    rows += 1
            svc._bucket_buf[g][: len(plan)] = 0
            return len(plan), rows

        n0, rows0 = fill(0, [("full", 1), ("inbatch", 0), ("full", 2)])
        n1, rows1 = fill(1, [("full", 3), ("full", 2), ("inbatch", 0)])

        def dispatch():
            tks = [_CoalesceTicket(0, n0, rows0),
                   _CoalesceTicket(1, n1, rows1)]
            svc._dispatch_segmented(tks)
            return tks

        assert svc._dedup_fused
        tks_on = dispatch()
        v_on = tks_on[0].values.materialize().copy()
        assert svc.counters()["fused_dedup"] == 1

        svc._dedup_fused = False
        tks_off = dispatch()
        v_off = tks_off[0].values.materialize()
        np.testing.assert_array_equal(v_on, v_off)
        # The duplicate (segment 1 entry 1) carries its original's value.
        assert v_on[1 * size + 1] == v_on[0 * size + 2]
    finally:
        svc.close()


def test_dedup_smoke_parity(monkeypatch):
    """Identical searches stepping in lockstep across sibling groups
    maximize cross-segment duplicate pressure; the dedup pass must not
    change any analysis vs FISHNET_NO_DEDUP=1. (Under anchor-table
    traffic the duplicates are overwhelmingly persistent STORE entries
    — table seeds the planner correctly refuses to drop, see
    doc/wire-format.md — so this smoke pins the no-misfire side; the
    staging unit above pins the retire side.)"""
    weights = NnueWeights.random(seed=11)
    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "4")
    fens = [_SMOKE_FENS[0]] * 4 + [_SMOKE_FENS[1]] * 4
    a, ca, _ = _smoke_run(weights, fens=fens)
    monkeypatch.setenv("FISHNET_NO_DEDUP", "1")
    b, cb, _ = _smoke_run(weights, fens=fens)
    assert a == b, "eval-dedup changed analysis output"
    assert cb["fused_dedup"] == 0
    assert ca["fused_dedup"] >= 0  # organic anchored traffic: often 0


# -- anchor-placement policy (deterministic, bit-exact) -----------------------


@pytest.fixture(scope="module")
def baseline_smoke():
    """One shared async default-rung smoke (seed-7 weights, width 4):
    the baseline half of both placement tests below, run once."""
    old = os.environ.get("FISHNET_COALESCE_WIDTH")
    os.environ["FISHNET_COALESCE_WIDTH"] = "4"
    try:
        result = _smoke_run(NnueWeights.random(seed=7))
    finally:
        if old is None:
            os.environ.pop("FISHNET_COALESCE_WIDTH", None)
        else:
            os.environ["FISHNET_COALESCE_WIDTH"] = old
    return result


def test_anchor_placement_is_deterministic(baseline_smoke, monkeypatch):
    weights = NnueWeights.random(seed=7)
    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "4")
    a1, c1, _ = baseline_smoke
    a2, c2, _ = _smoke_run(weights)
    assert a1 == a2
    for key in ("eval_steps", "delta_evals", "anchor_deltas", "nodes"):
        assert c1[key] == c2[key], key


def test_anchor_placement_off_is_bit_identical(baseline_smoke, monkeypatch):
    """Placement only reorders entries within an emission block (values
    are exact integers either way): analyses must not move."""
    weights = NnueWeights.random(seed=7)
    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "4")
    monkeypatch.setenv("FISHNET_NO_ANCHOR_PLACEMENT", "1")
    b, _, _ = _smoke_run(weights)
    assert baseline_smoke[0] == b, "anchor placement changed analysis output"


# -- overlap smoke ------------------------------------------------------------


class _SlowValues:
    """Wraps a dispatched array; materializing costs an extra sleep,
    standing in for wire transport on a slow link."""

    def __init__(self, arr, delay):
        self._arr = arr
        self._delay = delay

    def __array__(self, dtype=None, copy=None):
        time.sleep(self._delay)
        return np.asarray(self._arr)


def test_overlap_smoke(monkeypatch):
    """With materialization slowed to transport-like latencies, the
    double buffer must actually overlap dispatches: overlap_ratio > 0
    live (counters + gauge inputs) and via the span flight recorder
    (critical_path.dispatch_overlap)."""
    from fishnet_tpu import telemetry
    from fishnet_tpu.telemetry.critical_path import dispatch_overlap
    from fishnet_tpu.telemetry.spans import RECORDER

    monkeypatch.setenv("FISHNET_COALESCE_WIDTH", "2")
    telemetry.enable()
    try:
        def mutate(svc):
            orig_seg = svc._dispatch_segmented
            orig_solo = svc._dispatch_eval

            def slow_segmented(tickets):
                orig_seg(tickets)
                fv = tickets[0].values
                fv._arr = _SlowValues(fv._arr, 0.05)

            def slow_solo(group, n, rows):
                values, acct = orig_solo(group, n, rows)
                return _SlowValues(values, 0.05), acct

            svc._dispatch_segmented = slow_segmented
            svc._dispatch_eval = slow_solo

        weights = NnueWeights.random(seed=7)
        _, counters, meta = _smoke_run(weights, mutate=mutate)
        assert meta["async"]
        assert counters["overlap_busy_us"] > 0
        assert counters["overlap_dual_us"] > 0
        assert meta["overlap_ratio"] > 0

        stages = RECORDER.stages_seen()
        assert "dispatch_issue" in stages and "dispatch_wait" in stages

        report = dispatch_overlap(RECORDER.spans())
        assert report["dispatches_paired"] > 0
        assert report["overlap_ratio"] > 0
    finally:
        telemetry.disable()
