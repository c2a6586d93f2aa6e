"""Overload-safe multi-tenant serving (doc/resilience.md): the lane
scheduler's strict-priority + DRR contract, the watermark shed policy,
the "queue.admit" fault site, shutdown accounting for still-incoming
batches, requeue caps and deadline flushes under concurrent tenants,
the /healthz serving-state probe, the FISHNET_NO_MULTITENANT escape
hatch, and a small saturation run."""

import asyncio
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).parent))
from fake_server import FakeServer  # noqa: E402
from test_client_e2e import make_client, wait_for  # noqa: E402
from test_protocol import ANALYSIS_ACQUIRE  # noqa: E402

from fishnet_tpu.engine.mock import MockEngineFactory
from fishnet_tpu.protocol.types import AcquireResponseBody
from fishnet_tpu.resilience import accounting, faults
from fishnet_tpu.resilience.shedding import (
    ADMIT,
    LANE_LATENCY,
    LANE_THROUGHPUT,
    SHED,
    ShedPolicy,
)
from fishnet_tpu.sched import frontend as frontend_mod
from fishnet_tpu.sched import queue as queue_mod
from fishnet_tpu.sched.queue import LaneScheduler
from fishnet_tpu.telemetry import exporter as exporter_mod
from fishnet_tpu.telemetry.registry import percentile
from fishnet_tpu.utils.logger import Logger
from fishnet_tpu.utils.stats import StatsRecorder

pytestmark = pytest.mark.anyio


def _pos(batch_id: str, position_id: int = 0):
    """The minimal duck-typed position the scheduler touches."""
    return SimpleNamespace(
        work=SimpleNamespace(id=batch_id), position_id=position_id
    )


# ---------------------------------------------------------------------------
# LaneScheduler units
# ---------------------------------------------------------------------------


def test_lane_scheduler_strict_priority():
    sched = LaneScheduler()
    for i in range(5):
        sched.push(_pos("bulk", i), "t0", LANE_THROUGHPUT)
    sched.push(_pos("move", 0), "t1", LANE_LATENCY)
    # The latency lane drains first even though it was pushed last.
    assert sched.pop().work.id == "move"
    assert sched.pop().work.id == "bulk"
    assert sched.depth(LANE_LATENCY) == 0
    assert sched.depth(LANE_THROUGHPUT) == 4


def test_lane_scheduler_drr_alternates_by_quantum():
    sched = LaneScheduler(quantum=8)
    for i in range(20):
        sched.push(_pos("a", i), "ta", LANE_THROUGHPUT)
        sched.push(_pos("b", i), "tb", LANE_THROUGHPUT)
    order = []
    while True:
        p = sched.pop()
        if p is None:
            break
        order.append(p.work.id)
    assert len(order) == 40
    # Quantum-sized turns, alternating tenants: a x8, b x8, a x8, ...
    assert order[:8] == ["a"] * 8
    assert order[8:16] == ["b"] * 8
    assert order[16:24] == ["a"] * 8
    assert order.count("a") == order.count("b") == 20
    assert len(sched) == 0


def test_lane_scheduler_drop_batch_and_front_push():
    sched = LaneScheduler()
    for i in range(3):
        sched.push(_pos("keep", i), "t0", LANE_THROUGHPUT)
        sched.push(_pos("drop", i), "t0", LANE_THROUGHPUT)
    assert sched.drop_batch("drop") == 3
    assert len(sched) == 3
    # A requeued position goes to the FRONT of its tenant queue.
    sched.push(_pos("keep", 99), "t0", LANE_THROUGHPUT, front=True)
    assert sched.pop().position_id == 99


# ---------------------------------------------------------------------------
# ShedPolicy units
# ---------------------------------------------------------------------------


def test_shed_policy_watermark_hysteresis():
    policy = ShedPolicy(high_watermark=10)  # low defaults to 5
    assert policy.note_depth(9) is False
    assert policy.note_depth(10) is True  # crossed high: shedding
    assert policy.note_depth(6) is True  # above low: still shedding
    assert policy.note_depth(5) is False  # at low: recovered
    assert policy.admit(LANE_THROUGHPUT, 4, throughput_depth=3,
                        latency_depth=0) == ADMIT
    assert policy.admit(LANE_THROUGHPUT, 4, throughput_depth=30,
                        latency_depth=0) == SHED
    assert policy.shed_count == 1 and policy.admit_count == 1


def test_shed_policy_latency_lane_only_bounded():
    policy = ShedPolicy(high_watermark=10)  # latency_bound = 40
    # The latency lane ignores throughput saturation...
    assert policy.admit(LANE_LATENCY, 1, throughput_depth=10_000,
                        latency_depth=0) == ADMIT
    # ...and sheds only past its own hard bound.
    assert policy.admit(LANE_LATENCY, 1, throughput_depth=0,
                        latency_depth=40) == SHED
    snap = policy.snapshot()
    assert snap["latency_bound"] == 40
    assert snap["shed_count"] == 1


def test_shed_policy_capacity_scales_with_rung_and_breaker():
    breaker_open = False
    policy = ShedPolicy(
        high_watermark=100,
        rung_fn=lambda: "xla",
        breaker_open_fn=lambda: breaker_open,
    )
    assert policy.effective_high() == 50  # xla rung halves capacity
    breaker_open = True
    assert policy.effective_high() == 25  # open breaker halves it again
    assert policy.effective_low() <= policy.effective_high()
    # A degraded plane sheds at depths a healthy one would admit.
    assert policy.admit(LANE_THROUGHPUT, 1, throughput_depth=30,
                        latency_depth=0) == SHED


async def test_queue_admit_fault_site():
    assert "queue.admit" in faults.SITES
    faults.install("queue.admit:nth=1:error")
    try:
        with pytest.raises(faults.FaultInjected):
            await faults.fire_async("queue.admit")
        await faults.fire_async("queue.admit")  # nth=1 only: second passes
        assert faults.current().counts()["queue.admit"] == 2  # site visits
    finally:
        faults.clear()


# ---------------------------------------------------------------------------
# Shutdown accounting (satellite: batches still incoming at shutdown)
# ---------------------------------------------------------------------------


class FakeApi:
    """The slice of ApiStub the queue side calls."""

    def __init__(self) -> None:
        self.endpoint = "http://fake/fishnet"
        self.tenant = ""
        self.aborted = []
        self.submitted = []

    def abort(self, batch_id: str) -> None:
        self.aborted.append(batch_id)

    def submit_analysis(self, batch_id, flavor, analysis, final=True) -> None:
        self.submitted.append(batch_id)


def _queue_pair(api: FakeApi):
    logger = Logger(verbose=0)
    rx: "asyncio.Queue" = asyncio.Queue()
    interrupt = asyncio.Event()
    state = queue_mod.QueueState(
        2, StatsRecorder(2, no_stats_file=True), logger
    )
    stub = queue_mod.QueueStub(rx, interrupt, state, api)
    actor = queue_mod.QueueActor(
        rx, interrupt, state, api, queue_mod.BacklogOpt(), logger
    )
    return state, stub, actor


async def test_queue_shutdown_abandons_scheduled_batch():
    led = accounting.install()
    try:
        api = FakeApi()
        state, stub, actor = _queue_pair(api)
        body = AcquireResponseBody.from_json(ANALYSIS_ACQUIRE)
        await actor.handle_acquired(body)
        assert "work_id" in state.pending and state.incoming_len() > 0
        stub.shutdown()
        rec = led.record("work_id")
        assert rec.terminal == "abandoned" and rec.reason == "shutdown_abort"
        assert api.aborted == ["work_id"]
        # The abandoned batch's queued positions went with it.
        assert state.incoming_len() == 0 and not state.pending
        led.assert_clean()
    finally:
        accounting.clear()


async def test_acquired_during_shutdown_abandons_through_ledger():
    # An in-flight acquire resolving AFTER shutdown() must hand the
    # batch back (accounted + aborted), not drop it on the floor.
    led = accounting.install()
    try:
        api = FakeApi()
        state, stub, actor = _queue_pair(api)
        state.shutdown_soon = True
        await actor.handle_acquired(
            AcquireResponseBody.from_json(ANALYSIS_ACQUIRE)
        )
        rec = led.record("work_id")
        assert rec.terminal == "abandoned"
        assert rec.reason == "shutdown_incoming"
        assert api.aborted == ["work_id"]
        assert not state.pending and state.incoming_len() == 0
        led.assert_clean()
    finally:
        accounting.clear()


async def test_acquire_cancelled_in_the_turn_it_was_fulfilled_is_handed_back():
    """The api actor fulfils a still-pending acquire future (so its
    callback-dropped path does not fire) and the awaiting stream is
    cancelled before it resumes: the batch must be abandoned + aborted,
    not left acquired-and-forgotten (soak phase C lost one this way)."""
    from fishnet_tpu.net import api as api_mod
    from fishnet_tpu.protocol.types import Acquired

    led = accounting.install()
    try:
        queue: "asyncio.Queue" = asyncio.Queue()
        stub = api_mod.ApiStub(_queue=queue, endpoint="http://unused")
        task = asyncio.ensure_future(stub.acquire(False))
        msg = await queue.get()
        await asyncio.sleep(0)  # the stream now awaits its future
        led.record_acquired("work_id")  # what _parse_acquired does
        msg.future.set_result(
            Acquired.accepted(AcquireResponseBody.from_json(ANALYSIS_ACQUIRE))
        )
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        rec = led.record("work_id")
        assert rec.terminal == "abandoned"
        assert rec.reason == "shutdown_cancelled"
        abort = queue.get_nowait()
        assert (abort.kind, abort.batch_id) == ("abort", "work_id")
        led.assert_clean()
    finally:
        accounting.clear()


# ---------------------------------------------------------------------------
# Requeue cap + deadline flush under concurrent tenants
# ---------------------------------------------------------------------------


async def test_requeue_generation_cap_under_concurrent_tenants():
    # Same contract as the single-stream cap test in test_resilience.py,
    # but through the multi-tenant front end: the doomed batch is
    # abandoned after MAX_REQUEUE_GENERATIONS while the other tenant's
    # stream keeps flowing.
    led = accounting.install()
    async with FakeServer() as server:
        doomed = server.lichess.add_analysis_job(moves="e2e4 e7e5 g1f3")
        survivor = server.lichess.add_analysis_job(moves="d2d4")
        factory = MockEngineFactory(fail_on="#3")
        client = make_client(
            server.endpoint, cores=1, engine_factory=factory, tenants=2
        )
        await client.start()
        assert client._frontend is not None
        assert await wait_for(lambda: survivor in server.lichess.analyses)
        assert await wait_for(
            lambda: (led.record(doomed) or None) is not None
            and led.record(doomed).terminal == "abandoned"
        )
        await client.stop(abort_pending=False)
        assert doomed not in server.lichess.analyses
    rec = led.record(doomed)
    assert rec.reason == "requeue_cap"
    assert rec.requeues == queue_mod.MAX_REQUEUE_GENERATIONS
    led.assert_clean()


async def test_deadline_flush_under_concurrent_tenants():
    # Workers park in the front end's _waiting deque when the queue is
    # empty, so the acquire rounds must drive flush_expired — a hung
    # engine's batch still flushes partially within the budget.
    led = accounting.install()
    async with FakeServer() as server:
        job = server.lichess.add_analysis_job(moves="e2e4 e7e5")
        factory = MockEngineFactory(hang_on="#1")  # ply 1 hangs forever
        client = make_client(
            server.endpoint, cores=2, engine_factory=factory,
            batch_deadline=1.0, tenants=2,
        )
        await client.start()
        assert client._frontend is not None
        assert await wait_for(
            lambda: job in server.lichess.analyses, timeout=20
        )
        body = server.lichess.analyses[job]
        await client.stop(abort_pending=True)
    parts = body["analysis"]
    assert len(parts) == 3
    assert parts[1] == {"skipped": True}  # the hung ply, flushed as skipped
    assert parts[0] is not None and parts[2] is not None
    assert server.lichess.analysis_submission_counts[job] == 1
    rec = led.record(job)
    assert rec.flushed and rec.terminal == "submitted"
    led.assert_clean()


# ---------------------------------------------------------------------------
# /healthz serving state
# ---------------------------------------------------------------------------


@pytest.fixture
def clean_health():
    with exporter_mod._HEALTH_LOCK:
        saved = dict(exporter_mod._HEALTH_PROVIDERS)
        exporter_mod._HEALTH_PROVIDERS.clear()
    yield
    with exporter_mod._HEALTH_LOCK:
        exporter_mod._HEALTH_PROVIDERS.clear()
        exporter_mod._HEALTH_PROVIDERS.update(saved)


def test_healthz_provider_states(clean_health):
    assert exporter_mod.health_snapshot() == (200, None)  # bare liveness
    exporter_mod.register_health_provider("good", lambda: {"healthy": True})
    code, body = exporter_mod.health_snapshot()
    assert code == 200 and body["status"] == "ok"
    exporter_mod.register_health_provider(
        "shedder", lambda: {"healthy": False, "shedding": True}
    )
    code, body = exporter_mod.health_snapshot()
    assert code == 503 and body["status"] == "degraded"
    exporter_mod.unregister_health_provider("shedder")
    code, _ = exporter_mod.health_snapshot()
    assert code == 200
    # A provider returning None self-unregisters (collector idiom).
    exporter_mod.register_health_provider("stale", lambda: None)
    assert exporter_mod.health_snapshot()[0] == 200
    assert "stale" not in exporter_mod._HEALTH_PROVIDERS
    # A raising provider reads as unhealthy, never a 500.
    def boom():
        raise RuntimeError("probe broke")
    exporter_mod.register_health_provider("boom", boom)
    code, body = exporter_mod.health_snapshot()
    assert code == 503
    assert body["providers"]["boom"] == {
        "healthy": False, "error": "provider raised"
    }


async def test_frontend_health_flips_with_shedding(clean_health):
    fe = frontend_mod.FrontEnd(
        "http://127.0.0.1:1/fishnet", "key", Logger(verbose=0),
        cores=1, tenants=2,
    )
    code, body = exporter_mod.health_snapshot()
    assert code == 200
    serving = body["providers"]["serving"]
    assert serving["healthy"] is True and serving["shedding"] is False
    assert set(serving["tenants"]) == {"t0", "t1"}
    fe.shed_policy.note_depth(10_000)  # saturate: hysteresis flips on
    code, body = exporter_mod.health_snapshot()
    assert code == 503
    assert body["providers"]["serving"]["shedding"] is True


# ---------------------------------------------------------------------------
# Escape hatch + saturation run
# ---------------------------------------------------------------------------


async def test_no_multitenant_env_restores_single_stream(monkeypatch):
    monkeypatch.setenv(frontend_mod.NO_MULTITENANT_ENV, "1")
    async with FakeServer() as server:
        job = server.lichess.add_analysis_job(moves="e2e4")
        client = make_client(server.endpoint, tenants=4)
        await client.start()
        assert client._frontend is None  # classic single-stream wiring
        assert await wait_for(lambda: job in server.lichess.analyses)
        await client.stop()


async def _saturation_run(
    seconds: float, tenants: int, saturation: int, high_watermark: int
) -> dict:
    """``tenants`` acquire streams against a fake server that refills
    faster than the client can drain (``saturation`` x), mock engine,
    real front end: admission control sheds analysis work at the
    watermark while the best-move lane keeps its p99. Transport- and
    device-free: what it shows is the serving plane's queueing."""
    ledger = accounting.install()
    try:
        async with FakeServer() as server:
            li = server.lichess
            li.auto_refill = saturation * tenants * 2
            li.refill_move_every = 4  # every 4th synthesized job: best-move
            policy = ShedPolicy(high_watermark=high_watermark)
            client = make_client(
                server.endpoint,
                engine_factory=MockEngineFactory(delay_seconds=0.02),
                tenants=tenants,
                shed_policy=policy,
            )
            await client.start()
            frontend = client._frontend
            sched = frontend.state.scheduler
            max_throughput_depth = 0
            loop = asyncio.get_running_loop()
            t_end = loop.time() + seconds
            while loop.time() < t_end:
                max_throughput_depth = max(
                    max_throughput_depth, sched.depths()[LANE_THROUGHPUT]
                )
                await asyncio.sleep(0.02)
            await client.stop(abort_pending=True)
            # Server-observed: handout -> move done.
            move_ms = [
                (li.move_done_at[k] - li.handed_at[k]) * 1e3
                for k in li.move_done_at if k in li.handed_at
            ]
            return {
                "ledger": ledger.report(),
                "max_throughput_depth": max_throughput_depth,
                "move_p99_ms": percentile(move_ms, 99),
                "shed_total": sum(
                    ts.shed for ts in frontend.tenants.values()
                ),
                "served": [v for v in sched.served.values() if v > 0],
            }
    finally:
        accounting.clear()


async def test_saturation_sheds_analysis_and_keeps_the_move_lane():
    """The acceptance run, small: 4 tenants against a saturating fake
    server — analysis sheds at the watermark, best-move p99 holds, the
    queue stays bounded, and the ledger is exactly-once throughout."""
    tenants, high_watermark = 4, 12
    run = await _saturation_run(
        seconds=5.0, tenants=tenants, saturation=4,
        high_watermark=high_watermark,
    )
    assert run["ledger"]["lost"] == []
    assert run["ledger"]["duplicated"] == []
    # Admission is checked per batch BEFORE its positions are pushed, so
    # depth can overshoot the watermark by at most the batches every
    # tenant had in flight at the crossing.
    assert run["max_throughput_depth"] <= high_watermark + tenants * 8
    assert run["move_p99_ms"] is not None and run["move_p99_ms"] <= 10_000.0
    assert run["shed_total"] >= 1
    if len(run["served"]) >= 2:
        assert max(run["served"]) / min(run["served"]) <= 2.0
