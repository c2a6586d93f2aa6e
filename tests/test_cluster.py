"""Fleet-scale crash tolerance (doc/resilience.md "Fleet chaos"):
fleet fault-site parsing, the chaos proxy's deterministic injection
(502s, latency, partition windows), the liveness/readiness split under
graceful drain, a real client process SIGTERM-drained to exit 0, and
the full fleet smoke — kills, a drain, a partition, restart under
budget, the server-side fleet ledger exactly-once, and the fleet
metric families on /metrics. ``make cluster-smoke`` runs the
``smoke or drain`` subset of this file."""

import asyncio
import json
import os
import signal
import socket
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import aiohttp
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from fake_server import FakeLichess, FakeServer  # noqa: E402

from fishnet_tpu.cluster.proxy import ChaosProxy
from fishnet_tpu.cluster.supervisor import FleetSupervisor, ProcSpec
from fishnet_tpu.resilience import drain
from fishnet_tpu.resilience.faults import FaultPlan, FaultPlanError

pytestmark = pytest.mark.anyio

_REPO_ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Fleet fault sites
# ---------------------------------------------------------------------------


def test_fleet_sites_parse_and_poll_deterministically():
    plan = FaultPlan.parse(
        "seed=5;proxy.partition:nth=2:latency=1.5;proxy.error5xx:every=3:error;"
        "proc.kill:nth=4:crash;proc.sigterm:nth=6:error"
    )
    # proxy.partition fires exactly on its 2nd poll, with the window arg.
    assert plan.poll("proxy.partition") is None
    rule = plan.poll("proxy.partition")
    assert rule is not None and rule.action == "latency" and rule.arg == 1.5
    assert plan.poll("proxy.partition") is None
    # every=3 on its own independent count.
    assert plan.poll("proxy.error5xx") is None
    assert plan.poll("proxy.error5xx") is None
    assert plan.poll("proxy.error5xx") is not None
    # proc sites: nth = that process's Nth supervisor tick.
    assert [plan.poll("proc.kill") for _ in range(3)] == [None] * 3
    assert plan.poll("proc.kill").action == "crash"
    counts = plan.counts()
    assert counts["proc.kill"] == 4 and counts["proxy.partition"] == 3


def test_unknown_fleet_site_rejected():
    with pytest.raises(FaultPlanError):
        FaultPlan.parse("proxy.meteor:nth=1:error")


# ---------------------------------------------------------------------------
# Chaos proxy
# ---------------------------------------------------------------------------


async def test_chaos_proxy_quiet_is_faithful():
    """With no plan the proxy is pure plumbing: same statuses, same
    bodies, nothing counted but forwards."""
    async with FakeServer() as server:
        proxy = await ChaosProxy(server.endpoint).start()
        try:
            async with aiohttp.ClientSession() as session:
                async with session.get(f"{proxy.endpoint}/status") as r:
                    via_proxy = (r.status, await r.json())
                async with session.get(f"{server.endpoint}/status") as r:
                    direct = (r.status, await r.json())
                assert via_proxy == direct
                # An unknown path's 404 passes through too.
                async with session.get(f"{proxy.endpoint}/nope") as r:
                    assert r.status == 404
            assert proxy.stats()["forwarded"] == 2
            assert proxy.stats()["dropped"] == 0
        finally:
            await proxy.close()


async def test_chaos_proxy_injects_502_and_latency_on_schedule():
    # Site counters are polled in order (partition, error5xx, latency)
    # and a firing site short-circuits the rest — so the latency site
    # first sees the SECOND request, and nth=1 delays exactly that one.
    plan = FaultPlan.parse(
        "proxy.error5xx:nth=1:error;proxy.latency:nth=1:latency=0.3"
    )
    async with FakeServer() as server:
        proxy = await ChaosProxy(server.endpoint, plan=plan).start()
        try:
            async with aiohttp.ClientSession() as session:
                url = f"{proxy.endpoint}/status"
                async with session.get(url) as r:
                    assert r.status == 502  # injected, never hit the server
                t0 = time.monotonic()
                async with session.get(url) as r:
                    assert r.status == 200
                assert time.monotonic() - t0 >= 0.3
                async with session.get(url) as r:  # 3rd: clean
                    assert r.status == 200
            stats = proxy.stats()
            assert stats["injected_5xx"] == 1
            assert stats["delayed"] == 1
            assert stats["forwarded"] == 2
        finally:
            await proxy.close()


async def test_chaos_proxy_partition_window_drops_every_request():
    """`proxy.partition:...:latency=S` = connection resets (no HTTP
    response) for the whole S-second window, then traffic resumes."""
    plan = FaultPlan.parse("proxy.partition:nth=2:latency=0.6")
    async with FakeServer() as server:
        proxy = await ChaosProxy(server.endpoint, plan=plan).start()
        try:
            async with aiohttp.ClientSession() as session:
                url = f"{proxy.endpoint}/status"
                async with session.get(url) as r:
                    assert r.status == 200  # poll 1: no rule
                t0 = time.monotonic()
                for _ in range(3):  # window open: every request dies raw
                    with pytest.raises(aiohttp.ClientError):
                        async with session.get(url):
                            pass
                await asyncio.sleep(max(0.0, 0.7 - (time.monotonic() - t0)))
                async with session.get(url) as r:  # window passed
                    assert r.status == 200
            stats = proxy.stats()
            assert stats["partitions"] == 1
            # Connection-level counter: aiohttp retries once on a
            # reused-connection disconnect, so each logical request is
            # dropped at least once, possibly twice.
            assert stats["dropped"] >= 3
            assert stats["forwarded"] == 2
        finally:
            await proxy.close()


# ---------------------------------------------------------------------------
# Liveness/readiness split under drain (in-process)
# ---------------------------------------------------------------------------


def _get(url: str):
    try:
        with urllib.request.urlopen(url, timeout=5) as res:
            return res.status, res.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


def test_drain_flips_readiness_not_liveness():
    from fishnet_tpu import telemetry

    exporter = telemetry.start_exporter(0)
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        # Before drain: both probes 200, and the readiness body is the
        # pre-drain bare "ok" (no provider registered yet — the
        # single-process behavior is byte-for-byte unchanged).
        assert _get(f"{base}/healthz") == (200, b"ok\n")
        assert _get(f"{base}/healthz/ready") == (200, b"ok\n")
        assert _get(f"{base}/healthz/live") == (200, b"ok\n")

        assert drain.begin(
            "sigterm", deadline=25.0, depth_fn=lambda: {"batches": 2}
        ) is True
        assert drain.begin("sigterm") is False  # idempotent

        status, body = _get(f"{base}/healthz")
        assert status == 503
        payload = json.loads(body)["providers"]["drain"]
        assert payload["draining"] is True
        assert payload["reason"] == "sigterm"
        assert payload["pending"] == {"batches": 2}
        assert _get(f"{base}/healthz/ready")[0] == 503
        # Liveness NEVER couples to drain: the process is flushing,
        # not wedged — an orchestrator must not kill it mid-drain.
        assert _get(f"{base}/healthz/live") == (200, b"ok\n")

        metrics = _get(f"{base}/metrics")[1].decode()
        assert "fishnet_drain_state 1" in metrics

        drain.reset()
        assert _get(f"{base}/healthz") == (200, b"ok\n")
        assert "fishnet_drain_state 0" in _get(f"{base}/metrics")[1].decode()
    finally:
        drain.reset()
        exporter.close()
        telemetry.disable()


# ---------------------------------------------------------------------------
# Real process: SIGTERM drain
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def test_sigterm_drains_real_process_to_exit_zero(tmp_path):
    """The whole drain contract against a REAL `python -m fishnet_tpu`
    process: on SIGTERM it goes 503 on readiness (while liveness stays
    200), flushes in-flight work within the deadline, and exits 0 —
    with the server-side fleet ledger clean afterwards. A submit
    latency fault stretches the flush window so the draining state is
    reliably observable from outside."""
    metrics_port = _free_port()
    lichess = FakeLichess(require_key=False)
    lichess.auto_refill = 4
    lichess.refill_move_every = 4
    async with FakeServer(lichess) as server:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(_REPO_ROOT)
        env.setdefault("JAX_PLATFORMS", "cpu")
        log_path = tmp_path / "client.log"
        logf = open(log_path, "ab")
        try:
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "fishnet_tpu", "run",
                "--no-conf", "--no-stats-file", "--engine", "mock",
                "--endpoint", server.endpoint, "--key", "DRAINPROC",
                "--cores", "1", "--max-backoff", "1s",
                "--drain-deadline", "10s",
                "--metrics-port", str(metrics_port),
                "--fault-plan", "net.submit:every=1:latency=0.5",
                stdout=logf, stderr=asyncio.subprocess.STDOUT,
                cwd=str(tmp_path), env=env,
            )
        finally:
            logf.close()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if lichess.acquire_count > 0 and lichess.fleet.units:
                    break
                await asyncio.sleep(0.05)
            assert lichess.acquire_count > 0, log_path.read_text()

            proc.send_signal(signal.SIGTERM)
            saw_unready = saw_alive = False
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not saw_unready:
                try:
                    status, body = _get(
                        f"http://127.0.0.1:{metrics_port}/healthz"
                    )
                    if status == 503 and b"draining" in body:
                        saw_unready = True
                        saw_alive = _get(
                            f"http://127.0.0.1:{metrics_port}/healthz/live"
                        ) == (200, b"ok\n")
                except OSError:
                    pass  # exporter may already be gone — checked below
                await asyncio.sleep(0.05)

            rc = await asyncio.wait_for(proc.wait(), 30)
            assert rc == 0, f"drain exited {rc}: {log_path.read_text()}"
            assert saw_unready, "readiness never went 503 during drain"
            assert saw_alive, "liveness failed during drain"
        finally:
            if proc.returncode is None:
                proc.kill()
                await proc.wait()
        # Server-side audit: everything handed to the drained process
        # either completed or is back in the queue — nothing lost.
        report = lichess.fleet_report()
        assert report["clean"], report


# ---------------------------------------------------------------------------
# Fleet smoke: kills + drain + partition, exactly-once, metric families
# ---------------------------------------------------------------------------


async def test_sigkill_reassignment_fleet_smoke(monkeypatch):
    """kill -9 mid-dispatch on one process of a two-process fleet: the
    server's reassignment sweep hands its work out again, the
    supervisor restarts it under budget, and the fleet ledger ends
    exactly-once — 0 lost, 0 duplicated."""
    # A position takes 50 ms, as a real analysis takes time: a process
    # holds a unit nearly always, not for the instant its submit flies.
    monkeypatch.setenv("FISHNET_MOCK_ENGINE_DELAY", "0.05")
    lichess = FakeLichess(require_key=False)
    lichess.auto_refill = 4
    lichess.refill_move_every = 4
    lichess.reassign_after = 1.5
    async with FakeServer(lichess) as server:
        supervisor = FleetSupervisor(
            server.endpoint,
            [
                ProcSpec(name="KA", fault_spec="proc.kill:nth=10:crash"),
                ProcSpec(name="KB"),
            ],
            tick_seconds=0.2,
            drain_deadline=5.0,
        )
        await supervisor.start()
        # KA's plan counts its ticks from KA's first unit: a process
        # still starting when the kill comes leaves nothing to reassign.
        ka = supervisor.procs["KA"]
        plan, ka.plan = ka.plan, None
        try:
            # Wait for what is asserted below, not for the clock: on a
            # loaded machine each step comes when it comes.
            # The drain waits for the restarted KA's first unit too: a
            # SIGTERM before its handler is up ends it with -15.
            deadline = time.monotonic() + 45.0
            restarted_at = None
            handed = lichess.fleet.acquires_by_proc
            while time.monotonic() < deadline:
                if ka.plan is None and "KA" in handed:
                    ka.plan = plan
                seen = {k for _, _, k in supervisor.events}
                if restarted_at is None and "restart" in seen:
                    restarted_at = time.monotonic()
                if (
                    "kill" in seen
                    and restarted_at is not None
                    and handed["KA"][-1] > restarted_at
                    and lichess.fleet_report()["reassigned"] >= 1
                ):
                    break
                await asyncio.sleep(0.25)
            exit_codes = await supervisor.drain()
        except BaseException:
            await supervisor.kill_all()
            raise
    kinds = [k for _, _, k in supervisor.events]
    assert "kill" in kinds, kinds
    assert "restart" in kinds, kinds
    assert supervisor.procs["KA"].exit_codes[0] == -signal.SIGKILL
    assert exit_codes == {"KA": 0, "KB": 0}
    report = lichess.fleet_report()
    assert report["clean"], report
    assert report["completed"] > 0
    assert report["reassigned"] >= 1, report


async def test_cluster_chaos_smoke_end_to_end():
    """The canned fleet scenario (SIGKILL + SIGTERM drain + partition
    across 3 real processes) via the chaos harness: ledger clean,
    restart under budget, every drained process exits 0, and the fleet
    metric families exported on /metrics."""
    from fishnet_tpu.cluster.chaos import run_chaos

    report = await run_chaos(procs=3, seconds=8.0, drain_deadline=5.0)
    assert report["ok"] is True
    kinds = [k for _, _, k in report["events"]]
    assert "kill" in kinds
    assert "sigterm" in kinds
    assert sum(p["partitions"] for p in report["proxies"].values()) >= 1
    assert report["fleet"]["clean"]
    assert report["fleet"]["lost"] == [] and report["fleet"]["duplicated"] == []
    assert all(rc == 0 for rc in report["exit_codes"].values())
    assert report["metric_families"] == sorted(
        [
            "fishnet_proc_restarts_total",
            "fishnet_fleet_partitions_total",
            "fishnet_faults_injected_total",
        ]
    )
