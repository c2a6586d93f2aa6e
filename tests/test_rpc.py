"""Split-plane RPC transport (doc/disaggregation.md): ring wraparound
and flow control at tiny FISHNET_RPC_RING_SLOTS, FISHNET_RPC_SLOT_BYTES
sizing failures, torn-record read-as-miss, stale-epoch refusal after a
frontend restart, demand timeout (FISHNET_RPC_TIMEOUT) and resubmit
after an evaluator rebirth, the ``rpc.detach`` fault site, the
FISHNET_RPC escape hatch (unset/"0" builds the monolith — the
supervisor's ``role=`` specs flip it per process), role federation
across scraped frontend/evaluator processes (FISHNET_RPC_DIR wiring),
and the two-process real smoke ``make rpc-smoke`` builds on: a
subprocess evaluator host serving a frontend ``RemoteBackend`` with
analyses bit-identical to a monolith. No test runs the full
3-frontend fleet with SIGKILLs (ROADMAP D14)."""

import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

from fishnet_tpu.resilience import faults
from fishnet_tpu.rpc import rings
from fishnet_tpu.rpc.client import (
    EvaluatorLostError,
    RemoteBackend,
    _RpcClient,
)
from fishnet_tpu.rpc.host import EvaluatorHost

_REPO_ROOT = Path(__file__).resolve().parents[1]


def _delta(before: dict, key: str) -> int:
    return rings.stats().get(key, 0) - before.get(key, 0)


def _nnue_payload(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, 1000, (n, 2, 32), dtype=np.uint16)
    buckets = rng.integers(0, 8, n, dtype=np.int32)
    parents = np.full(n, -1, np.int32)
    material = rng.integers(-100, 100, n, dtype=np.int32)
    return rings.pack_nnue_submit(feats, buckets, parents, material)


# -- transport units ---------------------------------------------------------


def test_ring_wraparound_and_flow_control(tmp_path, monkeypatch):
    """FISHNET_RPC_RING_SLOTS=2: records must survive many laps of the
    ring, and a producer outrunning the consumer must get RingFull —
    bounded blocking, never a clobbered slot."""
    monkeypatch.setenv(rings.RING_SLOTS_ENV, "2")
    front = rings.create_frontend_link(str(tmp_path), name="wrap.ring")
    host = rings.attach_host_link(front.path)
    try:
        for lap in range(7):  # > 3 full laps of a 2-slot ring
            payload = _nnue_payload(3, seed=lap)
            front.push(rings.KIND_NNUE_SUBMIT, lap + 1, 1, 3, payload)
            got = host.drain()
            assert len(got) == 1
            kind, ticket, epoch, n, back = got[0]
            assert (kind, ticket, epoch, n) == (
                rings.KIND_NNUE_SUBMIT, lap + 1, 1, 3,
            )
            assert back == payload
        # Fill both slots, then overflow within a short deadline.
        front.push(rings.KIND_NNUE_SUBMIT, 100, 1, 1, b"\0" * 8)
        front.push(rings.KIND_NNUE_SUBMIT, 101, 1, 1, b"\0" * 8)
        with pytest.raises(rings.RingFull):
            front.push(
                rings.KIND_NNUE_SUBMIT, 102, 1, 1, b"\0" * 8,
                deadline_s=0.05,
            )
        assert [t for _, t, _, _, _ in host.drain()] == [100, 101]
    finally:
        front.close()
        host.close()


def test_record_too_large_fails_loudly(tmp_path, monkeypatch):
    """A payload no slot can hold must raise RecordTooLarge (pointing
    at FISHNET_RPC_SLOT_BYTES), never truncate."""
    monkeypatch.setenv(rings.SLOT_BYTES_ENV, "256")
    front = rings.create_frontend_link(str(tmp_path), name="small.ring")
    try:
        assert front.slot_capacity == 256 - rings.REC_HEADER_BYTES
        with pytest.raises(rings.RecordTooLarge):
            front.push(rings.KIND_NNUE_SUBMIT, 1, 1, 8, b"\0" * 512)
    finally:
        front.close()


def test_torn_record_reads_as_miss(tmp_path):
    """A record whose payload was clobbered after publish (the
    SIGKILLed-writer shape) must fail the checksum and be SKIPPED —
    counted as torn, its slot consumed so the ring never wedges."""
    front = rings.create_frontend_link(str(tmp_path), name="torn.ring")
    host = rings.attach_host_link(front.path)
    before = rings.stats()
    try:
        payload = _nnue_payload(2)
        front.push(rings.KIND_NNUE_SUBMIT, 1, 1, 2, payload)
        # Corrupt one published payload byte in the mapped slot.
        front._submit[rings.REC_HEADER_BYTES] ^= 0xFF
        assert host.drain() == []
        assert _delta(before, "torn") == 1
        # The ring is not wedged: the next record flows.
        front.push(rings.KIND_NNUE_SUBMIT, 2, 1, 2, payload)
        got = host.drain()
        assert [t for _, t, _, _, _ in got] == [2]
        assert got[0][4] == payload
    finally:
        front.close()
        host.close()


def test_stale_epoch_refused_after_frontend_restart(tmp_path):
    """A restarted frontend bumps its epoch; the host must refuse the
    previous life's submit records (fencing) while serving the new
    ones."""
    first = rings.create_frontend_link(str(tmp_path), name="fe.ring")
    assert first.frontend_epoch == 1
    first.push(rings.KIND_NNUE_SUBMIT, 1, first.frontend_epoch, 2,
               _nnue_payload(2))
    first.close()  # SIGKILL: no unlink, the record is in the ring

    reborn = rings.create_frontend_link(str(tmp_path), name="fe.ring")
    assert reborn.frontend_epoch == 2
    reborn.push(rings.KIND_NNUE_SUBMIT, 2, reborn.frontend_epoch, 2,
                _nnue_payload(2))
    before = rings.stats()
    host = EvaluatorHost(rpc_dir=str(tmp_path))  # no backends needed
    try:
        host.sweep()
        assert _delta(before, "stale_refusals") == 1
        # The fresh-epoch record got past the fence (no NNUE backend
        # in this host, so it lands as unserviceable, not refused).
        assert _delta(before, "unserviceable") == 1
    finally:
        host.close()
        reborn.close()


def test_evaluator_death_demand_timeout_raises(tmp_path, monkeypatch):
    """No evaluator within FISHNET_RPC_TIMEOUT: the demand wait must
    surface EvaluatorLostError promptly (the service requeues the
    batch) — never hang."""
    monkeypatch.setenv(rings.TIMEOUT_ENV, "1")
    client = _RpcClient(str(tmp_path))
    try:
        payload = _nnue_payload(2)
        ticket = client.submit(rings.KIND_NNUE_SUBMIT, 2, payload)
        t0 = time.monotonic()
        with pytest.raises(EvaluatorLostError, match="requeue"):
            client.wait(ticket, 2, rings.KIND_NNUE_SUBMIT, payload)
        assert time.monotonic() - t0 < 10.0
    finally:
        client.close()


def test_evaluator_restart_resubmits_inflight_ticket(tmp_path):
    """Evaluator A consumes a submit record and dies unanswered; when
    evaluator B attaches (host-epoch bump), the waiting client must
    resubmit the kept payload and consume B's answer exactly once."""
    client = _RpcClient(str(tmp_path))
    before = rings.stats()
    try:
        payload = _nnue_payload(3, seed=9)
        ticket = client.submit(rings.KIND_NNUE_SUBMIT, 3, payload)

        host_a = rings.attach_host_link(client.link.path)
        rings.bump_host_epoch([host_a])
        assert len(host_a.drain()) == 1  # consumed, never answered
        host_a.close()  # death

        got = {}

        def waiter():
            got["res"] = client.wait(
                ticket, 3, rings.KIND_NNUE_SUBMIT, payload
            )

        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.05)  # the wait observes epoch 1 first

        host_b = rings.attach_host_link(client.link.path)
        rings.bump_host_epoch([host_b])  # rebirth signal -> resubmit
        values = np.array([11, -22, 33], np.int32)
        deadline = time.monotonic() + 10.0
        served = False
        while not served and time.monotonic() < deadline:
            for kind, tkt, epoch, n, pay in host_b.drain():
                assert pay == payload  # self-contained resubmit
                host_b.push(
                    rings.KIND_NNUE_RESULT, tkt, epoch, n,
                    rings.pack_nnue_result(values),
                )
                served = True
            time.sleep(0.001)
        th.join(timeout=10.0)
        assert not th.is_alive() and served
        _kind, _n, result = got["res"]
        assert (rings.unpack_nnue_result(result, 3) == values).all()
        assert _delta(before, "resubmits") >= 1
        host_b.close()
    finally:
        client.close()


def test_rpc_detach_fault_site(tmp_path):
    """faults grammar ``rpc.detach``: the host drops one live link on
    the matched sweep (reason="fault", file kept) and re-attaches it on
    the next — the deterministic chaos hook a split fleet's fault
    plan scripts."""
    front = rings.create_frontend_link(str(tmp_path), name="fa.ring")
    host = EvaluatorHost(rpc_dir=str(tmp_path))
    before = rings.stats()
    faults.install("rpc.detach:nth=1:error")
    try:
        host.sweep()  # attaches, then the injected detach fires
        assert host._links == {}
        assert _delta(before, "detach.fault") == 1
        assert os.path.exists(front.path)  # fault detach keeps the file
        host.sweep()  # nth=1 already consumed: re-attach, keep serving
        assert len(host._links) == 1
        assert _delta(before, "attach.host") == 2
    finally:
        faults.clear()
        host.close()
        front.close()


# -- the escape hatch --------------------------------------------------------


def test_flag_off_builds_monolith_flag_on_builds_remote(monkeypatch):
    """FISHNET_RPC unset and "0" must keep the monolithic path (a plain
    SearchService — byte-for-byte the no-rpc build; the split parity
    itself is pinned by the two-process smoke below); "1" must route
    build_search_service to RemoteBackend."""
    from fishnet_tpu import __main__ as cli
    from fishnet_tpu.configure import Opt
    from fishnet_tpu.search.service import SearchService
    from fishnet_tpu.utils.logger import Logger

    monkeypatch.delenv("FISHNET_RPC", raising=False)
    assert not rings.rpc_enabled()
    monkeypatch.setenv("FISHNET_RPC", "0")
    assert not rings.rpc_enabled()

    opt = Opt(microbatch=64, pipeline=2, search_threads=1)
    logger = Logger(verbose=0)
    svc = cli.build_search_service(opt, logger)
    try:
        assert type(svc) is SearchService  # the monolith, not a shim
        assert not isinstance(svc, RemoteBackend)
    finally:
        svc.close()

    monkeypatch.setenv("FISHNET_RPC", "1")
    assert rings.rpc_enabled()

    class _Probe:
        def __init__(self, **kwargs):
            self.kwargs = kwargs

    import fishnet_tpu.rpc.client as client_mod

    monkeypatch.setattr(client_mod, "RemoteBackend", _Probe)
    probe = cli.build_search_service(opt, logger)
    assert isinstance(probe, _Probe)
    assert probe.kwargs["pipeline_depth"] == 2


# -- role federation ---------------------------------------------------------


def test_federation_distinct_proc_labels_for_roles():
    """The fleet aggregator must keep a frontend and an evaluator as
    distinct scraped procs, each with its role readable from
    fishnet_rpc_role (the console's ROLE column)."""
    from fishnet_tpu.telemetry.exporter import MetricsExporter
    from fishnet_tpu.telemetry.fleet import FleetAggregator, _role_of
    from fishnet_tpu.telemetry.registry import (
        MetricsRegistry,
        gauge_family,
    )

    def role_collector(role):
        def collect():
            return [gauge_family(
                "fishnet_rpc_role",
                "This process's split-plane role.",
                1,
                labels={"role": role},
            )]
        return collect

    reg_f = MetricsRegistry()
    reg_f.register_collector(role_collector("frontend"), name="rpc")
    reg_e = MetricsRegistry()
    reg_e.register_collector(role_collector("evaluator"), name="rpc")
    exp_f = MetricsExporter(port=0, registry=reg_f)
    exp_e = MetricsExporter(port=0, registry=reg_e)
    try:
        agg = FleetAggregator(
            targets={"F0": exp_f.url, "EVAL0": exp_e.url},
            poll_interval=60.0,
        )
        agg.poll_once()
        assert set(agg._procs) == {"F0", "EVAL0"}
        assert _role_of(agg._procs["F0"]) == "frontend"
        assert _role_of(agg._procs["EVAL0"]) == "evaluator"
    finally:
        exp_f.close()
        exp_e.close()


# -- two-process real smoke (make rpc-smoke's big brother) -------------------

_FENS = [
    "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
    "r1bqkbnr/pppp1ppp/2n5/4p3/4P3/5N2/PPPP1PPP/RNBQKB1R w KQkq - 2 3",
    "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
]


def _analyses(svc):
    import asyncio

    svc.set_prefetch(0, adaptive=False)

    async def go():
        out = []
        for fen in _FENS:
            r = await svc.search(fen, [], nodes=160)
            out.append((
                r.best_move, r.depth, r.nodes,
                tuple((l.multipv, l.depth, l.is_mate, l.value,
                       tuple(l.pv)) for l in r.lines),
            ))
        return out

    return asyncio.run(go())


@pytest.mark.slow
def test_two_process_split_bit_identical_analyses(tmp_path, monkeypatch):
    """THE split-plane assertion: a frontend RemoteBackend served by a
    REAL subprocess evaluator host (different pid, own device context)
    must produce bit-identical analyses to an in-process monolith over
    the same weights. (No test runs the 3-frontend fused-fill and
    SIGKILL ledger gates: ROADMAP D14.)"""
    from fishnet_tpu.nnue.weights import NnueWeights
    from fishnet_tpu.search.service import SearchService

    monkeypatch.setenv("FISHNET_NO_EVAL_CACHE", "1")
    weights = NnueWeights.random(seed=7)
    wpath = tmp_path / "w.nnue"
    weights.save(str(wpath))
    rpc_dir = tmp_path / "rpc"

    common = dict(
        weights=weights, pool_slots=8, batch_capacity=64,
        tt_bytes=8 << 20, backend="jax", psqt_path="host-material",
        pipeline_depth=2, driver_threads=1,
    )
    mono = SearchService(**common)
    mono_out = _analyses(mono)
    mono.close()

    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO_ROOT)
    env["JAX_PLATFORMS"] = "cpu"
    host = subprocess.Popen(
        [sys.executable, "-m", "fishnet_tpu.rpc.host",
         "--dir", str(rpc_dir), "--nnue-file", str(wpath),
         "--poll", "0.001"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        split = RemoteBackend(rpc_dir=str(rpc_dir), **common)
        split_out = _analyses(split)
        split.close()
    finally:
        host.terminate()
        try:
            host.wait(timeout=10)
        except subprocess.TimeoutExpired:
            host.kill()
            host.wait(timeout=10)
    assert host.returncode is not None
    assert split_out == mono_out, (
        "split-plane analyses diverged from the monolith"
    )
