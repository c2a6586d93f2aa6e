"""Fleet-wide position tier (doc/eval-cache.md "Fleet tier"): segment
units (NNUE int32 + AZ fp16 round-trips, owner scoping, fingerprint
isolation), the graceful attach-fallback ladder, torn-slot safety under
real multi-process writers, SIGKILL-while-writing recovery (slot
reclaim), and the two-process cross-process-hit smoke that ``make
fleet-cache-smoke`` gates on. No test runs the full 3-process
supervisor fleet with a mid-replay SIGKILL (ROADMAP D14)."""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

from fishnet_tpu.cluster import position_tier
from fishnet_tpu.nnue.weights import NnueWeights
from fishnet_tpu.resilience.faults import FaultPlan
from fishnet_tpu.search import eval_cache

_REPO_ROOT = Path(__file__).resolve().parents[1]


def _val_of(key: int) -> int:
    """Deterministic value-from-key: ANY value a reader accepts can be
    checked against its key, so a torn or interleaved write that slips
    past the seqlock+checksum would be caught as a wrong value."""
    return int((key * 2654435761) & 0x7FFFFFFF) - (1 << 30)


@pytest.fixture
def tier_env(tmp_path, monkeypatch):
    seg = tmp_path / "tier.seg"
    monkeypatch.setenv("FISHNET_POSITION_TIER", "1")
    monkeypatch.setenv("FISHNET_POSITION_TIER_PATH", str(seg))
    monkeypatch.setenv("FISHNET_POSITION_TIER_CAPACITY", "4096")
    monkeypatch.setenv("FISHNET_POSITION_TIER_AZ_CAPACITY", "32")
    position_tier.reset_tier()
    yield seg
    position_tier.reset_tier()


# -- units ------------------------------------------------------------------


def test_tier_nnue_roundtrip_exact_and_owner_scope(tier_env):
    tier = position_tier.get_tier()
    assert tier is not None
    keys = np.array([0x1234, 0x9876, 0xDEADBEEF], dtype=np.uint64)
    vals = np.array([17, -250, 31000], dtype=np.int32)
    tier.insert_nnue_block(keys, vals)
    out = np.zeros(3, np.int32)
    mask = np.zeros(3, bool)
    assert tier.probe_nnue_block(keys, out, mask) == 3
    assert mask.all() and (out == vals).all(), "int32 evals must be exact"
    # Rows already filled (mask set) are never re-probed or clobbered.
    out2 = np.array([111, 0, 0], np.int32)
    mask2 = np.array([True, False, False])
    assert tier.probe_nnue_block(keys, out2, mask2) == 2
    assert out2[0] == 111
    st = position_tier.stats()
    # Same pid wrote the slots -> hits are scope=local, not fleet.
    assert st.get("hits.local.nnue", 0) >= 5
    assert st.get("hits.fleet.nnue", 0) == 0


def test_tier_az_roundtrip_exact_fp16(tier_env):
    tier = position_tier.get_tier()
    policy = (
        np.random.RandomState(3)
        .randn(position_tier.AZ_POLICY_SIZE)
        .astype(np.float16)
    )
    tier.insert_az(0x777, policy, 0.125)
    got = tier.probe_az(0x777)
    assert got is not None
    gpol, gval = got
    assert gval == 0.125
    assert gpol.dtype == np.float16 and (gpol == policy).all(), (
        "fp16 policy payload must round-trip bit-exact"
    )
    assert tier.probe_az(0x778) is None


def test_tier_fingerprint_mismatch_isolation(tier_env):
    """Keys are salted ``zobrist ^ net_fingerprint`` BY THE CALLER, so
    two processes serving different nets key disjoint regions: net B
    never reads net A's evals for the same position."""
    tier = position_tier.get_tier()
    zobrist = 0xABCDEF0123456789
    fp_a, fp_b = 0x1111, 0x2222
    tier.insert_nnue_block(
        np.array([zobrist ^ fp_a], np.uint64), np.array([555], np.int32)
    )
    out = np.zeros(1, np.int32)
    mask = np.zeros(1, bool)
    assert tier.probe_nnue_block(
        np.array([zobrist ^ fp_b], np.uint64), out, mask
    ) == 0
    assert not mask[0]
    mask[:] = False
    assert tier.probe_nnue_block(
        np.array([zobrist ^ fp_a], np.uint64), out, mask
    ) == 1
    assert out[0] == 555


def test_tier_generation_clock_shared(tier_env):
    tier = position_tier.get_tier()
    g0 = tier.generation()
    tier.advance_generation()
    # A second attach of the same segment sees the tick: the clock
    # lives in the shared header, not in any process.
    position_tier.reset_tier()
    tier2 = position_tier.get_tier()
    assert tier2.generation() == g0 + 1


def test_tier_disabled_and_absent_fallbacks(tmp_path, monkeypatch):
    # Env off -> no tier, no segment file created.
    monkeypatch.setenv("FISHNET_POSITION_TIER", "0")
    position_tier.reset_tier()
    assert position_tier.get_tier() is None
    # Env on but the path is unwritable -> graceful local fallback.
    monkeypatch.setenv("FISHNET_POSITION_TIER", "1")
    monkeypatch.setenv(
        "FISHNET_POSITION_TIER_PATH", str(tmp_path / "no" / "such" / "dir/x")
    )
    position_tier.reset_tier()
    before = position_tier.stats().get("attach.local", 0)
    assert position_tier.get_tier() is None
    assert position_tier.stats().get("attach.local", 0) == before + 1
    position_tier.reset_tier()


def test_tier_corrupt_segment_rejected(tmp_path, monkeypatch):
    """A file that isn't a tier segment (foreign magic) must fall back
    to process-local, never be reinterpreted as slots."""
    seg = tmp_path / "garbage.seg"
    seg.write_bytes(b"\x00" * 64 + os.urandom(8192))
    monkeypatch.setenv("FISHNET_POSITION_TIER", "1")
    monkeypatch.setenv("FISHNET_POSITION_TIER_PATH", str(seg))
    position_tier.reset_tier()
    assert position_tier.get_tier() is None
    position_tier.reset_tier()


# -- multi-process torn-slot safety -----------------------------------------

# Writer child: hammers an overlapping key range with values derived
# from the key (``_val_of``), so the parent can verify EVERY hit it
# reads while the writers race. numpy-only — no jax import cost.
_WRITER = r"""
import os, sys
import numpy as np
from fishnet_tpu.cluster import position_tier as pt

base, n, rounds = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
tier = pt.get_tier()
assert tier is not None, "writer failed to attach"
keys = np.array(
    [((base + i) * 0x9E3779B97F4A7C15) & ((1 << 64) - 1) or 1
     for i in range(n)],
    dtype=np.uint64,
)
vals = np.array(
    [int((int(k) * 2654435761) & 0x7FFFFFFF) - (1 << 30) for k in keys],
    dtype=np.int32,
)
print("ready", flush=True)
for _ in range(rounds):
    tier.insert_nnue_block(keys, vals)
print("done", flush=True)
"""


def _spawn_writer(base: int, n: int, rounds: int) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO_ROOT)
    return subprocess.Popen(
        [sys.executable, "-c", _WRITER, str(base), str(n), str(rounds)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def test_tier_multiprocess_writers_never_serve_torn_values(tier_env):
    """Two real writer processes hammering an overlapping window while
    this process reads continuously: every hit must carry the value
    derived from its key — a torn read or an interleaved write must
    surface as a miss (seqlock/checksum reject), never a wrong value —
    and hits against sibling-written slots must count scope=fleet."""
    n, rounds = 64, 200
    writers = [_spawn_writer(0, n, rounds), _spawn_writer(0, n, rounds)]
    try:
        tier = position_tier.get_tier()
        keys = np.array(
            [(i * 0x9E3779B97F4A7C15) & ((1 << 64) - 1) or 1
             for i in range(n)],
            dtype=np.uint64,
        )
        expected = np.array([_val_of(int(k)) for k in keys], np.int32)
        out = np.zeros(n, np.int32)
        deadline = time.monotonic() + 20.0
        total_hits = 0
        while time.monotonic() < deadline:
            mask = np.zeros(n, bool)
            hits = tier.probe_nnue_block(keys, out, mask)
            if hits:
                total_hits += hits
                assert (out[mask] == expected[mask]).all(), (
                    "tier served a value inconsistent with its key"
                )
            if all(w.poll() is not None for w in writers):
                break
        for w in writers:
            stdout, stderr = w.communicate(timeout=30)
            assert w.returncode == 0, stderr
            assert "done" in stdout
        # Final sweep: the settled segment serves the full window.
        mask = np.zeros(n, bool)
        assert tier.probe_nnue_block(keys, out, mask) == n
        assert (out == expected).all()
        assert position_tier.stats().get("hits.fleet.nnue", 0) > 0, (
            "sibling-written slots must count as fleet-scope hits"
        )
    finally:
        for w in writers:
            if w.poll() is None:
                w.kill()
                w.communicate()


def test_tier_sigkill_while_writing_recovers(tier_env):
    """SIGKILL a writer mid-flight (fired through the chaos fault-plan
    grammar, ``proc.kill`` — the same site the fleet supervisor polls):
    the survivor must read only key-consistent values, and a later
    writer must reclaim any slot the victim left mid-write (odd seq)."""
    plan = FaultPlan.parse("seed=3;proc.kill:nth=3:crash")
    n = 64
    victim = _spawn_writer(0, n, 100_000)
    assert victim.stdout.readline().strip() == "ready"
    while True:  # the supervisor's per-tick poll, verbatim
        time.sleep(0.02)
        if plan.poll("proc.kill") is not None:
            victim.send_signal(signal.SIGKILL)
            break
    victim.communicate()
    assert victim.returncode == -signal.SIGKILL

    tier = position_tier.get_tier()
    keys = np.array(
        [(i * 0x9E3779B97F4A7C15) & ((1 << 64) - 1) or 1 for i in range(n)],
        dtype=np.uint64,
    )
    expected = np.array([_val_of(int(k)) for k in keys], np.int32)
    out = np.zeros(n, np.int32)
    mask = np.zeros(n, bool)
    hits = tier.probe_nnue_block(keys, out, mask)
    assert (out[mask] == expected[mask]).all(), "post-kill torn value"
    # Reclaim: re-inserting the full window must make every key
    # probeable again, including any slot killed mid-write.
    tier.insert_nnue_block(keys, expected)
    mask = np.zeros(n, bool)
    assert tier.probe_nnue_block(keys, out, mask) == n, (
        f"dead writer's slots not reclaimed (first pass served {hits})"
    )
    assert (out == expected).all()


# -- service integration (one pid, fleet shape) -----------------------------


def test_service_fleet_tier_parity_and_reuse(tier_env, monkeypatch):
    """The supervisor-respawn shape in one process: run A populates the
    segment, the process cache dies (reset), run B warm-starts off the
    TIER — analyses bit-identical to tier-off, pre-wire hits > 0,
    fewer dispatches than the cold run. Also pins satellite wiring:
    tier hits ride the same hmask the provide-time fc_pool_tt_fill
    loop consumes, so parity here covers the TT back-fill path too."""
    from test_eval_cache import _smoke

    weights = NnueWeights.random(seed=7)
    monkeypatch.setenv("FISHNET_POSITION_TIER", "0")
    position_tier.reset_tier()
    eval_cache.reset_cache()
    off, c_off = _smoke(weights)

    monkeypatch.setenv("FISHNET_POSITION_TIER", "1")
    position_tier.reset_tier()
    eval_cache.reset_cache()
    cold, c_cold = _smoke(weights)
    assert cold == off, "tier-on cold run changed analysis output"

    eval_cache.reset_cache()  # process death; the segment survives
    warm, c_warm = _smoke(weights)
    assert warm == off, "tier-warmed run changed analysis output"
    assert c_warm["cache_prewire_hits"] > 0
    assert c_warm["dispatches"] < c_cold["dispatches"], (
        c_warm["dispatches"], c_cold["dispatches"],
    )
    assert position_tier.stats().get("hits.local.nnue", 0) > 0
    eval_cache.reset_cache()


# -- two-process cross-process-hit smoke (make fleet-cache-smoke) -----------

# Driver child: a real SearchService run against the shared segment,
# emitting (analyses, tier stats) as one JSON line. Sequential
# submissions keep the schedule deterministic across processes.
_DRIVER = r"""
import asyncio, json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from fishnet_tpu.nnue.weights import NnueWeights
from fishnet_tpu.search.service import SearchService
from fishnet_tpu.cluster import position_tier

FENS = [
    "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
    "r1bqkbnr/pppp1ppp/2n5/4p3/4P3/5N2/PPPP1PPP/RNBQKB1R w KQkq - 2 3",
    "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
    "4rrk1/pp1n3p/3q2pQ/2p1pb2/2PP4/2P3N1/P2B2PP/4RRK1 b - - 7 19",
]

svc = SearchService(
    weights=NnueWeights.random(seed=7), pool_slots=8, batch_capacity=256,
    tt_bytes=8 << 20, backend="jax", pipeline_depth=4, driver_threads=1,
)
svc.set_prefetch(0, adaptive=False)


async def go():
    out = []
    for fen in FENS:
        r = await svc.search(fen, [], nodes=160)
        out.append([
            r.best_move, r.depth,
            [[l.multipv, l.depth, l.is_mate, l.value, list(l.pv)]
             for l in r.lines],
        ])
    return out


analyses = asyncio.run(go())
svc.close()
print(json.dumps({"analyses": analyses, "stats": position_tier.stats()}))
"""


def _run_driver(seg: Path) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO_ROOT)
    env["JAX_PLATFORMS"] = "cpu"
    env["FISHNET_POSITION_TIER"] = "1"
    env["FISHNET_POSITION_TIER_PATH"] = str(seg)
    env["FISHNET_POSITION_TIER_CAPACITY"] = "4096"
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER],
        env=env, capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_fleet_cache_two_process_smoke(tmp_path):
    """THE cross-process assertion: process A pays the evals and
    populates the shared segment; process B — a genuinely different
    pid — replays the same traffic and must take fleet-scope tier hits
    (owner != pid) with bit-identical analyses."""
    seg = tmp_path / "fleet.seg"
    a = _run_driver(seg)
    b = _run_driver(seg)
    assert b["analyses"] == a["analyses"], (
        "cross-process tier reuse changed analysis output"
    )
    fleet_hits = b["stats"].get("hits.fleet.nnue", 0)
    assert fleet_hits > 0, b["stats"]
    assert a["stats"].get("hits.fleet.nnue", 0) == 0, a["stats"]
    assert a["stats"].get("attach.fleet", 0) == 1
