#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that fishnet-tpu starts and serves
on the chip. Run from the root of a checkout: ``python3 chip_smoke.py``.

It drives the client's main path once through the entry point a user
calls (``python -m fishnet_tpu run``) against the in-repo fake lichess
server, at SFNNv5's published width (22528 features x L1 1024, random
weights from a seed), and checks what comes back:

* Phase A (kernel): the compiled fused FT-gather kernel against its XLA
  twin, bit-identical, at the real table size over every wire entry kind
  (plain and anchor fulls, in-batch and persistent deltas with and
  without a perspective swap), single-group and segmented, PSQT fused.
* Phase B (tpu-nnue client): analysis and best-move jobs acquired,
  searched on the chip, submitted exactly once, drained on SIGTERM.
* Phase C (az-mcts client): the same for the repo's second net.

This parent process NEVER imports JAX: a chip belongs to one process at
a time, so it only hosts the fake server (aiohttp) and runs one
chip-owning child after another. The native core is built in this run
from cpp/src + cpp/Makefile into a fresh directory and the children load
that library and no other. Any failed check, a child that exits
non-zero, or a phase over its time limit ends the run with a non-zero
exit code and no result line; without a TPU it fails in seconds.

The last line of stdout is one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
the device as JAX reports it. The line before it is the report (also
written to ``chiprun_out/chip_smoke/report.json``): versions, compile
cache directory and entry counts, seconds of start-up and of each phase,
nodes, dispatches, shipped evals.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / "chiprun_out" / "chip_smoke"

#: Seconds each step may take; the whole run must fit the driver's 1200.
LIMITS = {"kernel": 300, "build": 180, "nnue": 360, "az": 240}

#: Exit code of the kernel child when JAX reports no TPU.
NO_TPU = 3

#: Node budget per analysed ply of the NNUE jobs. Sized to the rate the
#: CLI's defaults deliver today on a 13-core v5e host (~3.4k nodes/s
#: with its 12 driver threads, PERF.md section 5): every position of
#: every job is in flight at once, and each must finish inside the
#: worker's 60 s + 7 s budget with a wide margin (4000 nodes/ply took
#: 35 s). A smoke, not a benchmark.
ANALYSIS_NODES = 2000
GAMES = (
    "e2e4 e7e5 g1f3 b8c6 f1b5 a7a6 b5a4 g8f6 e1g1 f8e7",
    "d2d4 g8f6 c2c4 e7e6 b1c3 f8b4 e2e3 e8g8",
    "c2c4 e7e5 b1c3 g8f6 g1f3 b8c6 g2g3 d7d5",
)


class SmokeFailure(Exception):
    """A check failed: the run exits non-zero and prints no result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# -- compile cache (parent side: count only; fishnet_tpu places it) ---------


def cache_dir() -> Path:
    # compile_cache imports jax only inside configure(): safe here.
    from fishnet_tpu.utils import compile_cache

    return Path(compile_cache.cache_dir())


def cache_entries() -> int:
    d = cache_dir()
    if not d.is_dir():
        return 0
    return sum(1 for p in d.rglob("*") if p.is_file())


# -- Phase A child: the only code in this file that imports JAX -------------


def kernel_child() -> int:
    """Fused kernel vs XLA twin on the chip. Prints one JSON line."""
    import importlib.metadata as md

    import jax

    dev = jax.devices()
    platform = dev[0].platform
    if platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: JAX found no TPU (platform={platform!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "this check only runs on the chip.\n"
        )
        return NO_TPU

    import jax.numpy as jnp
    import numpy as np

    from fishnet_tpu.nnue import spec
    from fishnet_tpu.nnue.jax_eval import (
        evaluate_packed_anchored_jit,
        evaluate_packed_anchored_segmented_jit,
        params_from_weights,
    )
    from fishnet_tpu.nnue.weights import NnueWeights
    from fishnet_tpu.ops import ft_gather
    from fishnet_tpu.utils import compile_cache

    compile_cache.configure()
    params = jax.device_put(params_from_weights(NnueWeights.random(seed=0)))
    rng = np.random.default_rng(21)

    # 1) ft_accumulate on 1040 entries: two full _CHUNKs plus a ragged
    #    third, so the anchor carry crosses pallas_call boundaries.
    n_blocks, block = 260, 4
    idx, parent = all_kinds_batch(rng, n_blocks, block)
    batch = len(parent)
    check(batch > 2 * ft_gather._CHUNK, "batch must span more than two chunks")
    tab = rng.integers(-5000, 5000, (n_blocks, 2, spec.L1)).astype(np.int32)
    ptab = rng.integers(
        -4000, 4000, (n_blocks, 2, spec.NUM_PSQT_BUCKETS)
    ).astype(np.int32)
    args = dict(
        delta_base=spec.DELTA_BASE, parent=jnp.asarray(parent),
        anchor_tab=jnp.asarray(tab), ft_psqt=params["ft_psqt"],
        psqt_tab=jnp.asarray(ptab),
    )
    t0 = time.monotonic()
    acc_f, psqt_f = ft_gather.ft_accumulate(
        params["ft_w"], params["ft_b"], jnp.asarray(idx),
        use_pallas=True, interpret=False, **args,
    )
    acc_f, psqt_f = np.asarray(acc_f), np.asarray(psqt_f)
    first_fused_s = time.monotonic() - t0
    acc_x, psqt_x = ft_gather.ft_accumulate(
        params["ft_w"], params["ft_b"], jnp.asarray(idx),
        use_pallas=False, **args,
    )
    acc_x, psqt_x = np.asarray(acc_x), np.asarray(psqt_x)
    check(acc_f.shape == (batch, 2, spec.L1), f"acc shape {acc_f.shape}")
    check(psqt_f.shape == (batch, 2, 8), f"psqt shape {psqt_f.shape}")
    check(np.array_equal(acc_f, acc_x), "fused accumulators != XLA twin")
    check(np.array_equal(psqt_f, psqt_x), "fused PSQT != XLA twin")
    check(len(np.unique(acc_x[:, 0, 0])) > batch // 2, "degenerate fixture")

    # 2) The serving jit (packed wire, donated tables, scatter-back).
    packed, n_rows = pack_rows(idx, parent)
    buckets = rng.integers(0, spec.NUM_PSQT_BUCKETS, (batch,)).astype(np.int32)

    def serve(fused: bool):
        out = evaluate_packed_anchored_jit(
            params, packed, buckets, parent, None, jnp.asarray(tab),
            np.array([n_rows], np.int32), jnp.asarray(ptab),
            use_pallas=fused, interpret=False,
        )
        return [np.asarray(o) for o in jax.block_until_ready(out)]

    for name, f, x in zip(("values", "anchor table", "PSQT table"),
                          serve(True), serve(False)):
        check(np.array_equal(f, x), f"packed-anchored {name}: fused != XLA")
    vals = serve(True)[0]
    check(vals.shape == (batch,) and vals.dtype == np.int32, "values shape")
    check(int(np.abs(vals).max()) < 1_000_000, "poisoned or overflowed score")

    # 3) The coalescer's segmented form: K=2 groups, own tables each.
    k_segs, seg_blocks = 2, 132  # 2 x 528 entries: three chunks again
    size = seg_blocks * block
    tier = 4 * size + 4
    seg_packed = np.full((k_segs * tier, 2, 8), spec.NUM_FEATURES, np.uint16)
    seg_parent = np.empty((k_segs, size), np.int32)
    seg_rows = np.empty((k_segs,), np.int32)
    for k in range(k_segs):
        s_idx, s_parent = all_kinds_batch(rng, seg_blocks, block)
        rows, n = pack_rows(s_idx, s_parent)
        seg_packed[k * tier : k * tier + len(rows)] = rows
        seg_parent[k], seg_rows[k] = s_parent, n
    seg_buckets = rng.integers(0, 8, (k_segs * size,)).astype(np.int32)
    tabs = rng.integers(
        -5000, 5000, (k_segs, seg_blocks, 2, spec.L1)
    ).astype(np.int32)
    ptabs = rng.integers(-4000, 4000, (k_segs, seg_blocks, 2, 8)).astype(np.int32)

    def serve_seg(fused: bool):
        out = evaluate_packed_anchored_segmented_jit(
            params, seg_packed, seg_buckets, seg_parent.reshape(-1), None,
            jnp.asarray(tabs), seg_rows, jnp.asarray(ptabs),
            use_pallas=fused, interpret=False,
        )
        return [np.asarray(o) for o in jax.block_until_ready(out)]

    for name, f, x in zip(("values", "anchor tables", "PSQT tables"),
                          serve_seg(True), serve_seg(False)):
        check(np.array_equal(f, x), f"segmented {name}: fused != XLA")

    def median_ms(fn, fused):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            fn(fused)
            ts.append(time.perf_counter() - t0)
        return round(sorted(ts)[2] * 1e3, 3)

    print(json.dumps({
        "device": {
            "platform": platform,
            "kind": dev[0].device_kind,
            "count": len(dev),
        },
        "versions": {
            "jax": jax.__version__,
            "jaxlib": md.version("jaxlib"),
            "libtpu": md.version("libtpu"),
            "python": sys.version.split()[0],
        },
        "kernel": {
            "bit_identical": True,
            "entries": batch,
            "segmented_entries": k_segs * size,
            "first_fused_call_s": round(first_fused_s, 2),
            # Blocking host round trips of the whole eval jit at 1040
            # entries, transfers included: a smoke reading, not a
            # benchmark.
            "eval_fused_ms": median_ms(serve, True),
            "eval_xla_ms": median_ms(serve, False),
        },
    }))
    return 0


def all_kinds_batch(rng, n_blocks: int, block: int):
    """Dense [B, 2, 32] indices + wire parent codes covering EVERY entry
    kind (lifted from tests/test_ops.py build_psqt_parity_batch): blocks
    cycle anchor full (re)seed / plain full / persistent anchor delta
    with a random swap, each followed by in-batch deltas with random
    swaps against it. Block k owns anchor-table row k, so stores never
    collide within the batch (the pool's one-block-per-slot contract)."""
    import numpy as np

    from fishnet_tpu.nnue import spec

    slots, nf = spec.DELTA_SLOTS, spec.NUM_FEATURES
    active = spec.MAX_ACTIVE_FEATURES
    batch = n_blocks * block
    idx = np.full((batch, 2, active), nf, np.int32)
    parent = np.full((batch,), -1, np.int32)

    def code(row, is_delta, swap=0):
        return -(2 + ((row << 2) | (2 if is_delta else 0) | swap))

    def fill_full(e):
        idx[e, :, : active - 3] = rng.integers(0, nf, (2, active - 3))

    def fill_delta(e):
        for p in range(2):
            n_add = int(rng.integers(0, slots + 1))
            n_rem = int(rng.integers(0, slots + 1))
            idx[e, p, :n_add] = rng.integers(0, nf, n_add)
            idx[e, p, slots : slots + n_rem] = (
                spec.DELTA_BASE + rng.integers(0, nf, n_rem)
            )
            idx[e, p, slots + n_rem : 2 * slots] = spec.DELTA_BASE + nf

    for k, s in enumerate(range(0, batch, block)):
        kind = k % 3
        if kind == 1:  # plain full
            fill_full(s)
        elif kind == 2:  # persistent anchor delta (load + store)
            parent[s] = code(k, True, swap=int(rng.integers(0, 2)))
            fill_delta(s)
        else:  # anchor full (re)seed; entry 0 is always one
            parent[s] = code(k, False)
            fill_full(s)
        for e in range(s + 1, s + block):
            parent[e] = (s << 1) | int(rng.integers(0, 2))
            fill_delta(e)
    return idx, parent


def pack_rows(idx, parent):
    """The pool's packed uint16 row stream for a dense batch: 4 rows of
    [2, 8] per full entry, 1 per delta, then one sentinel block at the
    emitted-row count (doc/wire-format.md). Returns (rows, n_rows)."""
    import numpy as np

    from fishnet_tpu.nnue import spec
    from fishnet_tpu.nnue.jax_eval import is_delta_np

    rows = []
    for e, delta in enumerate(is_delta_np(parent)):
        for r in range(1 if delta else 4):
            rows.append(idx[e, :, 8 * r : 8 * r + 8])
    n_rows = len(rows)
    rows.extend([np.full((2, 8), spec.NUM_FEATURES, np.int32)] * 4)
    return np.stack(rows).astype(np.uint16), n_rows


# -- parent: children, fake server, checks ----------------------------------


def run_logged(name: str, argv, limit: float) -> str:
    """Run one child to completion under its time limit; stderr goes to a
    log file, stdout is returned. Non-zero exit or a timeout fails the
    run (subprocess.run kills the child on timeout)."""
    log_path = WORK / f"{name}.log"
    with open(log_path, "w") as err:
        try:
            proc = subprocess.run(
                argv, cwd=ROOT, stdout=subprocess.PIPE, stderr=err,
                text=True, timeout=limit,
            )
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: over its {limit}s limit ({log_path})")
    if proc.returncode != 0:
        tail = log_path.read_text()[-3000:]
        sys.stderr.write(tail)
        if proc.returncode == NO_TPU:
            raise SmokeFailure("no TPU: JAX reports no accelerator here")
        raise SmokeFailure(f"{name}: exit code {proc.returncode} ({log_path})")
    return proc.stdout


def build_native_core() -> Path:
    """Build the native core from git's files into a fresh directory, so
    nothing built elsewhere (cpp/libfishnetcore.so is -march=native and
    ignored by git) can be what the children load."""
    build = WORK / "native"
    shutil.copytree(ROOT / "cpp" / "src", build / "src")
    shutil.copy2(ROOT / "cpp" / "Makefile", build / "Makefile")
    run_logged(
        "build", ["make", "-C", str(build), "libfishnetcore.so"],
        LIMITS["build"],
    )
    lib = build / "libfishnetcore.so"
    check(lib.is_file(), "native core build produced no library")
    return lib


def scrape(port: int) -> dict:
    """Prometheus text -> {(name, frozenset(labels)): value}."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for part in rest.rstrip("}").split('",'):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k.strip()] = v.strip().strip('"')
        out[(name, frozenset(labels.items()))] = float(value)
    return out


def metric_sum(metrics: dict, name: str) -> float:
    return sum(v for (n, _), v in metrics.items() if n == name)


def metric_labels(metrics: dict, name: str) -> list:
    return [dict(lbl) for (n, lbl), _ in metrics.items() if n == name]


async def client_phase(name: str, engine_args, add_jobs, core_lib: Path,
                       limit: float) -> dict:
    """Start the client CLI against a fresh fake server, wait until every
    job handed out came back, scrape /metrics, SIGTERM, expect exit 0."""
    from tests.fake_server import VALID_KEY, FakeServer

    port_file = WORK / f"{name}.port"
    port_file.unlink(missing_ok=True)
    env = dict(os.environ, FISHNET_TPU_CORE_LIB=str(core_lib))
    deadline = time.monotonic() + limit
    async with FakeServer() as server:
        lichess = server.lichess
        analysis_ids, move_ids = add_jobs(lichess)
        argv = [
            sys.executable, "-m", "fishnet_tpu", "run", "--no-conf",
            "--no-stats-file", "--endpoint", server.endpoint,
            "--key", VALID_KEY, "--metrics-port", "0",
            "--metrics-port-file", str(port_file), *engine_args,
        ]
        t_spawn = time.monotonic()
        with open(WORK / f"{name}.log", "w") as err:
            proc = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdout=err, stderr=err,
            )
        try:
            def done() -> bool:
                return all(i in lichess.analyses for i in analysis_ids) and all(
                    i in lichess.moves for i in move_ids
                )

            while not done():
                check(proc.poll() is None,
                      f"{name}: client exited early (code {proc.returncode})")
                check(time.monotonic() < deadline,
                      f"{name}: over its {limit}s limit")
                await asyncio.sleep(0.1)
            # The client acquires only once its service is warm, so the
            # first handout marks the end of start-up (fake_server stamps
            # handouts on the same monotonic clock).
            startup_s = min(lichess.handed_at.values()) - t_spawn
            served_s = time.monotonic() - t_spawn - startup_s
            metrics = await asyncio.to_thread(
                scrape, int(port_file.read_text())
            )
            proc.send_signal(signal.SIGTERM)
            while proc.poll() is None:
                check(time.monotonic() < deadline,
                      f"{name}: no exit after SIGTERM")
                await asyncio.sleep(0.1)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        check(proc.returncode == 0, f"{name}: exit code {proc.returncode}")

        # Every job came back exactly once, nothing aborted or lost.
        report = lichess.fleet_report()
        check(report["clean"] and report["handed"] == report["completed"]
              == len(analysis_ids) + len(move_ids), f"{name}: ledger {report}")
        check(not lichess.aborted, f"{name}: aborted {lichess.aborted}")
        check(report["reassigned"] == 0, f"{name}: work was re-queued")
        nodes = 0
        for wid, plies in analysis_ids.items():
            check(lichess.analysis_submission_counts[wid] == 1,
                  f"{name}: {wid} submitted twice")
            parts = lichess.analyses[wid]["analysis"]
            check(len(parts) == plies + 1, f"{name}: {wid} has {len(parts)} parts")
            for part in parts:
                check(
                    isinstance(part, dict) and part.get("pv")
                    and "score" in part and part.get("depth", 0) > 0
                    and part.get("nodes", 0) > 0,
                    f"{name}: incomplete ply in {wid}: {part}",
                )
                nodes += part["nodes"]
        for wid in move_ids:
            check(lichess.moves[wid]["move"]["bestmove"],
                  f"{name}: {wid} has no best move")

    for family in ("fishnet_degradations_total", "fishnet_pool_respawns_total",
                   "fishnet_shard_degradations_total"):
        check(metric_sum(metrics, family) == 0, f"{name}: {family} > 0")
    return {
        "metrics": metrics, "startup_s": round(startup_s, 1),
        "served_s": round(served_s, 1), "nodes": nodes,
    }


def opening(game: int, plies: int) -> str:
    return " ".join(GAMES[game].split()[:plies])


def add_nnue_jobs(lichess):
    """-> ({analysis work id: plies}, [move work ids])."""
    analysis = {
        lichess.add_analysis_job(moves=g, nodes=ANALYSIS_NODES): len(g.split())
        for g in GAMES
    }
    moves = [
        lichess.add_move_job(moves=opening(0, 6), level=3),
        lichess.add_move_job(moves=opening(1, 7), level=6),
        lichess.add_move_job(
            moves=opening(2, 4), level=8,
            clock={"wtime": 6000, "btime": 6000, "inc": 2},
        ),
    ]
    return analysis, moves


def add_az_jobs(lichess):
    return (
        {lichess.add_analysis_job(moves=opening(1, 6), nodes=1_000_000): 6},
        [lichess.add_move_job(moves=opening(0, 6), level=4)],
    )


def check_device(name: str, labels: list, device: dict) -> None:
    check(labels, f"{name}: no device info in /metrics")
    for lbl in labels:
        check(lbl.get("platform") == "tpu" and lbl.get("device_kind"),
              f"{name}: serving on {lbl}")
        check(lbl["device_kind"] == device["kind"], f"{name}: {lbl} != {device}")


def result_line(device: dict) -> str:
    """The contract's line, last on stdout: these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main() -> int:
    for needed in ("fishnet_tpu/__main__.py", "cpp/Makefile", "cpp/src",
                   "tests/fake_server.py"):
        if not (ROOT / needed).exists():
            sys.stderr.write(
                f"chip_smoke: {needed} not found next to chip_smoke.py — "
                "run it from the root of a fishnet-tpu checkout.\n"
            )
            return 2
    sys.path.insert(0, str(ROOT))
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    cache_before = cache_entries()

    # Phase A first: its child is also the device probe, so a machine
    # without a TPU fails here in seconds, before anything is built.
    t0 = time.monotonic()
    out = run_logged(
        "kernel", [sys.executable, str(ROOT / "chip_smoke.py"), "--kernel-child"],
        LIMITS["kernel"],
    )
    result = json.loads(out.strip().splitlines()[-1])
    device = result["device"]
    result["kernel"]["phase_s"] = round(time.monotonic() - t0, 1)
    log(f"phase A ok on {device}: {result['kernel']}")

    t0 = time.monotonic()
    core_lib = build_native_core()
    result["build_s"] = round(time.monotonic() - t0, 1)
    log(f"native core built in {result['build_s']}s")

    t0 = time.monotonic()
    nnue = asyncio.run(client_phase(
        "nnue", [], add_nnue_jobs, core_lib, LIMITS["nnue"]
    ))
    m = nnue.pop("metrics")
    info = metric_labels(m, "fishnet_service_info")
    check_device("nnue", info, device)
    check(all(lbl["psqt_path"] == "fused" for lbl in info),
          f"nnue: eval path {info}")
    shard_dispatches = [
        int(v) for (n, lbl), v in sorted(
            m.items(), key=lambda kv: dict(kv[0][1]).get("shard", "")
        ) if n == "fishnet_shard_dispatches_total"
    ]
    check(len(shard_dispatches) == device["count"],
          f"nnue: {len(shard_dispatches)} shards on {device['count']} devices")
    check(all(d > 0 for d in shard_dispatches),
          f"nnue: idle shard, dispatches {shard_dispatches}")
    check(metric_sum(m, "fishnet_shard_ladder_rung") == 0, "nnue: rung > 0")
    nnue.update(
        phase_s=round(time.monotonic() - t0, 1),
        dispatches=int(metric_sum(m, "fishnet_dispatches_total")),
        evals_shipped=int(metric_sum(m, "fishnet_pool_evals_shipped_total")),
        pool_nodes=int(metric_sum(m, "fishnet_pool_nodes_total")),
        shard_dispatches=shard_dispatches,
    )
    check(nnue["dispatches"] > 0 and nnue["evals_shipped"] > 0,
          f"nnue: the device did no work: {nnue}")
    result["nnue"] = nnue
    log(f"phase B ok: {nnue}")

    t0 = time.monotonic()
    az = asyncio.run(client_phase(
        "az", ["--engine", "az-mcts"], add_az_jobs, core_lib, LIMITS["az"]
    ))
    m = az.pop("metrics")
    check_device("az", metric_labels(m, "fishnet_az_plane_info"), device)
    check(metric_sum(m, "fishnet_az_shard_ladder_rung") == 0, "az: rung > 0")
    az.update(
        phase_s=round(time.monotonic() - t0, 1),
        dispatches=int(metric_sum(m, "fishnet_az_dispatches_total")),
        evals_shipped=int(metric_sum(m, "fishnet_az_rows_dispatched_total")),
        visits=int(metric_sum(m, "fishnet_mcts_visits_total")),
    )
    check(az["dispatches"] > 0 and az["evals_shipped"] > 0,
          f"az: the device did no work: {az}")
    result["az"] = az
    log(f"phase C ok: {az}")

    result["compile_cache"] = {
        "dir": str(cache_dir()),
        "entries_before": cache_before,
        "entries_after": cache_entries(),
    }
    result["total_s"] = round(time.monotonic() - _T0, 1)
    report = json.dumps(result)
    (WORK / "report.json").write_text(report + "\n")
    print(f"report: {report}")
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    try:
        if sys.argv[1:] == ["--kernel-child"]:
            sys.exit(kernel_child())
        sys.exit(main())
    except SmokeFailure as failure:
        sys.stderr.write(f"chip_smoke: FAILED: {failure}\n")
        sys.exit(1)
