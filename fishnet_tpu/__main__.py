"""Process entry point: ``python -m fishnet_tpu``.

Equivalent of the reference's main()/run() supervisor
(src/main.rs:44-260): resolve config, dispatch subcommands, then start
the actor fleet — API actor, queue actor, one worker per core — with
two-phase signal handling (first SIGINT drains, second aborts) and the
120 s summary line.
"""

from __future__ import annotations

import os as _os

# SSLKEYLOGFILE is applied globally by CPython's ssl module (and thus by
# aiohttp at import time); an unopenable path would otherwise crash the
# process inside `import aiohttp`. Validate early and degrade to a
# warning, matching the reference's rustls KeyLogFile behavior.
_keylog = _os.environ.get("SSLKEYLOGFILE")
if _keylog:
    try:
        with open(_keylog, "a"):
            pass
    except OSError as _err:
        import sys as _sys

        _sys.stderr.write(f"W: Ignoring unopenable SSLKEYLOGFILE {_keylog!r}: {_err}\n")
        del _os.environ["SSLKEYLOGFILE"]

import asyncio
import signal
import sys
from typing import Optional

from fishnet_tpu import configure as configure_mod
from fishnet_tpu import systemd as systemd_mod
from fishnet_tpu.configure import ConfigError, Opt
from fishnet_tpu.engine.base import EngineFactory
from fishnet_tpu.sched.queue import BacklogOpt
from fishnet_tpu.utils.logger import Logger
from fishnet_tpu.utils.stats import StatsRecorder
from fishnet_tpu.version import __version__

LICENSE_NOTICE = """\
fishnet-tpu is free software: you can redistribute it and/or modify it
under the terms of the GNU General Public License as published by the
Free Software Foundation, either version 3 of the License, or (at your
option) any later version. It is distributed WITHOUT ANY WARRANTY; see
https://www.gnu.org/licenses/gpl-3.0.html for the full text.
"""


def _check_key_over_network(endpoint: str, key: str) -> Optional[str]:
    """Live key validation for the config dialog (configure.rs:474-492)."""
    from fishnet_tpu.net import api as api_mod

    async def check() -> Optional[str]:
        stub, actor = api_mod.channel(endpoint, key, Logger())
        task = asyncio.ensure_future(actor.run())
        try:
            err = await asyncio.wait_for(stub.check_key(), timeout=15.0)
            return None if err is None else str(err)
        except asyncio.TimeoutError:
            return None  # network error: accept, like the reference retry path
        finally:
            actor.stop()
            task.cancel()

    try:
        return asyncio.run(check())
    except Exception as err:  # server unreachable: don't block configuration
        sys.stderr.write(f"W: Could not verify key: {err}\n")
        return None


def log_devices(logger: Logger) -> None:
    """Say once, at start-up, what JAX will run on. An engine that
    silently landed on the CPU backend looks exactly like a slow TPU
    from the outside; this line and the ``platform`` / ``device_kind``
    labels of ``fishnet_service_info`` are how an operator tells."""
    import jax

    devices = jax.devices()
    logger.info(
        f"JAX devices: platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} count={len(devices)} "
        f"(jax {jax.__version__})."
    )


def validate_mesh(opt: Opt) -> None:
    """Fail an explicit --mesh DxM that exceeds the visible devices NOW,
    with a clean ConfigError — the service itself is built lazily (inside
    the engine factory's rebuild path), where a config mistake would
    otherwise surface as an endless worker-restart backoff loop."""
    mesh_spec = opt.resolved_mesh()
    if mesh_spec in ("auto", "off"):
        return
    import jax

    data, model = (int(x) for x in mesh_spec.split("x"))
    n = len(jax.devices())
    if data * model > n:
        raise ConfigError(f"--mesh {mesh_spec} needs {data * model} devices, found {n}")


def resolve_mesh_devices(opt: Opt, logger: Logger):
    """The placement-aware serving mesh request for SearchService
    (doc/sharding.md): "auto" follows the visible devices, an explicit
    DxM pins the shard count at D * M, and --mesh off stays
    single-device. The service itself degrades to the single-device
    path when fewer than two devices remain (or FISHNET_NO_MESH=1)."""
    mesh_spec = opt.resolved_mesh()
    if mesh_spec == "off":
        return None
    if mesh_spec == "auto":
        import jax

        if len(jax.devices()) < 2:
            return None
        logger.info(
            f"Placement-aware serving mesh over {len(jax.devices())} "
            "devices (per-shard dispatch from the coalescer)."
        )
        return "auto"
    validate_mesh(opt)
    data, model = (int(x) for x in mesh_spec.split("x"))
    n = data * model
    if n < 2:
        return None
    logger.info(
        f"Placement-aware serving mesh over {n} devices "
        "(per-shard dispatch from the coalescer)."
    )
    return n


def build_search_service(opt: Opt, logger: Logger, psqt_path=None):
    """The shared batched-search backend, from CLI options (dev-mode
    random weights when no --nnue-file is given). Without --pipeline the
    depth is probed for DEVICE dispatch overlap and floored at 2, so the
    host phase (fiber stepping, feature extraction) of one group can
    overlap the other group's device wait. With >1 visible
    device (or an explicit --mesh) the service drives the whole mesh
    from the coalescer — per-shard placed dispatches, doc/sharding.md.
    ``psqt_path`` requests a rung of the eval-path lattice (the
    degradation ladder's seam, resilience/supervisor.py); None =
    auto-select."""
    from fishnet_tpu.nnue.weights import NnueWeights
    from fishnet_tpu.search.service import SearchService, suggest_pipeline_depth

    if opt.nnue_file:
        weights = NnueWeights.load(opt.nnue_file)
    else:
        logger.warn("No --nnue-file given; using random NNUE weights (dev mode).")
        weights = NnueWeights.random(seed=0)

    # Split plane (FISHNET_RPC=1, doc/disaggregation.md): this process
    # is a FRONTEND — no local evaluator, no dispatch probe; every eval
    # microbatch rides the shared-memory ring to the evaluator host.
    # Unset/0 falls through to the monolithic build below byte-for-byte.
    from fishnet_tpu.rpc import rpc_enabled

    if rpc_enabled():
        from fishnet_tpu.rpc.client import RemoteBackend

        logger.info(
            "FISHNET_RPC=1: frontend role — eval traffic rides the "
            "shared-memory ring transport to the evaluator host."
        )
        return RemoteBackend(
            weights=weights,
            net_path=opt.nnue_file,
            batch_capacity=opt.resolved_microbatch(),
            pipeline_depth=opt.pipeline or 2,
            driver_threads=opt.resolved_search_threads(),
            psqt_path=psqt_path,
        )

    # Place the compile cache before the first jit (the start-up probe
    # below compiles; JAX decides at its first compile whether a cache
    # is in use). Covers both `run` and `uci`.
    from fishnet_tpu.utils import compile_cache

    compile_cache.configure()
    mesh_devices = resolve_mesh_devices(opt, logger)

    depth = opt.pipeline
    dispatch_probe = None
    if depth is None:
        # Probe at the production microbatch size: overlap ratios are
        # shape-dependent (dispatch overhead vs compute time). The
        # same probe run reports the fixed-vs-marginal dispatch cost
        # that seeds the dispatch coalescer's width policy. A probe
        # that cannot dispatch means the service cannot either: its
        # error is the start-up error.
        depth, dispatch_probe = suggest_pipeline_depth(
            weights,
            size=max(64, min(opt.resolved_microbatch(), 4096)),
            return_probe=True,
        )
        logger.info(
            f"Dispatch cost probe: fixed {dispatch_probe.fixed_ms} ms, "
            f"marginal {dispatch_probe.marginal_ms_per_kslot} ms/kslot."
        )
        # The probe only sees DEVICE dispatch overlap; the e2e step also
        # contains the host phase (fiber stepping, feature extraction,
        # emission) that depth >= 2 can overlap with the device wait
        # even where the probe alone says 1. Floor at 2; explicit
        # --pipeline still pins any value. The floor is UNMEASURED on
        # the current machine (ROADMAP D6 decides its fate).
        depth = max(2, depth)
        logger.info(f"Pipelining {depth} eval batches (host/device overlap).")
    return SearchService(
        weights=weights,
        net_path=opt.nnue_file,  # native pool reads the original file
        batch_capacity=opt.resolved_microbatch(),
        pipeline_depth=depth,
        mesh_devices=mesh_devices,
        driver_threads=opt.resolved_search_threads(),
        psqt_path=psqt_path,
        dispatch_probe=dispatch_probe,
    )


def build_engine_factory(opt: Opt, logger: Logger) -> EngineFactory:
    """Select the backend behind the engine seam (north star: the
    `--engine tpu-nnue` flavor replaces stockfish.rs subprocesses)."""
    engine = opt.resolved_engine()
    from fishnet_tpu.rpc import rpc_enabled

    if engine == "az-mcts" or (engine == "tpu-nnue" and not rpc_enabled()):
        # The engines that compile: say what the programs will run on.
        # A split-plane frontend (FISHNET_RPC=1) evaluates in another
        # process and must never initialise a JAX backend: a chip
        # belongs to one process at a time, and that process is the
        # evaluator.
        log_devices(logger)
    if engine == "tpu-nnue":
        from fishnet_tpu.engine.tpu_engine import TpuNnueEngineFactory
        from fishnet_tpu.resilience.supervisor import ServiceSupervisor

        validate_mesh(opt)  # fail fast, before the service is built
        # The supervisor owns respawns: every rebuild of a dead service
        # goes through its bounded respawn budget and — after repeated
        # rapid deaths — steps the eval path down the degradation
        # ladder (fused -> xla -> host-material, doc/resilience.md).
        # The FIRST build is not a respawn: run_client builds and warms
        # it through factory.prepare() before acquiring, and a failure
        # there ends the process instead of starting down the ladder.
        supervisor = ServiceSupervisor(
            lambda rung: build_search_service(opt, logger, psqt_path=rung),
            logger=logger,
        )
        factory = TpuNnueEngineFactory(service_builder=supervisor.build)
        # Exposed so run_client can hand the ladder to the front end's
        # shed policy (a degraded rung shrinks admission capacity).
        factory.supervisor = supervisor
        return factory
    if engine == "az-mcts":
        import jax

        from fishnet_tpu.engine.az_engine import AzMctsEngineFactory, AzMctsService
        from fishnet_tpu.models.az import az_config_from_params, init_az_params
        from fishnet_tpu.search.mcts import MctsConfig

        from fishnet_tpu.utils import compile_cache

        compile_cache.configure()  # before init_az_params' first jit
        if opt.az_net_file:
            import zipfile

            import numpy as np

            # Checkpoints carry no explicit architecture metadata; every
            # AzConfig field is recoverable from parameter shapes, and a
            # missing/corrupt/non-AZ file fails here with a clear message
            # instead of a traceback or a shape error inside the jitted
            # forward at warmup.
            try:
                loaded = np.load(opt.az_net_file)
                params = {k: loaded[k] for k in loaded.files}
                az_cfg = az_config_from_params(params)
            except (OSError, ValueError, zipfile.BadZipFile) as err:
                raise ConfigError(f"--az-net-file {opt.az_net_file}: {err}") from err
            cfg = MctsConfig(batch_capacity=opt.resolved_microbatch(), az=az_cfg)
        else:
            logger.warn("No --az-net-file given; using random policy+value net (dev mode).")
            cfg = MctsConfig(batch_capacity=opt.resolved_microbatch())
            params = init_az_params(jax.random.PRNGKey(0), cfg.az)
        # Variant work can't ride the AZ policy encoding; route it to the
        # native HCE alpha-beta tier (scalar backend: no device traffic).
        from fishnet_tpu.engine.tpu_engine import TpuNnueEngineFactory
        from fishnet_tpu.nnue.weights import NnueWeights
        from fishnet_tpu.search.service import SearchService

        fallback_service = SearchService(
            weights=NnueWeights.random(seed=0), backend="scalar",
            pool_slots=64, batch_capacity=64,
        )
        return AzMctsEngineFactory(
            AzMctsService(params, cfg),
            variant_fallback=TpuNnueEngineFactory(fallback_service),
        )
    if engine == "uci":
        from fishnet_tpu.engine.uci import UciEngineFactory

        if not opt.engine_exe:
            raise ConfigError("--engine uci requires --engine-exe")
        return UciEngineFactory(opt.engine_exe, logger=logger)
    if engine == "mock":
        from fishnet_tpu.engine.mock import MockEngineFactory

        # Per-position artificial latency, for harnesses that need
        # realistic in-flight windows (a SIGKILL should strand work
        # mid-unit the way a real multi-second analysis would, not hit
        # the sub-ms gaps of an instant engine).
        delay = float(_os.environ.get("FISHNET_MOCK_ENGINE_DELAY", 0) or 0)
        if delay > 0:
            return MockEngineFactory(delay_seconds=delay)
        return MockEngineFactory()
    raise ConfigError(f"unknown engine backend: {engine!r}")


async def run_client(opt: Opt, logger: Logger) -> None:
    """The supervisor loop (main.rs:76-260)."""
    from fishnet_tpu.client import Client
    from fishnet_tpu.resilience import drain
    from fishnet_tpu.search import eval_cache as eval_cache_mod

    from pathlib import Path

    stats = StatsRecorder(
        cores=opt.resolved_cores(),
        stats_file=Path(opt.stats_file) if opt.stats_file else None,
        no_stats_file=opt.no_stats_file,
    )

    # Live telemetry (opt-in via --metrics-port / MetricsPort ini key):
    # /metrics + /json on an http.server thread, span recording in the
    # pipeline hot paths, SIGUSR2 armed to dump the flight recorder.
    # --spans-dir / SpansDir steers where the flight recorder dumps its
    # fishnet-spans-<pid>.jsonl (spans.default_path reads the env var;
    # exporting keeps engine subprocesses consistent with this process).
    if opt.spans_dir is not None:
        _os.environ["FISHNET_SPANS_DIR"] = opt.spans_dir

    exporter = None
    if opt.metrics_port is not None:
        from fishnet_tpu import telemetry
        from fishnet_tpu.utils.stats import register_stats_collector

        exporter = telemetry.start_exporter(opt.metrics_port)
        register_stats_collector(stats)
        logger.info(
            f"Serving telemetry on http://127.0.0.1:{exporter.port}/metrics "
            "(SIGUSR2 dumps the span flight recorder)."
        )
        if opt.metrics_port_file is not None:
            # Written AFTER bind so the port is live when read; atomic
            # rename so a fleet aggregator polling the file never sees
            # a half-written number.
            tmp = f"{opt.metrics_port_file}.tmp"
            with open(tmp, "w", encoding="utf-8") as fp:
                fp.write(f"{exporter.port}\n")
            _os.replace(tmp, opt.metrics_port_file)

    if opt.spans_journal is not None:
        # Batch-span write-ahead for the fleet stitcher: spans recorded
        # between the aggregator's last scrape and a SIGKILL survive on
        # disk; the aggregator tails this file per incarnation.
        from fishnet_tpu.telemetry.spans import RECORDER as _span_recorder

        _span_recorder.journal_to(opt.spans_journal)

    # Deterministic fault injection (--fault-plan / FISHNET_FAULT_PLAN):
    # a testing/soak aid — loudly flagged, never silently active.
    plan_spec = opt.resolved_fault_plan()
    if plan_spec:
        from fishnet_tpu.resilience import faults

        faults.install(plan_spec)
        logger.error(
            f"FAULT INJECTION ACTIVE ({plan_spec!r}). "
            "Never run this against production traffic."
        )

    # Warm-restart snapshot (FISHNET_EVAL_CACHE_SNAPSHOT): reload the
    # previous process's eval cache so the first warm batches resolve
    # pre-wire. The net fingerprint keys the snapshot to the serving
    # weights — a mismatch discards it cleanly (doc/eval-cache.md).
    net_fp = (
        eval_cache_mod.net_fingerprint(opt.nnue_file) if opt.nnue_file else 0
    )
    # The AZ cache rides the same snapshot under its own fingerprint
    # (az params hash, 0 for dev-mode random weights) so a restarted
    # MCTS fleet warm-starts pre-wire too.
    az_fp = 0
    if opt.az_net_file:
        try:
            import numpy as _np

            with _np.load(opt.az_net_file) as _loaded:
                az_fp = eval_cache_mod.az_net_fingerprint(
                    {k: _loaded[k] for k in _loaded.files}
                )
        except (OSError, ValueError, KeyError):
            az_fp = 0
    if eval_cache_mod.snapshot_path() is not None:
        if eval_cache_mod.load_snapshot(
            fingerprint=net_fp, az_fingerprint=az_fp
        ):
            cache = eval_cache_mod.get_cache()
            n = len(cache) if cache is not None else 0
            az_cache = eval_cache_mod.get_az_cache()
            n_az = len(az_cache) if az_cache is not None else 0
            logger.info(
                f"Restored {n} eval-cache entries "
                f"(+{n_az} az) from snapshot."
            )

    engine_factory = build_engine_factory(opt, logger)
    shed_policy = None
    if opt.lane_depth_limit is not None:
        from fishnet_tpu.resilience.shedding import ShedPolicy
        from fishnet_tpu.resilience.supervisor import any_breaker_open

        sup = getattr(engine_factory, "supervisor", None)
        shed_policy = ShedPolicy(
            high_watermark=opt.lane_depth_limit,
            breaker_open_fn=any_breaker_open,
            rung_fn=(lambda: sup.rung) if sup is not None else None,
        )
    client = Client(
        endpoint=opt.resolved_endpoint(),
        key=opt.key,
        cores=opt.resolved_cores(),
        engine_factory=engine_factory,
        logger=logger,
        stats=stats,
        backlog=BacklogOpt(user=opt.user_backlog, system=opt.system_backlog),
        max_backoff=opt.resolved_max_backoff(),
        workers=opt.resolved_workers(),
        batch_deadline=opt.batch_deadline,
        tenants=opt.resolved_tenants(),
        shed_policy=shed_policy,
        supervisor=getattr(engine_factory, "supervisor", None),
    )
    if opt.resolved_workers() != opt.resolved_cores():
        shared = opt.resolved_engine() in ("tpu-nnue", "az-mcts")
        what = ("over the shared device service" if shared
                else "(one engine instance per worker)")
        logger.info(
            f"Analyzing up to {opt.resolved_workers()} positions "
            f"concurrently {what}."
        )

    stop = asyncio.Event()
    sigints = 0
    sigterms = 0
    drain_guard: Optional[asyncio.Task] = None

    def on_sigint() -> None:
        nonlocal sigints
        sigints += 1
        if sigints == 1:
            logger.fishnet_info("Stopping soon. Press ^C again to abort pending batches ...")
            drain.begin("sigint", depth_fn=client.queue_depth)
            client.shutdown_soon()
        else:
            logger.fishnet_info("Stopping now.")
            stop.set()

    def on_sigterm() -> None:
        # Graceful drain (doc/resilience.md): stop acquiring, flush
        # in-flight batches until the deadline, then abort the rest
        # upstream (accounted — the server reassigns) and exit 0.
        # Readiness (/healthz, /healthz/ready) flips to 503 so an
        # orchestrator stops routing at this process; liveness
        # (/healthz/live) stays 200 — draining is not wedged.
        nonlocal sigterms, drain_guard
        sigterms += 1
        if sigterms > 1:
            logger.fishnet_info("Stopping now.")
            stop.set()
            return
        deadline = opt.resolved_drain_deadline()
        logger.fishnet_info(
            f"SIGTERM: draining (flushing in-flight batches, deadline "
            f"{deadline:.0f}s; send SIGTERM again to abort now) ..."
        )
        drain.begin("sigterm", deadline=deadline, depth_fn=client.queue_depth)
        client.shutdown_soon()

        async def deadline_guard() -> None:
            await asyncio.sleep(deadline)
            logger.fishnet_info(
                "Drain deadline reached; aborting remaining batches upstream."
            )
            stop.set()

        drain_guard = asyncio.create_task(deadline_guard())

    loop = asyncio.get_running_loop()
    try:
        loop.add_signal_handler(signal.SIGINT, on_sigint)
        loop.add_signal_handler(signal.SIGTERM, on_sigterm)
    except NotImplementedError:  # non-Unix
        pass

    # Periodic auto-update (main.rs:179-199): every 5 h re-check the
    # release channel; an installed update drains work (shutdown_soon ->
    # wait_drained resolves the supervisor wait) and the restart happens
    # after teardown below — the reference's drain-then-exec, exactly.
    restart_to = None  # UpdateStatus of a staged, deferred install

    async def update_loop() -> None:
        nonlocal restart_to
        from fishnet_tpu.update import UPDATE_INTERVAL_SECONDS, apply_update

        while True:
            await asyncio.sleep(UPDATE_INTERVAL_SECONDS)
            try:
                status = await apply_update(
                    logger=logger, allow_default=True, defer_promote=True
                )
            except Exception as err:  # noqa: BLE001 - keep serving on failures
                logger.error(f"Periodic update check failed: {err}")
                continue
            if status.updated:
                logger.fishnet_info(
                    f"Update {status.latest} staged; draining before restart ..."
                )
                restart_to = status
                client.shutdown_soon()
                return

    # Warm before acquiring (doc/resilience.md "Start-up"): an acquired
    # job's 60 s + timeout budget must not pay for XLA compiles, and a
    # backend that cannot start must fail HERE, not as aborted batches.
    try:
        await engine_factory.prepare()
    except BaseException:
        engine_factory.close()
        if exporter is not None:
            exporter.close()
        raise
    logger.fishnet_info(f"fishnet-tpu {__version__} connecting to {opt.resolved_endpoint()}")
    await client.start()
    summary = asyncio.create_task(client.run_summary_loop())
    updater = (
        asyncio.create_task(update_loop()) if opt.auto_update else None
    )
    # Exit on explicit stop (second ^C / SIGTERM) OR when a first-^C
    # drain completes on its own (main.rs:248-259).
    stop_task = asyncio.create_task(stop.wait())
    drained_task = asyncio.create_task(client.wait_drained())
    try:
        await asyncio.wait({stop_task, drained_task}, return_when=asyncio.FIRST_COMPLETED)
    finally:
        for t in (stop_task, drained_task, summary, updater, drain_guard):
            if t is not None:
                t.cancel()
        await client.stop(abort_pending=stop.is_set())
        # Persist the eval cache for a warm restart (no-op unless
        # FISHNET_EVAL_CACHE_SNAPSHOT is set). After client.stop so the
        # snapshot holds the final working set; before engine teardown
        # so a slow native close can't outlive the write.
        if eval_cache_mod.snapshot_path() is not None:
            eval_cache_mod.save_snapshot(
                fingerprint=net_fp, az_fingerprint=az_fp
            )
        # Tear down shared engine backends before interpreter exit: a
        # daemon driver thread still inside native/JAX code when Python
        # unwinds takes the process down with SIGABRT.
        engine_factory.close()
        # Flush the (interval-debounced) stats file and stop serving
        # scrapes before teardown completes.
        stats.flush()
        if drain.draining():
            from fishnet_tpu import telemetry

            if telemetry.enabled():
                telemetry.RECORDER.dump(reason="drain")
        if exporter is not None:
            exporter.close()
        logger.fishnet_info(client.stats_summary())
    # Promote + restart only on a clean drain with no operator stop
    # intent: a second ^C / SIGTERM (stop) or even a single ^C (drain
    # then EXIT) must actually stop — resurrecting a unit systemd just
    # killed is worse than missing one update cycle. Deliberately after
    # the try/finally (never inside it: a `return` there would swallow
    # an in-flight CancelledError). The install lands HERE, once the
    # engines are torn down, so no live process ever has files swapped
    # under it (update.py promote_staged).
    if restart_to is not None and not stop.is_set() and sigints == 0 and sigterms == 0:
        from fishnet_tpu.update import (
            default_install_root,
            promote_staged,
            restart_process,
        )

        ok = True
        if restart_to.staged is not None:
            try:
                promote_staged(restart_to.staged, default_install_root())
            except Exception as err:  # noqa: BLE001
                logger.error(f"Update promotion failed: {err}")
                ok = False
        elif restart_to.command:
            # Async subprocess (R1): run_client is still on the event
            # loop here; even post-drain, a sync subprocess.run would
            # block signal handlers and any late api-actor I/O.
            proc = await asyncio.create_subprocess_exec(*restart_to.command)
            rc = await proc.wait()
            if rc != 0:
                logger.error(f"Update command failed with exit code {rc}.")
                ok = False
        if ok:
            restart_process(logger, restart_to.latest)


def main(argv=None) -> int:
    try:
        opt = configure_mod.parse_and_configure(argv, key_check=_check_key_over_network)
    except ConfigError as err:
        sys.stderr.write(f"E: {err}\n")
        return 2

    logger = Logger(verbose=opt.verbose, stderr=opt.is_systemd())

    if opt.command == "license":
        print(LICENSE_NOTICE)
        return 0
    if opt.command == "systemd":
        systemd_mod.systemd_system(opt)
        return 0
    if opt.command == "systemd-user":
        systemd_mod.systemd_user(opt)
        return 0
    if opt.command == "configure":
        return 0  # dialog already ran inside parse_and_configure
    if opt.command == "verify-net":
        # One-command compatibility proof for a user-supplied real net
        # (the reference embeds its net at build time, build.rs:7; no
        # real net can exist offline here, so the proof is shipped
        # instead — see fishnet_tpu/verify_net.py).
        if not opt.nnue_file:
            sys.stderr.write("E: verify-net requires --nnue-file PATH\n")
            return 2
        from fishnet_tpu.verify_net import run_cli

        return run_cli(str(opt.nnue_file))
    if opt.command == "uci":
        from fishnet_tpu.uci_server import serve

        # stdout belongs to the UCI protocol; all logging goes to stderr.
        logger = Logger(verbose=opt.verbose, stderr=True)
        try:
            service = build_search_service(opt, logger)
        except ConfigError as err:
            sys.stderr.write(f"E: {err}\n")
            return 2
        try:
            asyncio.run(serve(service))
        except KeyboardInterrupt:
            pass
        finally:
            service.close()
        return 0

    if opt.auto_update:
        from fishnet_tpu.update import auto_update

        auto_update(logger)

    try:
        asyncio.run(run_client(opt, logger))
    except KeyboardInterrupt:
        pass
    except ConfigError as err:
        # Late config errors (e.g. a bad --az-net-file discovered while
        # building the engine factory) exit cleanly, not as a traceback.
        sys.stderr.write(f"E: {err}\n")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
