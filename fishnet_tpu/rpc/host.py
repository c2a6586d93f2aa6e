"""Evaluator host: one device-holding process serving N frontends.

The fusion story (doc/disaggregation.md): the host's sweep drains every
attached frontend's submit ring and stages each record as a ticket on
ONE process-local ``_DispatchCoalescer`` per search family — the exact
machinery a monolith uses to fuse ITS pipeline groups — so microbatches
from DIFFERENT PROCESSES fuse into the same segmented device dispatches.
Cross-process batch fill is the direct payoff: three frontends each
trickling 60%-full MCTS leaf batches become one evaluator dispatching
near-full buckets (``fishnet_rpc_fused_rows_total`` over
``fishnet_rpc_fused_slots_total``; no test measures the fill).

Parity: NNUE records carry the exact padded dense arrays the
remote-evaluator seam emits, replayed through the same
``evaluate_batch`` graph (row independence makes concat+pad
bit-identical — the host-material rung contract); AZ records carry the
exact uint8 plane wire, replayed through the identical jitted forward
``az_plane.AzDispatchPlane`` compiles, and answered with the same fp16
logits wire, so a remote round-trip reconstructs bit-identical fp32.

Failure contract (the PR 12 lease/fencing semantics across the
boundary): submit records carrying an epoch older than the link's
current frontend epoch are refused (a restarted frontend's predecessor
must never be double-served); a frontend past the lease without a
heartbeat has its link detached and unlinked, staged work dropped; an
injected ``rpc.detach`` fault (resilience/faults.py grammar) drops one
live link mid-flight — the next sweep re-attaches and the host-epoch
bump makes the frontend resubmit anything the dead attachment consumed
without answering.

Run it: ``python -m fishnet_tpu.rpc.host --nnue-file w.nnue --az-seed 0``
(the supervisor's ``role="evaluator"`` specs build this command line).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from fishnet_tpu import telemetry as _telemetry
from fishnet_tpu.resilience import faults
from fishnet_tpu.rpc import rings
from fishnet_tpu.telemetry.spans import RECORDER as _SPANS
from fishnet_tpu.search.service import (
    CoalesceBackend,
    NativeCoreError,
    _DispatchCoalescer,
)

__all__ = ["EvaluatorHost", "main"]


def _pad_bucket(total: int, floor: int = 32) -> int:
    """Dispatch-shape bucket: next power of two ≥ total (floor 32), so
    the host's compile-shape count stays logarithmic in load while the
    fill accounting sees honest padded slot counts."""
    b = floor
    while b < total:
        b *= 2
    return b


class _HostNnueBackend(CoalesceBackend):
    """Minimal CoalesceBackend over ``evaluate_batch_jit``: single
    shard, no router, no async pipes — the sweep thread is the only
    driver, so pinned-width parking plus demand-side flushing is the
    whole scheduler."""

    driver_threads = 1

    def __init__(self, params) -> None:
        self._params = params
        self._staged: Dict[int, Tuple] = {}
        self._async_pipes: List = []
        self._coalescer = _DispatchCoalescer(
            self, pinned_width=_DispatchCoalescer.MAX_WIDTH
        )

    def stage(self, group: int, feats, buckets, parents, material) -> None:
        self._staged[group] = (feats, buckets, parents, material)

    def _run(self, segs: List[Tuple]) -> np.ndarray:
        from fishnet_tpu.nnue import spec
        from fishnet_tpu.nnue.jax_eval import evaluate_batch_jit

        tel = _telemetry.enabled()
        t0 = time.monotonic() if tel else 0.0
        total = sum(len(s[1]) for s in segs)
        bucket = _pad_bucket(total)
        feats = np.full((bucket, 2, 32), spec.NUM_FEATURES, np.uint16)
        buckets = np.zeros(bucket, np.int32)
        parents = np.full(bucket, -1, np.int32)
        material = np.zeros(bucket, np.int32)
        off = 0
        for f, b, p, m in segs:
            k = len(b)
            feats[off : off + k] = f
            buckets[off : off + k] = b
            material[off : off + k] = m
            pp = np.array(p, np.int32, copy=True)
            # Delta parent codes index BATCH ENTRIES (code >> 1, low bit
            # = perspective swap): rebase each segment's references by
            # its entry offset in the fused batch.
            pp[pp >= 0] += off << 1
            parents[off : off + k] = pp
            off += k
        values = np.ascontiguousarray(
            np.asarray(
                evaluate_batch_jit(
                    self._params, feats, buckets, parents, material
                )
            ),
            np.int32,
        )
        rings.note("fused.rows.nnue", total)
        rings.note("fused.slots.nnue", bucket)
        if bucket > total:
            rings.note("pad.rows", bucket - total)
        if tel:
            _SPANS.record(
                "dispatch_issue", t0, width=len(segs),
                n=total, slots=bucket, fill=total / bucket,
            )
        return values

    def _dispatch_eval(self, group: int, n: int, rows: int):
        values = self._run([self._staged.pop(group)])
        return values[:n], (n, n * (2 * 32 * 2 + 12), n * 4)

    def _dispatch_segmented(self, tickets) -> None:
        segs = [self._staged.pop(tk.group) for tk in tickets]
        full = self._run(segs)
        off = 0
        for tk, seg in zip(tickets, segs):
            k = len(seg[1])
            tk.values = full[off : off + k]
            tk.start, tk.seg_size = 0, k
            tk.acct = (k, k * (2 * 32 * 2 + 12), k * 4)
            off += k


class _HostAzBackend(CoalesceBackend):
    """AZ twin: the identical jitted forward the in-process
    ``AzDispatchPlane`` compiles (uint8 wire in, fp16 logits out — the
    bit-parity contract), fed with concatenated leaf rows from every
    frontend's MCTS pools."""

    driver_threads = 1

    def __init__(self, params, cfg) -> None:
        import jax
        import jax.numpy as jnp

        from fishnet_tpu.models.az import az_forward

        self._params = jax.device_put(params)
        az_cfg = cfg.az

        def forward(p, x_u8):
            x = x_u8.astype(jnp.float32)
            x = x.at[..., 17].multiply(1.0 / 100.0)
            logits, values = az_forward(p, x, az_cfg)
            return logits.astype(jnp.float16), values

        self._fwd = jax.jit(forward)
        self._staged: Dict[int, np.ndarray] = {}
        self._async_pipes: List = []
        self._coalescer = _DispatchCoalescer(
            self, pinned_width=_DispatchCoalescer.MAX_WIDTH
        )

    def stage(self, group: int, planes_u8: np.ndarray) -> None:
        self._staged[group] = planes_u8

    def _run(self, segs: List[np.ndarray]):
        tel = _telemetry.enabled()
        t0 = time.monotonic() if tel else 0.0
        total = sum(len(s) for s in segs)
        bucket = _pad_bucket(total)
        planes = np.zeros((bucket,) + rings.AZ_PLANE_SHAPE, np.uint8)
        off = 0
        for s in segs:
            planes[off : off + len(s)] = s
            off += len(s)
        logits16, values = self._fwd(self._params, planes)
        rings.note("fused.rows.az", total)
        rings.note("fused.slots.az", bucket)
        if bucket > total:
            rings.note("pad.rows", bucket - total)
        if tel:
            _SPANS.record(
                "dispatch_issue", t0, width=len(segs),
                n=total, slots=bucket, fill=total / bucket,
            )
        return (
            np.asarray(logits16, np.float16),
            np.asarray(values, np.float32),
        )

    def _dispatch_eval(self, group: int, n: int, rows: int):
        logits16, values = self._run([self._staged.pop(group)])
        out = (logits16[:n], values[:n])
        pol = logits16.shape[1]
        return out, (n, n * 8 * 8 * 19, n * (pol * 2 + 4))

    def _dispatch_segmented(self, tickets) -> None:
        segs = [self._staged.pop(tk.group) for tk in tickets]
        logits16, values = self._run(segs)
        pol = logits16.shape[1]
        off = 0
        for tk, seg in zip(tickets, segs):
            k = len(seg)
            tk.values = (logits16[off : off + k], values[off : off + k])
            tk.start, tk.seg_size = 0, k
            tk.acct = (k, k * 8 * 8 * 19, k * (pol * 2 + 4))
            off += k


class EvaluatorHost:
    """Discovers link files in the rpc dir, drains their submit rings
    into the family coalescers, fans results back by link. One sweep
    thread owns every host-side ring word (the single-writer contract).

    ``sweep()`` is public and synchronous so in-process tests can drive
    the host deterministically without the polling thread."""

    def __init__(
        self,
        nnue_params=None,
        az_params=None,
        az_cfg=None,
        rpc_dir: Optional[str] = None,
        lease_s: float = rings.LEASE_S,
        poll_s: float = 0.002,
        linger_s: Optional[float] = None,
    ) -> None:
        self._dir = rpc_dir or rings.rpc_dir()
        self._lease_s = lease_s
        self._poll_s = poll_s
        if linger_s is None:
            linger_s = float(
                os.environ.get("FISHNET_HOST_LINGER_MS", "2")
            ) / 1000.0
        self._linger_s = max(0.0, linger_s)
        self._links: Dict[str, rings.RingLink] = {}
        self._groups = itertools.count(1)
        self._nnue = (
            _HostNnueBackend(nnue_params) if nnue_params is not None else None
        )
        self._az = (
            _HostAzBackend(az_params, az_cfg)
            if az_params is not None else None
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Guards _links: the sweep loop runs on the driver thread while
        # close() detaches from the caller's thread.
        self._lock = threading.Lock()
        rings.set_role("evaluator")

    # -- link lifecycle ----------------------------------------------------

    def _scan(self) -> None:
        try:
            names = sorted(os.listdir(self._dir))
        except OSError:
            return
        fresh = []
        for name in names:
            if not name.endswith(".ring"):
                continue
            path = os.path.join(self._dir, name)
            if path in self._links:
                continue
            try:
                link = rings.attach_host_link(path)
            except (OSError, ValueError):
                continue  # foreign/torn/vanished file: skip, never serve
            with self._lock:
                self._links[path] = link
            fresh.append(link)
            rings.note("attach.host")
        if fresh:
            # Generation tick: every frontend watching one of these
            # links sees the epoch move and resubmits its in-flight
            # work — covers both host restart and fault re-attach.
            rings.bump_host_epoch(fresh)

    def _detach(self, path: str, reason: str, unlink: bool) -> None:
        with self._lock:
            link = self._links.pop(path, None)
        if link is None:
            return
        link.close()
        if unlink:
            try:
                os.unlink(path)
            except OSError:
                pass
        rings.note(f"detach.{reason}")

    # -- the sweep ---------------------------------------------------------

    def _drain(self) -> List[Tuple]:
        """Beat, reap, and drain every attached link's submit ring;
        returns the fenced-filtered records. Called once per sweep plus
        once per linger re-drain tick."""
        work: List[Tuple] = []
        for path, link in list(self._links.items()):
            link.beat()
            if link.peer_age() > self._lease_s:
                self._detach(path, "lease", unlink=True)
                continue
            for kind, ticket, epoch, n, payload in link.drain():
                if epoch < link.frontend_epoch:
                    # Fenced: a record from the link's previous life.
                    rings.note("stale_refusals")
                    continue
                work.append((link, kind, ticket, epoch, n, payload))
        return work

    def sweep(self) -> int:
        """One full service round: scan, fault poll, lease reap, drain,
        fuse-dispatch, fan results back. Returns records served."""
        self._scan()
        plan = faults.current()
        if plan is not None and self._links:
            rule = plan.poll("rpc.detach")
            if rule is not None:
                # Drop one live link mid-flight: records its attachment
                # consumed are gone; the re-attach epoch bump makes the
                # frontend re-pay them.
                self._detach(
                    sorted(self._links)[0], "fault", unlink=False
                )
        work = self._drain()
        if not work:
            return 0
        if self._linger_s > 0.0 and len(self._links) > 1:
            # Cross-process fusion pathology: K frontends'
            # waves land microseconds apart, so each sweep used to
            # catch ONE wave and pay its own pow2 bucket — 3×40-row
            # waves dispatched as three 64-slot buckets (192 slots)
            # instead of one 128-slot fused dispatch. A bounded linger
            # re-drains the rings until the window closes, so skewed
            # waves bucket by their FUSED row count. Gated on multiple
            # attached links: with one frontend the linger is pure
            # latency with nothing to fuse.
            deadline = time.monotonic() + self._linger_s
            while True:
                now = time.monotonic()
                if now >= deadline:
                    break
                time.sleep(min(0.0005, deadline - now))
                work.extend(self._drain())
        staged = []
        for link, kind, ticket, epoch, n, payload in work:
            gid = next(self._groups)
            if kind == rings.KIND_NNUE_SUBMIT and self._nnue is not None:
                be = self._nnue
                be.stage(gid, *rings.unpack_nnue_submit(payload, n))
            elif kind == rings.KIND_AZ_SUBMIT and self._az is not None:
                be = self._az
                be.stage(gid, rings.unpack_az_submit(payload, n))
            else:
                rings.note("unserviceable")
                continue
            # Submit-all-then-demand: everything drained this sweep
            # parks together, so the first demand's flush fuses the
            # cross-process batch into one segmented dispatch.
            tk = be._coalescer.submit(gid, n, n)
            staged.append((link, kind, ticket, epoch, n, be, tk))
        served = 0
        for link, kind, ticket, epoch, n, be, tk in staged:
            try:
                values = be._coalescer.demand(tk)
            except NativeCoreError:
                rings.note("eval_errors")
                continue  # the frontend's demand timeout requeues it
            if kind == rings.KIND_NNUE_SUBMIT:
                rkind = rings.KIND_NNUE_RESULT
                out = rings.pack_nnue_result(values)
                family = "nnue"
            else:
                rkind = rings.KIND_AZ_RESULT
                out = rings.pack_az_result(*values)
                family = "az"
            try:
                link.push(rkind, ticket, epoch, n, out, deadline_s=2.0)
            except (rings.RingFull, rings.RecordTooLarge, ValueError):
                # A frontend not draining results is dying; the lease
                # will reap it, and a survivor re-pays via resubmit.
                rings.note("result_drops")
                continue
            rings.note(f"results.{family}")
            served += 1
        return served

    # -- run modes ---------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="rpc-evaluator", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            if self.sweep() == 0:
                time.sleep(self._poll_s)

    def serve_forever(self) -> None:
        self._loop()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        with self._lock:
            links = list(self._links.values())
            self._links.clear()
        for link in links:
            link.close()


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import signal

    # Fleet drain sends SIGTERM (cluster/supervisor.py drain): exit the
    # serve loop cleanly so the supervisor books exit code 0, exactly
    # like a draining frontend.
    def _graceful(_sig, _frm):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _graceful)

    parser = argparse.ArgumentParser(
        prog="python -m fishnet_tpu.rpc.host",
        description="Evaluator host for the disaggregated (split) plane.",
    )
    parser.add_argument("--dir", default=None,
                        help="link directory (default: FISHNET_RPC_DIR)")
    parser.add_argument("--nnue-file", default=None,
                        help="NNUE weights to serve alpha-beta traffic")
    parser.add_argument("--az-seed", type=int, default=None,
                        help="serve AZ/MCTS traffic with params from "
                        "init_az_params(PRNGKey(seed))")
    parser.add_argument("--az-capacity", type=int, default=256,
                        help="AZ bucket-ladder capacity")
    parser.add_argument("--lease", type=float, default=rings.LEASE_S)
    parser.add_argument("--poll", type=float, default=0.002)
    parser.add_argument("--linger-ms", type=float, default=None,
                        help="cross-frontend fusion window (default: "
                        "FISHNET_HOST_LINGER_MS, 2ms)")
    parser.add_argument("--metrics-port", type=int, default=None)
    parser.add_argument("--metrics-port-file", default=None)
    args = parser.parse_args(argv)

    faults.install_from_env()
    from fishnet_tpu.utils import compile_cache

    compile_cache.configure()  # before the first jit
    nnue_params = None
    if args.nnue_file:
        import jax

        from fishnet_tpu.nnue.jax_eval import params_from_weights
        from fishnet_tpu.nnue.weights import NnueWeights

        nnue_params = jax.device_put(
            params_from_weights(NnueWeights.load(args.nnue_file))
        )
    az_params = az_cfg = None
    if args.az_seed is not None:
        import jax

        from fishnet_tpu.models.az import init_az_params
        from fishnet_tpu.search.mcts import MctsConfig

        az_cfg = MctsConfig(batch_capacity=args.az_capacity)
        az_params = init_az_params(
            jax.random.PRNGKey(args.az_seed), az_cfg.az
        )
    if nnue_params is None and az_params is None:
        parser.error("nothing to serve: pass --nnue-file and/or --az-seed")

    if args.metrics_port is not None:
        from fishnet_tpu import telemetry

        exporter = telemetry.start_exporter(args.metrics_port)
        if args.metrics_port_file is not None:
            tmp = f"{args.metrics_port_file}.tmp"
            with open(tmp, "w", encoding="utf-8") as fp:
                fp.write(f"{exporter.port}\n")
            os.replace(tmp, args.metrics_port_file)

    host = EvaluatorHost(
        nnue_params=nnue_params, az_params=az_params, az_cfg=az_cfg,
        rpc_dir=args.dir, lease_s=args.lease, poll_s=args.poll,
        linger_s=(
            None if args.linger_ms is None else args.linger_ms / 1000.0
        ),
    )
    try:
        host.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        host.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
