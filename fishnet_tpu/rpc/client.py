"""Frontend-side shims: the split plane behind the in-process seams.

Two entry points, one per search family (doc/disaggregation.md):

* :class:`RemoteBackend` IS a ``SearchService`` whose evaluator ships
  each group's padded microbatch over this frontend's ring link instead
  of running a local jit — the remote-evaluator seam
  (``search/service.py _remote_evaluator``, ``_dispatch_eval``)
  produces exactly the self-contained dense arrays the wire carries, so
  alpha-beta drivers, the engine factories and ``train/selfplay.py``
  ride unchanged. The
  evaluator returns a LAZY handle; the service's ``_resolve_eval``
  materializes it one loop iteration later, which preserves the
  per-group pipeline overlap across the process boundary.
* :class:`RemoteAzPlane` implements the AZ dispatch-plane lane API
  (``register_lane``/``warmup``/``evaluate``/``counters``/``close``),
  so ``MctsPool``'s existing ``hasattr(evaluator, "register_lane")``
  wrap routes MCTS leaf microbatches over the same transport.

Failure contract: a demand wait survives an evaluator death by
watching the host epoch and heartbeat — when the evaluator is reborn
(epoch bump) the client cancels its groups' device anchors via the
existing ``fc_pool_cancel_anchors`` path and RESUBMITS the kept
payload bytes; only the total ``FISHNET_RPC_TIMEOUT`` budget expiring
surfaces as an error (the service's requeue machinery takes over).
Results are deduplicated by ticket id, so an at-least-once transport
still yields exactly-once consumption.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from fishnet_tpu.rpc import rings
from fishnet_tpu.search.service import NativeCoreError, SearchService
from fishnet_tpu.telemetry.spans import RECORDER as _SPANS

__all__ = ["RemoteBackend", "RemoteAzPlane", "RemoteEvaluator"]


class EvaluatorLostError(NativeCoreError):
    """The evaluator host stayed unreachable past FISHNET_RPC_TIMEOUT."""


class _RpcClient:
    """One frontend link: serialized submits, ticket table, demand
    waits. All ring writes go through ``_lock`` (the SPSC single-writer
    contract); results drain under the same lock and park in
    ``_results`` until their owner claims them."""

    def __init__(self, directory: Optional[str] = None,
                 name: Optional[str] = None) -> None:
        self._link = rings.create_frontend_link(directory, name=name)
        self._epoch = self._link.frontend_epoch
        self._tickets = itertools.count(1)
        self._lock = threading.Lock()
        self._results: Dict[int, Tuple[int, int, bytes]] = {}
        self._done: set = set()
        self._closed = False
        rings.set_role("frontend")

    @property
    def link(self) -> rings.RingLink:
        return self._link

    def submit(self, kind: int, n: int, payload: bytes) -> int:
        ticket = next(self._tickets)
        with self._lock:
            self._link.beat()
            self._link.push(kind, ticket, self._epoch, n, payload)
        family = "nnue" if kind == rings.KIND_NNUE_SUBMIT else "az"
        rings.note(f"submits.{family}")
        return ticket

    def _drain_locked(self) -> None:
        for kind, ticket, epoch, n, payload in self._link.drain():
            # Fenced results: a record answering a previous life of
            # this frontend (or a duplicate of one already claimed —
            # a resubmit can be answered twice) must not double-
            # consume — exactly-once by ticket id.
            if (epoch != self._epoch or ticket in self._results
                    or ticket in self._done):
                continue
            self._results[ticket] = (kind, n, payload)

    def wait(self, ticket: int, n: int, kind: int,
             payload: bytes) -> Tuple[int, int, bytes]:
        """Block until ``ticket``'s result lands. Resubmits the kept
        ``payload`` after an evaluator rebirth (host epoch moved) and
        raises :class:`EvaluatorLostError` only when the total timeout
        budget runs out — a requeue signal, never a silent hang."""
        t0 = time.monotonic()
        deadline = t0 + rings.timeout_s()
        host_epoch = self._link.host_epoch
        while True:
            with self._lock:
                self._link.beat()
                self._drain_locked()
                got = self._results.pop(ticket, None)
                if got is not None:
                    self._done.add(ticket)
                    if len(self._done) > 8192:
                        floor = ticket - 8192
                        self._done = {t for t in self._done if t > floor}
            if got is not None:
                _SPANS.record(
                    "rpc_wait", t0, ticket=ticket,
                    family="nnue" if kind == rings.KIND_NNUE_SUBMIT
                    else "az",
                )
                return got
            now_epoch = self._link.host_epoch
            if now_epoch != host_epoch:
                # The evaluator died and a successor attached: any
                # record it consumed without answering is gone, so
                # fence local device state and re-pay the submit.
                host_epoch = now_epoch
                self._on_evaluator_lost()
                with self._lock:
                    self._link.push(kind, ticket, self._epoch, n, payload)
                rings.note("resubmits")
            if time.monotonic() >= deadline:
                raise EvaluatorLostError(
                    f"rpc demand timeout: no result for ticket {ticket} "
                    f"within {rings.timeout_s():.0f}s "
                    f"(host heartbeat age {self._link.peer_age():.1f}s); "
                    "requeue the batch"
                )
            time.sleep(0.001)

    def _on_evaluator_lost(self) -> None:
        """Hook: RemoteBackend cancels its groups' device anchors."""

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        import os

        path = self._link.path
        self._link.close()
        try:
            os.unlink(path)
        except OSError:
            pass


class _PendingEval:
    """Lazy result handle for one in-flight NNUE microbatch: the
    service's ``_resolve_eval`` calls ``np.asarray`` on it one pipeline
    iteration after dispatch, so the demand wait overlaps the next
    group's fiber stepping exactly like a device future would."""

    __slots__ = ("_client", "_ticket", "_n", "_payload", "_arr")

    def __init__(self, client: _RpcClient, ticket: int, n: int,
                 payload: bytes) -> None:
        self._client = client
        self._ticket = ticket
        self._n = n
        self._payload = payload
        self._arr: Optional[np.ndarray] = None

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        if self._arr is None:
            _kind, _n, result = self._client.wait(
                self._ticket, self._n, rings.KIND_NNUE_SUBMIT,
                self._payload,
            )
            self._arr = rings.unpack_nnue_result(result, self._n)
            self._payload = b""  # free the kept bytes
        return self._arr if dtype is None else self._arr.astype(dtype)


class RemoteEvaluator:
    """The callable ``(params, feats, buckets, parents, material) ->
    lazy int32 [B]`` that ``SearchService._remote_evaluator`` names:
    packs the full padded microbatch into one self-contained submit
    record and returns a :class:`_PendingEval`."""

    def __init__(self, client: _RpcClient) -> None:
        self._client = client

    def __call__(self, params, feats, buckets, parents, material):
        n = len(buckets)
        payload = rings.pack_nnue_submit(feats, buckets, parents, material)
        ticket = self._client.submit(rings.KIND_NNUE_SUBMIT, n, payload)
        return _PendingEval(self._client, ticket, n, payload)


class RemoteBackend(SearchService):
    """A SearchService whose eval plane lives in another process.

    Byte-compatible with the in-process seam: construction takes the
    same arguments (plus ``rpc_dir``), drivers and engine factories see
    a plain SearchService, and analyses are bit-identical to a
    monolith's because the host replays the exact dense microbatch
    through the same ``evaluate_batch`` graph (the host-material rung's
    parity contract; tests/test_rpc.py
    ``test_two_process_split_bit_identical_analyses``, a ``slow``
    test)."""

    def __init__(self, *args, rpc_dir: Optional[str] = None,
                 **kwargs) -> None:
        client = _RpcClient(rpc_dir)
        client._on_evaluator_lost = self._cancel_inflight_anchors
        self._rpc = client
        kwargs.setdefault("backend", "jax")
        super().__init__(*args, **kwargs)

    def _remote_evaluator(self) -> RemoteEvaluator:
        return RemoteEvaluator(self._rpc)

    def _cancel_inflight_anchors(self) -> None:
        """Evaluator death fences every group's device anchor state via
        the existing cancellation path. A remote evaluator never
        enables persistent anchors (in-batch refs only), so this is the
        same no-op-safe call the in-process cache-skip path makes —
        kept so a future anchor-carrying wire inherits the fencing."""
        pool = getattr(self, "_pool", None)
        if not pool:
            return
        for group in range(self._n_groups):
            self._lib.fc_pool_cancel_anchors(pool, group)

    def close(self) -> None:
        try:
            super().close()
        finally:
            self._rpc.close()


class RemoteAzPlane:
    """The AZ dispatch-plane lane API over the ring transport.

    ``MctsPool`` wraps any evaluator exposing ``register_lane`` in its
    ``_PlaneEvaluator`` adapter, so handing this to a pool routes every
    leaf microbatch through the evaluator host — where microbatches
    from ALL frontends fuse into shared bucket dispatches (the
    cross-process fill win, which no test measures). ``params`` is
    optional and only salts the client-side pre-wire
    :class:`~fishnet_tpu.search.eval_cache.AzEvalCache` probe; the wire
    payload is the exact uint8 planes / fp16 logits the local plane
    uses, so results are bit-identical either way."""

    def __init__(self, cfg, params: Optional[Dict] = None,
                 rpc_dir: Optional[str] = None,
                 link_name: Optional[str] = None) -> None:
        import os

        from fishnet_tpu.models.az_encoding import POLICY_SIZE

        self.cfg = cfg
        self._policy_size = POLICY_SIZE
        # Link names are per-frontend: same-process planes (tests)
        # must pass distinct ``link_name``s or the second
        # attach bumps the frontend epoch and fences the first plane's
        # in-flight submits as stale.
        self._client = _RpcClient(
            rpc_dir, name=link_name or f"link-{os.getpid()}-az.ring"
        )
        self._salt = None
        if params is not None:
            from fishnet_tpu.search import eval_cache as _eval_cache

            if not _eval_cache.cache_disabled():
                self._salt = _eval_cache.az_net_fingerprint(params)
        self._lane_lock = threading.Lock()
        self._next_lane = 0
        self._stats_lock = threading.Lock()
        self._prewire_hits = 0
        self._skipped_dispatches = 0
        self._rows_submitted = 0
        self._dispatches = 0

    def register_lane(self) -> int:
        with self._lane_lock:
            lane = self._next_lane
            self._next_lane += 1
            return lane

    def warmup(self) -> None:
        """One tiny round trip: proves the link is served and lets the
        host compile its smallest AZ bucket before real traffic."""
        planes = np.zeros((1,) + rings.AZ_PLANE_SHAPE, np.uint8)
        payload = rings.pack_az_submit(planes)
        ticket = self._client.submit(rings.KIND_AZ_SUBMIT, 1, payload)
        self._client.wait(ticket, 1, rings.KIND_AZ_SUBMIT, payload)

    def evaluate(
        self, lane: int, planes_u8: np.ndarray, n: int, keys=None
    ) -> Tuple[np.ndarray, np.ndarray]:
        out_logits = np.empty((n, self._policy_size), np.float32)
        out_values = np.empty((n,), np.float32)
        if n == 0:
            return out_logits, out_values
        cache = None
        salted = None
        miss = list(range(n))
        if keys is not None and self._salt is not None:
            from fishnet_tpu.search import eval_cache as _eval_cache

            cache = _eval_cache.get_az_cache()
        if cache is not None:
            salted = [
                (int(k) ^ self._salt) & ((1 << 64) - 1) for k in keys
            ]
            miss = []
            hits = 0
            for i, ent in enumerate(cache.probe_many(salted)):
                if ent is None:
                    miss.append(i)
                    continue
                lg16, val = ent
                out_logits[i] = lg16.astype(np.float32)
                out_values[i] = val
                hits += 1
            if hits:
                with self._stats_lock:
                    self._prewire_hits += hits
            if not miss:
                with self._stats_lock:
                    self._skipped_dispatches += 1
                return out_logits, out_values
        rows = np.ascontiguousarray(planes_u8[np.asarray(miss, np.intp)])
        payload = rings.pack_az_submit(rows)
        ticket = self._client.submit(
            rings.KIND_AZ_SUBMIT, len(miss), payload
        )
        _kind, _n, result = self._client.wait(
            ticket, len(miss), rings.KIND_AZ_SUBMIT, payload
        )
        logits16, values = rings.unpack_az_result(
            result, len(miss), self._policy_size
        )
        with self._stats_lock:
            self._rows_submitted += len(miss)
            self._dispatches += 1
        for j, i in enumerate(miss):
            lg16 = logits16[j]
            out_logits[i] = lg16.astype(np.float32)
            out_values[i] = values[j]
            if cache is not None and salted is not None:
                # The exact fp16 wire payload — warm replays
                # reconstruct identical fp32 bits (az_plane contract).
                cache.insert(
                    salted[i],
                    (np.array(lg16, np.float16), np.float32(values[j])),
                )
        return out_logits, out_values

    def counters(self) -> Dict[str, float]:
        """Client-side view (host-side fill rides the rpc_* metric
        families)."""
        with self._stats_lock:
            return {
                "prewire_hits": self._prewire_hits,
                "skipped_dispatches": self._skipped_dispatches,
                "rows_dispatched": self._rows_submitted,
                "slots_dispatched": 0,
                "dispatches": self._dispatches,
                "dispatch_fill": 0.0,
            }

    def close(self) -> None:
        self._client.close()
