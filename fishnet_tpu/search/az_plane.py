"""Shared AZ dispatch plane: MCTS leaf traffic on the coalesced mesh.

ISSUE 14's tentpole. Before this, the two search families had two
dispatch stacks: NNUE alpha-beta microbatches rode SearchService's
_DispatchCoalescer -> per-shard _AsyncDispatchPipeline -> ShardRouter
placement -> degradation ladder, while AZ/MCTS leaves went through
MctsPool's private ``jax.jit`` call — no coalescing, no pipelining, no
placement, no ladder, no eval reuse. This module gives the AZ family
the SAME spine by implementing the extracted ``CoalesceBackend`` seam
(search/service.py): one plane owns the serving mesh, per-shard weight
replicas, a coalescer, and lazily-started per-shard async pipelines;
each MctsPool registers a COALESCE LANE and pushes its per-step leaf
microbatch through ``evaluate()``.

Design decisions the tests pin (doc/search.md "Two search families,
one dispatch plane"):

* **Bucketed shapes.** Every device call uses a shape from a fixed
  bucket ladder (single bucket == ``batch_capacity`` when the capacity
  is <= 256, else powers of two from 256 up to the capacity). Padding
  rows are stale staging content, NOT zeroed — the AZ net is per-row
  independent (convolutions and dense heads never mix batch rows), so
  row i's logits/value are bit-identical whatever rows j != i hold.
  With a single bucket the dispatch shape equals the legacy pool's jit
  shape, which is what makes shared-plane vs legacy BIT-IDENTICAL.
* **fp16 wire, fp32 consumers.** The jitted forward matches the legacy
  pool's exactly (uint8 planes in, fp16 logits + fp32 values out); the
  plane converts fp16 -> fp32 on materialize, the same conversion the
  legacy path performs, preserving bitwise parity.
* **Pre-wire eval reuse.** Keys are ``az_position_key(zobrist,
  halfmove) ^ az_net_fingerprint(params)`` into the process-wide
  :class:`~fishnet_tpu.search.eval_cache.AzEvalCache`. Full-hit
  microbatches never touch the coalescer (a skipped dispatch, like the
  NNUE pre-wire short-circuit of PR 11); partial hits dispatch only the
  miss rows. Cached entries are the exact fp16 wire payload, so a warm
  replay is bit-identical to a cold one.
* **Its own three-rung ladder.** ``AZ_RUNGS = ("fused", "solo",
  "chunk")``: fused segmented dispatch -> per-ticket solo dispatches ->
  minimum-bucket chunks, then ShardRouter.drain + coalescer.migrate as
  the last resort, sharing the NNUE ladder's
  ``fishnet_shard_degradations_total`` counter. Every rung calls the
  SAME jitted forward at bucket shapes, so degrading never changes
  results — the ladder trades fusion structure for blast radius, not
  numerics.

``FISHNET_NO_SHARED_AZ_PLANE=1`` is the operational escape hatch:
MctsPool then builds its legacy private evaluator and this module is
never imported on the hot path.
"""

from __future__ import annotations

import os
import threading
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from fishnet_tpu import telemetry as _telemetry
from fishnet_tpu.telemetry import cost as _cost
from fishnet_tpu.models.az import az_forward
from fishnet_tpu.models.az_encoding import POLICY_SIZE
from fishnet_tpu.parallel.mesh import (
    ShardRouter,
    replicate_params,
    serving_devices,
)
from fishnet_tpu.search import eval_cache as _eval_cache
from fishnet_tpu.search.service import (
    CoalesceBackend,
    _AsyncDispatchPipeline,
    _DispatchCoalescer,
    _FusedValues,
    _SeqAllocator,
    _SHARD_DEGRADATIONS,
)

__all__ = ["AZ_RUNGS", "AzDispatchPlane", "plane_disabled"]

#: AZ degradation ladder. Mirrors service._MESH_RUNGS in shape (index =
#: per-shard rung, drain after the last), but the rungs are AZ-specific
#: dispatch structures — all bit-identical (module docstring).
AZ_RUNGS = ("fused", "solo", "chunk")

_U64 = (1 << 64) - 1


def plane_disabled() -> bool:
    """The escape hatch, read per call so tests can monkeypatch env."""
    return os.environ.get("FISHNET_NO_SHARED_AZ_PLANE", "") == "1"


def speculation_disabled() -> bool:
    """Speculative pad-row escape hatch (``FISHNET_NO_SPECULATION=1``),
    read per call like :func:`plane_disabled`. Also implied by the eval
    cache hatch: speculative results land ONLY in the cache/fleet tier,
    so with no cache they would be pure wasted compute. With it set, no
    pad row is ever repurposed — dispatches are byte-for-byte today's
    (pad rows hold stale staging content, consumers never read them)."""
    return (
        _eval_cache.cache_disabled()
        or os.environ.get("FISHNET_NO_SPECULATION", "") == "1"
    )


#: Default speculative rows per dispatch when FISHNET_SPECULATION_BUDGET
#: is unset. Small by design: speculation only ever rides slots the pow2
#: ladder already paid for, and the control plane re-tunes it live.
DEFAULT_SPECULATION_BUDGET = 8


class _AzValues(_FusedValues):
    """A fused AZ dispatch's payload: a tuple of ``(logits_dev,
    values_dev, n_used, spec_keys)`` chunks, materialized ONCE into a
    list of per-row ``(logits_f32 [4672], value)`` pairs. A list, not an
    ndarray, so the coalescer's segment slicing (``[start : start +
    seg_size]``) and the decode worker's eager ``materialize()`` both
    work unchanged on the shared machinery.

    ``spec_keys`` are the salted cache keys of speculative pad rows the
    plane parked at ``[n_used : n_used + len(spec_keys)]`` of the chunk
    (doc/eval-cache.md "Speculative pad rows"); ``sink`` receives their
    fp16 logits + values exactly once, at materialize time — the first
    device->host transfer that exists anyway — so speculation adds no
    extra sync point. Demand consumers still read ``[:n_used]`` only,
    untouched by whatever rides the padding."""

    __slots__ = ("_sink",)

    def __init__(self, arr, sink=None) -> None:
        super().__init__(arr)
        self._sink = sink

    def materialize(self) -> list:  # type: ignore[override]
        with self._lock:
            if self._np is None:
                rows: list = []
                for logits_dev, values_dev, k, spec in self._arr:
                    lg16 = np.asarray(logits_dev)
                    vals = np.asarray(values_dev)
                    lg = lg16[:k].astype(np.float32)
                    rows.extend(
                        (lg[i], float(vals[i])) for i in range(k)
                    )
                    if spec and self._sink is not None:
                        self._sink(
                            spec,
                            lg16[k : k + len(spec)],
                            vals[k : k + len(spec)],
                        )
                self._np = rows
                self._arr = None
            return self._np


def _bucket_ladder(cap: int) -> List[int]:
    """Dispatch-shape buckets for a pool capacity: a powers-of-two
    ladder from 32 up to cap, so a late-search (or warm-cache) trickle
    of 5 leaves pays a 32-wide dispatch, not a 16k-wide one. Safe for
    bit-parity because AZ rows are batch-shape invariant — the net is
    per-row independent and XLA's within-row reductions don't depend on
    the batch dimension (pinned by tests/test_mcts_plane.py)."""
    buckets: List[int] = []
    b = 32
    while b < cap:
        buckets.append(b)
        b *= 2
    buckets.append(cap)
    return buckets


class AzDispatchPlane(CoalesceBackend):
    """One process-wide dispatch spine for AZ leaf microbatches.

    Several MctsPools may share one plane (one coalesce lane each, up
    to ``max_lanes``); each lane carries at most one outstanding
    microbatch because ``MctsPool.step`` is synchronous, which is the
    invariant that lets staged planes ride a plain per-lane dict.

    ``force_rung`` pins every dispatch to one AZ_RUNGS index (the
    parity tests sweep all three); ``coalesce_width`` pins the
    coalescer policy width (multi-pool drivers set >1 to see fusion —
    the NNUE DispatchProbe never runs here, so the width would
    otherwise stay 1).
    """

    def __init__(
        self,
        params: Dict,
        cfg,
        devices: Optional[Sequence] = None,
        max_lanes: int = 8,
        coalesce_width: Optional[int] = None,
        force_rung: Optional[int] = None,
    ) -> None:
        import jax
        import jax.numpy as jnp

        from fishnet_tpu.utils import compile_cache

        compile_cache.configure()
        self.cfg = cfg
        self._cap = int(cfg.batch_capacity)
        self._buckets = _bucket_ladder(self._cap)
        devs = serving_devices(devices)
        self._devices = devs
        self._n_shards = len(devs)
        self._n_groups = max_lanes
        self._replicas = replicate_params(params, devs)
        self._salt = _eval_cache.az_net_fingerprint(params)
        # FLEET POSITION TIER (doc/eval-cache.md "Fleet tier"): AZ leaf
        # traffic rides the shared segment's AZ region under its own
        # fingerprint salt. Probed only for rows the process AzEvalCache
        # missed; the policy-size guard drops the tier on architecture
        # drift rather than reading misaligned rows.
        self._postier = None
        if not _eval_cache.cache_disabled():
            from fishnet_tpu.cluster import position_tier as _postier_mod

            tier = _postier_mod.get_tier()
            if tier is not None and tier.az_policy_size == POLICY_SIZE:
                self._postier = tier
        self._router = (
            ShardRouter(max_lanes, self._n_shards)
            if self._n_shards > 1 else None
        )
        self._shard_rungs = [0] * self._n_shards
        self._forced_rung = force_rung
        self._no_async = os.environ.get("FISHNET_NO_ASYNC", "") == "1"
        self._async_pipes: List[Optional[_AsyncDispatchPipeline]] = (
            [] if self._no_async else [None] * self._n_shards
        )
        self._seq_alloc = _SeqAllocator()
        self._pipe_lock = threading.Lock()
        self._lane_lock = threading.Lock()
        self._next_lane = 0
        # lane -> staged uint8 miss rows for its ONE outstanding ticket.
        self._staged: Dict[int, np.ndarray] = {}
        # Per-(shard, bucket) ping-pong staging rings (DEPTH buffers):
        # the pack worker may stage dispatch N+1 while N's host->device
        # transfer is still riding, so the buffer N used must not be
        # overwritten until its slot cycles — same invariant as the
        # NNUE pipeline's staging slots.
        self._staging_lock = threading.Lock()
        self._staging_bufs: Dict[Tuple[int, int], Tuple[list, int]] = {}
        # Lock-guarded dispatch stats (one update per dispatch, ~Hz).
        self._stats_lock = threading.Lock()
        self._prewire_hits = 0
        self._skipped_dispatches = 0
        self._rows_dispatched = 0
        self._slots_dispatched = 0
        # Speculative pad rows (doc/eval-cache.md "Speculative pad
        # rows"): a bounded queue of candidate positions (salted key ->
        # wire planes) that _dispatch_chunks parks in slots the pow2
        # bucket ladder would otherwise ship as padding. The budget is
        # a control-plane actuator (set_speculation_budget); 0 pins
        # speculation off without touching the env hatch.
        self._spec_lock = threading.Lock()
        self._spec_queue: "OrderedDict[int, np.ndarray]" = OrderedDict()
        budget = _env_int("FISHNET_SPECULATION_BUDGET")
        self._spec_budget = (
            DEFAULT_SPECULATION_BUDGET if budget is None else max(0, budget)
        )
        self._pad_rows = 0
        self._spec_rows = 0
        self._closed = False
        # Cost-plane tenant tag for this plane's dispatches (telemetry/
        # cost.py): AZ leaf traffic is selfplay by default; a serving
        # deployment mixing tenants can re-tag per plane.
        self.cost_tenant = "selfplay"

        # Same graph/wire as the legacy MctsPool jit (bit-parity).
        az_cfg = cfg.az

        def forward(p, x_u8):
            x = x_u8.astype(jnp.float32)
            x = x.at[..., 17].multiply(1.0 / 100.0)
            logits, values = az_forward(p, x, az_cfg)
            return logits.astype(jnp.float16), values

        self._fwd = jax.jit(forward)
        self._coalescer = _DispatchCoalescer(self, pinned_width=(
            coalesce_width
            if coalesce_width is not None
            else _env_int("FISHNET_AZ_COALESCE_WIDTH")
        ))
        ref = weakref.ref(self)

        def _collect():
            plane = ref()
            if plane is None or plane._closed:
                return None  # self-unregister
            return plane._families()

        from fishnet_tpu.telemetry.registry import REGISTRY

        self._collector_token = REGISTRY.register_collector(
            _collect, name="az-dispatch-plane"
        )

    # -- lane API (MctsPool side) -----------------------------------------

    def register_lane(self) -> int:
        with self._lane_lock:
            if self._next_lane >= self._n_groups:
                raise ValueError(
                    f"az plane lanes exhausted ({self._n_groups}); "
                    "raise max_lanes or share lanes across fewer pools"
                )
            lane = self._next_lane
            self._next_lane += 1
            return lane

    # -- speculation (doc/eval-cache.md "Speculative pad rows") -----------

    def speculation_budget(self) -> int:
        """Current speculative rows-per-dispatch cap (actuator getter)."""
        with self._spec_lock:
            return self._spec_budget

    def set_speculation_budget(self, budget: int) -> None:
        """Control-plane actuation: re-bound speculative pad-row fill.
        0 pins speculation off (the controller's move when dispatch
        fill is already high — padding is scarce, so speculation would
        only displace nothing and pollute the cache's hot set)."""
        with self._spec_lock:
            self._spec_budget = max(0, int(budget))

    def offer_speculation(
        self, rows: np.ndarray, keys: Sequence[int]
    ) -> int:
        """Queue candidate positions for future pad rows. ``rows[i]`` is
        the uint8 wire planes of UNSALTED az-position-key ``keys[i]``
        (likely children of in-flight nodes, ranked by the caller).
        Already-cached and already-queued keys are dropped; the queue is
        FIFO-bounded at 4x the budget so stale candidates from finished
        subtrees age out instead of occupying tomorrow's padding.
        Returns the number of candidates accepted."""
        if speculation_disabled():
            return 0
        with self._spec_lock:
            budget = self._spec_budget
            cap = 4 * budget
        if budget <= 0:
            return 0
        cache = _eval_cache.get_az_cache()
        accepted = 0
        for i, key in enumerate(keys):
            salted = (int(key) ^ self._salt) & _U64
            if cache is not None and cache.contains(salted):
                continue
            with self._spec_lock:
                if salted in self._spec_queue:
                    continue
                self._spec_queue[salted] = np.array(rows[i], copy=True)
                accepted += 1
                while len(self._spec_queue) > cap:
                    self._spec_queue.popitem(last=False)
        return accepted

    def _take_speculation(self, room: int) -> List[Tuple[int, np.ndarray]]:
        """Pop up to ``min(room, budget)`` queued candidates (FIFO)."""
        if room <= 0 or speculation_disabled():
            return []
        out: List[Tuple[int, np.ndarray]] = []
        with self._spec_lock:
            take = min(room, self._spec_budget)
            while take > 0 and self._spec_queue:
                out.append(self._spec_queue.popitem(last=False))
                take -= 1
        return out

    def _land_speculation(self, spec_keys, lg16, vals) -> None:
        """Materialize-time sink for speculative rows: the exact fp16
        wire payload lands in the process cache and the fleet tier —
        the same stores a demand row feeds — so the NEXT probe of these
        positions is a pre-wire hit instead of a dispatch row."""
        cache = _eval_cache.get_az_cache()
        for j, key in enumerate(spec_keys):
            lg_row = np.asarray(lg16[j], np.float16)
            val = np.float32(vals[j])
            if cache is not None:
                cache.insert(key, (lg_row, val))
            if self._postier is not None:
                self._postier.insert_az(key, lg_row, float(val))

    def warmup(self) -> None:
        """Compile shard 0's bucket shapes (first-traffic re-homing may
        still compile another shard lazily — acceptable, like the NNUE
        service's lazy segmented warms)."""
        for bucket in self._buckets:
            planes = np.zeros((bucket, 8, 8, 19), np.uint8)
            _logits, values = self._fwd(self._replicas[0], planes)
            np.asarray(values)

    def evaluate(
        self,
        lane: int,
        planes_u8: np.ndarray,
        n: int,
        keys: Optional[Sequence[int]] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Evaluate ``planes_u8[:n]`` (uint8 wire planes) for ``lane``.
        Returns ``(logits_f32 [n, POLICY_SIZE], values_f32 [n])`` in row
        order. ``keys`` are UNSALTED ``az_position_key`` ints enabling
        the pre-wire cache short-circuit; None disables reuse for this
        call (the cache hatch itself is read inside get_az_cache)."""
        out_logits = np.empty((n, POLICY_SIZE), np.float32)
        out_values = np.empty((n,), np.float32)
        if n == 0:
            return out_logits, out_values
        cache = _eval_cache.get_az_cache() if keys is not None else None
        miss = list(range(n))
        salted: Optional[List[int]] = None
        if cache is not None:
            salted = [(int(k) ^ self._salt) & _U64 for k in keys]
            cached = cache.probe_many(salted)
            miss = []
            hits = 0
            for i, ent in enumerate(cached):
                if ent is None:
                    miss.append(i)
                else:
                    lg16, val = ent
                    out_logits[i] = lg16.astype(np.float32)
                    out_values[i] = val
                    hits += 1
            if hits:
                with self._stats_lock:
                    self._prewire_hits += hits
            # Fleet-tier probe for the rows the process cache missed
            # (local -> fleet -> miss). A fleet hit is the exact fp16
            # payload a sibling dispatched, so the fp32 reconstruction
            # below is bit-identical to paying the eval here; promote
            # it into the process cache so the next probe stays local.
            if self._postier is not None and miss:
                still = []
                fleet = 0
                for i in miss:
                    ent = self._postier.probe_az(salted[i])
                    if ent is None:
                        still.append(i)
                        continue
                    lg16, val = ent
                    out_logits[i] = lg16.astype(np.float32)
                    out_values[i] = val
                    cache.insert(salted[i], (lg16, np.float32(val)))
                    fleet += 1
                miss = still
                if fleet:
                    with self._stats_lock:
                        self._prewire_hits += fleet
            if not miss:
                with self._stats_lock:
                    self._skipped_dispatches += 1
                return out_logits, out_values
        if len(miss) == n:
            rows = np.array(planes_u8[:n], copy=True)
        else:
            rows = planes_u8[np.asarray(miss, np.intp)]  # fancy-index copy
        shard = self._router.shard_of(lane) if self._router else 0
        self._ensure_pipe(shard)
        self._staged[lane] = rows
        try:
            # Cost plane (telemetry/cost.py): AZ leaf traffic is all
            # one workload family; the tenant defaults to "selfplay"
            # but a serving integration can re-tag the plane.
            owners = (
                [((self.cost_tenant, "selfplay"), len(miss))]
                if _cost.enabled() else None
            )
            ticket = self._coalescer.submit(
                lane, len(miss), rows=len(miss), owners=owners
            )
            # demand() synchronizes and raises dispatch errors; its
            # return slice uses seg_size (0 on solo tickets), so the
            # plane self-slices by ticket.n below instead.
            self._coalescer.demand(ticket)
        finally:
            self._staged.pop(lane, None)
        seg = ticket.values.materialize()[
            ticket.start : ticket.start + ticket.n
        ]
        for j, i in enumerate(miss):
            lg, val = seg[j]
            out_logits[i] = lg
            out_values[i] = val
            if cache is not None and salted is not None:
                # Store the exact fp16 wire payload: fp32 -> fp16 here
                # round-trips exactly (the row WAS fp16 on the wire),
                # so a warm replay reconstructs identical fp32 bits.
                cache.insert(
                    salted[i], (np.asarray(lg, np.float16), val)
                )
                # Publish the freshly paid row fleet-wide (same exact
                # fp16 payload the process cache stores).
                if self._postier is not None:
                    self._postier.insert_az(
                        salted[i], np.asarray(lg, np.float16), float(val)
                    )
        return out_logits, out_values

    # -- CoalesceBackend surface ------------------------------------------

    def _dispatch_eval(self, group: int, n: int, rows: int):
        seg = self._staged.pop(group)
        shard = self._router.shard_of(group) if self._router else 0
        holder = self._run_rungs(shard, group, [seg])
        return holder, {
            "n": n,
            "wire_bytes": int(seg.nbytes),
            "slots": _holder_slots(holder),
        }

    def _dispatch_segmented(self, tickets) -> None:
        segs = [self._staged.pop(tk.group) for tk in tickets]
        shard = (
            self._router.shard_of(tickets[0].group) if self._router else 0
        )
        holder = self._run_rungs(shard, tickets[0].group, segs)
        # One fused dispatch, one slots figure: parked on the FIRST
        # ticket only, so the coalescer's per-dispatch fill sum
        # (service._DispatchCoalescer._execute) counts it once.
        slots = _holder_slots(holder)
        off = 0
        for i, (tk, seg) in enumerate(zip(tickets, segs)):
            tk.values = holder
            tk.start = off
            tk.seg_size = len(seg)
            tk.acct = {
                "n": tk.n,
                "wire_bytes": int(seg.nbytes),
                "slots": slots if i == 0 else 0,
            }
            off += len(seg)

    # -- dispatch internals ------------------------------------------------

    def _ensure_pipe(self, shard: int) -> None:
        if self._no_async or shard >= len(self._async_pipes):
            return
        if self._async_pipes[shard] is not None:
            return
        with self._pipe_lock:
            if self._async_pipes[shard] is None and not self._closed:
                self._async_pipes[shard] = _AsyncDispatchPipeline(
                    self, shard, seq_alloc=self._seq_alloc
                )

    def _run_rungs(self, shard: int, group: int, segs: List[np.ndarray]):
        """Execute one dispatch under the AZ ladder: try the shard's
        rung, degrade (or drain) on failure, re-run — every rung is
        bit-identical so a degraded dispatch is still the SAME result."""
        while True:
            rung = (
                self._forced_rung
                if self._forced_rung is not None
                else self._shard_rungs[shard]
            )
            try:
                return self._execute_rung(shard, rung, segs)
            except Exception as err:  # noqa: BLE001 - ladder decides
                if self._forced_rung is not None:
                    raise
                shard = self._degrade(shard, group, err)

    def _degrade(self, shard: int, group: int, err: Exception) -> int:
        rung = self._shard_rungs[shard]
        if rung < len(AZ_RUNGS) - 1:
            self._shard_rungs[shard] = rung + 1
            _SHARD_DEGRADATIONS.inc(**{
                "shard": str(shard),
                "from": AZ_RUNGS[rung],
                "to": AZ_RUNGS[rung + 1],
            })
            return shard
        router = self._router
        if router is None or len(router.alive_shards()) <= 1:
            raise err
        moved = router.drain(shard)
        self._coalescer.migrate(moved)
        _SHARD_DEGRADATIONS.inc(**{
            "shard": str(shard),
            "from": AZ_RUNGS[rung],
            "to": "drained",
        })
        return moved.get(group, router.shard_of(group))

    def _execute_rung(self, shard: int, rung: int, segs: List[np.ndarray]):
        if rung == 1 and len(segs) > 1:
            # solo: one dispatch chain per segment (no fusion).
            chunks: list = []
            for seg in segs:
                chunks.extend(self._dispatch_chunks(shard, seg, self._cap))
        else:
            rows = segs[0] if len(segs) == 1 else np.concatenate(segs)
            limit = self._buckets[0] if rung == 2 else self._cap
            chunks = self._dispatch_chunks(shard, rows, limit)
        return _AzValues(tuple(chunks), sink=self._land_speculation)

    def _dispatch_chunks(
        self, shard: int, rows: np.ndarray, cap_limit: int
    ) -> list:
        out = []
        off, total = 0, len(rows)
        while off < total:
            k = min(cap_limit, total - off)
            bucket = self._bucket_for(k)
            buf = self._staging(shard, bucket)
            buf[:k] = rows[off : off + k]
            # Pad rows the pow2 bucket already pays for become
            # speculative eval slots (doc/eval-cache.md "Speculative
            # pad rows"): park queued candidates at [k : k+s]. Demand
            # consumers slice [:k], so results are byte-for-byte
            # whatever rides the padding; _AzValues harvests [k : k+s]
            # into the cache at materialize time.
            spec = self._take_speculation(bucket - k)
            for j, (_skey, srow) in enumerate(spec):
                buf[k + j] = srow
            spec_keys = tuple(skey for skey, _srow in spec)
            logits, values = self._fwd(self._replicas[shard], buf)
            out.append((logits, values, k, spec_keys))
            with self._stats_lock:
                self._rows_dispatched += k
                self._slots_dispatched += bucket
                self._spec_rows += len(spec)
                self._pad_rows += bucket - k - len(spec)
            off += k
        return out

    def _bucket_for(self, k: int) -> int:
        for b in self._buckets:
            if b >= k:
                return b
        return self._buckets[-1]

    def _staging(self, shard: int, bucket: int) -> np.ndarray:
        key = (shard, bucket)
        depth = _AsyncDispatchPipeline.DEPTH
        with self._staging_lock:
            ring, idx = self._staging_bufs.get(key, (None, 0))
            if ring is None:
                ring = [
                    np.zeros((bucket, 8, 8, 19), np.uint8)
                    for _ in range(depth)
                ]
            self._staging_bufs[key] = (ring, idx + 1)
        return ring[idx % depth]

    # -- stats / telemetry -------------------------------------------------

    def counters(self) -> Dict[str, float]:
        co = self._coalescer
        with self._stats_lock:
            stats = {
                "prewire_hits": self._prewire_hits,
                "skipped_dispatches": self._skipped_dispatches,
                "rows_dispatched": self._rows_dispatched,
                "slots_dispatched": self._slots_dispatched,
                "pad_rows": self._pad_rows,
                "spec_rows": self._spec_rows,
            }
        stats["speculation_budget"] = self.speculation_budget()
        stats["dispatch_fill"] = (
            stats["rows_dispatched"] / stats["slots_dispatched"]
            if stats["slots_dispatched"] else 0.0
        )
        stats["dispatches"] = co.dispatches
        stats["fused_dispatches"] = co.fused_dispatches
        stats["shard_dispatches"] = list(co.shard_dispatches)
        stats["shard_rungs"] = [
            AZ_RUNGS[r] for r in self._shard_rungs
        ]
        return stats

    def _families(self):
        from fishnet_tpu.telemetry.registry import (
            counter_family,
            gauge_family,
        )

        with self._stats_lock:
            hits = self._prewire_hits
            skipped = self._skipped_dispatches
            pad = self._pad_rows
            spec = self._spec_rows
            rows = self._rows_dispatched
        rungs = [
            gauge_family(
                "fishnet_az_shard_ladder_rung",
                "Per-shard AZ degradation-ladder rung index "
                "(0=fused, 1=solo, 2=chunk).",
                rung, labels={"shard": str(shard)},
            )
            for shard, rung in enumerate(self._shard_rungs)
        ]
        return rungs + [
            gauge_family(
                "fishnet_az_plane_info",
                "Static AZ dispatch-plane configuration (value is "
                "always 1).",
                1,
                labels={
                    "platform": self._devices[0].platform,
                    "device_kind": self._devices[0].device_kind,
                    "shards": str(self._n_shards),
                },
            ),
            counter_family(
                "fishnet_az_dispatches_total",
                "Device dispatches issued by the AZ plane (a fused "
                "dispatch counts once).",
                self._coalescer.dispatches,
            ),
            counter_family(
                "fishnet_az_rows_dispatched_total",
                "Leaf positions shipped to the device by the AZ plane "
                "(padding and speculative rows excluded).",
                rows,
            ),
            counter_family(
                "fishnet_eval_cache_hits_total",
                "Eval-cache hits by scope.",
                hits,
                labels={"scope": "prewire", "family": "az"},
            ),
            counter_family(
                "fishnet_az_skipped_dispatches_total",
                "AZ microbatches fully satisfied pre-wire (no dispatch).",
                skipped,
            ),
            counter_family(
                "fishnet_dispatch_pad_rows_total",
                "Padding slots shipped in device dispatches (bucket "
                "size minus real entries), by dispatch path.",
                pad,
                labels={"path": "az"},
            ),
            counter_family(
                "fishnet_az_speculative_rows_total",
                "Pad rows repurposed as speculative evals (results "
                "land in the cache/fleet tier).",
                spec,
            ),
            gauge_family(
                "fishnet_az_speculation_budget",
                "Current speculative rows-per-dispatch cap (control-"
                "plane actuator).",
                self.speculation_budget(),
            ),
        ]

    def close(self) -> None:
        """Tear down pipelines and unregister the collector. Idempotent."""
        if self._closed:
            return
        self._closed = True
        with self._pipe_lock:
            pipes = [p for p in self._async_pipes if p is not None]
            self._async_pipes = [None] * len(self._async_pipes)
        for pipe in pipes:
            pipe.close()
        from fishnet_tpu.telemetry.registry import REGISTRY

        REGISTRY.unregister_collector(self._collector_token)


def _holder_slots(holder: _AzValues) -> int:
    """Total device slots (bucket widths) a dispatch's chunks shipped.
    Read from the un-materialized chunk tuples; 0 after materialize
    (then the figure has already been consumed by acct)."""
    arr = holder._arr
    if not arr:
        return 0
    return int(sum(int(chunk[0].shape[0]) for chunk in arr))


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else None
    except ValueError:
        return None
