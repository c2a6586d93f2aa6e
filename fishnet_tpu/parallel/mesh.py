"""Device-mesh construction and sharded batched evaluation.

The reference has no multi-device tier at all — its "distributed backend"
is one HTTPS client (SURVEY.md §5, reference src/api.rs:489-536) and its
intra-client parallelism is one engine subprocess per core. The TPU-native
equivalent introduced here sits *below* the engine seam: NNUE microbatches
are sharded across a ``jax.sharding.Mesh`` so the evaluator scales over
ICI instead of over processes.

Axes:

* ``data``  — batch dimension of eval/training microbatches (dp).
* ``model`` — the feature-transformer width L1 and the contracting
  dimension of the first dense layer (tp). Only the *trainer* shards
  over it (the FT table is the one big tensor, 22528 x 1024, and its
  optimizer state triples the footprint); serving replicates params
  and uses the model axis as extra batch parallelism — see
  ``ShardedEvaluator``.

All collectives are inserted by XLA/GSPMD from sharding annotations —
there are no hand-written collectives anywhere in the framework.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def serving_devices(requested=None) -> List[jax.Device]:
    """Resolve the device list the placement-aware serving mesh drives
    (doc/sharding.md). ``requested`` is ``None``/``"auto"`` (every
    visible device), an int (the first N devices), or an explicit device
    sequence. ``FISHNET_NO_MESH=1`` is the operational escape hatch: it
    clamps any request to the first device, restoring the single-device
    serving path byte-for-byte."""
    if requested is None or requested == "auto":
        devs = list(jax.devices())
    elif isinstance(requested, int):
        devs = list(jax.devices())[: max(1, requested)]
    else:
        devs = list(requested)
    if os.environ.get("FISHNET_NO_MESH", "0") == "1":
        devs = devs[:1]
    return devs


def replicate_params(params, devices: Sequence[jax.Device]) -> List[Dict]:
    """Place one full replica of a (pytree) param dict on each serving
    device — the per-shard weight placement both dispatch planes use
    (doc/sharding.md, doc/search.md): each mesh shard evaluates its own
    groups' microbatches against its local replica, so a dispatch never
    crosses devices. Returns one params handle per device, in device
    order; with a single device this is one ``device_put`` (the
    single-shard service's existing placement, byte-for-byte)."""
    return [
        jax.tree_util.tree_map(lambda a, d=dev: jax.device_put(a, d), params)
        for dev in devices
    ]


class ShardRouter:
    """Occupancy-weighted pipeline-group -> mesh-slot assignment for the
    placement-aware coalescer (doc/sharding.md).

    Groups start on the deterministic round-robin layout (group g ->
    shard g % n_shards), so each driver thread's contiguous group range
    spreads over the mesh and table placement is decidable before any
    traffic. The load-balancing step happens at a group's FIRST traffic
    (``note_occupancy``, called by the coalescer per submitted
    microbatch): the group is re-homed to the least-loaded alive shard —
    ordered by (occupancy EMA, assigned-group count, keep-current,
    shard id) — which is a no-op while the mesh is balanced (ties
    prefer the current home) but moves a waking group off a hot shard
    onto an idle one, the failure mode a round-6 run showed (8-shard
    dispatches [253,240,0,0,8,34,35,20] under pure round-robin).
    ``FISHNET_SHARD_PLACEMENT=rr`` restores the static assignment.

    With no traffic the assignment stays a pure function of (n_groups,
    n_shards), including after ``drain`` — the per-shard degradation
    ladder's last resort — which re-homes the dead shard's groups
    least-loaded-first (identical to the old round-robin walk when
    loads are equal, which keeps the drain decision deterministic in
    the fault drills).

    Thread safety: every driver thread reads ``shard_of`` per step while
    a degrading sibling may be draining — all state is guarded by one
    leaf lock (never held while calling out), the pattern the R4
    cross-thread checker certifies (tests/analysis_fixtures).
    """

    def __init__(self, n_groups: int, n_shards: int) -> None:
        if n_shards < 1 or n_groups < 1:
            raise ValueError("need at least one group and one shard")
        self.n_groups = n_groups
        self.n_shards = n_shards
        self._lock = threading.Lock()
        self._alive = list(range(n_shards))
        self._assign = {g: g % n_shards for g in range(n_groups)}
        self._rr_only = (
            os.environ.get("FISHNET_SHARD_PLACEMENT", "lb") == "rr"
        )
        self._active: set = set()
        self._load = [0.0] * n_shards

    def _least_loaded_locked(self, current: Optional[int] = None) -> int:
        counts = {s: 0 for s in self._alive}
        for s in self._assign.values():
            if s in counts:
                counts[s] += 1
        return min(
            self._alive,
            key=lambda s: (
                self._load[s], counts[s], 0 if s == current else 1, s
            ),
        )

    def shard_of(self, group: int) -> int:
        with self._lock:
            return self._assign[group]

    def note_occupancy(self, group: int, n: int) -> None:
        """Record one submitted microbatch of ``n`` entries against
        ``group``'s shard (EMA matching the coalescer's width policy).
        A group's first note re-homes it to the least-loaded shard
        unless FISHNET_SHARD_PLACEMENT=rr pins the static layout."""
        with self._lock:
            s = self._assign[group]
            if not self._rr_only and group not in self._active:
                self._active.add(group)
                tgt = self._least_loaded_locked(current=s)
                if tgt != s:
                    self._assign[group] = tgt
                    s = tgt
            self._load[s] = 0.8 * self._load[s] + 0.2 * float(n)

    def shard_loads(self) -> List[float]:
        with self._lock:
            return list(self._load)

    def groups_of(self, shard: int) -> List[int]:
        with self._lock:
            return sorted(g for g, s in self._assign.items() if s == shard)

    def group_count(self, shard: int) -> int:
        with self._lock:
            return sum(1 for s in self._assign.values() if s == shard)

    def alive_shards(self) -> List[int]:
        with self._lock:
            return list(self._alive)

    def drain(self, shard: int) -> Dict[int, int]:
        """Mark ``shard`` dead and reassign its groups over the
        surviving shards — least-loaded-first (round-robin under
        FISHNET_SHARD_PLACEMENT=rr, and equivalent to it when loads are
        level). Returns {group: new_shard} for the moved groups. Raises
        RuntimeError when no shard would remain — the caller escalates
        to the whole-service failure path."""
        with self._lock:
            if shard in self._alive:
                if len(self._alive) == 1:
                    raise RuntimeError("no alive shard left in the mesh")
                self._alive.remove(shard)
            moved = {}
            drained = sorted(g for g, s in self._assign.items() if s == shard)
            for i, g in enumerate(drained):
                if self._rr_only:
                    tgt = self._alive[i % len(self._alive)]
                else:
                    tgt = self._least_loaded_locked()
                self._assign[g] = tgt
                moved[g] = tgt
            return moved


def factor_mesh(n_devices: int, max_model: int = 2) -> Tuple[int, int]:
    """Split ``n_devices`` into (data, model) sizes. Model parallelism
    beyond a few ways does not pay for a 1024-wide FT, so ``model`` is
    capped and the rest goes to data parallelism."""
    model = 1
    for cand in range(min(max_model, n_devices), 0, -1):
        if n_devices % cand == 0:
            model = cand
            break
    return n_devices // model, model


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    data: Optional[int] = None,
    model: Optional[int] = None,
) -> Mesh:
    """Build a ("data", "model") mesh over the given (default: all)
    devices."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if data is None and model is None:
        data, model = factor_mesh(n)
    elif data is None:
        data = n // model
    elif model is None:
        model = n // data
    if data * model != n:
        raise ValueError(f"mesh {data}x{model} != {n} devices")
    arr = np.asarray(devices).reshape(data, model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dimension over BOTH mesh axes — for
    inference there is no reason to leave the model axis idle."""
    return NamedSharding(mesh, P((DATA_AXIS, MODEL_AXIS)))


def pad_to_multiple(n: int, multiple: int) -> int:
    return int(math.ceil(n / multiple)) * multiple


class ShardedEvaluator:
    """Batched NNUE evaluation sharded across a mesh.

    Serving shards the *batch* over every device on both mesh axes (pure
    dp — for a ~47 MiB net, replicating params and splitting positions is
    strictly better than splitting the FT width; tp over the model axis
    is used by the trainer, not here). Drop-in for ``evaluate_batch_jit``
    behind ``SearchService``'s ``evaluator`` seam.

    The sharded computation is a ``shard_map``: every device evaluates
    its batch shard COMPLETELY LOCALLY — zero collectives in the
    compiled program (asserted by tests/test_parallel.py against the
    HLO). That is only sound because incremental (delta) entries never
    reference across a shard boundary: the native pool aligns block
    emission to the shard size (cpp/src/pool.cpp emit_block `align`;
    SearchService passes group_capacity / n_devices) and this wrapper
    rebases the anchor codes to shard-local indices. Round 2 instead
    let GSPMD resolve batch-relative references, which required an
    all-gather of the [B, 2, 1024] int32 accumulators over ICI —
    ~134 MB per 16k eval step, a scaling hazard the alignment deletes.
    """

    def __init__(self, params, mesh: Optional[Mesh] = None, batch_capacity: int = 1024):
        from jax.sharding import PartitionSpec

        from fishnet_tpu.nnue.jax_eval import evaluate_batch

        from jax import shard_map as _shard_map

        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_devices = self.mesh.devices.size
        #: Batch sizes fed to __call__ must be multiples of this so the
        #: leading dimension splits evenly across the mesh.
        self.size_multiple = self.n_devices
        self.batch_capacity = pad_to_multiple(batch_capacity, self.n_devices)
        self.params = jax.device_put(params, replicated(self.mesh))
        batch_axes = PartitionSpec((DATA_AXIS, MODEL_AXIS))
        repl = PartitionSpec()

        def local_eval(params, indices, buckets, parent, material):
            return evaluate_batch(params, indices, buckets, parent, material)

        def local_eval_nomat(params, indices, buckets, parent):
            return evaluate_batch(params, indices, buckets, parent)

        self._fn_mat = jax.jit(
            _shard_map(
                local_eval, mesh=self.mesh,
                in_specs=(repl, batch_axes, batch_axes, batch_axes, batch_axes),
                out_specs=batch_axes,
            )
        )
        self._fn = jax.jit(
            _shard_map(
                local_eval_nomat, mesh=self.mesh,
                in_specs=(repl, batch_axes, batch_axes, batch_axes),
                out_specs=batch_axes,
            )
        )

        # PACKED WIRE over the mesh (VERDICT r4 item 4): the service
        # repacks the pool's row stream into a fixed per-shard row tier
        # (see SearchService._dispatch_eval), so the leading axis splits
        # evenly and each shard expands ITS OWN rows locally inside the
        # shard_map — the multi-chip path now ships ~32 bytes per delta
        # entry like the single-device path, instead of the 128-byte
        # dense expansion (plus host CPU for expand_packed_np) it paid
        # before. Jitted per row-tier (3 shapes), like the single-device
        # compile matrix.
        from fishnet_tpu.nnue.jax_eval import evaluate_packed

        def local_packed(params, packed, offsets, buckets, parent, material):
            return evaluate_packed(params, packed, offsets, buckets, parent,
                                   material)

        self._packed_fn = jax.jit(
            _shard_map(
                local_packed, mesh=self.mesh,
                in_specs=(repl, batch_axes, batch_axes, batch_axes,
                          batch_axes, batch_axes),
                out_specs=batch_axes,
            )
        )

    #: SearchService probes this to keep the packed wire on (service-side
    #: per-shard repack + on-device expansion) instead of falling back to
    #: the dense host-side expansion.
    supports_packed = True

    def packed_eval(self, params, packed, offsets, buckets, parent, material):
        """Evaluate an ALREADY per-shard-repacked row stream: ``packed``
        [n_devices * tier, 2, 8] (each shard's rows padded to the same
        tier, trailing 4 sentinel rows per shard), ``offsets`` [B] with
        SHARD-LOCAL row values. ``params`` is ignored like __call__."""
        import numpy as _np

        batch = offsets.shape[0]
        parent = self._local_parents(parent, batch)
        if material is None:
            material = _np.zeros((batch,), _np.int32)
        return self._packed_fn(
            self.params, packed, offsets, buckets, parent, material
        )

    def _local_parents(self, parent, batch):
        """Rebase batch-relative anchor codes to shard-local indices.
        Valid because the pool's aligned emission keeps every delta and
        its anchor inside one shard (asserted here: a violation would
        silently read another position's accumulator)."""
        import numpy as _np

        shard = batch // self.n_devices
        parent = _np.asarray(parent, _np.int32)
        valid = parent >= 0
        ref = parent >> 1
        if valid.any():
            same_shard = (ref[valid] // shard) == (
                _np.nonzero(valid)[0] // shard
            )
            if not same_shard.all():
                raise ValueError(
                    "delta entry references an anchor outside its mesh "
                    "shard — the pool must emit with align = shard size"
                )
        return _np.where(valid, ((ref % shard) << 1) | (parent & 1), -1).astype(
            _np.int32
        )

    def __call__(self, params, indices, buckets, parent=None, material=None):
        # Signature-compatible with evaluate_batch_jit; `params` is
        # ignored — the replicated tree from construction is used.
        import numpy as _np

        batch = indices.shape[0]
        if parent is None:
            parent = _np.full((batch,), -1, _np.int32)
        else:
            parent = self._local_parents(parent, batch)
        if material is None:
            return self._fn(self.params, indices, buckets, parent)
        return self._fn_mat(self.params, indices, buckets, parent, material)


class ShardedSegmentedEvaluator:
    """shard_map over the packed-anchored SEGMENTED evaluator: the fused
    coalescer wire (nnue/jax_eval.evaluate_packed_anchored_segmented)
    as ONE mesh-wide program, segments sharded over the data axis with
    each shard's persistent anchor/PSQT tables resident on that shard.

    Segment-locality is what makes this collective-free: every
    segment's parent codes are SEGMENT-LOCAL (in-batch refs and
    persistent-anchor rows both rebase inside the segment —
    ops/ft_gather.recode_segment_parents / derive_segment_offsets), so
    a device holding segments [k, k+K/n) never reads another device's
    rows or tables. tests/test_parallel.py asserts the compiled HLO
    contains zero collectives, the same invariant the single-program
    benchmark path proved for evaluate_packed in round 5.

    Serving itself uses per-shard PLACEMENT (independent per-device
    dispatches driven by SearchService's shard router) rather than this
    one fused program — placement lets shards degrade, drain, and
    pipeline independently, which one mesh-wide program cannot. This
    class is the topology's reference semantics: sharded-vs-single
    parity and the zero-collectives proof are pinned against it.

    The XLA realization is pinned (``use_pallas=False``): inside
    shard_map the fused Pallas kernel's interpreter fallback is not a
    supported venue, and all rungs are bit-identical anyway.
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        from jax.sharding import PartitionSpec

        from fishnet_tpu.nnue.jax_eval import (
            evaluate_packed_anchored_segmented,
        )

        from jax import shard_map as _shard_map

        if mesh is None:
            devs = devices if devices is not None else jax.devices()
            mesh = make_mesh(devs, model=1)
        self.mesh = mesh
        self.n_devices = mesh.devices.size
        seg = PartitionSpec(DATA_AXIS)
        repl = PartitionSpec()

        def local_mat(params, packed, buckets, parent, material,
                      anchor_tabs, seg_rows, psqt_tabs):
            return evaluate_packed_anchored_segmented(
                params, packed, buckets, parent, material,
                anchor_tabs, seg_rows, psqt_tabs, use_pallas=False,
            )

        def local_nomat(params, packed, buckets, parent,
                        anchor_tabs, seg_rows, psqt_tabs):
            return evaluate_packed_anchored_segmented(
                params, packed, buckets, parent, None,
                anchor_tabs, seg_rows, psqt_tabs, use_pallas=False,
            )

        self._fn_mat = jax.jit(
            _shard_map(
                local_mat, mesh=mesh,
                in_specs=(repl, seg, seg, seg, seg, seg, seg, seg),
                out_specs=(seg, seg, seg),
            )
        )
        self._fn = jax.jit(
            _shard_map(
                local_nomat, mesh=mesh,
                in_specs=(repl, seg, seg, seg, seg, seg, seg),
                out_specs=(seg, seg, seg),
            )
        )

    def __call__(self, params, packed, buckets, parent, material,
                 anchor_tabs, seg_rows, psqt_tabs):
        """Same contract as evaluate_packed_anchored_segmented; the
        segment count K (= anchor_tabs.shape[0]) must divide evenly over
        the mesh so each device owns whole segments."""
        k = anchor_tabs.shape[0]
        if k % self.n_devices:
            raise ValueError(
                f"segment count {k} does not divide over {self.n_devices} "
                "devices — pad the dispatch to a whole-segment multiple"
            )
        if material is None:
            return self._fn(params, packed, buckets, parent,
                            anchor_tabs, seg_rows, psqt_tabs)
        return self._fn_mat(params, packed, buckets, parent, material,
                            anchor_tabs, seg_rows, psqt_tabs)
