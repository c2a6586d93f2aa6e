"""Device meshes for the trainers and per-device placement for serving.

The reference has no multi-device tier at all — its "distributed backend"
is one HTTPS client (SURVEY.md §5, reference src/api.rs:489-536) and its
intra-client parallelism is one engine subprocess per core. The TPU-native
equivalent sits *below* the engine seam and has two halves:

* Training builds a ``jax.sharding.Mesh`` (``make_mesh``) with the axes

  * ``data``  — batch dimension of training microbatches (dp);
  * ``model`` — the feature-transformer width L1 and the contracting
    dimension of the first dense layer (tp): the FT table is the one
    big tensor, 22528 x 1024, and its optimizer state triples the
    footprint.

  Its collectives are inserted by XLA/GSPMD from sharding annotations —
  there are no hand-written collectives anywhere in the framework.

* Serving uses no mesh-wide program. Each visible device is one SHARD
  with its own replica of the params (``replicate_params``) and the
  tables of the pipeline groups ``ShardRouter`` homes on it; a dispatch
  is a plain single-device program placed by its committed inputs, so it
  never crosses devices (doc/sharding.md). ``--mesh DxM`` asks for
  ``D * M`` such shards.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

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
