"""Process-wide position-keyed eval reuse plane.

One ``EvalCache`` per process maps Zobrist position hash -> (static
eval, generation). It is shared across pipeline groups, mesh shards,
tenants and — because it outlives any single ``SearchService`` — across
pool respawns, which is exactly where the pool's own TT (torn down with
the pool) loses its history. The service probes it in the driver loop
right after ``fc_pool_step`` hands over a batch (whole-batch
short-circuit: every entry cached -> the dispatch is skipped entirely)
and inside ``plan_segment_dedup`` (per-entry drops inside a fused
dispatch), and inserts at provide time — the one site every ladder rung
(fused / xla / host-material), the coalescer-off path and the mesh path
all funnel through.

Correctness stance: the NNUE static eval is a pure function of the
position, so substituting a cached value for a recomputed one is
bit-identical (modulo 64-bit Zobrist collisions — the same accepted
risk the native TT already carries). ``FISHNET_NO_EVAL_CACHE=1``
disables every probe/insert; cold-cache and cache-off runs must produce
byte-identical analyses (gated by ``make cache-smoke``).

Concurrency: lock-striped buckets (doc/static-analysis.md R4 — every
stripe access holds that stripe's lock). Writers are the per-group
driver threads at provide time; each batch's inserts scatter over
stripes, so cross-group contention is bounded by stripe count, not by a
global lock. Memory is bounded: each stripe holds at most
``capacity // stripes`` entries, and overflow evicts the oldest
*generations* first (a generation advances at batch completion, see
``sched/queue.py``), so entries from long-dead batches leave before the
working set of live ones.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

#: Default bound on total entries (score + generation per entry; at the
#: default 1M entries the table tops out around ~100 MB of dict
#: overhead — a deliberate host-RAM-for-dispatches trade).
DEFAULT_CAPACITY = 1 << 20

#: Stripe count: enough that 8 driver threads rarely collide, small
#: enough that the per-stripe capacity stays meaningful at tiny test
#: capacities.
DEFAULT_STRIPES = 64


def cache_disabled() -> bool:
    """The escape hatch, read per call so tests can monkeypatch env."""
    return os.environ.get("FISHNET_NO_EVAL_CACHE", "") == "1"


def bounds_disabled() -> bool:
    """The bounds-tier escape hatch (``FISHNET_NO_BOUNDS=1``), read per
    call like :func:`cache_disabled`. With it set, no bound record is
    ever probed, harvested or seeded — the search plane behaves
    byte-for-byte like the exact-eval memo alone (doc/eval-cache.md
    "Bounds tier"). The shared ``FISHNET_NO_EVAL_CACHE=1`` hatch
    implies this one: bounds ride the same reuse plane."""
    return (
        cache_disabled()
        or os.environ.get("FISHNET_NO_BOUNDS", "") == "1"
    )


#: Warm-restart snapshot file (doc/resilience.md "Graceful drain"): when
#: set, the client persists the cache here on drain and reloads it at
#: startup, so a restarted process's first batches resolve pre-wire
#: instead of paying the cold-cache dispatches again.
SNAPSHOT_ENV = "FISHNET_EVAL_CACHE_SNAPSHOT"

#: Snapshot format version; a mismatch discards the file like a
#: fingerprint mismatch does.
SNAPSHOT_VERSION = 1


def snapshot_path() -> Optional[str]:
    """The configured snapshot file, or None (snapshots off)."""
    return os.environ.get(SNAPSHOT_ENV) or None


def az_net_fingerprint(params) -> int:
    """64-bit blake2b over an AZ param pytree's raw array bytes — the
    network-identity salt the shared AZ dispatch plane XORs into every
    AZ cache key (doc/search.md). Serialization is canonical (leaves
    hashed in ``jax.tree_util`` flatten order, shape+dtype prefixed), so
    the same weights always key the same region and AZ entries NEVER
    collide with NNUE entries: the two families' fingerprints hash
    disjoint byte streams (param arrays vs the .nnue file) and each key
    is only ever probed by its own family's plane."""
    import hashlib

    import jax

    h = hashlib.blake2b(digest_size=8)
    h.update(b"az-params/1")
    for leaf in jax.tree_util.tree_leaves(params):
        arr = np.asarray(leaf)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return int.from_bytes(h.digest(), "little")


#: Odd 64-bit multiplier (golden-ratio) mixing the halfmove clock into
#: an AZ position key. The AZ input planes encode the clock (plane 17)
#: but the Zobrist hash does not, so two positions differing only in
#: clock would alias under a raw-Zobrist key and replay the wrong
#: policy row. NNUE keys never mix the clock — its features are
#: piece-square only — so the two families' key schemes differ even
#: before the fingerprint salt.
_HALFMOVE_MIX = 0x9E3779B97F4A7C15
_U64 = (1 << 64) - 1


def az_position_key(zobrist: int, halfmove: int) -> int:
    """The UNSALTED AZ cache key for one position: Zobrist hash mixed
    with the halfmove clock (the one board fact the AZ planes see that
    Zobrist omits). The dispatch plane XORs :func:`az_net_fingerprint`
    on top before probing, so the pool side never needs the weights."""
    return (zobrist ^ ((halfmove * _HALFMOVE_MIX) & _U64)) & _U64


def net_fingerprint(path: str) -> int:
    """64-bit blake2b of the ``.nnue`` file — the network-identity salt
    the service XORs into every cache key. Positions only collide with
    themselves *under the same network*: a respawn onto updated weights
    (or a second service with a different net in the same process)
    keys a disjoint region of the shared cache instead of reading the
    old network's evals. Matches ``NnueWeights.fingerprint()`` because
    ``save`` writes the canonical form this hashes."""
    import hashlib

    h = hashlib.blake2b(digest_size=8)
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return int.from_bytes(h.digest(), "little")


class EvalCache:
    """Sharded hash -> (eval, generation) map with striped locking and
    generation-based eviction. All methods are thread-safe."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        stripes: int = DEFAULT_STRIPES,
    ) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        stripes = max(1, min(int(stripes), int(capacity)))
        # Per-stripe cap; rounding up keeps tiny-capacity configs usable.
        self._stripe_cap = max(1, (int(capacity) + stripes - 1) // stripes)
        self._locks = [threading.Lock() for _ in range(stripes)]
        self._stripes: List[Dict[int, Tuple[int, int]]] = [
            {} for _ in range(stripes)
        ]
        self._n_stripes = stripes
        # Generation clock + stats share one leaf lock (cold counters;
        # the per-probe hit/miss tallies are batched by callers).
        self._meta_lock = threading.Lock()
        self._generation = 0
        self._hits = 0
        self._misses = 0
        self._insertions = 0
        self._evictions = 0

    # -- internals --------------------------------------------------------

    def _stripe_of(self, h: int) -> int:
        # Mix the high bits in: Zobrist hashes are uniform, but the TT
        # downstream indexes on low bits — keep the stripe choice
        # decorrelated from any other consumer of the same hash.
        return ((h >> 48) ^ h) % self._n_stripes

    def _evict_locked(self, s: int) -> None:
        """Drop the oldest generation(s) from stripe `s` until it is
        under its cap. Caller holds the stripe lock."""
        stripe = self._stripes[s]
        dropped = 0
        while len(stripe) >= self._stripe_cap and stripe:
            oldest = min(g for (_, g) in stripe.values())
            stale = [h for h, (_, g) in stripe.items() if g == oldest]
            for h in stale:
                del stripe[h]
            dropped += len(stale)
        if dropped:
            with self._meta_lock:
                self._evictions += dropped

    # -- core API ---------------------------------------------------------

    def probe(self, h: int) -> Optional[int]:
        """Cached eval for hash `h`, or None. A hit refreshes the
        entry's generation (hot openings outlive eviction sweeps)."""
        s = self._stripe_of(h)
        gen = self._generation
        with self._locks[s]:
            ent = self._stripes[s].get(h)
            if ent is not None:
                self._stripes[s][h] = (ent[0], gen)
        with self._meta_lock:
            if ent is None:
                self._misses += 1
            else:
                self._hits += 1
        return None if ent is None else ent[0]

    def contains(self, h: int) -> bool:
        """Stats-neutral membership test: no hit/miss accounting, no
        generation refresh. For advisory callers (speculation admission)
        whose probes must not skew the hit-rate telemetry the control
        plane steers on."""
        s = self._stripe_of(h)
        with self._locks[s]:
            return h in self._stripes[s]

    def insert(self, h: int, value: int) -> None:
        s = self._stripe_of(h)
        gen = self._generation
        with self._locks[s]:
            stripe = self._stripes[s]
            if h not in stripe and len(stripe) >= self._stripe_cap:
                self._evict_locked(s)
            stripe[h] = (int(value), gen)
        with self._meta_lock:
            self._insertions += 1

    def probe_block(
        self, hashes: np.ndarray, out: Optional[np.ndarray] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vector probe for one batch: returns ``(values, hit_mask)``
        with ``values[i]`` valid where ``hit_mask[i]``. Misses are NOT
        charged per-entry locks twice: each hash takes exactly one
        stripe-lock round trip."""
        n = len(hashes)
        values = out if out is not None else np.zeros(n, dtype=np.int32)
        mask = np.zeros(n, dtype=bool)
        hits = 0
        gen = self._generation
        for i in range(n):
            h = int(hashes[i])
            s = self._stripe_of(h)
            with self._locks[s]:
                ent = self._stripes[s].get(h)
                if ent is not None:
                    self._stripes[s][h] = (ent[0], gen)
            if ent is not None:
                values[i] = ent[0]
                mask[i] = True
                hits += 1
        with self._meta_lock:
            self._hits += hits
            self._misses += n - hits
        return values, mask

    def insert_block(self, hashes: np.ndarray, values: np.ndarray) -> None:
        """Single-writer batch insert (the provide-time fill path)."""
        n = min(len(hashes), len(values))
        gen = self._generation
        for i in range(n):
            h = int(hashes[i])
            s = self._stripe_of(h)
            with self._locks[s]:
                stripe = self._stripes[s]
                if h not in stripe and len(stripe) >= self._stripe_cap:
                    self._evict_locked(s)
                stripe[h] = (int(values[i]), gen)
        with self._meta_lock:
            self._insertions += n

    # -- generations ------------------------------------------------------

    def advance_generation(self) -> int:
        """Tick the eviction clock (called at batch completion by the
        scheduler, ``sched/queue.py``). Entries keep their insert/touch
        generation; eviction drops oldest-generation entries first."""
        with self._meta_lock:
            self._generation += 1
            return self._generation

    # -- introspection ----------------------------------------------------

    def __len__(self) -> int:
        total = 0
        for s in range(self._n_stripes):
            with self._locks[s]:
                total += len(self._stripes[s])
        return total

    def stats(self) -> Dict[str, int]:
        with self._meta_lock:
            st = {
                "hits": self._hits,
                "misses": self._misses,
                "insertions": self._insertions,
                "evictions": self._evictions,
                "generation": self._generation,
            }
        st["entries"] = len(self)
        return st

    def clear(self) -> None:
        """Drop all entries (stats and generation survive): a cold-run
        reset."""
        for s in range(self._n_stripes):
            with self._locks[s]:
                self._stripes[s].clear()

    # -- snapshot (warm restart) ------------------------------------------

    def dump_entries(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """All entries as ``(hashes, values, generations)`` arrays.
        Stripe-by-stripe under each stripe's lock — concurrent inserts
        land in the snapshot or not, either is a valid snapshot."""
        hashes: List[int] = []
        values: List[int] = []
        gens: List[int] = []
        for s in range(self._n_stripes):
            with self._locks[s]:
                for h, (v, g) in self._stripes[s].items():
                    hashes.append(h)
                    values.append(v)
                    gens.append(g)
        return (
            np.array(hashes, dtype=np.uint64),
            np.array(values, dtype=np.int32),
            np.array(gens, dtype=np.int64),
        )

    def load_entries(
        self,
        hashes: np.ndarray,
        values: np.ndarray,
        gens: np.ndarray,
    ) -> int:
        """Restore dumped entries (normal eviction applies if they
        exceed capacity). The generation clock advances to at least the
        newest restored generation so eviction ordering stays sane."""
        n = min(len(hashes), len(values), len(gens))
        top = 0
        for i in range(n):
            h = int(hashes[i])
            g = int(gens[i])
            top = max(top, g)
            s = self._stripe_of(h)
            with self._locks[s]:
                stripe = self._stripes[s]
                if h not in stripe and len(stripe) >= self._stripe_cap:
                    self._evict_locked(s)
                stripe[h] = (int(values[i]), g)
        with self._meta_lock:
            self._generation = max(self._generation, top)
        return n


#: Default AZ-cache bound. AZ entries are ~300x heavier than NNUE's
#: (a full fp16 policy row + value vs one int32), so the default is
#: correspondingly smaller: 4096 entries is ~40 MB of logits payload.
DEFAULT_AZ_CAPACITY = 1 << 12


class AzEvalCache(EvalCache):
    """Object-valued twin of :class:`EvalCache` for the AZ family: each
    entry is ``(policy_logits float16 [4672], value float)`` — the
    EXACT wire payload a device dispatch returns, so substituting a hit
    for a recomputed row reconstructs bit-identical float32 logits
    (``.astype(np.float32)`` of the same fp16 bits) and the shared-
    plane-vs-legacy parity gate holds through warm caches. Striping,
    generation eviction and stats are all inherited; only the value
    coercion (objects, not ints) and the per-row probe/insert surface
    differ. Keyed ``(zobrist ^ halfmove-mix) ^ az_net_fingerprint`` by
    the AZ dispatch plane (doc/search.md) — the fingerprint keeps AZ
    and NNUE keys disjoint in principle, and in practice the two
    families also live in SEPARATE cache instances (:func:`get_az_cache`
    vs :func:`get_cache`) so their capacity budgets never compete."""

    def insert(self, h: int, value) -> None:
        s = self._stripe_of(h)
        gen = self._generation
        with self._locks[s]:
            stripe = self._stripes[s]
            if h not in stripe and len(stripe) >= self._stripe_cap:
                self._evict_locked(s)
            stripe[h] = (value, gen)
        with self._meta_lock:
            self._insertions += 1

    def probe_many(self, keys) -> List[Optional[object]]:
        """Per-row object probe: ``out[i]`` is the cached value for
        ``keys[i]`` or None. One stripe-lock round trip per key; hits
        refresh the entry's generation like :meth:`probe`."""
        out: List[Optional[object]] = []
        hits = 0
        gen = self._generation
        for k in keys:
            h = int(k)
            s = self._stripe_of(h)
            with self._locks[s]:
                ent = self._stripes[s].get(h)
                if ent is not None:
                    self._stripes[s][h] = (ent[0], gen)
            out.append(None if ent is None else ent[0])
            if ent is not None:
                hits += 1
        with self._meta_lock:
            self._hits += hits
            self._misses += len(out) - hits
        return out

    # -- snapshot (warm restart) ------------------------------------------

    def dump_az_entries(
        self,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """All entries as ``(hashes, logits_fp16 [n, P], values_f32,
        generations)`` arrays — the object payloads flattened into
        dense arrays npz can round-trip exactly (the fp16 rows ARE the
        stored bits). Rows whose policy width disagrees with the first
        row are skipped (a cache can in principle hold mixed
        architectures; a snapshot cannot)."""
        hashes: List[int] = []
        rows: List[np.ndarray] = []
        values: List[float] = []
        gens: List[int] = []
        width: Optional[int] = None
        for s in range(self._n_stripes):
            with self._locks[s]:
                items = list(self._stripes[s].items())
            for h, (ent, g) in items:
                try:
                    lg, val = ent
                    lg = np.asarray(lg, dtype=np.float16).reshape(-1)
                except (TypeError, ValueError):
                    continue
                if width is None:
                    width = len(lg)
                elif len(lg) != width:
                    continue
                hashes.append(h)
                rows.append(lg)
                values.append(float(val))
                gens.append(g)
        logits = (
            np.stack(rows) if rows else np.empty((0, 0), dtype=np.float16)
        )
        return (
            np.array(hashes, dtype=np.uint64),
            logits.astype(np.float16, copy=False),
            np.array(values, dtype=np.float32),
            np.array(gens, dtype=np.int64),
        )

    def load_az_entries(
        self,
        hashes: np.ndarray,
        logits: np.ndarray,
        values: np.ndarray,
        gens: np.ndarray,
    ) -> int:
        """Restore dumped AZ entries; the inverse of
        :meth:`dump_az_entries`. Each restored entry is the exact
        ``(fp16 row, float32 value)`` tuple the plane would have
        inserted, so warm-restart replays reconstruct identical fp32
        logits. Generation clock semantics match the base loader."""
        n = min(len(hashes), len(logits), len(values), len(gens))
        top = 0
        for i in range(n):
            h = int(hashes[i])
            g = int(gens[i])
            top = max(top, g)
            ent = (
                np.array(logits[i], dtype=np.float16),
                np.float32(values[i]),
            )
            s = self._stripe_of(h)
            with self._locks[s]:
                stripe = self._stripes[s]
                if h not in stripe and len(stripe) >= self._stripe_cap:
                    self._evict_locked(s)
                stripe[h] = (ent, g)
        with self._meta_lock:
            self._generation = max(self._generation, top)
        return n


#: Bound-type codes, matching the native TT's ``TTBound`` enum
#: (cpp/src/search.h) so records cross the ctypes boundary without
#: translation: 0 = none/miss, 1 = upper bound (fail-low), 2 = lower
#: bound (fail-high), 3 = exact.
BOUND_NONE = 0
BOUND_UPPER = 1
BOUND_LOWER = 2
BOUND_EXACT = 3

#: The native 21-bit packed-move "no move" sentinel (all ones). Bound
#: records store moves in packed native form — they are only ever fed
#: back through ``fc_pool_tt_fill_bound``, never decoded host-side.
MOVE_NONE_BITS = 0x1FFFFF

#: Default bound on bounds-tier entries. Each record is a small tuple
#: (5 ints + generation); 64k entries cover the working set of a long
#: analysis session at a few MB.
DEFAULT_BOUNDS_CAPACITY = 1 << 16


class BoundsCache(EvalCache):
    """Bound-record twin of :class:`EvalCache`: each entry is
    ``(value, eval, depth, bound, move_bits, uci)`` — a full search fact in
    the native TT's own representation (value in stored/value_to_tt
    form, move packed 21-bit), keyed ``zobrist ^ net_fingerprint`` like
    the exact-eval memo. Unlike the memo, replacement is
    **deeper-entry-wins**: a same-key insert only lands when its depth
    is >= the resident entry's (an exact bound additionally beats a
    non-exact one at equal depth), so a shallow re-search can never
    clobber the deep record that makes the cutoff. Striping,
    generation eviction and stats are inherited."""

    def insert_bound(
        self,
        h: int,
        value: int,
        eval_: int,
        depth: int,
        bound: int,
        move_bits: int,
        uci: Optional[str] = None,
    ) -> bool:
        """Deeper-entry-wins insert; returns True when the record
        landed (new key, or it beat the resident entry). ``uci`` is the
        best move in UCI form when the harvester knows it (PV replay) —
        the submit-time chain walk needs a move it can PLAY on a host
        board, while ``move_bits`` (the packed native form) is what
        seeds the pool TT."""
        if bound <= BOUND_NONE or bound > BOUND_EXACT:
            return False
        s = self._stripe_of(h)
        gen = self._generation
        rec = (
            int(value), int(eval_), int(depth), int(bound),
            int(move_bits), uci,
        )
        with self._locks[s]:
            stripe = self._stripes[s]
            ent = stripe.get(h)
            if ent is not None:
                old = ent[0]
                if old[2] > depth or (
                    old[2] == depth
                    and old[3] == BOUND_EXACT
                    and bound != BOUND_EXACT
                ):
                    # Refresh the survivor's generation — it just proved
                    # it is hot.
                    stripe[h] = (old, gen)
                    return False
            elif len(stripe) >= self._stripe_cap:
                self._evict_locked(s)
            stripe[h] = (rec, gen)
        with self._meta_lock:
            self._insertions += 1
        return True

    def probe_bound(
        self, h: int
    ) -> Optional[Tuple[int, int, int, int, int, Optional[str]]]:
        """Cached bound record for ``h``, or None. Hits refresh the
        entry's generation like the base probe."""
        s = self._stripe_of(h)
        gen = self._generation
        with self._locks[s]:
            ent = self._stripes[s].get(h)
            if ent is not None:
                self._stripes[s][h] = (ent[0], gen)
        with self._meta_lock:
            if ent is None:
                self._misses += 1
            else:
                self._hits += 1
        return None if ent is None else ent[0]

    def probe_bounds_block(
        self, hashes: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Vector probe: returns ``(values, evals, depths, bounds,
        moves)`` int32/uint32 arrays with ``bounds[i] == BOUND_NONE``
        marking a miss — the exact column layout
        ``fc_pool_tt_fill_bound`` consumes, so the seeding loop never
        unpacks tuples per row on the hot path."""
        n = len(hashes)
        values = np.zeros(n, dtype=np.int32)
        evals = np.zeros(n, dtype=np.int32)
        depths = np.zeros(n, dtype=np.int32)
        bounds = np.zeros(n, dtype=np.int32)
        moves = np.full(n, MOVE_NONE_BITS, dtype=np.uint32)
        hits = 0
        gen = self._generation
        for i in range(n):
            h = int(hashes[i])
            s = self._stripe_of(h)
            with self._locks[s]:
                ent = self._stripes[s].get(h)
                if ent is not None:
                    self._stripes[s][h] = (ent[0], gen)
            if ent is not None:
                v, e, d, b, m = ent[0][:5]
                values[i] = v
                evals[i] = e
                depths[i] = d
                bounds[i] = b
                moves[i] = m
                hits += 1
        with self._meta_lock:
            self._hits += hits
            self._misses += n - hits
        return values, evals, depths, bounds, moves


# -- process-wide singleton -----------------------------------------------

_global_lock = threading.Lock()
_global_cache: Optional[EvalCache] = None
_collector_token: Optional[int] = None
_global_az_cache: Optional[AzEvalCache] = None
_az_collector_token: Optional[int] = None
_global_bounds_cache: Optional[BoundsCache] = None
_bounds_collector_token: Optional[int] = None


def _collect_families():
    """Registry collector: entry count + eviction total for the process
    cache (hit counters are exported by the service collector, where
    the prewire/pool scope split lives)."""
    cache = _global_cache
    if cache is None:
        return None  # self-unregister after reset_cache()
    from ..telemetry.registry import counter_family, gauge_family

    st = cache.stats()
    return [
        gauge_family(
            "fishnet_eval_cache_entries",
            "Live entries in the process-wide eval cache.",
            st["entries"],
        ),
        counter_family(
            "fishnet_eval_cache_evictions_total",
            "Entries evicted from the eval cache (generation sweeps).",
            st["evictions"],
        ),
    ]


def _collect_az_families():
    """Registry collector for the AZ twin: same family names, tagged
    ``family="az"`` so the fleet plane can tell the two reuse caches
    apart (hit counters, scope-split, are exported by the AZ dispatch
    plane's collector — mirroring the NNUE service split)."""
    cache = _global_az_cache
    if cache is None:
        return None  # self-unregister after reset_cache()
    from ..telemetry.registry import counter_family, gauge_family

    st = cache.stats()
    return [
        gauge_family(
            "fishnet_eval_cache_entries",
            "Live entries in the process-wide eval cache.",
            st["entries"],
            labels={"family": "az"},
        ),
        counter_family(
            "fishnet_eval_cache_evictions_total",
            "Entries evicted from the eval cache (generation sweeps).",
            st["evictions"],
            labels={"family": "az"},
        ),
    ]


def get_az_cache() -> Optional[AzEvalCache]:
    """The process-wide AZ eval cache, or None when the shared
    ``FISHNET_NO_EVAL_CACHE=1`` hatch is set. Created on first use;
    capacity via ``FISHNET_AZ_EVAL_CACHE_CAPACITY``. A separate
    instance from :func:`get_cache` — the object-valued AZ entries are
    ~300x heavier, so they get their own (much smaller) budget instead
    of evicting NNUE's million-entry working set."""
    if cache_disabled():
        return None
    global _global_az_cache, _az_collector_token
    with _global_lock:
        if _global_az_cache is None:
            cap = int(
                os.environ.get(
                    "FISHNET_AZ_EVAL_CACHE_CAPACITY", DEFAULT_AZ_CAPACITY
                )
            )
            _global_az_cache = AzEvalCache(capacity=cap)
            from ..telemetry.registry import REGISTRY

            _az_collector_token = REGISTRY.register_collector(
                _collect_az_families, name="az-eval-cache"
            )
        return _global_az_cache


def _collect_bounds_families():
    """Registry collector for the bounds tier: same family names,
    tagged ``family="bounds"`` (consumption counters — seeds, cutoff
    credit — are exported by the service collector)."""
    cache = _global_bounds_cache
    if cache is None:
        return None  # self-unregister after reset_cache()
    from ..telemetry.registry import counter_family, gauge_family

    st = cache.stats()
    return [
        gauge_family(
            "fishnet_eval_cache_entries",
            "Live entries in the process-wide eval cache.",
            st["entries"],
            labels={"family": "bounds"},
        ),
        counter_family(
            "fishnet_eval_cache_evictions_total",
            "Entries evicted from the eval cache (generation sweeps).",
            st["evictions"],
            labels={"family": "bounds"},
        ),
    ]


def get_bounds_cache() -> Optional[BoundsCache]:
    """The process-wide bounds cache, or None when ``FISHNET_NO_BOUNDS=1``
    (or the shared cache hatch) is set. Created on first use; capacity
    via ``FISHNET_BOUNDS_CACHE_CAPACITY``. A separate instance from
    :func:`get_cache`: bound records and exact evals have different
    replacement policies (deeper-entry-wins vs last-write), so sharing
    a table would let a shallow eval overwrite a deep cutoff record."""
    if bounds_disabled():
        return None
    global _global_bounds_cache, _bounds_collector_token
    with _global_lock:
        if _global_bounds_cache is None:
            cap = int(
                os.environ.get(
                    "FISHNET_BOUNDS_CACHE_CAPACITY", DEFAULT_BOUNDS_CAPACITY
                )
            )
            _global_bounds_cache = BoundsCache(capacity=cap)
            from ..telemetry.registry import REGISTRY

            _bounds_collector_token = REGISTRY.register_collector(
                _collect_bounds_families, name="bounds-cache"
            )
        return _global_bounds_cache


def get_cache() -> Optional[EvalCache]:
    """The process-wide cache, or None when FISHNET_NO_EVAL_CACHE=1.
    Created on first use; capacity via FISHNET_EVAL_CACHE_CAPACITY."""
    if cache_disabled():
        return None
    global _global_cache, _collector_token
    with _global_lock:
        if _global_cache is None:
            cap = int(
                os.environ.get("FISHNET_EVAL_CACHE_CAPACITY", DEFAULT_CAPACITY)
            )
            _global_cache = EvalCache(capacity=cap)
            from ..telemetry.registry import REGISTRY

            _collector_token = REGISTRY.register_collector(
                _collect_families, name="eval-cache"
            )
        return _global_cache


def reset_cache() -> None:
    """Tear down the process caches — BOTH families; a cold start is a
    cold start (tests). The registered collectors
    self-unregister on their next scrape."""
    global _global_cache, _global_az_cache, _global_bounds_cache
    with _global_lock:
        _global_cache = None
        _global_az_cache = None
        _global_bounds_cache = None


# -- warm-restart snapshot --------------------------------------------------


def save_snapshot(
    path: Optional[str] = None, fingerprint: int = 0,
    az_fingerprint: int = 0,
) -> Optional[str]:
    """Persist the process caches to ``path`` (default: the
    ``FISHNET_EVAL_CACHE_SNAPSHOT`` file; None with neither = no-op).
    ``fingerprint`` is the serving net's identity
    (:func:`net_fingerprint`; 0 for dev-mode random weights) — a
    restart onto different weights must NOT read this snapshot's evals,
    so :func:`load_snapshot` discards on mismatch. The AZ cache rides
    the same file under its own ``az_fingerprint``
    (:func:`az_net_fingerprint`), so a restarted MCTS fleet warm-starts
    pre-wire too; either family may be empty. Atomic (tmp + rename): a
    SIGKILL mid-write leaves the previous snapshot intact, never a torn
    file. Returns the path written, or None."""
    path = path or snapshot_path()
    if path is None:
        return None
    cache = _global_cache
    az_cache = _global_az_cache
    if cache is None and az_cache is None:
        return None
    if cache is not None:
        hashes, values, gens = cache.dump_entries()
        generation = cache.stats()["generation"]
    else:
        hashes = np.empty(0, np.uint64)
        values = np.empty(0, np.int32)
        gens = np.empty(0, np.int64)
        generation = 0
    arrays = {}
    if az_cache is not None:
        az_hashes, az_logits, az_values, az_gens = (
            az_cache.dump_az_entries()
        )
        if len(az_hashes):
            arrays = dict(
                az_fingerprint=np.uint64(az_fingerprint & ((1 << 64) - 1)),
                az_hashes=az_hashes,
                az_logits=az_logits,
                az_values=az_values,
                az_gens=az_gens,
            )
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # Open explicitly: np.savez appends ".npz" to bare paths, which
        # would break the rename.
        with open(tmp, "wb") as f:
            np.savez(
                f,
                version=np.int64(SNAPSHOT_VERSION),
                fingerprint=np.uint64(fingerprint & ((1 << 64) - 1)),
                generation=np.int64(generation),
                hashes=hashes,
                values=values,
                gens=gens,
                **arrays,
            )
        os.replace(tmp, path)
    except OSError:
        # Snapshotting is an optimization, never a liveness dependency.
        try:
            os.remove(tmp)
        except OSError:
            pass
        return None
    return path


def load_snapshot(
    path: Optional[str] = None, fingerprint: int = 0,
    az_fingerprint: int = 0,
) -> bool:
    """Restore a snapshot into the process caches. Returns True when
    entries were restored. A version or NNUE fingerprint mismatch (or
    a corrupt file) DISCARDS the snapshot — the file is removed so a
    process that upgraded its net doesn't retry the stale snapshot on
    every restart — and returns False. The AZ section is checked
    against ``az_fingerprint`` independently: an AZ-only mismatch
    skips just that section (the NNUE warm-start is still good — the
    two nets upgrade on different cadences), and a malformed AZ
    section never poisons the cache (the partially restored entries
    are dropped and the file discarded)."""
    import zipfile

    path = path or snapshot_path()
    if path is None or not os.path.exists(path):
        return False
    cache = get_cache()
    if cache is None:
        return False
    restored = False
    try:
        with np.load(path) as data:
            version = int(data["version"])
            snap_fp = int(data["fingerprint"])
            if version != SNAPSHOT_VERSION or snap_fp != (
                fingerprint & ((1 << 64) - 1)
            ):
                raise ValueError("snapshot version/fingerprint mismatch")
            cache.load_entries(data["hashes"], data["values"], data["gens"])
            restored = True
            if "az_hashes" in data.files:
                az_fp = int(data["az_fingerprint"])
                if az_fp == (az_fingerprint & ((1 << 64) - 1)):
                    az_cache = get_az_cache()
                    if az_cache is not None:
                        try:
                            az_cache.load_az_entries(
                                data["az_hashes"],
                                data["az_logits"],
                                data["az_values"],
                                data["az_gens"],
                            )
                        except (TypeError, ValueError, KeyError):
                            az_cache.clear()
                            raise
    except (OSError, ValueError, KeyError, zipfile.BadZipFile):
        try:
            os.remove(path)
        except OSError:
            pass
        return restored
    return True


class MissHistory:
    """Per-group cache-miss history window, feeding the prefetch-budget
    steering policy (``SearchService._steer_prefetch``). Driver threads
    record; any thread may read a rate — one leaf lock, cold path."""

    def __init__(self, window: int = 2048) -> None:
        self._lock = threading.Lock()
        self._window = max(1, int(window))
        self._probes: Dict[int, int] = {}
        self._hits: Dict[int, int] = {}

    def record(self, group: int, hits: int, probes: int) -> None:
        with self._lock:
            p = self._probes.get(group, 0) + probes
            h = self._hits.get(group, 0) + hits
            if p > self._window:
                # Exponential forget: halve the window when it fills so
                # the rate tracks the current traffic mix, not history.
                p //= 2
                h //= 2
            self._probes[group] = p
            self._hits[group] = h

    def hit_rate(self, group: int) -> Optional[float]:
        """Hit rate over the window, or None below a minimum sample."""
        with self._lock:
            p = self._probes.get(group, 0)
            if p < 64:
                return None
            return self._hits.get(group, 0) / p
