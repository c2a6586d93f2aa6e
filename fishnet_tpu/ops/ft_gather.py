"""Pallas TPU kernel for the NNUE feature-transformer gather-accumulate.

The feature transformer is the NNUE hot op: for every position and both
perspectives, sum ~30 sparse rows of a [22529, 1024] int16 table and add
the bias. XLA's take+sum lowers to a dynamic-gather that materializes a
[B, 2, 32, 1024] int16 intermediate in HBM (128 MiB at B=1024) and then
reduces it — every gathered byte crosses HBM twice. This kernel streams
each row HBM->VMEM exactly once with 32 concurrent row DMAs per
accumulator and reduces in VMEM, so the traffic is the 64 KiB of rows
per accumulator and the 4 KiB result, nothing else.

The weight table stays resident in HBM (46 MiB > VMEM); row addresses
are data-dependent, which is exactly what PrefetchScalarGridSpec's
scalar-prefetched index argument enables: the indices are available
before the kernel body, so the DMAs can be issued immediately.

Incremental (delta) entries are RESOLVED in-kernel (round 3): the native
pool guarantees every delta entry references the most recent preceding
FULL entry of the same batch (cpp/src/pool.cpp evaluate_block's anchor
protocol), so the kernel keeps one running "anchor" accumulator in VMEM
scratch — full entries refresh it, delta entries add their few delta
rows to it (perspective-swapped when the sides to move differ). Round 2
instead shipped partial accumulators and resolved references with a
batch-wide XLA gather over [B, 2, L1] int32 — a full extra HBM pass
(~2 ms per 16k batch) that this design deletes outright.

The same pass now also produces the [B, 2, 8] PSQT accumulator
(``ft_psqt`` given), with the same running-anchor discipline and a
persistent anchor-PSQT table next to the accumulator table — so
anchor-code entries resolve ENTIRELY on device and the wire no longer
needs the host-computed material term (doc/wire-format.md). The PSQT
side issues no DMAs: an 8-lane int32 row is not a tile Mosaic can slice
(its DMA and block shapes must align to 128 lanes), so both PSQT tables
ride into VMEM whole, re-laid 128 lanes wide (_lane_dense, 0.7 MiB for
the 22529-row column table), and each row is one dynamic-sublane load
plus a lane mask.

Used by jax_eval.evaluate_batch on TPU backends; the plain XLA path
remains the fallback (CPU tests, odd shapes) and the parity test runs
this kernel in interpreter mode against it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fishnet_tpu.nnue.spec import DELTA_SLOTS as _DELTA_SLOTS
from fishnet_tpu.utils.tracing import is_concrete

__all__ = [
    "ft_accumulate",
    "derive_segment_offsets",
    "recode_segment_parents",
    "plan_segment_dedup",
]

#: Accumulator poison for persistent anchor codes evaluated WITHOUT an
#: anchor table.  Under tracing the misuse cannot raise (the values are
#: not inspectable), so the structural guard stamps the affected
#: entries' accumulators with this constant instead: every lane clips to
#: zero downstream, collapsing the entry's eval to a per-bucket constant
#: — loudly broken, unlike the plausibly-wrong unresolved partials the
#: old code returned.  Direct consumers of the accumulator see -2^30.
_POISON_ACC = -(1 << 30)


def _xla_ft_accumulate(
    ft_w: jax.Array,
    ft_b: jax.Array,
    indices: jax.Array,
    delta_base: int | None = None,
) -> jax.Array:
    if delta_base is not None:
        # Removal encodings (delta_base + f) subtract row f; their pads
        # decode to the zero sentinel, so the sign is irrelevant there.
        is_rem = indices >= delta_base
        indices = jnp.where(is_rem, indices - delta_base, indices)
        sign = jnp.where(is_rem, -1, 1)
    rows = jnp.take(ft_w, indices, axis=0).astype(jnp.int32)  # [B, 2, A, L1]
    if delta_base is not None:
        rows = rows * sign[..., None]
    return ft_b.astype(jnp.int32) + jnp.sum(rows, axis=2)


def _xla_psqt_accumulate(
    ft_psqt: jax.Array,
    indices: jax.Array,
    delta_base: int | None = None,
) -> jax.Array:
    """PSQT accumulators over the same index stream as the FT gather:
    int32 [B, 2, 8], no bias term. Removal encodings subtract their row;
    pads decode to the zero sentinel row either way."""
    if delta_base is not None:
        is_rem = indices >= delta_base
        indices = jnp.where(is_rem, indices - delta_base, indices)
        sign = jnp.where(is_rem, -1, 1)
    rows = jnp.take(ft_psqt, indices, axis=0)  # [B, 2, A, 8] int32
    if delta_base is not None:
        rows = rows * sign[..., None]
    return jnp.sum(rows, axis=2)


def _swap_persp(a: jax.Array, swap: jax.Array) -> jax.Array:
    """Swap the perspective axis (axis 1 of [B, 2, ...]) where ``swap``."""
    perm = jnp.where(swap[:, None], jnp.array([1, 0]), jnp.array([0, 1]))
    return jnp.take_along_axis(a, perm[:, :, None], axis=1)


def decode_parent(parent: jax.Array):
    """Split the wire's parent codes (cpp/src/pool.cpp emit_block) into
    masks: -1 plain full; >= 0 in-batch delta (ref << 1 | swap); <= -2
    anchor-entry codes -(2 + v), v = (table_row << 2) | (is_delta << 1)
    | swap — the entry resolves against (is_delta) and/or refreshes
    (always) its device anchor-table row. Returns (in_batch, persistent,
    stores, ref, swap, aid)."""
    parent = parent.astype(jnp.int32)
    v = -parent - 2
    stores = parent <= -2
    persistent = stores & ((v & 2) != 0)
    in_batch = parent >= 0
    ref = jnp.where(in_batch, parent >> 1, 0)
    # Plain fulls (-1) decode v = -1, whose low bit is set: mask the swap
    # bit with (in_batch | stores) so fulls come back swap=0 — otherwise
    # every full entry would grow a phantom perspective-swap flag that
    # only the where-masks downstream happen to ignore today.
    swap = jnp.where(
        in_batch, parent & 1, jnp.where(stores, v & 1, 0)
    ).astype(bool)
    aid = jnp.where(stores, v >> 2, 0)
    return in_batch, persistent, stores, ref, swap, aid


def derive_segment_offsets(parent: jax.Array, seg_rows: jax.Array,
                           tier: int) -> jax.Array:
    """Row offsets for a SEGMENTED (coalesced multi-group) dispatch.

    ``parent`` int32 [K, size] holds each segment's wire parent codes;
    ``seg_rows`` int32 [K] each segment's emitted row count; ``tier``
    is the common per-segment row tier of the concatenated [K*tier]
    stream. Per segment the offsets are the usual exclusive cumsum
    (4 rows per full entry, 1 per delta), but each segment's padding
    clamps into ITS OWN sentinel block at ``seg_rows[k]`` and the whole
    segment shifts by ``k*tier`` — offsets never cross a segment
    boundary, so a fused dispatch reads each group's rows and tables
    alone (search/service.py _dispatch_segmented). Returns flat int32
    [K*size] offsets into the concatenated row stream."""
    parent = parent.astype(jnp.int32)
    k_segs = parent.shape[0]
    in_batch, persistent, _, _, _, _ = decode_parent(parent.reshape(-1))
    is_delta = (in_batch | persistent).reshape(parent.shape)
    rows_per = jnp.where(is_delta, 1, 4)
    local = jnp.cumsum(rows_per, axis=1) - rows_per  # exclusive per segment
    local = jnp.minimum(local, seg_rows.astype(jnp.int32)[:, None])
    base = (jnp.arange(k_segs, dtype=jnp.int32) * jnp.int32(tier))[:, None]
    return (local + base).reshape(-1)


def recode_segment_parents(parent: jax.Array, anchor_rows: int) -> jax.Array:
    """Rebase segment-local wire parent codes into the fused frame of a
    segmented dispatch. ``parent`` int32 [K, size]; ``anchor_rows`` is
    one group's anchor-table row count A (the stacked [K, A, ...]
    tables flatten to [K*A, ...]).

    In-batch refs (code ``ref << 1 | swap``) shift by the segment's
    entry base ``k*size``; persistent anchor codes (``-(2 + v)``,
    ``v = (row << 2) | bits``) shift their table row by the segment's
    table base ``k*A``; plain fulls (-1) pass through. Because the pool
    guarantees every group batch STARTS with an anchor entry (full or
    persistent), the fused kernel's running in-VMEM anchor resets at
    each segment's first entry and never leaks across a segment
    boundary — the recoded stream satisfies exactly the contract the
    single-group kernel (and its bit-identical XLA twin,
    _xla_resolve_parents) already enforce, so no new kernel mode is
    needed. Returns flat int32 [K*size]."""
    parent = parent.astype(jnp.int32)
    k_segs, size = parent.shape
    entry_base = (jnp.arange(k_segs, dtype=jnp.int32) * size)[:, None]
    tab_base = (jnp.arange(k_segs, dtype=jnp.int32) * anchor_rows)[:, None]
    out = jnp.where(parent >= 0, parent + (entry_base << 1), parent)
    out = jnp.where(parent <= -2, parent - (tab_base << 2), out)
    return out.reshape(-1)


def plan_segment_dedup(parents, buckets, offsets, ns, packed, material=None,
                       hashes=None, cache_hits=None):
    """Plan cross-segment eval-dedup for ONE fused (coalesced) dispatch:
    deterministic, pure host-side planning (numpy in, plain lists out).

    Per-slot emission cannot see these duplicates — the in-step dedup
    was DELETED per VERDICT r4 because WITHIN one group it retired only
    ~0.1% of evals while its hash build sat on the per-step hot path —
    but ACROSS the segments of one fused dispatch, sibling groups
    searching adjacent plies of the same game routinely evaluate the
    same transpositions in the same step. Here the planning cost rides
    the async pack worker, off the driver threads entirely.

    Inputs are per-segment host views (only the first ``ns[k]`` entries
    of each are read):

    * ``parents``: int32 [size] segment-local wire parent codes
    * ``buckets``: int32 [size] layer-stack bucket ids
    * ``offsets``: int32 [size] each entry's row offset into its
      segment's packed stream (the host copy; the device re-derives)
    * ``ns``: real entry counts
    * ``packed``: uint16 [rows_k, 2, 8] row streams
    * ``material``: optional int32 [size] host-material columns

    A DUPLICATE is a plain full (code -1) whose 4-row feature block —
    keyed with its bucket (and material when shipped) — matches an
    earlier 4-row entry anywhere in the dispatch, provided it has no
    in-batch consumer and is not its segment's first entry. The anchor
    protocol makes removal safe: a full with no consumer is, by the
    most-recent-anchor rule, immediately followed by another anchor
    entry (or padding), so re-encoding it as a one-row sentinel
    in-batch delta never disturbs any other entry's resolution — the
    replacement computes garbage on device and its true value is
    restored host-side from its original (_FusedValues).

    POSITION-KEYED MODE (doc/eval-cache.md): when ``hashes`` carries
    per-segment uint64 Zobrist arrays, the dedup key is the position
    hash itself instead of the 4-row byte image — bucket and material
    are pure functions of the position, so the hash subsumes them, and
    a duplicate now matches ANY earlier kept entry decoding to the same
    position (delta-encoded entries included; a delta's device output
    is its true eval, so it is a valid fan-out source). The droppable
    set widens to EVERY encoding, because anchored traffic is ~100%
    persistent codes (each block's entry 0 stores its anchor row) and a
    plain-full-only rule would never fire:

    * plain fulls and in-batch deltas re-encode as the one-row sentinel
      in-batch delta exactly as before (nothing resolves through them —
      unconsumed — and they write no table row);
    * PERSISTENT codes (<= -2) re-encode as a one-row sentinel
      persistent DELTA that KEEPS the original aid and store bit, so
      the entry still refreshes its anchor-table row on device. The
      bytes it stores are made correct by the eval's ``copy_src``
      fan-in gather (_packed_anchored_core): the duplicate's resolved
      accumulator is replaced by its same-position source's before the
      head eval and the scatter. A persistent drop therefore REQUIRES
      an in-dispatch source (a ``pairs`` entry) — cache-satisfied fills
      have no device accumulator to store, so cache drops stay
      restricted to plain fulls and in-batch deltas.
    ``cache_hits`` (optional, per-segment ``(mask, values)`` from the
    driver's pre-dispatch probe) additionally drops droppable entries
    whose eval the process-wide cache already knows.

    Returns ``(drops, refs, pairs)``: per-segment lists of dropped
    entry indices, the replacement-code metadata, and global
    ``(dst_seg, dst_idx, src_seg, src_idx)`` value overwrites (every
    duplicate maps to the FIRST occurrence, which is by construction
    never itself dropped). ``refs`` in BYTE mode are in-batch anchor
    indices (the caller writes ``ref << 1``, swap 0 — the most recent
    preceding KEPT anchor, always present since entry 0 is an anchor
    and never dropped); in POSITION-KEYED mode they are ready-to-write
    WIRE PARENT CODES (sentinel in-batch delta or sentinel persistent
    delta, per the drop's original encoding). In position-keyed mode a
    FOURTH element is returned: ``fills`` — ``(seg, idx, value)``
    cache-satisfied drops whose value comes from the cache, not from
    another entry of this dispatch."""
    import numpy as np

    n_segs = len(parents)
    seen = {}
    fill_vals = {}  # hash -> cached value (position-keyed mode)
    drops = [[] for _ in range(n_segs)]
    refs = [[] for _ in range(n_segs)]
    pairs = []
    fills = []
    for k in range(n_segs):
        n = int(ns[k])
        if n <= 0:
            continue
        p = np.asarray(parents[k][:n])
        consumed = np.zeros(n, dtype=bool)
        inb = p >= 0
        if inb.any():
            consumed[p[inb] >> 1] = True
        # Anchor entries (fulls and persistent codes) vs 4-row entries
        # (fulls and persistent FULLS — persistent deltas ship 1 row).
        is_anchor = (p == -1) | (p <= -2)
        is_full4 = (p == -1) | ((p <= -2) & ((((-p - 2) >> 1) & 1) == 0))
        off = np.asarray(offsets[k][:n])
        rows = packed[k]
        hseg = None if hashes is None else hashes[k]
        cmask = cvals = None
        if cache_hits is not None and cache_hits[k] is not None:
            cmask, cvals = cache_hits[k]
        last_anchor = 0
        for i in range(n):
            dropped = False
            if hseg is not None:
                h = int(hseg[i])
                pers = bool(p[i] <= -2)
                droppable = not consumed[i] and i > 0
                # A persistent drop still stores its anchor row: its
                # sentinel keeps aid + store bit (delta form, swap 0)
                # and the copy_src gather supplies the true bytes.
                sentinel = (
                    -(2 + ((((-int(p[i]) - 2) >> 2) << 2) | 2))
                    if pers else (last_anchor << 1)
                )
                src = seen.get(h)
                if droppable and src is not None:
                    # Fan out from the earlier kept entry (any wire
                    # encoding — its device output is the true eval).
                    drops[k].append(i)
                    refs[k].append(sentinel)
                    pairs.append((k, i, src[0], src[1]))
                    dropped = True
                elif droppable and not pers and cmask is not None \
                        and cmask[i]:
                    drops[k].append(i)
                    refs[k].append(sentinel)
                    fills.append((k, i, int(cvals[i])))
                    fill_vals.setdefault(h, int(cvals[i]))
                    dropped = True
                elif droppable and not pers and h in fill_vals:
                    # Duplicate of an entry that itself left the wire on
                    # a cache hit: same cached value, no device source.
                    drops[k].append(i)
                    refs[k].append(sentinel)
                    fills.append((k, i, fill_vals[h]))
                    dropped = True
                elif src is None:
                    seen[h] = (k, i)
            elif is_full4[i]:
                key = (int(buckets[k][i]),
                       rows[off[i] : off[i] + 4].tobytes())
                if material is not None:
                    key = key + (int(material[k][i]),)
                src = seen.get(key)
                if (src is not None and p[i] == -1
                        and not consumed[i] and i > 0):
                    drops[k].append(i)
                    refs[k].append(last_anchor)
                    pairs.append((k, i, src[0], src[1]))
                    dropped = True
                elif src is None:
                    seen[key] = (k, i)
            if not dropped and is_anchor[i]:
                last_anchor = i
    if hashes is not None:
        return drops, refs, pairs, fills
    return drops, refs, pairs


def _xla_resolve_parents(
    acc: jax.Array,
    bias: jax.Array,
    parent: jax.Array,
    anchor_tab: Optional[jax.Array] = None,
) -> jax.Array:
    """Resolve incremental entries of an XLA-partials accumulator batch
    (see decode_parent for the codes). Two passes: persistent deltas
    resolve against their anchor-table rows first (anchor entries are
    never in-batch deltas, so their resolution is final), then in-batch
    deltas gather their — now resolved — anchor entries. Exact: integer
    adds commute, so delta partial + referenced accumulator - (the
    doubly counted) bias is bit-identical to a full gather.

    ``bias`` is whatever the partials already include and must not be
    double-counted: the FT bias for the feature-transformer accumulator,
    a zero scalar for the (bias-free) PSQT accumulator. Works for any
    trailing accumulator shape ([B, 2, L1] and [B, 2, 8] alike)."""
    in_batch, persistent, _, ref, swap, aid = decode_parent(parent)
    if anchor_tab is not None:
        tab_acc = _swap_persp(
            jnp.take(anchor_tab.astype(jnp.int32), aid, axis=0), swap
        )
        acc = jnp.where(persistent[:, None, None], acc + tab_acc - bias, acc)
    else:
        # Structural misuse guard (works under tracing, where the eager
        # check in ft_accumulate cannot see the codes): persistent
        # entries have no table to resolve against — poison them instead
        # of returning unresolved partials that read as plausible evals.
        acc = jnp.where(
            persistent[:, None, None], jnp.int32(_POISON_ACC), acc
        )
    ref_acc = _swap_persp(jnp.take(acc, ref, axis=0), swap)
    return jnp.where(in_batch[:, None, None], acc + ref_acc - bias, acc)


#: Slot budget of the SPARSE mode, per perspective: incremental (delta)
#: entries carry up to DELTA_SLOTS added rows in slots [0, DELTA_SLOTS)
#: and up to DELTA_SLOTS removed rows (encoded delta_base + f) in slots
#: [DELTA_SLOTS, 2*DELTA_SLOTS), each region padded with its own
#: sentinel. The kernel fetches exactly these 2*DELTA_SLOTS slots,
#: pads included (sentinel rows are zero, so sums stay exact), and
#: reduces adds minus removes. Both modes are branch-free per row —
#: per-row control flow (predicates or dynamic loops) was measured to
#: cost MORE than the padded DMAs it avoids; a 4x shorter unrolled loop
#: is what cashes in the gather's ~12 ns/row DMA-count bound.
#: The slot count _DELTA_SLOTS (imported above) is the WIRE contract
#: shared with the native pool (spec.DELTA_SLOTS == cpp/src/nnue.h
#: NNUE_DELTA_SLOTS).
_SPARSE_SLOTS = 2 * _DELTA_SLOTS

_LANES = 128


def _lane_dense(x: jax.Array) -> jax.Array:
    """Re-lay a small int32 table 128 lanes wide for VMEM residency:
    flatten row-major, zero-pad to whole (8, 128) tiles, view as
    [M, 128]. A [rows, 8] PSQT column table puts row f at sublane
    f >> 4, lanes [(f & 15) * 8, +8); a [A, 2, 8] anchor-PSQT table puts
    row a at sublane a >> 3, lanes [(a & 7) * 16, +16). (As [rows, 8]
    the same data would pad every row to 128 lanes — 11 MiB of VMEM for
    0.7 MiB of PSQT columns.)"""
    flat = x.astype(jnp.int32).reshape(-1)
    flat = jnp.pad(flat, (0, -flat.shape[0] % (8 * _LANES)))
    return flat.reshape(-1, _LANES)


def _kernel(idx_ref, flags_ref, aid_ref, ft_ref, bias_ref, carry_ref,
            tab_ref, *rest, delta_base, anchored, with_psqt):
    # Software-pipelined gather: scratch holds TWO positions' rows. Grid
    # step b waits on the buffer its predecessor filled for it, issues
    # position b+1's row DMAs into the other buffer, then reduces — so
    # row copies stay in flight at all times and the HBM pipe never
    # drains between positions. Row addresses come from the scalar-
    # prefetched index operand, available before the body runs.
    #
    # FUSED PSQT (with_psqt): the same index stream also selects each
    # feature's 8-bucket PSQT column out of the VMEM-resident lane-dense
    # column table (pq_ref, see _lane_dense), and the reduce produces a
    # second accumulator per position with the SAME anchor discipline
    # (running in-VMEM anchor, persistent rows from the lane-dense
    # anchor-PSQT table ptab_ref). A position's PSQT state is ONE
    # [1, 128] vector in the "canonical" layout: lane l holds
    # perspective (l >> 3) & 1, bucket l & 7, repeated every 16 lanes —
    # so a perspective swap is a roll by 8 lanes and the output row's
    # first 16 lanes are the [2, 8] accumulator. Integer adds commute,
    # so the fused PSQT is bit-identical to the XLA gather path and to
    # the host-side material walk the wire used to ship.
    #
    # Per-position flags (scalar-prefetched, so the issuing step for b+1
    # and the waiting step at b+1 always agree): bit 0 = sparse
    # (incremental/delta) entry touching only _SPARSE_SLOTS slots per
    # perspective with removal slots decoded by subtracting delta_base;
    # bit 1 (anchored mode) = the entry's perspectives are swapped
    # relative to its anchor; bit 2 (anchored mode) = PERSISTENT — the
    # anchor is not the running in-batch one but row aid_ref[b] of the
    # HBM anchor table (the accumulator this entry's pool slot stored in
    # a previous batch), DMA'd into the pa scratch alongside the delta
    # rows (~8 KB vs the ~120 KB of a full gather). Dense entries fetch
    # all slots as plain additions. Table WRITES happen outside the
    # kernel (jax_eval scatters the output accumulators of anchor
    # entries back into the table).
    if with_psqt:
        (pq_ref, pcarry_ref, ptab_ref, out_ref, pout_ref, rows, sems,
         anchor, pa, pa_sems, pq_anchor) = rest
    else:
        out_ref, rows, sems, anchor, pa, pa_sems = rest

    b = pl.program_id(0)
    n = pl.num_programs(0)
    n_active = rows.shape[1] // 2  # both perspectives share a buffer

    def transfer(pos, slot, start, limit, is_sparse):
        # Each feature row is one native (sub, 128) int16 tile, so
        # single-row HBM slices stay tile-aligned.
        for p in range(2):
            for k in range(limit):
                idx = idx_ref[pos, p, k]
                if is_sparse and k >= _DELTA_SLOTS:
                    idx = idx - delta_base  # removal slot: decode
                i = p * n_active + k
                dma = pltpu.make_async_copy(
                    ft_ref.at[idx], rows.at[slot, i], sems.at[slot, i],
                )
                dma.start() if start else dma.wait()

    def both_modes(pos, fn):
        # fn(limit, is_sparse); the flag is explicit rather than inferred
        # from the limit so a dense n_active equal to _SPARSE_SLOTS could
        # never alias into removal decoding.
        if delta_base is None:
            fn(n_active, False)
            return
        sparse = (flags_ref[pos] & 1) != 0

        @pl.when(sparse)
        def _():
            fn(_SPARSE_SLOTS, True)

        @pl.when(jnp.logical_not(sparse))
        def _():
            fn(n_active, False)

    def anchor_dma(pos, slot, start):
        # One DMA for the whole [2, sub, 128] anchor row; issued/awaited
        # only for persistent entries (scalar-prefetched flag, so the
        # issuing step for b+1 and the waiting step at b+1 agree). The
        # row's PSQT twin is read straight out of VMEM at reduce time.
        if not anchored:
            return

        @pl.when((flags_ref[pos] & 4) != 0)
        def _():
            dma = pltpu.make_async_copy(
                tab_ref.at[aid_ref[pos]], pa.at[slot], pa_sems.at[slot]
            )
            dma.start() if start else dma.wait()

    slot = jax.lax.rem(b, 2)

    @pl.when(b == 0)
    def _():
        both_modes(0, lambda lim, sp: transfer(0, 0, True, lim, sp))
        anchor_dma(0, 0, True)
        if anchored:
            # Chunk carry-in: the anchor as of the end of the previous
            # chunk (zeros for the first — the pool guarantees batch
            # entry 0 is an anchor entry, so it is never read there).
            anchor[...] = carry_ref[...]
            if with_psqt:
                pq_anchor[...] = pcarry_ref[...]

    @pl.when(b + 1 < n)
    def _():
        nxt = jax.lax.rem(b + 1, 2)
        both_modes(b + 1, lambda lim, sp: transfer(b + 1, nxt, True, lim, sp))
        anchor_dma(b + 1, nxt, True)

    both_modes(b, lambda lim, sp: transfer(b, slot, False, lim, sp))
    anchor_dma(b, slot, False)

    bias = bias_ref[...].astype(jnp.int32)

    if with_psqt:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def fold(v, period):
        # Add up the lane groups of a [1, 128] vector: afterwards every
        # lane l holds the sum over the lanes congruent to l mod period.
        shift = _LANES // 2
        while shift >= period:
            v = v + pltpu.roll(v, shift, 1)
            shift //= 2
        return v

    def tree_sum(terms):
        # Pairwise, not a serial add chain.
        while len(terms) > 1:
            terms = [
                terms[i] + terms[i + 1] if i + 1 < len(terms) else terms[i]
                for i in range(0, len(terms), 2)
            ]
        return terms[0]

    def pq_sum(limit, is_sparse):
        # Canonical PSQT vector over the first ``limit`` slots of both
        # perspectives (sparse: adds minus removals). Feature f's 8
        # buckets sit in row f >> 4 of the lane-dense column table at
        # lanes [(f & 15) * 8, +8): one dynamic-sublane load and a lane
        # mask per slot, then one fold per perspective. Sentinel slots
        # select the zero row.
        out = []
        for p in range(2):
            terms = []
            for k in range(limit):
                f = idx_ref[b, p, k]
                if is_sparse and k >= _DELTA_SLOTS:
                    f = f - delta_base  # removal slot: decode
                row = pq_ref[pl.ds(f >> 4, 1), :]
                terms.append(jnp.where((lane >> 3) == (f & 15), row, 0))
            if is_sparse:
                v = tree_sum(terms[:_DELTA_SLOTS]) - tree_sum(
                    terms[_DELTA_SLOTS:]
                )
            else:
                v = tree_sum(terms)
            out.append(fold(v, 8))
        return jnp.where((lane & 8) == 0, out[0], out[1])

    def reduce_full(limit):
        # jnp.sum (tree reduction), not a serial add chain.
        for p in range(2):
            base = p * n_active
            acc = bias + jnp.sum(
                rows[slot, base : base + limit].astype(jnp.int32), axis=0
            )
            out_ref[0, p] = acc
            if anchored:
                anchor[p] = acc
        if with_psqt:
            pq = pq_sum(limit, False)
            pout_ref[pl.ds(b, 1), :] = pq
            if anchored:
                pq_anchor[...] = pq

    def reduce_sparse():
        partial = []
        for p in range(2):
            base = p * n_active
            adds = jnp.sum(
                rows[slot, base : base + _DELTA_SLOTS].astype(jnp.int32),
                axis=0,
            )
            rems = jnp.sum(
                rows[slot, base + _DELTA_SLOTS : base + _SPARSE_SLOTS]
                .astype(jnp.int32),
                axis=0,
            )
            partial.append(adds - rems)
        if with_psqt:
            pq_partial = pq_sum(_SPARSE_SLOTS, True)
        if not anchored:
            for p in range(2):
                out_ref[0, p] = bias + partial[p]
            if with_psqt:
                pout_ref[pl.ds(b, 1), :] = pq_partial
            return
        # Resolve against the running anchor (the most recent anchor
        # entry), or — persistent entries — the anchor-table row DMA'd
        # into pa. Bit 1 says whether the perspectives are swapped.
        swap = (flags_ref[b] & 2) != 0
        persistent = (flags_ref[b] & 4) != 0
        base = [
            jnp.where(persistent, pa[slot, p], anchor[p]) for p in range(2)
        ]
        res = [
            jnp.where(swap, base[1 - p], base[p]) + partial[p]
            for p in range(2)
        ]
        for p in range(2):
            out_ref[0, p] = res[p]
        if with_psqt:
            # Anchor-PSQT row a: 16 values at lanes [(a & 7) * 16, +16)
            # of table row a >> 3, already in canonical lane order.
            # Non-persistent entries carry aid 0 and discard the read.
            a = aid_ref[b]
            trow = ptab_ref[pl.ds(a >> 3, 1), :]
            pq_tab = fold(jnp.where((lane >> 4) == (a & 7), trow, 0), 16)
            pq_base = jnp.where(persistent, pq_tab, pq_anchor[...])
            pq_res = jnp.where(
                swap, pltpu.roll(pq_base, 8, 1), pq_base
            ) + pq_partial
            pout_ref[pl.ds(b, 1), :] = pq_res

        @pl.when(persistent)
        def _():
            # A resolved persistent entry IS an anchor entry: later
            # in-batch deltas of its block reference it.
            for p in range(2):
                anchor[p] = res[p]
            if with_psqt:
                pq_anchor[...] = pq_res

    if delta_base is None:
        reduce_full(n_active)
    else:
        sparse = (flags_ref[b] & 1) != 0

        @pl.when(sparse)
        def _():
            reduce_sparse()

        @pl.when(jnp.logical_not(sparse))
        def _():
            reduce_full(n_active)


# Positions per pallas_call: the scalar-prefetch index operand lives in
# SMEM (1 MiB, shared with Mosaic's own scalar state — 1024-position
# chunks overflow it by a hair), so the whole batch's indices cannot
# ride one call; each call costs a launch plus a pipeline fill/drain,
# so use the largest chunk that reliably fits ([512, 2, 32] int32 =
# 128 KiB).
_CHUNK = 512


@functools.partial(
    jax.jit, static_argnames=("interpret", "delta_base", "anchored")
)
def _pallas_ft_accumulate(
    ft_w: jax.Array,
    ft_b: jax.Array,
    indices: jax.Array,
    flags: Optional[jax.Array] = None,
    anchor_ids: Optional[jax.Array] = None,
    anchor_tab: Optional[jax.Array] = None,
    ft_psqt: Optional[jax.Array] = None,
    psqt_tab: Optional[jax.Array] = None,
    interpret: bool = False,
    delta_base: int | None = None,
    anchored: bool = False,
):
    """Returns [B, 2, L1] int32 accumulators, or — with ``ft_psqt``
    given — the tuple (accumulators, [B, 2, 8] int32 PSQT accumulators)
    from one fused pass over the index stream."""
    batch, persp, n_active = indices.shape
    l1 = ft_w.shape[1]
    with_psqt = ft_psqt is not None
    assert persp == 2, "indices must be [B, 2, MAX_ACTIVE]"
    assert l1 % 1024 == 0, "L1 must fold into whole (8, 128) int16 tiles"
    sub = l1 // 128  # sublane count of one feature row viewed as a tile

    # View each L1-wide row as an (sub, 128) tile so single-row HBM
    # slices are tile-aligned (Mosaic requires sublane multiples of 8).
    ft_tiles = ft_w.reshape(ft_w.shape[0], sub, 128)
    bias_tile = ft_b.reshape(sub, 128)
    if anchor_tab is None:
        # Dummy 1-row table: flag bit 2 is never set without a real
        # table, so the kernel issues no anchor DMAs against it.
        tab_tiles = jnp.zeros((1, 2, sub, 128), jnp.int32)
    else:
        tab_tiles = anchor_tab.astype(jnp.int32).reshape(-1, 2, sub, 128)
    pq_tab = ptab = None
    if with_psqt:
        # The canonical lane layout (see _kernel) is built for 8 buckets.
        assert ft_psqt.shape[1] == 8, "PSQT tables must be [rows, 8]"
        pq_tab = _lane_dense(ft_psqt)
        if psqt_tab is None:
            ptab = jnp.zeros((8, _LANES), jnp.int32)
        else:
            ptab = _lane_dense(psqt_tab)

    def run_chunk(idx_chunk, flags_chunk, aid_chunk, carry, pcarry):
        chunk = idx_chunk.shape[0]
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),  # ft_w stays in HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bias
            pl.BlockSpec(memory_space=pltpu.VMEM),  # anchor carry-in
            pl.BlockSpec(memory_space=pl.ANY),  # anchor table (HBM)
        ]
        out_specs = pl.BlockSpec(
            (1, 2, sub, 128),
            lambda b, idx_ref, flags_ref, aid_ref: (b, 0, 0, 0),
        )
        out_shape = jax.ShapeDtypeStruct((chunk, 2, sub, 128), jnp.int32)
        scratch = [
            pltpu.VMEM((2, 2 * n_active, sub, 128), ft_w.dtype),
            pltpu.SemaphoreType.DMA((2, 2 * n_active)),
            pltpu.VMEM((2, sub, 128), jnp.int32),  # running anchor
            pltpu.VMEM((2, 2, sub, 128), jnp.int32),  # persistent rows
            pltpu.SemaphoreType.DMA((2,)),
        ]
        operands = [idx_chunk, flags_chunk, aid_chunk, ft_tiles, bias_tile,
                    carry, tab_tiles]
        if with_psqt:
            in_specs += [
                pl.BlockSpec(memory_space=pltpu.VMEM),  # PSQT columns
                pl.BlockSpec(memory_space=pltpu.VMEM),  # PSQT carry-in
                pl.BlockSpec(memory_space=pltpu.VMEM),  # anchor-PSQT table
            ]
            # One canonical PSQT row per position; the whole chunk's
            # block stays resident in VMEM and is written back once.
            out_specs = [
                out_specs,
                pl.BlockSpec(
                    (chunk, _LANES),
                    lambda b, idx_ref, flags_ref, aid_ref: (0, 0),
                ),
            ]
            out_shape = [
                out_shape,
                jax.ShapeDtypeStruct((chunk, _LANES), jnp.int32),
            ]
            scratch.append(
                pltpu.VMEM((1, _LANES), jnp.int32)  # running PSQT anchor
            )
            operands += [pq_tab, pcarry, ptab]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # indices + flags + anchor row ids
            grid=(chunk,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            functools.partial(_kernel, delta_base=delta_base,
                              anchored=anchored, with_psqt=with_psqt),
            out_shape=out_shape,
            grid_spec=grid_spec,
            interpret=interpret,
            name="ft_gather",
        )(*operands)

    idx = indices.astype(jnp.int32)
    if flags is None:
        flags = jnp.zeros((batch,), jnp.int32)
    else:
        flags = flags.astype(jnp.int32)
    if anchor_ids is None:
        anchor_ids = jnp.zeros((batch,), jnp.int32)
    else:
        anchor_ids = anchor_ids.astype(jnp.int32)
    def step(carries, chunk_args):
        """One pallas_call over one chunk; threads the anchor carry."""
        carry, pcarry = carries
        idx_c, fl_c, aid_c = chunk_args
        out = run_chunk(idx_c, fl_c, aid_c, carry, pcarry)
        pout = None
        if with_psqt:
            out, pout = out
        if anchored:
            # Next chunk's carry-in: the accumulator of the last ANCHOR
            # entry so far — full (bit 0 clear) or persistent-resolved
            # (bit 2) — matching the in-kernel running-anchor rule.
            is_anchor = ((fl_c & 1) == 0) | ((fl_c & 4) != 0)
            has_anchor = jnp.any(is_anchor)
            last_anchor = (
                idx_c.shape[0] - 1
                - jnp.argmax(is_anchor[::-1]).astype(jnp.int32)
            )
            carry = jnp.where(
                has_anchor, jnp.take(out, last_anchor, axis=0), carry
            )
            if with_psqt:
                pcarry = jnp.where(
                    has_anchor,
                    jax.lax.dynamic_slice_in_dim(pout, last_anchor, 1),
                    pcarry,
                )
        return (carry, pcarry), (out, pout)

    # Whole chunks ride ONE lax.scan — the kernel is traced, lowered and
    # compiled once however many chunks the batch spans (an unrolled
    # Python loop paid the kernel's multi-second trace once per chunk: 8x
    # for a 4096-entry fused dispatch) — plus at most one ragged tail.
    carries = (
        jnp.zeros((2, sub, 128), jnp.int32),
        jnp.zeros((1, _LANES), jnp.int32) if with_psqt else None,
    )
    n_full, tail = divmod(batch, _CHUNK)
    outs, pouts = [], []
    if n_full:
        whole = n_full * _CHUNK
        chunked = tuple(
            a[:whole].reshape(n_full, _CHUNK, *a.shape[1:])
            for a in (idx, flags, anchor_ids)
        )
        if n_full == 1:
            carries, (out, pout) = step(carries, tuple(a[0] for a in chunked))
        else:
            carries, (out, pout) = jax.lax.scan(step, carries, chunked)
            out = out.reshape(whole, *out.shape[2:])
            if with_psqt:
                pout = pout.reshape(whole, _LANES)
        outs.append(out)
        pouts.append(pout)
    if tail:
        _, (out, pout) = step(
            carries, tuple(a[batch - tail :] for a in (idx, flags, anchor_ids))
        )
        outs.append(out)
        pouts.append(pout)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    acc = out.reshape(batch, persp, l1)
    if not with_psqt:
        return acc
    pout = pouts[0] if len(pouts) == 1 else jnp.concatenate(pouts, axis=0)
    # The first 16 lanes of a canonical row are the [2, 8] accumulator.
    return acc, pout[:, :16].reshape(batch, 2, 8)


def ft_accumulate(
    ft_w: jax.Array,
    ft_b: jax.Array,
    indices: jax.Array,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
    delta_base: int | None = None,
    sparse: Optional[jax.Array] = None,
    parent: Optional[jax.Array] = None,
    anchor_tab: Optional[jax.Array] = None,
    ft_psqt: Optional[jax.Array] = None,
    psqt_tab: Optional[jax.Array] = None,
):
    """Feature-transformer accumulators, bias included: int32 [B, 2, L1].

    ``ft_w`` [rows, L1] int16 whose LAST row is the zero sentinel;
    ``ft_b`` [L1] int16; ``indices`` integer [B, 2, MAX_ACTIVE] padded
    with the sentinel index. With ``delta_base`` set, incremental
    (delta) entries follow the spec.DELTA_SLOTS wire contract: adds in
    the first slots, removals (encoded delta_base + f) after them — the
    fused kernel fetches only those few slots and subtracts the removal
    rows, which is where incremental eval's DMA savings land.

    Two incremental modes:

    * ``parent`` given (int32 [B]; see decode_parent for the codes):
      delta entries are RESOLVED — the result is every entry's complete
      accumulator. The fused kernel resolves from a running in-VMEM
      anchor, relying on the pool's guarantee that an in-batch ref is
      always the most recent preceding anchor entry; the XLA fallback
      gathers by the explicit ref index. Bit-identical either way.
      With ``anchor_tab`` ([A, 2, L1] int32) given, PERSISTENT codes
      (<= -2 with the delta bit) resolve against the table instead —
      callers own storing anchor entries' accumulators back (the table
      is read-only here).
    * ``sparse`` given (bool [B]) without ``parent``: delta entries come
      back as bias-included PARTIALS (adds - removes); the caller owns
      resolution. (Kept for tests and schema-level users.)

    FUSED PSQT: with ``ft_psqt`` ([rows, 8] int32, same zero sentinel
    last row as ``ft_w``) the return value is the tuple ``(acc, psqt)``
    where ``psqt`` is the int32 [B, 2, 8] PSQT accumulator built from
    the SAME index stream in the same pass — same removal decoding,
    same anchor resolution (persistent codes resolve against
    ``psqt_tab`` [A, 2, 8], the anchor-PSQT twin of ``anchor_tab``).
    Bit-identical to the XLA gather and to the host material walk.

    ``use_pallas=None`` auto-selects: the fused kernel on TPU backends
    when shapes conform (lane-aligned L1), XLA otherwise.
    """
    indices = indices.astype(jnp.int32)
    with_psqt = ft_psqt is not None
    if use_pallas is None:
        use_pallas = (
            jax.default_backend() == "tpu" and ft_w.shape[1] % 1024 == 0
        )
    if parent is not None:
        # Persistent codes REQUIRE a table: without one neither backend
        # can resolve them. Concrete parents (every direct caller) get
        # the precise eager error below; traced parents are handled
        # STRUCTURALLY — the XLA fallback poisons the affected entries'
        # accumulators (_xla_resolve_parents) and the fused kernel strips
        # the persistent flag (so no DMA is ever issued against the
        # 1-row dummy table) and poisons the outputs likewise.
        if anchor_tab is None and is_concrete(parent):
            import numpy as _np

            if bool((_np.asarray(parent) <= -2).any()):
                raise ValueError(
                    "parent contains persistent anchor codes but no "
                    "anchor_tab was given"
                )
        parent = parent.astype(jnp.int32)
        if use_pallas or interpret:
            # bit 0: sparse; bit 1: perspective swap vs the anchor;
            # bit 2: persistent (anchor-table row in anchor_ids).
            in_batch, persistent, _, _, swap, aid = decode_parent(parent)
            sparse_f = in_batch | persistent
            tab_persistent = (
                persistent if anchor_tab is not None
                else jnp.zeros_like(persistent)
            )
            flags = (
                sparse_f.astype(jnp.int32)
                | (swap.astype(jnp.int32) << 1)
                | (tab_persistent.astype(jnp.int32) << 2)
            )
            acc = _pallas_ft_accumulate(
                ft_w, ft_b, indices, flags, aid, anchor_tab,
                ft_psqt, psqt_tab,
                interpret=interpret, delta_base=delta_base, anchored=True,
            )
            psqt = None
            if with_psqt:
                acc, psqt = acc
            if anchor_tab is None:
                acc = jnp.where(
                    persistent[:, None, None], jnp.int32(_POISON_ACC), acc
                )
                if with_psqt:
                    psqt = jnp.where(
                        persistent[:, None, None], jnp.int32(_POISON_ACC),
                        psqt,
                    )
            return (acc, psqt) if with_psqt else acc
        acc = _xla_ft_accumulate(ft_w, ft_b, indices, delta_base=delta_base)
        acc = _xla_resolve_parents(
            acc, ft_b.astype(jnp.int32), parent, anchor_tab
        )
        if not with_psqt:
            return acc
        psqt = _xla_psqt_accumulate(ft_psqt, indices, delta_base=delta_base)
        psqt = _xla_resolve_parents(psqt, jnp.int32(0), parent, psqt_tab)
        return acc, psqt
    if use_pallas or interpret:
        flags = None if sparse is None else sparse.astype(jnp.int32)
        return _pallas_ft_accumulate(
            ft_w, ft_b, indices, flags, ft_psqt=ft_psqt,
            interpret=interpret, delta_base=delta_base,
        )
    acc = _xla_ft_accumulate(ft_w, ft_b, indices, delta_base=delta_base)
    if not with_psqt:
        return acc
    return acc, _xla_psqt_accumulate(ft_psqt, indices, delta_base=delta_base)
