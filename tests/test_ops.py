"""Pallas kernel parity tests (interpreter mode on CPU; the same kernel
is exercised on real TPU hardware by chip_smoke.py, Phase A)."""

import numpy as np
import pytest

import jax.numpy as jnp

from fishnet_tpu.ops.ft_gather import _xla_ft_accumulate, ft_accumulate


def _fixture(n_features=512, l1=1024, batch=5, active=32, seed=0):
    rng = np.random.default_rng(seed)
    ft_w = jnp.asarray(
        np.vstack(
            [rng.integers(-200, 200, (n_features, l1)), np.zeros((1, l1))]
        ).astype(np.int16)
    )
    ft_b = jnp.asarray(rng.integers(-100, 100, (l1,)).astype(np.int16))
    idx = rng.integers(0, n_features, (batch, 2, active)).astype(np.int32)
    # Pad a few slots with the sentinel row like real feature extraction.
    idx[:, :, active - 3 :] = n_features
    return ft_w, ft_b, jnp.asarray(idx)


def test_pallas_ft_gather_matches_xla_interpret():
    ft_w, ft_b, idx = _fixture()
    ref = np.asarray(_xla_ft_accumulate(ft_w, ft_b, idx))
    got = np.asarray(ft_accumulate(ft_w, ft_b, idx, interpret=True))
    assert np.array_equal(ref, got)


def test_pallas_ft_gather_sentinel_rows_are_noops():
    ft_w, ft_b, _ = _fixture()
    n = ft_w.shape[0] - 1
    idx = jnp.full((3, 2, 32), n, dtype=jnp.int32)  # all padding
    got = np.asarray(ft_accumulate(ft_w, ft_b, idx, interpret=True))
    expected = np.broadcast_to(np.asarray(ft_b, np.int32), got.shape)
    assert np.array_equal(got, expected)


def test_auto_selection_falls_back_on_cpu():
    # On the CPU test backend the auto path must use XLA (and agree).
    ft_w, ft_b, idx = _fixture(batch=2)
    auto = np.asarray(ft_accumulate(ft_w, ft_b, idx))
    ref = np.asarray(_xla_ft_accumulate(ft_w, ft_b, idx))
    assert np.array_equal(auto, ref)


def test_evaluate_batch_still_matches_cpp_oracle_path():
    # evaluate_batch routes through ft_accumulate now; the existing nnue
    # parity suite (test_nnue.py) covers full-score parity — here just a
    # smoke check that the plumbing holds shapes.
    from fishnet_tpu.nnue import spec
    from fishnet_tpu.nnue.jax_eval import evaluate_batch, params_from_weights
    from fishnet_tpu.nnue.weights import NnueWeights

    params = params_from_weights(NnueWeights.random(seed=1))
    rng = np.random.default_rng(2)
    idx = rng.integers(
        0, spec.NUM_FEATURES + 1, (4, 2, spec.MAX_ACTIVE_FEATURES)
    ).astype(np.int32)
    buckets = rng.integers(0, spec.NUM_PSQT_BUCKETS, (4,)).astype(np.int32)
    out = np.asarray(evaluate_batch(params, jnp.asarray(idx), jnp.asarray(buckets)))
    assert out.shape == (4,)
    assert np.all(np.abs(out) < 10_000_000)


@pytest.mark.parametrize("batch", [1, 3, 300])
def test_pallas_chunking_boundaries(batch):
    # _CHUNK = 256: cover under, at-boundary-crossing, and tiny batches.
    ft_w, ft_b, idx = _fixture(batch=batch, l1=1024)
    ref = np.asarray(_xla_ft_accumulate(ft_w, ft_b, idx))
    got = np.asarray(ft_accumulate(ft_w, ft_b, idx, interpret=True))
    assert np.array_equal(ref, got)


def _block_batch(n_features, active, n_blocks, block, rng):
    """Anchor-protocol batch: each block is one full entry followed by
    delta children referencing it (the most recent preceding full
    entry), with random perspective swaps — the shape the native pool
    emits (cpp/src/pool.cpp evaluate_block)."""
    from fishnet_tpu.ops.ft_gather import _DELTA_SLOTS

    delta_base = n_features + 1
    batch = n_blocks * block
    idx = np.full((batch, 2, active), n_features, np.int32)
    parent = np.full((batch,), -1, np.int32)
    for s in range(0, batch, block):
        idx[s, :, : active - 3] = rng.integers(0, n_features, (2, active - 3))
        for j in range(1, block):
            e = s + j
            swap = int(rng.integers(0, 2))
            parent[e] = (s << 1) | swap
            for p in range(2):
                n_add = int(rng.integers(0, _DELTA_SLOTS + 1))
                n_rem = int(rng.integers(0, _DELTA_SLOTS + 1))
                idx[e, p, :n_add] = rng.integers(0, n_features, n_add)
                idx[e, p, _DELTA_SLOTS : _DELTA_SLOTS + n_rem] = (
                    delta_base + rng.integers(0, n_features, n_rem)
                )
                idx[e, p, _DELTA_SLOTS + n_rem : 2 * _DELTA_SLOTS] = (
                    delta_base + n_features
                )
    return jnp.asarray(idx), jnp.asarray(parent), delta_base


def test_pallas_anchored_resolution_interpret(monkeypatch):
    """Anchored (in-VMEM running anchor) delta resolution must agree
    bit-exactly with the XLA explicit-index fallback, including across
    pallas-call chunk boundaries (the carry-in path): shrink _CHUNK so
    blocks straddle chunks and children must resolve against an anchor
    computed by the PREVIOUS pallas call."""
    from fishnet_tpu.ops import ft_gather

    monkeypatch.setattr(ft_gather, "_CHUNK", 8)
    n_features, l1, active = 512, 1024, 32
    rng = np.random.default_rng(11)
    ft_w = jnp.asarray(
        np.vstack(
            [rng.integers(-200, 200, (n_features, l1)), np.zeros((1, l1))]
        ).astype(np.int16)
    )
    ft_b = jnp.asarray(rng.integers(-100, 100, (l1,)).astype(np.int16))
    # Blocks of 5 against chunks of 8: entries 8-9 (etc.) are deltas
    # whose anchor lives in the previous chunk.
    idx, parent, delta_base = _block_batch(n_features, active, 4, 5, rng)
    ref = np.asarray(
        ft_gather.ft_accumulate(
            ft_w, ft_b, idx, use_pallas=False,
            delta_base=delta_base, parent=parent,
        )
    )
    got = np.asarray(
        ft_gather.ft_accumulate(
            ft_w, ft_b, idx, interpret=True,
            delta_base=delta_base, parent=parent,
        )
    )
    assert np.array_equal(ref, got)


def test_pallas_sparse_delta_mode_interpret():
    """The kernel's SPARSE mode (mode-predicated transfers, removal-slot
    index decode, adds-minus-removes reduce) must agree with the XLA
    signed fallback in interpreter mode — the only way to execute this
    branch offline before it serves real TPU traffic."""
    from fishnet_tpu.ops.ft_gather import _DELTA_SLOTS

    n_features, l1, active = 512, 1024, 32
    delta_base = n_features + 1
    rng = np.random.default_rng(3)
    ft_w = jnp.asarray(
        np.vstack(
            [rng.integers(-200, 200, (n_features, l1)), np.zeros((1, l1))]
        ).astype(np.int16)
    )
    ft_b = jnp.asarray(rng.integers(-100, 100, (l1,)).astype(np.int16))

    batch = 8
    idx = np.full((batch, 2, active), n_features, np.int32)
    sparse = np.zeros((batch,), bool)
    for b in range(batch):
        if b % 2 == 0:  # dense entry
            idx[b, :, : active - 3] = rng.integers(
                0, n_features, (2, active - 3)
            )
        else:  # sparse delta entry: adds + encoded removals, region-padded
            sparse[b] = True
            for p in range(2):
                n_add = int(rng.integers(0, _DELTA_SLOTS + 1))
                n_rem = int(rng.integers(0, _DELTA_SLOTS + 1))
                idx[b, p, :n_add] = rng.integers(0, n_features, n_add)
                idx[b, p, _DELTA_SLOTS : _DELTA_SLOTS + n_rem] = (
                    delta_base + rng.integers(0, n_features, n_rem)
                )
                idx[b, p, _DELTA_SLOTS + n_rem : 2 * _DELTA_SLOTS] = (
                    delta_base + n_features
                )

    ref = np.asarray(
        _xla_ft_accumulate(ft_w, ft_b, jnp.asarray(idx), delta_base=delta_base)
    )
    got = np.asarray(
        ft_accumulate(
            ft_w, ft_b, jnp.asarray(idx),
            interpret=True, delta_base=delta_base,
            sparse=jnp.asarray(sparse),
        )
    )
    assert np.array_equal(ref, got)


def _pers_code(aid, is_delta, swap=0):
    """Wire anchor-entry codes (cpp/src/pool.cpp emit_block)."""
    return -(2 + ((aid << 2) | (2 if is_delta else 0) | swap))


def _anchored_fixture(seed=21):
    n_features, l1, active = 512, 1024, 32
    rng = np.random.default_rng(seed)
    ft_w = np.vstack(
        [rng.integers(-200, 200, (n_features, l1)), np.zeros((1, l1))]
    ).astype(np.int16)
    ft_b = rng.integers(-100, 100, (l1,)).astype(np.int16)
    return n_features, l1, active, rng, ft_w, ft_b


def test_persistent_anchor_resolution_matches_manual():
    """Persistent parent codes resolve against the anchor TABLE (with
    the perspective swap), and a resolved persistent entry anchors the
    in-batch deltas that follow it — checked against hand-built sums in
    both the XLA fallback and the fused kernel (interpreter mode)."""
    from fishnet_tpu.ops.ft_gather import _DELTA_SLOTS, ft_accumulate

    n_features, l1, active, rng, ft_w, ft_b = _anchored_fixture()
    delta_base = n_features + 1
    tab = rng.integers(-5000, 5000, (4, 2, l1)).astype(np.int32)

    # e0: full storing row 1; e1: persistent delta vs row 2 (swapped),
    # stores row 2; e2: in-batch delta vs e1; e3: plain full.
    idx = np.full((4, 2, active), n_features, np.int32)
    feats0 = [[1, 5, 9], [2, 6]]
    adds1, rems1 = [[7], [8, 11]], [[3], []]
    adds2, rems2 = [[20], []], [[7], [8]]
    feats3 = [[100, 200], [300]]
    for p in range(2):
        idx[0, p, : len(feats0[p])] = feats0[p]
        idx[1, p, : len(adds1[p])] = adds1[p]
        idx[1, p, _DELTA_SLOTS : _DELTA_SLOTS + len(rems1[p])] = [
            delta_base + f for f in rems1[p]
        ]
        idx[1, p, _DELTA_SLOTS + len(rems1[p]) : 2 * _DELTA_SLOTS] = (
            delta_base + n_features
        )
        idx[2, p, : len(adds2[p])] = adds2[p]
        idx[2, p, _DELTA_SLOTS : _DELTA_SLOTS + len(rems2[p])] = [
            delta_base + f for f in rems2[p]
        ]
        idx[2, p, _DELTA_SLOTS + len(rems2[p]) : 2 * _DELTA_SLOTS] = (
            delta_base + n_features
        )
        idx[3, p, : len(feats3[p])] = feats3[p]
    parent = np.array(
        [_pers_code(1, False), _pers_code(2, True, swap=1), (1 << 1), -1],
        np.int32,
    )

    w64, b64 = ft_w.astype(np.int64), ft_b.astype(np.int64)
    exp = np.zeros((4, 2, l1), np.int64)
    for p in range(2):
        exp[0, p] = b64 + w64[feats0[p]].sum(0)
        exp[1, p] = tab[2, 1 - p] + w64[adds1[p]].sum(0) - w64[rems1[p]].sum(0)
        exp[2, p] = exp[1, p] + w64[adds2[p]].sum(0) - w64[rems2[p]].sum(0)
        exp[3, p] = b64 + w64[feats3[p]].sum(0)

    for interpret in (False, True):
        got = np.asarray(
            ft_accumulate(
                jnp.asarray(ft_w), jnp.asarray(ft_b), jnp.asarray(idx),
                use_pallas=False, interpret=interpret,
                delta_base=delta_base, parent=jnp.asarray(parent),
                anchor_tab=jnp.asarray(tab),
            )
        )
        assert np.array_equal(got.astype(np.int64), exp), interpret


def test_persistent_anchor_across_chunks_interpret(monkeypatch):
    """Persistent entries DMA their table rows regardless of chunk
    position, and the carry rule treats persistent-resolved entries as
    anchors: shrink _CHUNK so persistent entries and their in-batch
    children straddle pallas calls, then compare against the XLA
    fallback."""
    from fishnet_tpu.ops import ft_gather

    monkeypatch.setattr(ft_gather, "_CHUNK", 4)
    n_features, l1, active, rng, ft_w, ft_b = _anchored_fixture(seed=22)
    delta_base = n_features + 1
    idx, parent, _ = _block_batch(n_features, active, 5, 3, rng)
    idx, parent = np.asarray(idx).copy(), np.asarray(parent).copy()
    # Rewrite every block head to an anchor-entry code: alternate
    # full-stores and persistent deltas (vs distinct table rows).
    tab = rng.integers(-5000, 5000, (8, 2, l1)).astype(np.int32)
    for k, s in enumerate(range(0, len(parent), 3)):
        if k % 2 == 0:
            parent[s] = _pers_code(k, False)
        else:
            parent[s] = _pers_code(k, True, swap=int(rng.integers(0, 2)))
            row = np.full((2, active), n_features, np.int32)
            for p in range(2):
                row[p, :2] = rng.integers(0, n_features, 2)
                row[p, 4:6] = delta_base + rng.integers(0, n_features, 2)
                row[p, 6:8] = delta_base + n_features
            idx[s] = row
    ref = np.asarray(
        ft_gather.ft_accumulate(
            jnp.asarray(ft_w), jnp.asarray(ft_b), jnp.asarray(idx),
            use_pallas=False, delta_base=delta_base,
            parent=jnp.asarray(parent), anchor_tab=jnp.asarray(tab),
        )
    )
    got = np.asarray(
        ft_gather.ft_accumulate(
            jnp.asarray(ft_w), jnp.asarray(ft_b), jnp.asarray(idx),
            interpret=True, delta_base=delta_base,
            parent=jnp.asarray(parent), anchor_tab=jnp.asarray(tab),
        )
    )
    assert np.array_equal(ref, got)


def test_evaluate_packed_anchored_offsets_and_store():
    """The anchored packed path derives row offsets by cumsum (4 per
    full, 1 per delta; padding clamps into the tier-end sentinel
    block), returns values identical to the explicit-offsets packed
    path, and scatters anchor entries' resolved accumulators into
    their table rows — the PSQT table included (ABI 9 device-PSQT
    wire: material=None)."""
    from fishnet_tpu.nnue import spec
    from fishnet_tpu.nnue.jax_eval import (
        evaluate_packed,
        evaluate_packed_anchored,
        params_from_weights,
    )
    from fishnet_tpu.nnue.weights import NnueWeights

    params = params_from_weights(NnueWeights.random(seed=5))
    rng = np.random.default_rng(6)
    B, A = 6, 4
    real = 4  # entries; the last two are padding
    tier = 4 * B + 4
    packed = np.full((tier, 2, 8), spec.NUM_FEATURES, np.uint16)
    parent = np.full((B,), -1, np.int32)
    offsets = np.zeros((B,), np.int32)
    rows = 0
    # e0 full-store(row 0); e1 in-batch delta vs e0; e2 persistent delta
    # vs row 3; e3 plain full; e4/e5 padding.
    specs = [("full_store", 0), ("inbatch", 0), ("pers", 3), ("full", 0)]
    for e, (kind, aid) in enumerate(specs):
        offsets[e] = rows
        if kind in ("full_store", "full"):
            for r in range(4):
                packed[rows + r] = rng.integers(0, spec.NUM_FEATURES, (2, 8))
            parent[e] = _pers_code(aid, False) if kind == "full_store" else -1
            rows += 4
        else:
            packed[rows, :, :2] = rng.integers(0, spec.NUM_FEATURES, (2, 2))
            packed[rows, :, 2:4] = spec.NUM_FEATURES
            packed[rows, :, 4] = spec.DELTA_BASE + rng.integers(
                0, spec.NUM_FEATURES, (2,)
            )
            packed[rows, :, 5:8] = spec.DELTA_BASE + spec.NUM_FEATURES
            parent[e] = (0 << 1) if kind == "inbatch" else _pers_code(
                aid, True
            )
            rows += 1
    offsets[real:] = rows
    # ONE sentinel block at the emitted-stream end; the rows between it
    # and the tier end stay deliberately garbage (stale in production)
    # to prove padding offsets clamp to n_rows and never read them.
    packed[rows : rows + 4] = spec.NUM_FEATURES
    packed[rows + 4 :] = 60000  # would be far out of table bounds
    buckets = rng.integers(0, 8, (B,)).astype(np.int32)
    material = rng.integers(-400, 400, (B,)).astype(np.int32)
    tab = rng.integers(-3000, 3000, (A, 2, spec.L1)).astype(np.int32)
    ptab = rng.integers(-2000, 2000, (A, 2, spec.NUM_PSQT_BUCKETS)).astype(
        np.int32
    )

    vals, new_tab, new_ptab = evaluate_packed_anchored(
        params, jnp.asarray(packed), jnp.asarray(buckets),
        jnp.asarray(parent), jnp.asarray(material), jnp.asarray(tab),
        jnp.asarray(np.array([rows], np.int32)), jnp.asarray(ptab),
    )
    vals, new_tab = np.asarray(vals), np.asarray(new_tab)
    # Host-material mode: the PSQT table rides through untouched.
    assert np.array_equal(np.asarray(new_ptab), ptab)

    # Table-independent entries check against the explicit-offsets
    # packed path (persistent codes stripped to their wire-equivalent
    # plain forms).
    pure = [0, 1, 3]
    # All anchor codes map to plain fulls: entry 0's store-full IS a
    # full, and the persistent entry (2, excluded from `pure`) merely
    # decodes unused rows under its explicit offset.
    ref = np.asarray(
        evaluate_packed(
            params, jnp.asarray(packed), jnp.asarray(offsets),
            jnp.asarray(buckets),
            jnp.asarray(np.where(parent <= -2, -1, parent)),
            jnp.asarray(material),
        )
    )
    assert np.array_equal(vals[pure], ref[pure])
    # The persistent entry (2) checks against the ft-level resolution
    # (independently verified above) fed through the head directly —
    # covering the integrated path's offsets derivation and expansion.
    from fishnet_tpu.nnue.jax_eval import _evaluate_from_acc, expand_packed
    from fishnet_tpu.ops.ft_gather import ft_accumulate

    dense = expand_packed(
        jnp.asarray(packed), jnp.asarray(offsets), jnp.asarray(parent)
    )
    acc = ft_accumulate(
        params["ft_w"], params["ft_b"], dense, use_pallas=False,
        delta_base=spec.DELTA_BASE, parent=jnp.asarray(parent),
        anchor_tab=jnp.asarray(tab),
    )
    head = np.asarray(
        _evaluate_from_acc(
            params, acc, dense, jnp.asarray(buckets), jnp.asarray(parent),
            jnp.asarray(material),
        )
    )
    assert vals[2] == head[2]

    # Store semantics: rows 0 (full-store) and 3 (persistent) updated,
    # rows 1-2 untouched.
    assert not np.array_equal(new_tab[0], tab[0])
    assert not np.array_equal(new_tab[3], tab[3])
    assert np.array_equal(new_tab[1], tab[1])
    assert np.array_equal(new_tab[2], tab[2])

    # DEVICE-PSQT wire (material=None): the fused pass resolves PSQT
    # against ptab, the head selects the bucket itself, and anchor
    # entries' resolved PSQT accumulators scatter into their rows.
    vals_d, _, new_ptab_d = evaluate_packed_anchored(
        params, jnp.asarray(packed), jnp.asarray(buckets),
        jnp.asarray(parent), None, jnp.asarray(tab),
        jnp.asarray(np.array([rows], np.int32)), jnp.asarray(ptab),
    )
    vals_d, new_ptab_d = np.asarray(vals_d), np.asarray(new_ptab_d)
    psqt = np.asarray(
        ft_accumulate(
            params["ft_w"], params["ft_b"], dense, use_pallas=False,
            delta_base=spec.DELTA_BASE, parent=jnp.asarray(parent),
            anchor_tab=jnp.asarray(tab), ft_psqt=params["ft_psqt"],
            psqt_tab=jnp.asarray(ptab),
        )[1]
    )
    sel = psqt[np.arange(B), :, buckets]
    d = sel[:, 0].astype(np.int64) - sel[:, 1]
    mat = np.where(d >= 0, d // 2, -((-d) // 2))  # C truncation
    ref_d = np.asarray(
        _evaluate_from_acc(
            params, acc, dense, jnp.asarray(buckets), jnp.asarray(parent),
            jnp.asarray(mat.astype(np.int32)),
        )
    )
    assert np.array_equal(vals_d[:real], ref_d[:real])
    assert not np.array_equal(new_ptab_d[0], ptab[0])
    assert not np.array_equal(new_ptab_d[3], ptab[3])
    assert np.array_equal(new_ptab_d[1], ptab[1])
    assert np.array_equal(new_ptab_d[2], ptab[2])
    # The stored PSQT rows ARE the resolved accumulators.
    assert np.array_equal(new_ptab_d[0], psqt[0])
    assert np.array_equal(new_ptab_d[3], psqt[2])


def build_psqt_parity_batch(n_features, active, rng, n_blocks=6, block=4,
                            n_tab=8):
    """Batch covering EVERY wire entry kind the PSQT path must resolve:
    plain fulls (-1), anchor full (re)seeds, persistent anchor deltas
    (with swap), in-batch deltas (with swap), removal encodings
    (DELTA_BASE + f), and the per-region sentinel padding. In-batch refs
    always point at the most recent preceding anchor entry (the pool's
    emit contract, which the kernel's running anchor depends on)."""
    from fishnet_tpu.ops.ft_gather import _DELTA_SLOTS

    delta_base = n_features + 1
    batch = n_blocks * block
    idx = np.full((batch, 2, active), n_features, np.int32)
    parent = np.full((batch,), -1, np.int32)

    def fill_full(e):
        idx[e, :, : active - 3] = rng.integers(0, n_features, (2, active - 3))

    def fill_delta(e):
        idx[e] = n_features
        for p in range(2):
            n_add = int(rng.integers(0, _DELTA_SLOTS + 1))
            n_rem = int(rng.integers(0, _DELTA_SLOTS + 1))
            idx[e, p, :n_add] = rng.integers(0, n_features, n_add)
            idx[e, p, _DELTA_SLOTS : _DELTA_SLOTS + n_rem] = (
                delta_base + rng.integers(0, n_features, n_rem)
            )
            idx[e, p, _DELTA_SLOTS + n_rem : 2 * _DELTA_SLOTS] = (
                delta_base + n_features
            )

    for k, s in enumerate(range(0, batch, block)):
        kind = k % 3
        if kind == 0 and k > 0:  # plain full (entry 0 stays an anchor)
            fill_full(s)
        elif kind == 2 and k > 0:  # persistent anchor delta (load+store)
            parent[s] = _pers_code(k % n_tab, True, swap=int(rng.integers(0, 2)))
            fill_delta(s)
        else:  # anchor full (re)seed
            parent[s] = _pers_code(k % n_tab, False)
            fill_full(s)
        for j in range(1, block):
            e = s + j
            parent[e] = (s << 1) | int(rng.integers(0, 2))
            fill_delta(e)
    return idx, parent, delta_base


def np_resolve_psqt(idx, parent, psqt_rows, ptab, delta_base):
    """Independent numpy reconstruction of the resolved PSQT accumulator
    stream — the same walk cpp/src/pool.cpp fill_full/fill_delta does
    host-side (explicit chains, no kernel machinery). int64 to prove no
    intermediate overflow hides in the int32 paths."""
    B = idx.shape[0]
    nb = psqt_rows.shape[1]
    rows64 = psqt_rows.astype(np.int64)
    out = np.zeros((B, 2, nb), np.int64)
    for b in range(B):
        code = int(parent[b])
        v = -code - 2
        is_delta = code >= 0 or (code <= -2 and (v & 2) != 0)
        if code >= 0:
            base, swap = out[int(code) >> 1].copy(), code & 1
        elif code <= -2 and (v & 2) != 0:
            base, swap = ptab[v >> 2].astype(np.int64).copy(), v & 1
        else:
            base, swap = np.zeros((2, nb), np.int64), 0
        if swap:
            base = base[::-1]
        acc = base if is_delta else np.zeros((2, nb), np.int64)
        for p in range(2):
            for f in idx[b, p]:
                f = int(f)
                if f >= delta_base:
                    acc[p] -= rows64[f - delta_base]
                else:
                    acc[p] += rows64[f]
        out[b] = acc
    return out


def host_material_np(psqt, buckets):
    """The pool's host-side material term from a resolved [B, 2, 8] PSQT
    accumulator: bucket select, (stm - opp) / 2 with C truncation."""
    sel = psqt[np.arange(len(buckets)), :, buckets].astype(np.int64)
    d = sel[:, 0] - sel[:, 1]
    return np.where(d >= 0, d // 2, -((-d) // 2)).astype(np.int32)


def test_fused_psqt_parity_all_entry_kinds(monkeypatch):
    """Satellite parity pin: the fused kernel's PSQT accumulator is
    bit-identical to the XLA path, to an independent numpy chain walk
    (the host material recomputation), and both material routes produce
    identical SCORES — across plain fulls, in-batch deltas with swap,
    removal encodings, and persistent anchor store/load codes, with
    chunk boundaries straddled (_CHUNK shrunk so carries engage)."""
    from fishnet_tpu.nnue import spec
    from fishnet_tpu.nnue.jax_eval import (
        _evaluate_from_acc,
        params_from_weights,
    )
    from fishnet_tpu.nnue.weights import NnueWeights
    from fishnet_tpu.ops import ft_gather

    # _CHUNK=6 against blocks of 4: the 4..7 block's children straddle
    # the first chunk boundary (carry-in engages) and the 12..15 block's
    # persistent head lands exactly ON a boundary.
    # active=16 halves the kernel's unrolled transfer trace (the test's
    # cost is trace-bound); the full-spec oracle test below keeps the
    # 32-slot shape covered.
    monkeypatch.setattr(ft_gather, "_CHUNK", 6)
    n_features, l1, active = 512, 1024, 16
    rng = np.random.default_rng(77)
    ft_w = np.vstack(
        [rng.integers(-200, 200, (n_features, l1)), np.zeros((1, l1))]
    ).astype(np.int16)
    ft_b = rng.integers(-100, 100, (l1,)).astype(np.int16)
    psqt_rows = np.vstack(
        [rng.integers(-3000, 3000, (n_features, 8)), np.zeros((1, 8))]
    ).astype(np.int32)
    idx, parent, delta_base = build_psqt_parity_batch(
        n_features, active, rng, n_blocks=4, block=4
    )
    B = len(parent)
    tab = rng.integers(-5000, 5000, (8, 2, l1)).astype(np.int32)
    ptab = rng.integers(-4000, 4000, (8, 2, 8)).astype(np.int32)

    args = dict(delta_base=delta_base, parent=jnp.asarray(parent),
                anchor_tab=jnp.asarray(tab), ft_psqt=jnp.asarray(psqt_rows),
                psqt_tab=jnp.asarray(ptab))
    acc_x, psqt_x = ft_gather.ft_accumulate(
        jnp.asarray(ft_w), jnp.asarray(ft_b), jnp.asarray(idx),
        use_pallas=False, **args,
    )
    acc_f, psqt_f = ft_gather.ft_accumulate(
        jnp.asarray(ft_w), jnp.asarray(ft_b), jnp.asarray(idx),
        interpret=True, **args,
    )
    acc_x, psqt_x = np.asarray(acc_x), np.asarray(psqt_x)
    acc_f, psqt_f = np.asarray(acc_f), np.asarray(psqt_f)
    # Fused == XLA, accumulators and PSQT alike, bit for bit.
    assert np.array_equal(acc_x, acc_f)
    assert np.array_equal(psqt_x, psqt_f)
    # == the independent host chain walk (no int32 overflow hid either).
    ref = np_resolve_psqt(idx, parent, psqt_rows, ptab, delta_base)
    assert np.array_equal(psqt_x.astype(np.int64), ref)

    # Host-material wire vs device-PSQT wire: identical SCORES.
    params = params_from_weights(NnueWeights.random(seed=5))
    buckets = rng.integers(0, spec.NUM_PSQT_BUCKETS, (B,)).astype(np.int32)
    material = host_material_np(psqt_x, buckets)
    via_host = np.asarray(_evaluate_from_acc(
        params, jnp.asarray(acc_x), jnp.asarray(idx), jnp.asarray(buckets),
        jnp.asarray(parent), jnp.asarray(material),
    ))
    via_device = np.asarray(_evaluate_from_acc(
        params, jnp.asarray(acc_f), jnp.asarray(idx), jnp.asarray(buckets),
        jnp.asarray(parent), None, psqt=jnp.asarray(psqt_f),
    ))
    assert np.array_equal(via_host, via_device)


def test_device_psqt_score_parity_with_cpp_oracle(tmp_path):
    """Full-spec four-way parity on REAL positions: the C++ scalar
    oracle, the host-material wire, the XLA device-PSQT path, and the
    fused kernel (interpreter mode) agree bit for bit on the final
    centipawn scores."""
    import random

    from fishnet_tpu.chess import Board
    from fishnet_tpu.nnue import spec
    from fishnet_tpu.nnue.cpp_oracle import CppNnue
    from fishnet_tpu.nnue.jax_eval import (
        _evaluate_from_acc,
        evaluate_batch,
        params_from_weights,
    )
    from fishnet_tpu.nnue.weights import NnueWeights
    from fishnet_tpu.ops.ft_gather import ft_accumulate

    weights = NnueWeights.random(seed=7)
    net = tmp_path / "parity.nnue"
    weights.save(net)
    oracle = CppNnue(net)

    random.seed(99)
    boards = []
    while len(boards) < 12:
        b = Board()
        for _ in range(random.randrange(4, 70)):
            if b.outcome() != 0:
                break
            b.push_uci(random.choice(b.legal_moves()))
        boards.append(b)

    idx = np.stack([b.nnue_features()[0] for b in boards]).astype(np.int32)
    buckets = np.array(
        [b.nnue_features()[1] for b in boards], dtype=np.int32
    )
    params = params_from_weights(weights)

    cpp = np.array([oracle.evaluate(b) for b in boards], dtype=np.int32)

    # Host material, recomputed the way cpp fill_full walks ft_psqt.
    psqt_acc = np.zeros((len(boards), 2, spec.NUM_PSQT_BUCKETS), np.int64)
    for i in range(len(boards)):
        for p in range(2):
            for f in idx[i, p]:
                if f < spec.NUM_FEATURES:
                    psqt_acc[i, p] += weights.ft_psqt[f]
    material = host_material_np(psqt_acc, buckets)
    via_host = np.asarray(evaluate_batch(
        params, jnp.asarray(idx), jnp.asarray(buckets),
        material=jnp.asarray(material),
    ))
    # Device PSQT, XLA path (material=None routes through the same
    # fused-pass code with the XLA executor on CPU).
    via_xla = np.asarray(
        evaluate_batch(params, jnp.asarray(idx), jnp.asarray(buckets))
    )
    # Device PSQT, fused kernel in interpreter mode.
    acc, psqt = ft_accumulate(
        params["ft_w"], params["ft_b"], jnp.asarray(idx),
        interpret=True, ft_psqt=params["ft_psqt"],
    )
    via_fused = np.asarray(_evaluate_from_acc(
        params, acc, jnp.asarray(idx), jnp.asarray(buckets), None, None,
        psqt=psqt,
    ))
    assert np.array_equal(cpp, via_host)
    assert np.array_equal(cpp, via_xla)
    assert np.array_equal(cpp, via_fused)


def test_decode_parent_masks_swap_for_plain_fulls():
    """Plain fulls (-1) decode v=-1 whose low bit is set; the decoded
    swap must be masked with (in_batch | stores) so fulls come back
    swap=0 — any future consumer of the decoded mask relies on it."""
    from fishnet_tpu.ops.ft_gather import decode_parent

    parent = jnp.asarray(
        np.array(
            [
                -1,  # plain full
                5,  # in-batch delta ref 2, swap=1
                4,  # in-batch delta ref 2, swap=0
                -(2 + (3 << 2) + 2 + 1),  # persistent, row 3, swap=1
                -(2 + (7 << 2)),  # full anchor reseed row 7, swap=0
            ],
            np.int32,
        )
    )
    in_batch, persistent, stores, ref, swap, aid = decode_parent(parent)
    assert np.asarray(swap).tolist() == [False, True, False, True, False]
    assert np.asarray(in_batch).tolist() == [False, True, True, False, False]
    assert np.asarray(persistent).tolist() == [False, False, False, True, False]
    assert np.asarray(aid).tolist() == [0, 0, 0, 3, 7]


def test_persistent_codes_without_table_raise_eagerly():
    from fishnet_tpu.nnue import spec as _spec

    ft_w, ft_b, idx = _fixture(batch=3)
    parent = np.array([-1, -4, -1], np.int32)  # -4: persistent delta code
    with pytest.raises(ValueError, match="anchor_tab"):
        ft_accumulate(
            ft_w, ft_b, idx, use_pallas=False,
            delta_base=_spec.DELTA_BASE, parent=jnp.asarray(parent),
        )


def test_persistent_codes_without_table_poison_under_trace():
    """Traced misuse cannot raise: the structural guard must poison the
    affected entries (loudly constant) instead of returning plausible
    unresolved partials (the round-5 review's finding)."""
    import jax

    from fishnet_tpu.nnue import spec as _spec
    from fishnet_tpu.ops.ft_gather import _POISON_ACC

    ft_w, ft_b, idx = _fixture(batch=3)
    parent = jnp.asarray(np.array([-1, -4, -1], np.int32))

    @jax.jit
    def run(w, b, i, p):
        return ft_accumulate(
            w, b, i, use_pallas=False, delta_base=_spec.DELTA_BASE, parent=p
        )

    acc = np.asarray(run(ft_w, ft_b, idx, parent))
    assert (acc[1] == _POISON_ACC).all()
    assert (acc[0] != _POISON_ACC).any() and (acc[2] != _POISON_ACC).any()


def test_persistent_codes_without_material_poison_scores_under_trace():
    import jax

    from fishnet_tpu.nnue import spec as _spec
    from fishnet_tpu.nnue.jax_eval import evaluate_batch, params_from_weights
    from fishnet_tpu.nnue.weights import NnueWeights

    params = params_from_weights(NnueWeights.random(seed=5))
    feats = jnp.asarray(
        np.full((3, 2, _spec.MAX_ACTIVE_FEATURES), _spec.NUM_FEATURES, np.uint16)
    )
    buckets = jnp.zeros((3,), jnp.int32)
    parent = jnp.asarray(np.array([-1, -4, -1], np.int32))

    @jax.jit
    def run(p, f, b, par):
        return evaluate_batch(p, f, b, par)

    vals = np.asarray(run(params, feats, buckets, parent))
    assert abs(int(vals[1])) > 10**6  # ~2^24 cp: unmistakably poisoned
    assert abs(int(vals[0])) < 10**6 and abs(int(vals[2])) < 10**6


def test_persistent_codes_concrete_without_material_raise_structurally():
    """The eager-path twin of the poison tests above: a CONCRETE batch
    carrying a persistent anchor code with neither host material nor a
    device-resolved psqt must fail structurally in the network head —
    the in-batch-only PSQT fallback there cannot resolve table refs and
    would otherwise return plausible garbage (jax_eval
    _evaluate_from_acc)."""
    from fishnet_tpu.nnue import spec as _spec
    from fishnet_tpu.nnue.jax_eval import (
        _evaluate_from_acc,
        params_from_weights,
    )
    from fishnet_tpu.nnue.weights import NnueWeights

    params = params_from_weights(NnueWeights.random(seed=5))
    feats = jnp.asarray(
        np.full((3, 2, _spec.MAX_ACTIVE_FEATURES), _spec.NUM_FEATURES, np.int32)
    )
    buckets = jnp.zeros((3,), jnp.int32)
    parent = jnp.asarray(np.array([-1, -4, -1], np.int32))
    acc = jnp.zeros((3, 2, _spec.L1), jnp.int32)
    with pytest.raises(ValueError, match="persistent anchor codes"):
        _evaluate_from_acc(params, acc, feats, buckets, parent, None)
