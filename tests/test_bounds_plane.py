"""Bound-aware search plane (doc/eval-cache.md "Bounds tier",
doc/search.md): deeper-entry-wins replacement in the process
BoundsCache and the fleet tier's bounds slots, lower/upper cutoff
semantics pinned against a reference alpha-beta over transposing game
DAGs, torn-slot read-as-miss for the new tier slot kind, service-level
harvest/seed round-trips, the FISHNET_NO_BOUNDS / FISHNET_NO_SPECULATION
escape hatches, speculative pad-row evals riding AZ dispatch padding
without perturbing results, the speculation-budget control-plane rule,
and the host linger window that fuses staggered cross-process waves
into one pow2 bucket (the 3x40 -> 192-slot pathology)."""

import asyncio
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax

sys.path.insert(0, str(Path(__file__).parent))

from fishnet_tpu.cluster import position_tier
from fishnet_tpu.models.az import AzConfig, init_az_params
from fishnet_tpu.nnue.weights import NnueWeights
from fishnet_tpu.rpc import rings
from fishnet_tpu.search import eval_cache
from fishnet_tpu.search.eval_cache import (
    BOUND_EXACT,
    BOUND_LOWER,
    BOUND_NONE,
    BOUND_UPPER,
    MOVE_NONE_BITS,
    BoundsCache,
    EvalCache,
)

STARTPOS = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
TINY = AzConfig(channels=16, blocks=2, value_hidden=16)


# -- BoundsCache units -------------------------------------------------------


def test_bounds_cache_deeper_entry_wins():
    c = BoundsCache(capacity=64)
    assert c.insert_bound(5, 100, 90, 6, BOUND_EXACT, 123, uci="e2e4")
    # A shallower record must never clobber the resident deep one.
    assert not c.insert_bound(5, -4, 0, 3, BOUND_LOWER, 7)
    assert c.probe_bound(5) == (100, 90, 6, BOUND_EXACT, 123, "e2e4")
    # Equal depth: a non-exact bound cannot displace an exact one.
    assert not c.insert_bound(5, 1, 1, 6, BOUND_UPPER, 9)
    assert c.probe_bound(5)[3] == BOUND_EXACT
    # Strictly deeper always lands.
    assert c.insert_bound(5, 7, 8, 9, BOUND_LOWER, 11, uci="d2d4")
    assert c.probe_bound(5) == (7, 8, 9, BOUND_LOWER, 11, "d2d4")
    # BOUND_NONE and out-of-range bounds are refused outright.
    assert not c.insert_bound(6, 1, 1, 1, BOUND_NONE, 0)
    assert not c.insert_bound(6, 1, 1, 1, 4, 0)
    assert c.probe_bound(6) is None


def test_bounds_cache_block_probe_layout():
    c = BoundsCache(capacity=64)
    c.insert_bound(10, -50, -40, 4, BOUND_UPPER, 0x155)
    c.insert_bound(30, 900, 800, 7, BOUND_LOWER, 0x2AA)
    vals, evs, deps, bnds, movs = c.probe_bounds_block(
        np.array([10, 20, 30], dtype=np.uint64)
    )
    assert list(bnds) == [BOUND_UPPER, BOUND_NONE, BOUND_LOWER]
    assert list(vals) == [-50, 0, 900]
    assert list(evs) == [-40, 0, 800]
    assert list(deps) == [4, 0, 7]
    assert movs[0] == 0x155 and movs[2] == 0x2AA
    assert movs[1] == MOVE_NONE_BITS  # miss rows carry the no-move sentinel


def test_contains_is_stats_neutral():
    c = EvalCache(capacity=16)
    c.insert(7, 42)
    before = c.stats()
    assert c.contains(7) and not c.contains(8)
    after = c.stats()
    assert (after["hits"], after["misses"]) == (
        before["hits"], before["misses"],
    ), "speculation admission probes must not skew hit-rate telemetry"


# -- cutoff semantics vs reference alpha-beta --------------------------------


def _make_dag(rng, levels=5, width=6, fanout=3):
    """Depth-stratified random DAG with transpositions: level-i nodes
    draw children from the SHARED level-i+1 pool, so the same position
    is reached along many paths and TT records actually fire. Node ids
    are globally unique ints; leaves carry the static values."""
    ids = [[lvl * 1000 + i for i in range(width)] for lvl in range(levels)]
    children = {}
    for lvl in range(levels - 1):
        for node in ids[lvl]:
            k = int(rng.integers(2, fanout + 1))
            children[node] = list(
                rng.choice(ids[lvl + 1], size=k, replace=False)
            )
    values = {n: int(rng.integers(-1000, 1000)) for n in ids[-1]}
    return ids[0][0], children, values


def _negamax(children, values, node, depth):
    if depth == 0 or node not in children:
        return values.get(node, 0)
    return max(
        -_negamax(children, values, ch, depth - 1)
        for ch in children[node]
    )


INF = 10**6


def _ab_tt(children, values, node, depth, alpha, beta, tt):
    """Reference alpha-beta consuming/producing BoundsCache records
    with the native TT's cutoff rules: exact returns, lower raises
    alpha, upper lowers beta, depth-gated."""
    rec = tt.probe_bound(node)
    if rec is not None and rec[2] >= depth:
        v, _, _, b, _, _ = rec
        if b == BOUND_EXACT:
            return v
        if b == BOUND_LOWER:
            alpha = max(alpha, v)
        elif b == BOUND_UPPER:
            beta = min(beta, v)
        if alpha >= beta:
            return v
    if depth == 0 or node not in children:
        return values.get(node, 0)
    a0 = alpha
    best = -INF
    for ch in children[node]:
        best = max(
            best,
            -_ab_tt(children, values, ch, depth - 1, -beta, -alpha, tt),
        )
        alpha = max(alpha, best)
        if alpha >= beta:
            break
    bound = (
        BOUND_UPPER if best <= a0
        else BOUND_LOWER if best >= beta
        else BOUND_EXACT
    )
    tt.insert_bound(node, best, 0, depth, bound, MOVE_NONE_BITS)
    return best


def test_tt_cutoffs_match_reference_alpha_beta():
    """Lower/upper cutoff correctness: an alpha-beta consuming cached
    bound records (window narrowing + cutoff) must return the same root
    value as plain full-width negamax on transposing DAGs — and the
    cache must actually get hits, or the test proves nothing."""
    for seed in range(6):
        rng = np.random.default_rng(seed)
        root, children, values = _make_dag(rng)
        want = _negamax(children, values, root, 4)
        tt = BoundsCache(capacity=4096)
        got = _ab_tt(children, values, root, 4, -INF, INF, tt)
        assert got == want, f"seed {seed}: TT search diverged"
        # A replay over the warm table must short-circuit to the same
        # value (the exact root record makes it a single probe).
        assert _ab_tt(children, values, root, 4, -INF, INF, tt) == want
        assert tt.stats()["hits"] > 0, "DAG produced no transposition hits"


# -- fleet tier bounds slots -------------------------------------------------


@pytest.fixture
def tier_env(tmp_path, monkeypatch):
    seg = tmp_path / "tier.seg"
    monkeypatch.setenv(position_tier.TIER_ENV, "1")
    monkeypatch.setenv(position_tier.TIER_PATH_ENV, str(seg))
    monkeypatch.setenv(position_tier.TIER_CAPACITY_ENV, "4096")
    monkeypatch.setenv(position_tier.TIER_AZ_CAPACITY_ENV, "32")
    # TIER_BOUNDS_CAPACITY_ENV == FISHNET_POSITION_TIER_BOUNDS_CAPACITY
    monkeypatch.setenv(position_tier.TIER_BOUNDS_CAPACITY_ENV, "1024")
    position_tier.reset_tier()
    yield seg
    position_tier.reset_tier()


def _tier_probe(tier, keys):
    n = len(keys)
    cols = (
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.full(n, MOVE_NONE_BITS, np.uint32),
    )
    hits = tier.probe_bounds_block(
        np.asarray(keys, np.uint64), *cols
    )
    return hits, cols


def test_tier_bounds_roundtrip_and_deeper_wins(tier_env):
    tier = position_tier.get_tier()
    assert tier is not None
    tier.insert_bound(0xABC, -77, 12, 9, BOUND_LOWER, 0x1234)
    hits, (vals, evs, deps, bnds, movs) = _tier_probe(tier, [0xABC, 0xDEF])
    assert hits == 1
    assert (vals[0], evs[0], deps[0], bnds[0], movs[0]) == (
        -77, 12, 9, BOUND_LOWER, 0x1234,
    )
    assert bnds[1] == BOUND_NONE
    # Shallower same-key insert is refused; the deep record survives.
    tier.insert_bound(0xABC, 5, 5, 3, BOUND_EXACT, 1)
    _, (vals, _, deps, bnds, _) = _tier_probe(tier, [0xABC])
    assert (vals[0], deps[0], bnds[0]) == (-77, 9, BOUND_LOWER)
    # Deeper insert replaces.
    tier.insert_bound(0xABC, 31, 30, 12, BOUND_EXACT, 0x777)
    _, (vals, _, deps, bnds, movs) = _tier_probe(tier, [0xABC])
    assert (vals[0], deps[0], bnds[0], movs[0]) == (
        31, 12, BOUND_EXACT, 0x777,
    )
    # Block insert skips miss-marked rows.
    keys = np.array([0x111, 0x222], np.uint64)
    tier.insert_bounds_block(
        keys,
        np.array([10, 20], np.int32), np.array([1, 2], np.int32),
        np.array([4, 4], np.int32),
        np.array([BOUND_NONE, BOUND_UPPER], np.int32),
        np.array([0, 0], np.uint32),
    )
    hits, (_, _, _, bnds, _) = _tier_probe(tier, [0x111, 0x222])
    assert hits == 1 and bnds[0] == BOUND_NONE and bnds[1] == BOUND_UPPER


def test_tier_bounds_torn_slot_reads_as_miss(tier_env):
    """The SIGKILLed-writer shapes: a clobbered payload (checksum
    mismatch) and a writer dead mid-write (odd seq) must both read as
    misses for the bounds slot kind — never a value."""
    tier = position_tier.get_tier()
    tier.insert_bound(0x51, 400, 350, 8, BOUND_EXACT, 0x99)
    tier.insert_bound(0x52, -60, -50, 5, BOUND_UPPER, 0x11)
    assert _tier_probe(tier, [0x51, 0x52])[0] == 2

    def slot_of(key):
        for idx in range(len(tier._bounds)):
            if int(tier._bounds[idx]["key"]) == key:
                return idx
        raise AssertionError(f"key {key:#x} not found in bounds region")

    # Payload clobbered after publish: checksum catches it.
    tier._bounds[slot_of(0x51)]["value"] ^= 0xFF
    # Writer died mid-write: odd seq means never-published.
    tier._bounds[slot_of(0x52)]["seq"] |= 1
    hits, (_, _, _, bnds, _) = _tier_probe(tier, [0x51, 0x52])
    assert hits == 0 and not bnds.any()


# -- service harvest/seed + escape hatch -------------------------------------


def _analyses(svc, nodes=220):
    svc.set_prefetch(0, adaptive=False)

    async def go():
        out = []
        for fen, moves in (
            (STARTPOS, []),
            (STARTPOS, ["e2e4", "e7e5"]),
        ):
            r = await svc.search(fen, moves, nodes=nodes)
            out.append((
                r.best_move, r.depth, r.nodes,
                tuple((l.multipv, l.depth, l.is_mate, l.value,
                       tuple(l.pv)) for l in r.lines),
            ))
        return out

    return asyncio.run(go())


def _service(weights):
    from fishnet_tpu.search.service import SearchService

    return SearchService(
        weights=weights, pool_slots=8, batch_capacity=64,
        tt_bytes=8 << 20, backend="jax", pipeline_depth=2,
        driver_threads=1,
    )


def test_service_bounds_harvest_then_seed(monkeypatch):
    """Cold search harvests PV bound records into the BoundsCache;
    a FRESH service (empty native TT) over the warm cache seeds its
    pool TT pre-search — the respawn-survival path the bounds tier
    exists for."""
    monkeypatch.setenv("FISHNET_NO_BOUNDS", "0")
    eval_cache.reset_cache()
    weights = NnueWeights.random(seed=3)

    svc = _service(weights)
    try:
        _analyses(svc)
        c = svc.counters()
        assert c["bounds_harvested"] > 0
        assert c["bounds_seeded"] == 0  # nothing cached before the run
    finally:
        svc.close()
    bcache = eval_cache.get_bounds_cache()
    assert bcache is not None and len(bcache) > 0
    rec = next(iter(
        bcache.probe_bound(h)
        for s in bcache._stripes for h in s
    ))
    assert rec[3] in (BOUND_UPPER, BOUND_LOWER, BOUND_EXACT)

    svc2 = _service(weights)
    try:
        _analyses(svc2)
        assert svc2.counters()["bounds_seeded"] > 0
    finally:
        svc2.close()


def test_service_bounds_hatch_is_inert(monkeypatch):
    """FISHNET_NO_BOUNDS=1 (the conftest default): no bounds cache, no
    seed/harvest calls, and fresh-service runs stay deterministic —
    the byte-for-byte arm every parity test compares against."""
    assert eval_cache.bounds_disabled()
    assert eval_cache.get_bounds_cache() is None
    weights = NnueWeights.random(seed=3)
    outs = []
    for _ in range(2):
        svc = _service(weights)
        try:
            outs.append(_analyses(svc, nodes=160))
            c = svc.counters()
            assert c["bounds_harvested"] == 0
            assert c["bounds_seeded"] == 0
        finally:
            svc.close()
    assert outs[0] == outs[1]


def test_service_pad_rows_counter_advances(monkeypatch):
    """fishnet_dispatch_pad_rows_total{path="service"}: ragged NNUE
    dispatches must book their pow2 padding."""
    from fishnet_tpu.search.service import _PAD_ROWS

    before = _PAD_ROWS.value(path="service")
    svc = _service(NnueWeights.random(seed=3))
    try:
        _analyses(svc, nodes=160)
    finally:
        svc.close()
    assert _PAD_ROWS.value(path="service") > before


# -- speculative pad-row evals -----------------------------------------------


@pytest.fixture(scope="module")
def az_params():
    return init_az_params(jax.random.PRNGKey(3), TINY)


def _mcts_run(params, trees=5, visits=48, evaluator=None):
    from fishnet_tpu.search.mcts import MctsConfig, MctsPool

    cfg = MctsConfig(batch_capacity=64, az=TINY)
    pool = MctsPool(params, cfg, evaluator=evaluator)
    try:
        openings = [[], ["e2e4"], ["d2d4"], ["g1f3"], ["e2e4", "c7c5"]]
        sids = [
            pool.submit(STARTPOS, list(openings[i % len(openings)]), visits)
            for i in range(trees)
        ]
        while pool.active() > 0:
            pool.step()
        out = []
        for sid in sids:
            r = pool.harvest(sid)
            out.append((r.best_move, r.visits, r.value,
                        tuple(r.root_visits), tuple(r.pv)))
        return out, pool.counters()
    finally:
        pool.close()


def test_speculation_fills_pads_without_changing_results(
    az_params, monkeypatch
):
    """Speculative pad rows ride otherwise-wasted bucket padding: the
    hatch arm and the speculation arm must agree bit-for-bit (row
    independence), while the speculation arm lands extra rows in the
    AZ eval cache."""
    hatch_out, hatch_c = _mcts_run(az_params)  # conftest pins the hatch
    assert hatch_c["spec_offered"] == 0
    assert hatch_c["dispatch"]["spec_rows"] == 0

    monkeypatch.setenv("FISHNET_NO_SPECULATION", "0")
    eval_cache.reset_cache()
    spec_out, spec_c = _mcts_run(az_params)
    assert spec_out == hatch_out, "speculation must never perturb results"
    assert spec_c["spec_offered"] > 0
    assert spec_c["dispatch"]["spec_rows"] > 0
    # Landed rows are real cache entries (future pre-wire hits).
    az = eval_cache.get_az_cache()
    assert az is not None and az.stats()["insertions"] > 0


def test_speculation_budget_zero_pins_off(az_params, monkeypatch):
    """set_speculation_budget(0) — the controller's pin — must stop
    both the offers (tree side) and the pad fill (plane side), however
    generous the bind-time FISHNET_SPECULATION_BUDGET was."""
    from fishnet_tpu.search.az_plane import AzDispatchPlane
    from fishnet_tpu.search.mcts import MctsConfig

    monkeypatch.setenv("FISHNET_NO_SPECULATION", "0")
    eval_cache.reset_cache()
    cfg = MctsConfig(batch_capacity=64, az=TINY)
    plane = AzDispatchPlane(az_params, cfg)
    plane.set_speculation_budget(0)
    try:
        _, c = _mcts_run(az_params, evaluator=plane)
        assert c["spec_offered"] == 0
        assert plane.counters()["spec_rows"] == 0
    finally:
        plane.close()


def test_speculation_controller_pin_unpin():
    """The control-plane rule: dispatch fill above SPECULATION_PIN
    pins the budget to 0; back under SPECULATION_UNPIN restores the
    bind-time default; revert_all restores it too."""
    from fishnet_tpu.control.actuators import ActuatorRegistry
    from fishnet_tpu.control.controller import (
        RuleProbePolicy,
        standard_actuators,
    )
    from fishnet_tpu.control.signals import ControlSignals

    class FakePlane:
        def __init__(self):
            self._b = 8

        def speculation_budget(self):
            return self._b

        def set_speculation_budget(self, b):
            self._b = max(0, int(b))

    plane = FakePlane()
    reg = ActuatorRegistry()
    reg.register_all(standard_actuators(az_plane=plane))
    pol = RuleProbePolicy()

    def sig(fill):
        s = ControlSignals(window=1)
        s.counters = {"eval_steps": 5.0}
        if fill is not None:
            s.counters["dispatch_fill"] = fill
        return s

    acts = pol.decide(sig(0.95), reg.snapshot())
    assert [(a.knob, a.value) for a in acts] == [("speculation_budget", 0)]
    reg.apply(acts[0].knob, acts[0].value)
    assert plane.speculation_budget() == 0
    # Mid-band and fill-absent windows hold the pin (hysteresis).
    assert pol.decide(sig(0.7), reg.snapshot()) == []
    assert pol.decide(sig(None), reg.snapshot()) == []
    acts = pol.decide(sig(0.3), reg.snapshot())
    assert [(a.knob, a.value) for a in acts] == [
        ("speculation_budget", None)
    ]
    reg.apply(acts[0].knob, acts[0].value)
    assert plane.speculation_budget() == 8
    # The escape hatch restores the bind-time default from a pin too.
    reg.apply("speculation_budget", 0)
    reg.revert_all()
    assert plane.speculation_budget() == 8


# -- host linger: cross-process pow2 fusion ---------------------------------


def test_host_linger_fuses_staggered_waves(tmp_path):
    """Three frontends' 40-row waves landing WITHIN one linger window
    (``linger_s`` here; FISHNET_HOST_LINGER_MS / --linger-ms in
    production) must dispatch as one fused 128-slot bucket (120 rows +
    8 pads), not three 64-slot buckets (192 slots) — the pow2
    pathology."""
    from fishnet_tpu.nnue.jax_eval import params_from_weights
    from fishnet_tpu.rpc.host import EvaluatorHost

    params = params_from_weights(NnueWeights.random(seed=5))
    host = EvaluatorHost(
        nnue_params=params, rpc_dir=str(tmp_path), linger_s=0.6,
    )
    fronts = [
        rings.create_frontend_link(str(tmp_path), name=f"f{i}.ring")
        for i in range(3)
    ]
    rng = np.random.default_rng(0)

    def payload():
        feats = rng.integers(0, 1000, (40, 2, 32), dtype=np.uint16)
        buckets = rng.integers(0, 8, 40, dtype=np.int32)
        parents = np.full(40, -1, np.int32)
        material = rng.integers(-100, 100, 40, dtype=np.int32)
        return rings.pack_nnue_submit(feats, buckets, parents, material)

    before = rings.stats()
    try:
        fronts[0].push(
            rings.KIND_NNUE_SUBMIT, 1, fronts[0].frontend_epoch, 40,
            payload(),
        )

        def late_pushes():
            for delay, front in ((0.1, fronts[1]), (0.1, fronts[2])):
                time.sleep(delay)
                front.push(
                    rings.KIND_NNUE_SUBMIT, 1, front.frontend_epoch, 40,
                    payload(),
                )

        th = threading.Thread(target=late_pushes)
        th.start()
        served = host.sweep()  # first drain sees ONE wave; linger fuses
        th.join(timeout=10.0)
        assert served == 3
        after = rings.stats()

        def delta(key):
            return after.get(key, 0) - before.get(key, 0)

        assert delta("fused.rows.nnue") == 120
        assert delta("fused.slots.nnue") <= 128, (
            "staggered waves must bucket by FUSED row count"
        )
        assert delta("pad.rows") == 8
    finally:
        host.close()
        for front in fronts:
            front.close()
