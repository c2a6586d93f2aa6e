"""Shared-plane batched MCTS (ISSUE 14): plane-vs-legacy bit parity on
every degradation rung, pre-wire AZ eval reuse, the preallocated step
buffer, collision/terminal/multipv tree semantics, self-play parity
plane-on vs plane-off, and the tree-side telemetry families."""

import numpy as np
import pytest

import jax

from fishnet_tpu import telemetry
from fishnet_tpu.chess.board import Board
from fishnet_tpu.models.az import AzConfig, init_az_params
from fishnet_tpu.models.trunk import TrunkConfig
from fishnet_tpu.models.az_encoding import POLICY_SIZE
from fishnet_tpu.search import eval_cache
from fishnet_tpu.search.mcts import MctsConfig, MctsPool
from fishnet_tpu.telemetry.registry import REGISTRY
from fishnet_tpu.telemetry.spans import RECORDER

STARTPOS = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
TINY = AzConfig(channels=16, blocks=2, value_hidden=16)
# The sparse-expert trunk behind the same az_forward (models/trunk.py).
TINY_TRUNK = TrunkConfig(hidden=32, heads=2, head_dim=16, layers=1, experts=4,
                         experts_per_token=2, expert_width=16, value_hidden=16)

OPENINGS = [
    [], ["e2e4"], ["d2d4"], ["g1f3"],
    ["e2e4", "c7c5"], ["e2e4", "e7e5"], ["d2d4", "d7d5"],
    ["d2d4", "g8f6"],
]


@pytest.fixture(scope="module")
def params():
    return init_az_params(jax.random.PRNGKey(3), TINY)


class _CountingEval:
    """Instant uniform-policy evaluator (no jax): pins pure tree
    semantics independent of any dispatch path."""

    def __init__(self):
        self.calls = 0
        self.rows = 0

    def warmup(self, cap):
        pass

    def evaluate(self, planes_u8, n, keys=None):
        self.calls += 1
        self.rows += n
        return (
            np.zeros((n, POLICY_SIZE), np.float32),
            np.zeros(n, np.float32),
        )

    def close(self):
        pass


def _run_workload(pool, visits=80, trees=8):
    sids = [
        pool.submit(STARTPOS, list(OPENINGS[i % len(OPENINGS)]), visits)
        for i in range(trees)
    ]
    while pool.active() > 0:
        pool.step()
    out = []
    for sid in sids:
        r = pool.harvest(sid)
        out.append((r.best_move, r.visits, r.value,
                    tuple(r.root_visits), tuple(r.pv)))
    return out


# -- parity: legacy vs plane, every rung, escape hatch ----------------------


def _parity_run(params, monkeypatch, force_rung=None, legacy=False, net=TINY):
    eval_cache.reset_cache()
    cfg = MctsConfig(batch_capacity=64, az=net)
    plane = None
    if legacy:
        monkeypatch.setenv("FISHNET_NO_SHARED_AZ_PLANE", "1")
    else:
        monkeypatch.delenv("FISHNET_NO_SHARED_AZ_PLANE", raising=False)
        if force_rung is not None:
            from fishnet_tpu.search.az_plane import AzDispatchPlane

            plane = AzDispatchPlane(params, cfg, force_rung=force_rung)
    pool = MctsPool(params, cfg, evaluator=plane)
    try:
        return _run_workload(pool)
    finally:
        pool.close()
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("net", [TINY, TINY_TRUNK], ids=["tower", "trunk"])
def test_plane_parity_all_rungs_and_hatch(params, monkeypatch, net):
    """The escape hatch restores the legacy path, and the shared plane
    matches it bit-for-bit on every forced degradation rung — with the
    AZ eval cache live (pre-wire hits interleave with dispatches). The
    same holds for the trunk: MctsPool -> AzDispatchPlane -> az_forward
    with no edit to either."""
    if net is not TINY:
        params = init_az_params(jax.random.PRNGKey(3), net)
    legacy = _parity_run(params, monkeypatch, legacy=True, net=net)
    assert any(r[1] > 0 for r in legacy)
    assert all(r[0] for r in legacy)  # a best move from every tree
    for rung in (None, 0, 1, 2):  # default ladder + each forced rung
        assert _parity_run(params, monkeypatch, force_rung=rung, net=net) == legacy


def test_az_prewire_warm_replay(params, monkeypatch):
    """A respawned pool (fresh memo) against the surviving process
    AzEvalCache resolves its leaves PRE-WIRE: nonzero prewire hits, and
    the registry family carries scope=prewire, family=az."""
    monkeypatch.delenv("FISHNET_NO_SHARED_AZ_PLANE", raising=False)
    cfg = MctsConfig(batch_capacity=64, az=TINY)
    cold_pool = MctsPool(params, cfg)
    cold = _run_workload(cold_pool)
    cold_counters = cold_pool.counters()["dispatch"]
    cold_pool.close()
    assert cold_counters["rows_dispatched"] > 0
    assert cold_counters["prewire_hits"] == 0

    warm_pool = MctsPool(params, cfg)  # fresh pool, fresh plane, warm cache
    warm = _run_workload(warm_pool)
    warm_counters = warm_pool.counters()["dispatch"]
    # Collect while the plane is live: close() unregisters its collector.
    hits = [
        s for fam in REGISTRY.collect()
        if fam.name == "fishnet_eval_cache_hits_total"
        for s in fam.samples
        if s.labels.get("scope") == "prewire"
        and s.labels.get("family") == "az"
    ]
    warm_pool.close()
    assert warm == cold  # cache payload round-trips exactly
    assert warm_counters["prewire_hits"] > 0
    assert warm_counters["rows_dispatched"] < cold_counters["rows_dispatched"]
    assert hits and sum(s.value for s in hits) > 0


def test_az_fingerprint_keys_nets_apart(params):
    """Cache keys are salted by the net fingerprint, so two different
    AZ nets (and the NNUE cache) can never serve each other's entries."""
    other = init_az_params(jax.random.PRNGKey(9), TINY)
    fp_a = eval_cache.az_net_fingerprint(params)
    fp_b = eval_cache.az_net_fingerprint(other)
    assert fp_a != fp_b
    # Same net hashes stably across calls.
    assert fp_a == eval_cache.az_net_fingerprint(params)
    key = eval_cache.az_position_key(0x1234ABCD, 7)
    assert (key ^ fp_a) != (key ^ fp_b)
    # Halfmove clock is part of the position identity (plane 17).
    assert eval_cache.az_position_key(0x1234ABCD, 7) != \
        eval_cache.az_position_key(0x1234ABCD, 8)


# -- satellite: preallocated step buffer ------------------------------------


def test_step_reuses_preallocated_batch_buffer(monkeypatch):
    """MctsPool.step must never allocate a fresh full-capacity
    (cap, 8, 8, 19) batch per step (the old zero-fill regression)."""
    cfg = MctsConfig(batch_capacity=128, az=TINY)
    pool = MctsPool({}, cfg, evaluator=_CountingEval())
    sids = [pool.submit(STARTPOS, [], 40) for _ in range(4)]
    full_allocs = []
    real_zeros = np.zeros

    def spy(shape, *a, **k):
        if (
            isinstance(shape, tuple) and len(shape) == 4
            and shape[0] == cfg.batch_capacity
        ):
            full_allocs.append(shape)
        return real_zeros(shape, *a, **k)

    monkeypatch.setattr(np, "zeros", spy)
    while pool.active() > 0:
        pool.step()
    monkeypatch.setattr(np, "zeros", real_zeros)
    for sid in sids:
        assert pool.harvest(sid).visits == 40
    pool.close()
    assert full_allocs == []


# -- tree semantics ---------------------------------------------------------


def test_collision_release_under_forced_line():
    """A single-legal-move root funnels every speculative walk onto one
    edge: the excess walks must collide, release their virtual loss
    completely, and still let the search finish its exact budget."""
    # White king boxed in by Qc2 (a2/b1/b2 covered, a1 not attacked —
    # no check, no capture): h3h4 is the single legal move, and unlike
    # a queen capture it leads to a live position, so the pending-leaf
    # window actually exists for the follow-up walks to collide in.
    forced = "4k3/8/8/8/8/7P/2q5/K7 w - - 0 1"
    assert Board(forced).legal_moves() == ["h3h4"]
    cfg = MctsConfig(
        batch_capacity=32, leaves_per_step=8, adaptive_leaves=False,
        az=TINY,
    )
    pool = MctsPool({}, cfg, evaluator=_CountingEval())
    sid = pool.submit(forced, [], 30)
    search = pool._searches[sid]
    while pool.active() > 0:
        pool.step()
    r = pool.harvest(sid)
    pool.close()
    assert r.best_move == "h3h4"
    assert r.visits == 30
    assert search.collisions > 0
    for node in search.nodes:
        assert not node.vloss.any()  # every walk's loss released


def test_terminal_leaf_backup_sign():
    """A mate found at a leaf backs up as a WIN for the side delivering
    it: the mating edge's total value equals its visit count exactly."""
    fen = "6k1/8/6K1/8/8/8/8/R7 w - - 0 1"  # Ra8# available
    cfg = MctsConfig(batch_capacity=32, az=TINY)
    pool = MctsPool({}, cfg, evaluator=_CountingEval())
    sid = pool.submit(fen, [], 200)
    search = pool._searches[sid]
    while pool.active() > 0:
        pool.step()
    r = pool.harvest(sid)
    pool.close()
    assert r.best_move == "a1a8"
    root = search.nodes[0]
    edge = root.moves.index("a1a8")
    assert root.n[edge] > 0
    # Each backup through the mate is -(terminal -1) == +1 at the root.
    assert root.w[edge] == root.n[edge]
    assert r.value == 1.0


def test_multipv_ranking_at_zero_visits():
    """Harvesting before the first backup must rank lines by policy
    prior (not move-generation order)."""
    cfg = MctsConfig(batch_capacity=32, az=TINY)
    pool = MctsPool({}, cfg, evaluator=_CountingEval())
    sid = pool.submit(STARTPOS, [], 500, multipv=3)
    search = pool._searches[sid]
    pool.step()  # root eval only; no simulation has completed yet
    pool.stop_search(sid)
    r = pool.harvest(sid)
    pool.close()
    root = search.nodes[0]
    assert int(root.n.sum()) == 0
    expected = [
        root.moves[i] for i in np.lexsort((root.priors, root.n))[::-1][:3]
    ]
    assert [line.move for line in r.lines] == expected


# -- self-play parity -------------------------------------------------------


def test_selfplay_bit_identical_plane_on_off(params, monkeypatch):
    from fishnet_tpu.train.selfplay import SelfPlayConfig, play_games

    def one(plane_off):
        if plane_off:
            monkeypatch.setenv("FISHNET_NO_SHARED_AZ_PLANE", "1")
        else:
            monkeypatch.delenv("FISHNET_NO_SHARED_AZ_PLANE", raising=False)
        eval_cache.reset_cache()
        pool = MctsPool(params, MctsConfig(batch_capacity=32, az=TINY))
        games = play_games(
            pool, SelfPlayConfig(games=2, visits=16, max_plies=6), seed=5
        )
        pool.close()
        return [
            (g.moves, g.outcome_white,
             [(rec.policy.tobytes(), rec.stm_white) for rec in g.records])
            for g in games
        ]

    assert one(plane_off=True) == one(plane_off=False)


# -- telemetry --------------------------------------------------------------


def test_mcts_telemetry_families_and_collect_span():
    telemetry.enable()
    try:
        cfg = MctsConfig(batch_capacity=32, az=TINY)
        pool = MctsPool({}, cfg, evaluator=_CountingEval())
        sids = [pool.submit(STARTPOS, [], 25) for _ in range(3)]
        while pool.active() > 0:
            pool.step()
        for sid in sids:
            pool.harvest(sid)
        fams = {f.name: f for f in REGISTRY.collect()}
        for name in (
            "fishnet_mcts_visits_total",
            "fishnet_mcts_collisions_total",
            "fishnet_mcts_subtree_reuse_total",
            "fishnet_mcts_batch_fill_ratio",
            "fishnet_mcts_trees_active",
        ):
            assert name in fams, name
        assert sum(
            s.value for s in fams["fishnet_mcts_visits_total"].samples
        ) >= 75
        assert "mcts_collect" in RECORDER.stages_seen()
        pool.close()
    finally:
        telemetry.disable()

