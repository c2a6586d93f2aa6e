"""Multi-chip serving: the shard router, per-shard dispatch parity on
every rung, and the SearchService-level mesh smoke. Sharded analyses
must be bit-identical to the single-device service's."""

import asyncio
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.nnue import spec
from fishnet_tpu.nnue.jax_eval import params_from_weights
from fishnet_tpu.nnue.weights import NnueWeights


def test_graft_entry_dryrun():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    import jax

    out = np.asarray(jax.jit(fn)(*args))
    assert out.shape == (64,)
    ge.dryrun_multichip(8)


# ---------------------------------------------------------------------------
# Placement-aware serving mesh (doc/sharding.md): what --mesh asks the
# router for, shard router units, per-shard segmented-dispatch parity,
# the per-shard programs' zero collectives, and the SearchService-level
# mesh smoke (parity, escape hatch, per-shard ladder isolation, drain
# re-routing).
# ---------------------------------------------------------------------------


def _mesh_opt(mesh_spec):
    from fishnet_tpu.configure import Opt, parse_mesh

    return Opt(
        mesh=parse_mesh(mesh_spec), microbatch=320, pipeline=2,
        search_threads=4,
    )


@pytest.mark.parametrize(
    "mesh_spec, shards", [("4x2", 8), ("8x1", 8), ("2x1", 2), ("1x1", None)]
)
def test_mesh_spec_asks_the_router_for_data_times_model_shards(
    mesh_spec, shards, capsys
):
    """An explicit --mesh DxM is D * M shards of the placement-aware
    mesh whatever M is; one device is the single-device service."""
    from fishnet_tpu import __main__ as cli
    from fishnet_tpu.utils.logger import Logger

    assert cli.resolve_mesh_devices(_mesh_opt(mesh_spec), Logger()) == shards
    said = capsys.readouterr().out
    assert ("Placement-aware serving mesh" in said) == (shards is not None)
    if shards:
        assert f"over {shards} devices" in said


def test_mesh_spec_larger_than_the_devices_is_a_config_error():
    """--mesh 16x1 on eight devices fails before any service is built."""
    from fishnet_tpu import __main__ as cli
    from fishnet_tpu.configure import ConfigError
    from fishnet_tpu.utils.logger import Logger

    with pytest.raises(ConfigError, match="needs 16 devices, found 8"):
        cli.resolve_mesh_devices(_mesh_opt("16x1"), Logger())
    with pytest.raises(ConfigError, match="needs 16 devices"):
        cli.validate_mesh(_mesh_opt("16x1"))


def test_mesh_4x2_builds_a_service_of_eight_shards(monkeypatch, capsys):
    """`Mesh = 4x2` starts on the path the chip has run: the CLI's
    service has one shard a device, each with its own params replica."""
    from fishnet_tpu import __main__ as cli
    from fishnet_tpu.search.service import SearchService
    from fishnet_tpu.utils.logger import Logger

    monkeypatch.delenv("FISHNET_NO_MESH", raising=False)
    monkeypatch.delenv("FISHNET_RPC", raising=False)
    svc = cli.build_search_service(_mesh_opt("4x2"), Logger())
    try:
        assert type(svc) is SearchService
        report = svc.shard_report()
        assert report["n_shards"] == 8 and all(report["alive"])
        assert [len(g) for g in report["groups"]] == [1] * 8
        placed = {
            next(iter(p["ft_w"].devices())) for p in svc._shard_params
        }
        assert len(placed) == 8
        assert "over 8 devices" in capsys.readouterr().out
    finally:
        svc.close()


def test_serving_devices_resolution_and_escape_hatch(monkeypatch):
    """serving_devices resolves None/"auto"/int requests and the
    FISHNET_NO_MESH=1 escape hatch clamps ANY request to one device."""
    import jax

    from fishnet_tpu.parallel.mesh import serving_devices

    monkeypatch.delenv("FISHNET_NO_MESH", raising=False)
    all_devs = list(jax.devices())
    assert serving_devices(None) == all_devs
    assert serving_devices("auto") == all_devs
    assert serving_devices(3) == all_devs[:3]
    assert serving_devices(all_devs[1:3]) == all_devs[1:3]
    monkeypatch.setenv("FISHNET_NO_MESH", "1")
    assert serving_devices("auto") == all_devs[:1]
    assert serving_devices(4) == all_devs[:1]


def test_shard_router_determinism_and_drain():
    """Group -> shard assignment is a pure function of (n_groups,
    n_shards); drain moves the dead shard's groups round-robin over the
    survivors, deterministically, and refuses to kill the last shard."""
    import pytest

    from fishnet_tpu.parallel.mesh import ShardRouter

    r1, r2 = ShardRouter(8, 4), ShardRouter(8, 4)
    assert [r1.shard_of(g) for g in range(8)] == [g % 4 for g in range(8)]
    assert [r1.shard_of(g) for g in range(8)] == [
        r2.shard_of(g) for g in range(8)
    ]
    assert r1.groups_of(1) == [1, 5]
    assert r1.group_count(2) == 2
    assert r1.alive_shards() == [0, 1, 2, 3]

    moved = r1.drain(1)
    assert moved == {1: 0, 5: 2}  # round-robin over survivors [0, 2, 3]
    assert r1.alive_shards() == [0, 2, 3]
    assert r1.shard_of(1) == 0 and r1.shard_of(5) == 2
    assert r1.groups_of(1) == []
    assert r2.drain(1) == moved  # same decision on an identical twin

    r1.drain(0)
    r1.drain(2)
    assert r1.alive_shards() == [3]
    assert all(r1.shard_of(g) == 3 for g in range(8))
    with pytest.raises(RuntimeError, match="no alive shard"):
        r1.drain(3)


def _shard_split_segments(rung, monkeypatch):
    """Fixture segments for the per-shard parity tests, reusing the
    coalescer suite's wire builders. The interpret rung shrinks the
    pallas chunk to 8 and uses plans whose deltas sit right after a
    chunk boundary with their anchor in the PREVIOUS chunk — and the
    4-segment arrangement puts a shard boundary (segment 2's start,
    global entry 12) in the middle of chunk [8, 16): the carry-in path
    is exercised across both chunk and shard boundaries."""
    from test_coalesce import _INTERPRET_PLANS, _PLANS, _make_segment

    rng = np.random.default_rng(53)
    size, tab_rows = 6, 4
    if rung == "fused-interpret":
        from fishnet_tpu.ops import ft_gather

        monkeypatch.setattr(ft_gather, "_CHUNK", 8)
        kw = {"interpret": True}
        plans = _INTERPRET_PLANS + _INTERPRET_PLANS
    else:
        kw = {"use_pallas": False}
        plans = _PLANS + _INTERPRET_PLANS[:1]
    segs = [_make_segment(p, size, tab_rows, rng) for p in plans]
    for s in segs:
        s["mat"] = (
            rng.integers(-400, 400, (size,)).astype(np.int32)
            if rung == "host-material" else None
        )
    return segs, size, kw


def _cat_segments(segs, size):
    """Concatenate a shard's segments into one segmented-dispatch wire
    (exactly SearchService._dispatch_segmented's stacking)."""
    tier = 4 * size + 4
    mats = None
    if segs[0]["mat"] is not None:
        mats = jnp.asarray(np.concatenate([s["mat"] for s in segs]))
    return (
        jnp.asarray(np.concatenate([s["packed"][:tier] for s in segs])),
        jnp.asarray(np.concatenate([s["buckets"] for s in segs])),
        jnp.asarray(np.concatenate([s["parent"] for s in segs])),
        mats,
        jnp.asarray(np.stack([s["tab"] for s in segs])),
        jnp.asarray(np.array([s["rows"] for s in segs], np.int32)),
        jnp.asarray(np.stack([s["ptab"] for s in segs])),
    )


@pytest.mark.parametrize("rung", ["xla", "fused-interpret", "host-material"])
def test_per_shard_dispatch_matches_fused_and_single(rung, monkeypatch):
    """The placement-aware serving invariant on every ladder rung: K
    segments dispatched as TWO per-shard segmented programs (the mesh
    coalescer's _flush-per-shard) return bit-for-bit the values and
    updated tables of the whole-mesh fused dispatch AND of K per-group
    single dispatches — sharding never changes a single bit."""
    from fishnet_tpu.nnue.jax_eval import (
        evaluate_packed_anchored,
        evaluate_packed_anchored_segmented,
    )

    params = params_from_weights(NnueWeights.random(seed=29))
    segs, size, kw = _shard_split_segments(rung, monkeypatch)
    tier = 4 * size + 4

    # Per-group references (XLA executor: every rung is bit-identical
    # per group, pinned at the op level by test_ops).
    refs = []
    for s in segs:
        v, nt, npt = evaluate_packed_anchored(
            params, jnp.asarray(s["packed"]), jnp.asarray(s["buckets"]),
            jnp.asarray(s["parent"]),
            None if s["mat"] is None else jnp.asarray(s["mat"]),
            jnp.asarray(s["tab"]),
            jnp.asarray(np.array([s["rows"]], np.int32)),
            jnp.asarray(s["ptab"]), use_pallas=False,
        )
        refs.append((np.asarray(v), np.asarray(nt), np.asarray(npt)))

    # One fused whole-mesh dispatch vs two per-shard dispatches.
    fused = evaluate_packed_anchored_segmented(
        params, *_cat_segments(segs, size), **kw
    )
    fused = tuple(map(np.asarray, fused))
    shard_out = []
    for shard_segs in (segs[:2], segs[2:]):
        v, nt, npt = evaluate_packed_anchored_segmented(
            params, *_cat_segments(shard_segs, size), **kw
        )
        shard_out.append((np.asarray(v), np.asarray(nt), np.asarray(npt)))

    for k, s in enumerate(segs):
        ref_v, ref_t, ref_pt = refs[k]
        sh, loc = divmod(k, 2)
        got_v, got_t, got_pt = shard_out[sh]
        assert np.array_equal(
            got_v[loc * size : loc * size + s["n"]], ref_v[: s["n"]]
        ), (rung, k, "per-shard values")
        assert np.array_equal(got_t[loc], ref_t), (rung, k, "anchor tab")
        assert np.array_equal(got_pt[loc], ref_pt), (rung, k, "psqt tab")
        assert np.array_equal(
            fused[0][k * size : k * size + s["n"]], ref_v[: s["n"]]
        ), (rung, k, "fused values")
        assert np.array_equal(fused[1][k], ref_t), (rung, k)
        assert np.array_equal(fused[2][k], ref_pt), (rung, k)


def _lower(fn, *args):
    """Lower a rung's executor: a jitted function, or one under a
    functools.partial that pins (use_pallas, interpret)."""
    if isinstance(fn, functools.partial):
        return fn.func.lower(*fn.args, *args, **fn.keywords)
    return fn.lower(*args)


@pytest.mark.parametrize("rung", ["xla", "host-material"])
def test_per_shard_programs_hold_no_collectives_and_name_one_device(rung):
    """What the router dispatches never crosses devices: the solo and
    the fused program of a shard other than the first, compiled from
    that shard's replica and its group's tables, hold no collective and
    run on that shard's device alone."""
    from fishnet_tpu.search.service import SearchService

    svc = SearchService(
        weights=NnueWeights.random(seed=41), pool_slots=8,
        batch_capacity=256, tt_bytes=8 << 20, pipeline_depth=4,
        driver_threads=1, mesh_devices="auto", psqt_path=rung,
    )
    try:
        assert svc._router is not None and svc.psqt_path == rung
        shard = svc._n_shards - 1
        group = svc._router.groups_of(shard)[0]
        params, eval_fn, seg_fn, ship_material, dev = svc._eval_state(group)
        assert dev == svc._shard_devices[shard] != svc._shard_devices[0]
        assert ship_material == (rung == "host-material")
        size = svc._eval_sizes[0]
        tier = svc._row_tiers(size)[0]

        def wire(k):
            return (
                np.full((k * tier, 2, 8), spec.NUM_FEATURES, np.uint16),
                np.zeros((k * size,), np.int32),
                np.full((k * size,), -1, np.int32),
                np.zeros((k * size,), np.int32) if ship_material else None,
            )

        tab, ptab = svc._anchor_tabs[group], svc._psqt_tabs[group]
        programs = {
            "solo": _lower(
                eval_fn, params, *wire(1), tab, np.zeros((1,), np.int32),
                ptab,
            ),
            "fused": _lower(
                seg_fn, params, *wire(2), jnp.stack([tab, tab]),
                np.zeros((2,), np.int32), jnp.stack([ptab, ptab]),
            ),
        }
        for name, lowered in programs.items():
            compiled = lowered.compile()
            hlo = compiled.as_text()
            for collective in (
                "all-gather", "all-reduce", "collective-permute",
                "all-to-all",
            ):
                assert collective not in hlo, (rung, name, collective)
            assert compiled.runtime_executable().local_devices() == [dev], (
                rung, name,
            )
    finally:
        svc.close()


def _mesh_smoke(weights, mesh_devices):
    """One gated deterministic smoke run (the coalescer suite's
    discipline) on an optionally mesh-backed service, audited by the
    exactly-once ledger (every search acquired once, submitted once —
    clean even while shards degrade). Returns the analyses, the shard
    report, and whether the mesh path was active."""
    from test_coalesce import _SMOKE_FENS, _GatedService

    from fishnet_tpu.resilience import accounting
    from fishnet_tpu.search import eval_cache

    # Each smoke run cold-starts the process eval cache: consecutive
    # runs serve the SAME positions, and a warm cache would turn the
    # later services' dispatches into whole-batch skips — parity would
    # still hold (that's the cache's contract) but the traffic-spread
    # assertions below would see zero per-shard dispatches.
    eval_cache.reset_cache()
    svc = _GatedService(
        weights=weights, pool_slots=8, batch_capacity=256,
        tt_bytes=8 << 20, backend="jax", pipeline_depth=4,
        driver_threads=1, mesh_devices=mesh_devices,
    )
    ledger = accounting.install()
    try:
        svc.set_prefetch(0, adaptive=False)

        async def one(i, fen):
            ledger.record_acquired(f"mesh-{i}")
            r = await svc.search(fen, [], nodes=280)
            ledger.record_submitted(f"mesh-{i}")
            return r

        async def go():
            tasks = [
                asyncio.ensure_future(one(i, fen))
                for i, fen in enumerate(_SMOKE_FENS)
            ]
            await asyncio.sleep(0.3)
            svc.gate.set()
            return await asyncio.gather(*tasks)

        results = asyncio.run(go())
        ledger.assert_clean()
        analyses = [
            (
                r.best_move, r.depth, r.nodes,
                tuple(
                    (l.multipv, l.depth, l.is_mate, l.value, tuple(l.pv))
                    for l in r.lines
                ),
            )
            for r in results
        ]
        return analyses, svc.shard_report(), svc._router is not None
    finally:
        accounting.clear()
        svc.gate.set()
        svc.close()


def test_mesh_serving_parity_and_escape_hatch(monkeypatch):
    """Acceptance: the placement-aware mesh serves byte-identical
    analyses to the single-device path, spreads dispatches over more
    than one shard, and FISHNET_NO_MESH=1 restores the single-device
    service (router-less) byte-for-byte even when a mesh is
    requested."""
    monkeypatch.delenv("FISHNET_NO_MESH", raising=False)
    weights = NnueWeights.random(seed=7)

    single, rep1, meshed1 = _mesh_smoke(weights, None)
    assert not meshed1 and rep1["n_shards"] == 1

    sharded, rep2, meshed2 = _mesh_smoke(weights, "auto")
    assert meshed2 and rep2["n_shards"] > 1
    assert sum(1 for d in rep2["dispatches"] if d > 0) > 1, (
        f"traffic never spread over the mesh: {rep2['dispatches']}"
    )
    assert all(rep2["alive"]), rep2
    assert sharded == single, "mesh serving changed analysis output"

    monkeypatch.setenv("FISHNET_NO_MESH", "1")
    escaped, rep3, meshed3 = _mesh_smoke(weights, "auto")
    assert not meshed3 and rep3["n_shards"] == 1
    assert escaped == single, "FISHNET_NO_MESH=1 is not byte-for-byte"


def test_mesh_per_shard_ladder_isolation():
    """A device fault on ONE shard moves only that shard down its
    degradation ladder: siblings stay on the configured rung, every
    search completes, and the analyses match the un-faulted mesh run
    bit-for-bit (all rungs are bit-identical)."""
    from fishnet_tpu.resilience import faults

    weights = NnueWeights.random(seed=13)
    baseline, rep0, _ = _mesh_smoke(weights, "auto")
    rung0 = set(rep0["rungs"])
    assert len(rung0) == 1  # every shard starts on the configured rung

    faults.install("service.device_step:nth=1:error")
    try:
        faulted, rep1, _ = _mesh_smoke(weights, "auto")
    finally:
        faults.clear()

    degraded = [
        s for s in range(rep1["n_shards"])
        if rep1["rung_index"][s] != rep0["rung_index"][s]
    ]
    assert len(degraded) == 1, (
        f"ladder isolation broken: {rep0['rungs']} -> {rep1['rungs']}"
    )
    assert all(rep1["alive"]), "a single fault must degrade, not drain"
    assert rep1["rungs"][degraded[0]] != rep0["rungs"][degraded[0]]
    assert faulted == baseline, "per-shard degradation changed output"


def test_mesh_drain_reroutes_groups_to_siblings():
    """Walking one shard off the end of its ladder drains it: its
    groups re-route to surviving shards (tables migrate lazily at next
    dispatch), the report shows the shard dead, and the service keeps
    serving every search."""
    from test_coalesce import _SMOKE_FENS, _GatedService

    from fishnet_tpu.search.service import _MESH_RUNGS

    weights = NnueWeights.random(seed=17)
    svc = _GatedService(
        weights=weights, pool_slots=8, batch_capacity=256,
        tt_bytes=8 << 20, backend="jax", pipeline_depth=4,
        driver_threads=1, mesh_devices="auto",
    )
    try:
        svc.set_prefetch(0, adaptive=False)
        assert svc._router is not None and svc._n_shards > 1
        victim = 1
        victim_groups = svc._router.groups_of(victim)
        assert victim_groups
        err = RuntimeError("injected shard fault")
        # Ride the ladder to the bottom, then once more to drain.
        steps = len(_MESH_RUNGS) - svc._shard_rungs[victim]
        for _ in range(steps):
            svc._degrade_shard_for(victim_groups[0], err)
        rep = svc.shard_report()
        assert rep["alive"][victim] is False
        assert rep["rungs"][victim] == "drained"
        assert rep["groups"][victim] == []
        new_homes = {g: svc._router.shard_of(g) for g in victim_groups}
        assert all(s != victim for s in new_homes.values()), new_homes

        async def go():
            tasks = [
                asyncio.ensure_future(svc.search(fen, [], nodes=280))
                for fen in _SMOKE_FENS
            ]
            await asyncio.sleep(0.3)
            svc.gate.set()
            return await asyncio.gather(*tasks)

        results = asyncio.run(go())
        assert all(r.best_move and r.depth >= 1 for r in results)
        rep = svc.shard_report()
        # The pre-traffic drain means the dead shard never serves.
        assert rep["dispatches"][victim] == 0, rep["dispatches"]
    finally:
        svc.gate.set()
        svc.close()
