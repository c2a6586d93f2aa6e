"""Tests for fishnet_tpu.analysis: each rule fires on its fixture at the
right file:line, suppressions behave, the CLI round-trips exit codes —
and the TREE IS CLEAN (the tier-1 gate that makes the checker binding:
any reintroduced R1-R9 violation fails CI here, not in review).
"""

from __future__ import annotations

import json
import re
import subprocess
import sys
from pathlib import Path

from fishnet_tpu.analysis.contracts import EscapeHatchRule, TelemetryContractRule
from fishnet_tpu.analysis.donation import DonationSafetyRule
from fishnet_tpu.analysis.engine import (
    Project,
    check_paths,
    iter_python_files,
    to_json,
    to_sarif,
)
from fishnet_tpu.analysis.locks import LockOrderRule, build_lock_graph
from fishnet_tpu.analysis.registry import KNOBS, Knob
from fishnet_tpu.analysis.rules import (
    ALL_RULES,
    AsyncBlockingRule,
    CrossThreadStateRule,
    DeprecatedJaxRule,
    JitHostSyncRule,
    SwallowedExceptionRule,
)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"
PACKAGE = REPO / "fishnet_tpu"


def _lines(findings, rule=None):
    return sorted(
        (f.rule, f.line) for f in findings if rule is None or f.rule == rule
    )


# -- R1 -------------------------------------------------------------------


def test_r1_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r1_async_blocking.py"], [AsyncBlockingRule()]
    )
    assert _lines(findings) == [
        ("R1", 13),  # time.sleep
        ("R1", 17),  # aliased sleep
        ("R1", 21),  # subprocess.run
        ("R1", 25),  # requests.get
        ("R1", 29),  # un-awaited .communicate()
    ]


def test_r1_exempts_executor_and_nested_sync_defs():
    findings = check_paths(
        [FIXTURES / "r1_async_blocking.py"], [AsyncBlockingRule()]
    )
    flagged = {f.line for f in findings}
    # Nothing in fine() / sync_caller() (lines >= 33) may fire.
    assert all(line < 33 for line in flagged)


# -- R2 -------------------------------------------------------------------


def test_r2_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r2_jit_host_sync.py"], [JitHostSyncRule()]
    )
    assert _lines(findings) == [
        ("R2", 14),  # np.asarray in transitively-reached leaf
        ("R2", 19),  # branch on array truthiness (If)
        ("R2", 19),  # bool() concretization (same line)
        ("R2", 26),  # .item() in the decorated root
        ("R2", 31),  # float() in a jax.jit(partial(...))-assigned root
        ("R2", 69),  # np.asarray in a lambda-reached kernel nested def
        ("R2", 76),  # np.asarray in a pl.when-decorated `def _():`
        ("R2", 80),  # ... and in the SECOND `def _():` (qualname dedup)
    ]


def test_r2_reports_the_jit_root_for_transitive_hits():
    findings = check_paths(
        [FIXTURES / "r2_jit_host_sync.py"], [JitHostSyncRule()]
    )
    by_line = {f.line: f for f in findings}
    assert "jitted_root" in by_line[14].message  # leaf blames its root


def test_r2_exempts_guards_statics_and_host_code():
    findings = check_paths(
        [FIXTURES / "r2_jit_host_sync.py"], [JitHostSyncRule()]
    )
    flagged = {f.line for f in findings}
    # guarded() (is_concrete region), never_traced(), static_ok() clean
    # (lines 33-58; the fused-PSQT kernel fixture follows after).
    assert not any(33 <= line <= 58 for line in flagged)


def test_r2_reaches_fused_psqt_kernel_paths():
    """The fused-PSQT pallas_call entry point's kernel regions are in
    R2's call graph: host syncs inside a nested def reached only through
    a lambda argument, inside a `@pl.when`-decorated `def _():`, and
    inside a SECOND same-named `def _():` (engine qualname dedup) are
    all flagged and blamed on the kernel root."""
    findings = check_paths(
        [FIXTURES / "r2_jit_host_sync.py"], [JitHostSyncRule()]
    )
    by_line = {f.line: f for f in findings}
    for line in (69, 76, 80):
        assert line in by_line, f"fused-PSQT violation at {line} not flagged"
        assert "_psqt_kernel" in by_line[line].message


# -- R3 -------------------------------------------------------------------


def test_r3_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r3_deprecated_jax.py"], [DeprecatedJaxRule()]
    )
    assert _lines(findings) == [
        ("R3", 5),  # import jax._src.xla_bridge
        ("R3", 6),  # from jax._src import core
        ("R3", 10),  # jax.core.Tracer
    ]
    tracer = [f for f in findings if f.line == 10][0]
    assert "is_concrete" in (tracer.suggestion or "")


# -- R4 -------------------------------------------------------------------


def test_r4_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r4_cross_thread.py"], [CrossThreadStateRule()]
    )
    assert _lines(findings) == [
        ("R4", 11),  # module global from thread + async
        ("R4", 32),  # self._stopping unguarded in driver thread
        ("R4", 91),  # LeakyPipeline._seq unguarded in pack worker
        ("R4", 128),  # LeakyShardRouter._rungs unguarded ladder step
        ("R4", 162),  # LeakyStripedCache._entries unguarded insert
    ]


def test_r4_lock_guarded_class_is_clean():
    findings = check_paths(
        [FIXTURES / "r4_cross_thread.py"], [CrossThreadStateRule()]
    )
    assert not any("CleanService" in f.message for f in findings)
    assert not any("_items" in f.message for f in findings)
    assert not any("_queue" in f.message for f in findings)


def test_r4_pack_decode_handoff_pattern():
    """The async-dispatch handoff (two worker threads + async
    submitters sharing lock-guarded state) is clean; the same shape
    with an unguarded worker-side bump is flagged."""
    findings = check_paths(
        [FIXTURES / "r4_cross_thread.py"], [CrossThreadStateRule()]
    )
    assert not any("_inflight" in f.message for f in findings)
    assert not any("_ready" in f.message for f in findings)
    assert any("_seq" in f.message for f in findings)


def test_r4_shard_router_pattern():
    """The placement-aware serving shape (shard router + per-shard
    pipelines): lock-guarded ladder steps and drain re-routes shared
    between driver threads and async submitters are clean; the same
    shape with an unguarded thread-side rung bump is flagged."""
    findings = check_paths(
        [FIXTURES / "r4_cross_thread.py"], [CrossThreadStateRule()]
    )
    assert not any("ShardRouterPattern" in f.message for f in findings)
    assert not any("_assign" in f.message for f in findings)
    assert any(
        "LeakyShardRouter" in f.message and "_rungs" in f.message
        for f in findings
    )


def test_r4_striped_cache_pattern():
    """The lock-striped eval-cache shape (search/eval_cache.EvalCache):
    driver-thread inserts and async probes sharing striped buckets are
    clean when every access holds the stripe lock; the same shape with
    an unguarded thread-side insert is flagged."""
    findings = check_paths(
        [FIXTURES / "r4_cross_thread.py"], [CrossThreadStateRule()]
    )
    assert not any("StripedCachePattern" in f.message for f in findings)
    assert not any("_stripes" in f.message for f in findings)
    assert any(
        "LeakyStripedCache" in f.message and "_entries" in f.message
        for f in findings
    )


# -- R5 -------------------------------------------------------------------


def test_r5_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r5_swallowed.py"], [SwallowedExceptionRule()]
    )
    assert _lines(findings) == [
        ("R5", 12),  # bare except, pass-only
        ("R5", 19),  # except Exception, log-only (logging is invisible
        #              to the metrics plane — not observable)
        ("R5", 26),  # broad via tuple element
    ]


def test_r5_exempts_observable_handlers():
    # raise / counter .inc() / `return err` / set_exception(err) /
    # narrow types: all handled, none may fire (lines >= 30).
    findings = check_paths(
        [FIXTURES / "r5_swallowed.py"], [SwallowedExceptionRule()]
    )
    assert all(f.line < 30 for f in findings)


def test_r5_scopes_to_serving_layers():
    # The rule polices fishnet_tpu.net/sched/search (and stand-alone
    # files); an identical handler in, say, fishnet_tpu.train is out of
    # scope — broad excepts there have their own idioms (checkpoint
    # recovery) and their own review.
    rule = SwallowedExceptionRule()
    assert rule._SCOPES == (
        "fishnet_tpu.net", "fishnet_tpu.sched", "fishnet_tpu.search"
    )
    findings = check_paths([PACKAGE / "train"], [rule])
    assert findings == []


# -- R6 -------------------------------------------------------------------


def _package_project() -> Project:
    proj = Project()
    for path in iter_python_files([PACKAGE]):
        proj.add_file(path)
    return proj


def test_r6_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r6_lock_order.py"], [LockOrderRule()]
    )
    assert _lines(findings) == [
        ("R6", 36),  # pack->decode half of the cycle (call site)
        ("R6", 55),  # scrape lock reached under _pack_lock
        ("R6", 60),  # non-reentrant re-acquire via _sum()
    ]
    by_line = {f.line: f for f in findings}
    assert "cycle" in by_line[36].message
    assert "scrape" in by_line[55].message
    assert "not reentrant" in by_line[60].message


def test_r6_real_tree_lock_graph_crosses_threads_and_modules():
    """The cross-module contract behind R6: the static call graph must
    actually follow the platform's thread handoffs, or a clean run
    proves nothing. Driver threads are seeded from Thread(target=...),
    and the pack worker's dispatch must cross the CoalesceBackend seam
    into az_plane.py (virtual dispatch, not just name matching)."""
    graph = build_lock_graph(_package_project())
    entries = {fn.qualname for fn in graph.entry_points}
    # The serving plane's resident threads, found statically:
    for expected in (
        "SearchService._drive",
        "_AsyncDispatchPipeline._pack_loop",
        "_AsyncDispatchPipeline._decode_loop",
        "AzMctsService._drive",
        "FleetAggregator._run",
    ):
        assert expected in entries, f"{expected} not seeded as an entry"
    by_qualname = {}
    for fn in graph.callees:
        by_qualname.setdefault(fn.qualname, fn)
    # SearchService._drive hands work to the coalescer...
    drive = by_qualname["SearchService._drive"]
    reached = {fn.qualname for fn in graph.reachable_from(drive)}
    assert "_DispatchCoalescer.submit" in reached
    # ...and the pack worker's flush crosses the CoalesceBackend seam
    # into the AZ plane's module (az_plane.py), not just service.py.
    pack = by_qualname["_AsyncDispatchPipeline._pack_loop"]
    pack_mods = {
        fn.module.name for fn in graph.reachable_from(pack)
    }
    assert "fishnet_tpu.search.az_plane" in pack_mods
    # The AZ plane's evaluate() rides the SAME coalescer object.
    az_eval = by_qualname["AzDispatchPlane.evaluate"]
    az_reached = {fn.qualname for fn in graph.reachable_from(az_eval)}
    assert "_DispatchCoalescer.submit" in az_reached


def test_r6_real_tree_canonical_order_holds():
    """The canonical lock-order table (doc/static-analysis.md) is not
    aspirational: the real graph has the documented edges, no cycles,
    and the scrape lock is identified."""
    graph = build_lock_graph(_package_project())
    assert graph.scrape_lock is not None
    assert graph.scrape_lock.endswith("_scrape_lock")
    edge_pairs = set(graph.edges)
    # The mesh serving chain: mesh_lock above the coalescer above the
    # router (doc/static-analysis.md "Canonical lock order").
    assert any(
        "mesh_lock" in outer and "_DispatchCoalescer._lock" in inner
        for outer, inner in edge_pairs
    )
    assert any(
        "_DispatchCoalescer._lock" in outer and "ShardRouter._lock" in inner
        for outer, inner in edge_pairs
    )
    # No edge may point BACK UP from the router (leaf lock).
    assert not any(
        "ShardRouter._lock" in outer for outer, _inner in edge_pairs
    )


# -- R7 -------------------------------------------------------------------


def test_r7_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r7_telemetry_contract.py"],
        [TelemetryContractRule(doc_path=FIXTURES / "r7_observability.md")],
    )
    assert _lines(findings) == [
        ("R7", 11),  # doc row fishnet_fixture_orphan_total: no emitter
        ("R7", 14),  # fishnet_fixture_depth emitted, not documented
        ("R7", 15),  # doc stage fixture_decode never recorded
        ("R7", 16),  # fishnet_fixture_errors_total label drift (tenant)
        ("R7", 22),  # span stage fixture_pack not documented
    ]
    doc_findings = [
        f for f in findings if f.path.endswith("r7_observability.md")
    ]
    assert {f.line for f in doc_findings} == {11, 15}


def test_r7_real_tree_contract_holds():
    """Every fishnet_* family and span stage emitted by the package has
    a doc row (and vice versa) — the drift this PR fixed stays fixed."""
    findings = check_paths([PACKAGE], [TelemetryContractRule()])
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


# -- R8 -------------------------------------------------------------------

_FIXTURE_KNOBS = (
    Knob("FISHNET_FIXTURE_DECLARED", "env", "unset", "doc/install.md"),
    Knob("--fixture-declared", "cli", "unset", "doc/install.md"),
)


def test_r8_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r8_escape_hatch.py"],
        [EscapeHatchRule(knobs=_FIXTURE_KNOBS)],
    )
    assert _lines(findings) == [
        ("R8", 11),  # os.environ.get("FISHNET_FIXTURE_UNDECLARED")
        ("R8", 14),  # ROGUE_ENV = "FISHNET_FIXTURE_ROGUE" name constant
        ("R8", 24),  # add_argument("--fixture-undeclared")
    ]


def test_r8_registry_pointers_are_live():
    """Registry hygiene beyond the rule run: every declared knob's
    documented_in/tested_by names a real file that mentions the knob."""
    for knob in KNOBS:
        probe = knob.name.lstrip("-")
        for pointer in (knob.documented_in, knob.tested_by):
            if pointer is None:
                continue
            target = REPO / pointer
            assert target.exists(), f"{knob.name}: {pointer} missing"
            assert probe in target.read_text(encoding="utf-8"), (
                f"{knob.name}: {pointer} never mentions it"
            )


def test_r8_real_tree_contract_holds():
    findings = check_paths([PACKAGE], [EscapeHatchRule()])
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


def test_r8_rows_a_deleted_root_script_kept_alive_read_as_dead():
    """The text probe over root scripts keeps a row alive only while a
    script that mentions the knob EXISTS: these six were kept by the
    text of a root script the tree no longer has, and by nothing the
    rule counts as a usage."""
    rule = EscapeHatchRule()
    gone = (
        Knob("FISHNET_METRICS_PORT", "env", "unset", "doc/install.md"),
        Knob("--control", "cli", "off", "doc/install.md"),
        Knob("--depth", "cli", "off", "doc/install.md"),
        Knob("--fleet-cache", "cli", "off", "doc/install.md"),
        Knob("--split", "cli", "off", "doc/install.md"),
        Knob("role=", "cli", "monolith", "doc/install.md"),
    )
    assert not {k.name for k in gone} & {k.name for k in KNOBS}
    rule._knobs += gone
    findings = check_paths([PACKAGE], [rule])
    dead = re.compile(r"knob `(.+)` has no usage left in the tree")
    assert sorted(dead.search(f.message).group(1) for f in findings) == (
        sorted(k.name for k in gone)
    ), [f.render() for f in findings]


def test_one_record_of_measurements():
    """PERF_LEDGER.jsonl (the driver's) and PERF.md are the record: no
    per-round result file at the root, and no second benchmark script
    imported from the package or its tests, so a second yardstick
    cannot grow back one import at a time. Read from the tree on disk,
    which is also what an unpacked archive has."""
    records = [
        p.name for p in REPO.iterdir()
        if re.fullmatch(r"[A-Z_]+_r[0-9]+\.json", p.name)
    ]
    assert records == []
    imports_bench = re.compile(r"^\s*(import bench\b|from bench\b)", re.M)
    offenders = [
        str(p.relative_to(REPO))
        for top in (REPO / "tests", PACKAGE)
        for p in sorted(top.rglob("*.py"))
        if imports_bench.search(p.read_text(encoding="utf-8"))
    ]
    assert offenders == []


# -- R9 -------------------------------------------------------------------


def test_r9_fires_on_known_lines():
    findings = check_paths(
        [FIXTURES / "r9_donation.py"], [DonationSafetyRule()]
    )
    assert _lines(findings) == [
        ("R9", 23),  # module-level wrapper: `state` read after donation
        ("R9", 33),  # partial(jax.jit) decorator: `buf` read after
        ("R9", 46),  # self._fj attr wrapper: `self._buf` read after
    ]


def test_r9_ping_pong_rebinds_are_clean():
    findings = check_paths(
        [FIXTURES / "r9_donation.py"], [DonationSafetyRule()]
    )
    flagged = {f.line for f in findings}
    # train_good / run_good (the rebind idiom) never fire.
    assert not any(26 <= line <= 28 for line in flagged)
    assert not any(48 <= line <= 50 for line in flagged)


# -- suppressions ---------------------------------------------------------


def test_suppressions():
    findings = check_paths([FIXTURES / "suppressions.py"], [AsyncBlockingRule()])
    assert _lines(findings) == [
        ("R1", 17),  # wrong-rule suppression does not apply
        ("SUP", 13),  # suppression without justification is itself flagged
    ]


def test_stale_suppression_detection(tmp_path):
    """A suppression that stops matching becomes an error — but only
    when the rules it names actually ran, and never for backtick-quoted
    doc examples of the syntax."""
    f = tmp_path / "stale.py"
    f.write_text(
        '"""Doc example: `# fishnet: ignore[R1] -- quoted, not live`."""\n'
        "import time\n"
        "\n"
        "\n"
        "def sync_ok():\n"
        "    time.sleep(1)  # fishnet: ignore[R1] -- not async, never fired\n"
    )
    stale = check_paths([f], [AsyncBlockingRule()])
    assert _lines(stale) == [("SUP", 6)]  # line 1's quoted example exempt
    # Under a run that does NOT include R1 the comment is not judged.
    assert check_paths([f], [DeprecatedJaxRule()]) == []


# -- the repo gate --------------------------------------------------------


def test_fishnet_tpu_tree_is_clean():
    """THE tier-1 invariant: the package tree passes its own checker.

    If this fails, either fix the flagged code or add a justified
    inline suppression (`# fishnet: ignore[Rn] -- why`) — see
    doc/static-analysis.md.
    """
    findings = check_paths([PACKAGE], ALL_RULES)
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


# -- CLI ------------------------------------------------------------------


def test_cli_exit_codes():
    clean = subprocess.run(
        [sys.executable, "-m", "fishnet_tpu.analysis", str(PACKAGE), "-q"],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = subprocess.run(
        [
            sys.executable,
            "-m",
            "fishnet_tpu.analysis",
            str(FIXTURES / "r1_async_blocking.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert dirty.returncode == 1
    assert "R1" in dirty.stdout
    rules = subprocess.run(
        [sys.executable, "-m", "fishnet_tpu.analysis", "--list-rules"],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert rules.returncode == 0
    for rid in ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9"):
        assert rid in rules.stdout


def test_cli_unknown_rule_exits_2_with_known_list():
    """`--rules` with an unknown id must fail usage (2), and the error
    must LIST the known rules — a bare "unknown rule" message sends the
    user off to read the source."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "fishnet_tpu.analysis",
            "--rules",
            "R1,R99",
            str(FIXTURES / "r1_async_blocking.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert proc.returncode == 2
    assert "R99" in proc.stderr
    for rid in ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9"):
        assert rid in proc.stderr, f"{rid} missing from the known-rule list"


def test_cli_json_and_sarif_outputs(tmp_path):
    json_out = tmp_path / "findings.json"
    sarif_out = tmp_path / "findings.sarif"
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "fishnet_tpu.analysis",
            str(FIXTURES / "r1_async_blocking.py"),
            "--json",
            str(json_out),
            "--sarif",
            str(sarif_out),
            "-q",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert proc.returncode == 1  # findings still drive the exit code
    payload = json.loads(json_out.read_text())
    assert [f["rule"] for f in payload] == ["R1"] * 5
    assert {"rule", "path", "line", "col", "message", "suggestion"} <= set(
        payload[0]
    )
    sarif = json.loads(sarif_out.read_text())
    assert sarif["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in sarif["$schema"]
    run = sarif["runs"][0]
    assert len(run["results"]) == 5
    ids = {d["id"] for d in run["tool"]["driver"]["rules"]}
    assert {"R1", "R9"} <= ids
    loc = run["results"][0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 13


def test_findings_sorted_deterministically():
    """check_paths output is sorted by (path, line, col, rule) so CI
    diffs are stable run to run, and to_json preserves that order."""
    findings = check_paths(
        [FIXTURES / "r6_lock_order.py", FIXTURES / "r1_async_blocking.py"],
        [LockOrderRule(), AsyncBlockingRule()],
    )
    keys = [(f.path, f.line, f.col, f.rule) for f in findings]
    assert keys == sorted(keys)
    assert {f.rule for f in findings} == {"R1", "R6"}
    assert [d["line"] for d in to_json(findings)] == [f.line for f in findings]


def test_sarif_rule_descriptors_cover_sup_and_ast():
    from fishnet_tpu.analysis.engine import Finding

    findings = [
        Finding(rule="SUP", path="x.py", line=1, col=0, message="stale"),
        Finding(rule="AST", path="y.py", line=1, col=0, message="bad parse"),
    ]
    sarif = to_sarif(findings, ALL_RULES)
    ids = {d["id"] for d in sarif["runs"][0]["tool"]["driver"]["rules"]}
    assert {"SUP", "AST"} <= ids


def test_r4_plain_call_context_manager_is_skipped():
    """`with open(...)` (a Name-func call) inside a thread-bearing
    class must not crash _lock_spans, and the guarded JournalReader
    stays clean."""
    findings = check_paths(
        [FIXTURES / "r4_cross_thread.py"], [CrossThreadStateRule()]
    )
    assert not any("JournalReader" in f.message for f in findings)
    assert not any("_offsets" in f.message for f in findings)
