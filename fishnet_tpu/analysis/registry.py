"""The escape-hatch contract: every knob the platform reads, declared.

The codebase has grown ~30 ``FISHNET_*`` environment switches plus the
ini/CLI surface in ``configure.py``, and they drift: a kill switch gets
added under deadline, never lands in a doc, and six months later nobody
remembers whether ``FISHNET_NO_DEDUP`` disables byte-dedup, position
dedup, or both. R8 (:class:`~fishnet_tpu.analysis.contracts
.EscapeHatchRule`) closes the loop both ways against THIS file:

* an env read / CLI option / ini key in code that is not declared here
  is a finding at the usage site (add a row — and while you're at it, a
  doc line);
* a row declared here with no usage left in the tree is a finding here
  (delete the row — the knob is dead);
* ``documented_in`` / ``tested_by`` must name real files that actually
  mention the knob, so the pointers can't rot silently.

This module is DATA for the analysis package itself (the one deliberate
exception to "the analyzer never imports analyzed code" — it imports
its own contract, nothing from the runtime). Keep it dependency-free.

Conventions: ``documented_in`` is required — every knob a user can flip
deserves at least one sentence somewhere under ``doc/`` (or README).
``tested_by`` is ``None`` only when no test exercises the knob yet;
that's visible here on purpose, as a checklist, not hidden.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class Knob:
    name: str  # "FISHNET_X" | "--option" | "IniKey"
    kind: str  # "env" | "cli" | "ini"
    default: str  # human-readable default ("unset", "0", "auto", ...)
    documented_in: str  # repo-relative file that mentions the knob
    tested_by: Optional[str] = None  # repo-relative test file, if any


KNOBS: Tuple[Knob, ...] = (
    # -- environment switches (kill switches & tuning) ---------------------
    Knob("FISHNET_AZ_COALESCE_WIDTH", "env", "unset (service width policy)",
         "doc/search.md"),
    Knob("FISHNET_AZ_EVAL_CACHE_CAPACITY", "env", "unset (NNUE cache size)",
         "doc/search.md"),
    Knob("FISHNET_BREAKER_COOLDOWN", "env", "60 (seconds)",
         "doc/resilience.md"),
    Knob("FISHNET_BREAKER_THRESHOLD", "env", "5 (consecutive failures)",
         "doc/resilience.md"),
    Knob("FISHNET_BOUNDS_CACHE_CAPACITY", "env", "65536 bound records",
         "doc/eval-cache.md"),
    Knob("FISHNET_CACHE_PREFETCH", "env", "unset (prefetch enabled)",
         "doc/eval-cache.md"),
    Knob("FISHNET_COALESCE_WIDTH", "env", "unset (adaptive width)",
         "doc/wire-format.md", "tests/test_coalesce.py"),
    Knob("FISHNET_EVAL_CACHE_CAPACITY", "env", "1048576 entries",
         "doc/eval-cache.md", "tests/test_eval_cache.py"),
    Knob("FISHNET_EVAL_CACHE_SNAPSHOT", "env", "unset (no snapshot file)",
         "doc/eval-cache.md"),
    Knob("FISHNET_FAULT_PLAN", "env", "unset (no fault injection)",
         "doc/resilience.md", "tests/test_configure.py"),
    Knob("FISHNET_HOST_LINGER_MS", "env", "2 (milliseconds)",
         "doc/disaggregation.md", "tests/test_bounds_plane.py"),
    Knob("FISHNET_HOST_MATERIAL", "env", "unset (fused-PSQT wire path)",
         "doc/wire-format.md"),
    Knob("FISHNET_MOCK_ENGINE_DELAY", "env", "0 (seconds; test hook)",
         "doc/install.md"),
    Knob("FISHNET_NO_ASYNC", "env", "unset (async pipeline on)",
         "doc/observability.md", "tests/test_async_dispatch.py"),
    Knob("FISHNET_NO_BOUNDS", "env", "unset (bounds tier on)",
         "doc/eval-cache.md", "tests/test_bounds_plane.py"),
    Knob("FISHNET_NO_COALESCE", "env", "unset (coalescing on)",
         "doc/wire-format.md", "tests/test_coalesce.py"),
    Knob("FISHNET_NO_CONTROL", "env", "unset (control plane may actuate)",
         "doc/control-plane.md", "tests/test_control.py"),
    Knob("FISHNET_NO_DEDUP", "env", "unset (fused dedup on)",
         "doc/wire-format.md", "tests/test_eval_cache.py"),
    Knob("FISHNET_NO_EVAL_CACHE", "env", "unset (eval cache on)",
         "doc/eval-cache.md", "tests/test_eval_cache.py"),
    Knob("FISHNET_NO_EXPANSION_MEMO", "env", "unset (MCTS memo on)",
         "doc/search.md"),
    Knob("FISHNET_NO_MESH", "env", "unset (mesh sharding on)",
         "doc/sharding.md", "tests/test_parallel.py"),
    Knob("FISHNET_NO_MULTITENANT", "env", "unset (multi-tenant on)",
         "doc/resilience.md", "tests/test_overload.py"),
    Knob("FISHNET_NO_SHARED_AZ_PLANE", "env", "unset (shared plane on)",
         "doc/search.md", "tests/test_mcts_plane.py"),
    Knob("FISHNET_NO_SPECULATION", "env", "unset (speculative pads on)",
         "doc/search.md", "tests/test_bounds_plane.py"),
    Knob("FISHNET_NO_SUBTREE_REUSE", "env", "unset (subtree reuse on)",
         "doc/search.md"),
    Knob("FISHNET_POSITION_TIER", "env", "unset (fleet tier off)",
         "doc/eval-cache.md", "tests/test_position_tier.py"),
    Knob("FISHNET_POSITION_TIER_PATH", "env",
         "fishnet-postier-<uid>.seg in the system tempdir",
         "doc/eval-cache.md", "tests/test_position_tier.py"),
    Knob("FISHNET_POSITION_TIER_CAPACITY", "env", "65536 NNUE slots",
         "doc/eval-cache.md", "tests/test_position_tier.py"),
    Knob("FISHNET_POSITION_TIER_AZ_CAPACITY", "env", "256 AZ slots",
         "doc/eval-cache.md", "tests/test_position_tier.py"),
    Knob("FISHNET_POSITION_TIER_BOUNDS_CAPACITY", "env", "16384 bound slots",
         "doc/eval-cache.md", "tests/test_bounds_plane.py"),
    Knob("FISHNET_PROFILE", "env", "unset (profiler off)",
         "doc/observability.md", "tests/test_profiler.py"),
    Knob("FISHNET_PROFILE_HZ", "env", "29 (samples/second)",
         "doc/observability.md"),
    Knob("FISHNET_RPC", "env", "unset (monolith)",
         "doc/disaggregation.md", "tests/test_rpc.py"),
    Knob("FISHNET_RPC_DIR", "env",
         "fishnet-rpc-<uid> in the system tempdir",
         "doc/disaggregation.md", "tests/test_rpc.py"),
    Knob("FISHNET_RPC_RING_SLOTS", "env", "8 slots per ring",
         "doc/disaggregation.md", "tests/test_rpc.py"),
    Knob("FISHNET_RPC_SLOT_BYTES", "env", "4 MiB per slot",
         "doc/disaggregation.md", "tests/test_rpc.py"),
    Knob("FISHNET_RPC_TIMEOUT", "env", "120 (seconds)",
         "doc/disaggregation.md", "tests/test_rpc.py"),
    Knob("FISHNET_SHARD_PLACEMENT", "env", "auto (round-robin groups)",
         "doc/sharding.md"),
    Knob("FISHNET_SPANS_DIR", "env", "unset (system tempdir)",
         "doc/observability.md", "tests/test_tracing.py"),
    Knob("FISHNET_SPANS_FILE", "env", "unset (per-pid file in spans dir)",
         "doc/observability.md", "tests/test_tracing.py"),
    Knob("FISHNET_SPECULATION_BUDGET", "env", "8 pad rows per dispatch",
         "doc/search.md", "tests/test_bounds_plane.py"),
    Knob("FISHNET_TPU_CORE_LIB", "env", "bundled libfishnet_core",
         "doc/install.md"),
    Knob("FISHNET_TPU_UPDATE_ATTEMPTED", "env", "unset (recursion guard)",
         "doc/install.md"),
    Knob("FISHNET_TPU_UPDATE_PUBKEY", "env", "release signing key",
         "doc/install.md", "tests/test_update_channel.py"),
    Knob("FISHNET_TPU_UPDATE_URL", "env", "release channel URL",
         "doc/install.md"),
    # -- CLI options (fishnet_tpu/configure.py, the product argparser) -----
    Knob("--auto-update", "cli", "off", "README.md"),
    Knob("--az-net-file", "cli", "unset (random weights)", "doc/install.md",
         "tests/test_az_trainer.py"),
    Knob("--batch-deadline", "cli", "unset (no deadline flushes)",
         "doc/resilience.md", "tests/test_configure.py"),
    Knob("--conf", "cli", "fishnet.ini next to the module", "README.md"),
    Knob("--cores", "cli", "auto (n-1)", "README.md",
         "tests/test_configure.py"),
    Knob("--drain-deadline", "cli", "10s", "doc/resilience.md",
         "tests/test_cluster.py"),
    Knob("--endpoint", "cli", "https://lichess.org/fishnet",
         "doc/install.md", "tests/test_configure.py"),
    Knob("--engine", "cli", "auto", "README.md", "tests/test_configure.py"),
    Knob("--engine-exe", "cli", "bundled binary", "doc/install.md"),
    Knob("--fault-plan", "cli", "unset", "doc/resilience.md",
         "tests/test_configure.py"),
    Knob("--key", "cli", "unset (dialog asks)", "README.md",
         "tests/test_configure.py"),
    Knob("--key-file", "cli", "unset", "doc/install.md",
         "tests/test_configure.py"),
    Knob("--lane-depth-limit", "cli", "unset (no admission control)",
         "doc/install.md"),
    Knob("--max-backoff", "cli", "120s", "doc/install.md",
         "tests/test_cluster.py"),
    Knob("--mesh", "cli", "unset (single device)", "doc/sharding.md",
         "tests/test_configure.py"),
    Knob("--metrics-port", "cli", "unset (exporter off)",
         "doc/observability.md", "tests/test_cluster.py"),
    Knob("--metrics-port-file", "cli", "unset", "doc/observability.md"),
    Knob("--microbatch", "cli", "auto", "README.md",
         "tests/test_configure.py"),
    Knob("--nnue-file", "cli", "bundled network", "README.md"),
    Knob("--no-conf", "cli", "off", "doc/install.md",
         "tests/test_configure.py"),
    Knob("--no-stats-file", "cli", "off", "doc/install.md",
         "tests/test_configure.py"),
    Knob("--pipeline", "cli", "2 (double buffer)", "doc/install.md",
         "tests/test_async_dispatch.py"),
    Knob("--search-concurrency", "cli", "auto", "doc/install.md"),
    Knob("--search-threads", "cli", "1", "doc/install.md"),
    Knob("--spans-dir", "cli", "unset (system tempdir)",
         "doc/observability.md"),
    Knob("--spans-journal", "cli", "unset (ring dumps only)",
         "doc/observability.md"),
    Knob("--stats-file", "cli", "platform data dir", "doc/install.md",
         "tests/test_configure.py"),
    Knob("--system-backlog", "cli", "0s", "doc/install.md"),
    Knob("--tenants", "cli", "unset (single tenant)", "doc/resilience.md",
         "tests/test_overload.py"),
    Knob("--user-backlog", "cli", "0s", "doc/install.md",
         "tests/test_configure.py"),
    Knob("--version", "cli", "-", "doc/install.md",
         "tests/test_configure.py"),
    Knob("--verbose", "cli", "off", "doc/install.md",
         "tests/test_configure.py"),
    # -- fishnet.ini keys (mirror of _INI_FIELDS in configure.py) ----------
    Knob("Endpoint", "ini", "https://lichess.org/fishnet",
         "doc/install.md", "tests/test_configure.py"),
    Knob("Key", "ini", "unset", "doc/install.md",
         "tests/test_configure.py"),
    Knob("Cores", "ini", "auto (n-1)", "doc/install.md",
         "tests/test_configure.py"),
    Knob("UserBacklog", "ini", "0s", "doc/install.md",
         "tests/test_configure.py"),
    Knob("SystemBacklog", "ini", "0s", "doc/install.md",
         "tests/test_configure.py"),
    Knob("MaxBackoff", "ini", "120s", "doc/install.md"),
    Knob("Engine", "ini", "auto", "doc/install.md"),
    Knob("EngineExe", "ini", "bundled binary", "doc/install.md"),
    Knob("NnueFile", "ini", "bundled network", "doc/install.md"),
    Knob("AzNetFile", "ini", "unset", "doc/install.md"),
    Knob("Mesh", "ini", "unset (single device)", "doc/install.md",
         "tests/test_eval_cache.py"),
    Knob("SearchThreads", "ini", "1", "doc/install.md"),
    Knob("SearchConcurrency", "ini", "auto", "doc/install.md"),
    Knob("MetricsPort", "ini", "unset (exporter off)",
         "doc/install.md"),
    Knob("MetricsPortFile", "ini", "unset", "doc/install.md"),
    Knob("SpansDir", "ini", "unset (system tempdir)", "doc/install.md"),
    Knob("SpansJournal", "ini", "unset", "doc/install.md"),
    Knob("FaultPlan", "ini", "unset", "doc/install.md"),
    Knob("BatchDeadline", "ini", "unset", "doc/install.md"),
    Knob("Tenants", "ini", "unset (single tenant)", "doc/install.md"),
    Knob("LaneDepthLimit", "ini", "unset", "doc/install.md"),
    Knob("DrainDeadline", "ini", "10s", "doc/install.md"),
)
