# Repo-level developer entry points. The native core's own build lives
# in cpp/Makefile; this file only aliases the checker/test harnesses
# that CI and doc/static-analysis.md reference.

PYTHON ?= python

.PHONY: analysis analysis-fixtures sanitize-smoke sanitize test tier1 metrics-smoke soak-smoke overload-smoke coalesce-smoke async-smoke trace-smoke multichip-smoke cache-smoke cluster-smoke fleet-cache-smoke rpc-smoke control-smoke fleet-obs-smoke mcts-smoke profile-smoke depth-smoke

# Project-invariant static checker (R1-R9); exit 0 = clean tree. The
# JSON artifact feeds the CI annotation step (build.yml "analysis").
analysis:
	$(PYTHON) -m fishnet_tpu.analysis --json analysis-findings.json

# Prove every rule still fires on its violation fixtures (a rule that
# goes blind keeps the tree green while drift accumulates).
analysis-fixtures:
	$(PYTHON) tools/check_fixtures.py

# Telemetry contract (doc/observability.md): start the exporter on an
# ephemeral port, scrape /metrics, validate exposition syntax and the
# contract families, span dumps, net/api outcome counters.
metrics-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_telemetry.py -q

# Resilience contract (doc/resilience.md): a <=60 s soak under the
# canned fault plan (acquire flaps + submit failures + one engine
# crash + one device_step crash) asserting ledger-clean exit (every
# acquired batch submitted exactly once), at least one fused->xla
# degradation + pool respawn, and the four resilience metric families
# on /metrics.
soak-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_soak.py -q

# Overload-serving contract (doc/resilience.md "Admission control and
# load shedding", ≤60 s): the multi-tenant lane scheduler + shed
# policy units, shutdown/requeue/deadline accounting under concurrent
# tenants, the /healthz serving state, and a small saturation run —
# analysis sheds at the watermark, best-move p99 holds, the
# queue stays bounded, and the ledger is exactly-once throughout.
overload-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_overload.py -q

# Coalesced-dispatch contract (doc/wire-format.md "Segmented
# dispatch"): segmented-vs-per-group bit parity on all three psqt_path
# rungs, the deterministic width policy, and the smoke — a
# low-occupancy mock workload run once coalesced and once with
# FISHNET_NO_COALESCE=1 must produce identical analyses while the
# coalesced run issues strictly fewer device dispatches than eval
# steps.
coalesce-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_coalesce.py -q

# Async double-buffered dispatch contract (≤60 s subset of
# tests/test_async_dispatch.py): sync-vs-async bit parity on the xla
# rung, ping-pong donation correctness (never >2 dispatches in
# flight), the FISHNET_NO_ASYNC escape hatch, and the overlap smoke
# (overlap_ratio > 0 with dispatch_issue/dispatch_wait spans
# recorded). The full file — all rungs, fault ladder, wire-diet
# planner units — runs in tier-1.
async-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_async_dispatch.py -q \
		-k "xla or overlap or ping_pong or no_async_env"

# Multi-chip serving contract (doc/sharding.md, ≤60 s, 8 virtual
# devices; the shard router is the one multi-chip serving path): the
# mesh run must spread dispatches over more than one shard with
# analyses bit-identical to the single-device path and the
# exactly-once ledger clean; FISHNET_NO_MESH=1 restores the
# single-device service byte-for-byte; a per-shard device fault
# degrades ONLY its shard's ladder rung without changing output.
multichip-smoke:
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PYTHON) -m pytest tests/test_parallel.py -q \
		-k "mesh_serving_parity or ladder_isolation"

# Position-keyed eval reuse contract (doc/eval-cache.md, ≤60 s subset
# of tests/test_eval_cache.py): cache-off vs cache-cold vs cache-warm
# analyses bit-identical on each single-device rung (warm = fresh
# service against the surviving process cache), with warm runs
# answering pre-wire and skipping device dispatches. The full file —
# mesh parity, fault-plan ledger audit, cross-group dedup fan-out,
# telemetry families, EvalCache units — runs in tier-1.
cache-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_eval_cache.py -q \
		-k "parity and not mesh"

# Shared-plane batched MCTS contract (doc/search.md "Two search
# families, one dispatch plane", ≤45 s subset of
# tests/test_mcts_plane.py): plane-vs-legacy bit parity on every
# forced degradation rung with the AZ eval cache live, the
# FISHNET_NO_SHARED_AZ_PLANE escape hatch, pre-wire AZ eval reuse
# across a pool respawn, and the preallocated step-buffer guard. The
# full file — tree semantics, self-play parity, telemetry families —
# runs in tier-1.
mcts-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_mcts_plane.py -q \
		-k "parity_all_rungs or prewire or preallocated"

# Fleet crash-tolerance contract (doc/resilience.md "Fleet chaos",
# ≤60 s): real client processes behind chaos proxies — a SIGKILL, a
# SIGTERM drain (exit 0), a partition window — restart under budget,
# the server-side fleet ledger exactly-once (0 lost / 0 duplicated),
# and the fleet metric families on /metrics.
cluster-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_cluster.py -q \
		-k "smoke or drain"

# Fleet position-tier contract (doc/eval-cache.md "Fleet tier",
# ≤45 s subset of tests/test_position_tier.py): exact NNUE/AZ slot
# round-trips through the mmap'd segment, graceful fallback with the
# tier disabled or the segment absent, and the two-process smoke — a
# second real service process resolves another process's evals from
# the shared segment pre-wire with bit-identical analyses.
fleet-cache-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_position_tier.py -q \
		-k "two_process or roundtrip or fallback"

# Bound-aware search plane contract (doc/eval-cache.md "Bounds tier" +
# doc/search.md "Move ordering", ≤60 s): bound-record replacement
# (deeper wins), lower/upper cutoff correctness vs a reference
# alpha-beta, torn bounds-slot read-as-miss in the position tier, the
# FISHNET_NO_BOUNDS / FISHNET_NO_SPECULATION escape hatches
# byte-for-byte, speculative pad-row fill with unchanged MCTS results,
# the controller's speculation pin/unpin rule, and the host linger
# window fusing staggered cross-process waves.
depth-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_bounds_plane.py -q

# Split-plane RPC transport contract (doc/disaggregation.md, ≤45 s):
# ring wraparound + flow control, torn-record read-as-miss, stale-epoch
# refusal after a frontend restart, evaluator-death demand timeout →
# requeue not hang, the rpc.detach chaos site, the FISHNET_RPC=0
# monolith escape hatch, and federation role labels. The `slow`
# two-process real-service smoke stays out of this budget (tier-1
# carries it via the full suite's slow lane).
rpc-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_rpc.py -q \
		-m "not slow"

# Self-tuning control plane (doc/control-plane.md, ≤60 s): signal
# folding + hysteresis, actuator bounds/revert and the
# FISHNET_NO_CONTROL byte-for-byte escape hatch, the deterministic
# rule/probe decision tables, degraded-shard skip, the burn_snapshot
# seam, the subsystem actuation seams, the fleet --control panel, and
# a real-service end-to-end controller probe loop.
control-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_control.py -q

# Fleet observability contract (doc/observability.md "Fleet
# observability", ≤45 s): metrics federation with proc labels and
# staleness (a SIGKILLed process stays in the exposition, marked
# stale), cross-process trace stitching (reassignment joins, fenced
# late submits, zero orphans), SLO burn rates over federated series,
# the span write-ahead journal, and a valid fleet Perfetto export —
# including the `slow` real-process churn and supervised-fleet tests.
fleet-obs-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_fleet_obs.py -q \
		-m "slow or not slow"

# Continuous profiling plane + per-tenant cost attribution
# (doc/observability.md "Profiling", ≤90 s): gate discipline (off =
# one attribute read, zero hot-path work), role folding + the /profile
# endpoint contract, the stage-duration histogram hook, profiler
# on-vs-off bit-identical analyses with a measured <3% sampler duty
# cycle, and the per-tenant device-ms sum landing within 2% of the
# measured dispatch wall on a real multi-tenant coalesced run.
profile-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_profiler.py -q

# Causal-tracing contract (doc/observability.md "Causal tracing",
# ≤60 s): a gated mock-server run must yield complete span trees (zero
# orphans), trace-context propagation across the pack/decode worker
# handoff (fused fan-in included), a structurally valid Chrome/Perfetto
# export, and critical-path attribution covering >=95% of steady-state
# per-batch wall time.
trace-smoke:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_tracing.py -q

# ASan+UBSan pool stress incl. the anchor full-provide guard case —
# the non-tier-1 `slow` job.
sanitize-smoke:
	$(PYTHON) -m pytest tests/test_sanitizers.py -q -m slow

# Full sanitizer sweep (adds TSan; ~10x wall clock).
sanitize:
	tools/sanitize.sh

# Tier-1 test suite (CPU, 8 virtual devices).
test tier1:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow'
