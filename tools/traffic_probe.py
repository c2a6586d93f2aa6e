"""Local (CPU-JAX) eval-traffic probe.

Runs the bench's production-shaped workload through a small
SearchService and prints the traffic ratios the perf work targets
(VERDICT r4 item 1): nodes_per_eval, delta coverage, prefetch ROI,
suspensions per search. CPU JAX makes the absolute nps meaningless,
but the RATIOS are a pure function of the search + emission logic, so
this is the fast feedback loop for wire/prefetch changes without the
device link.

Usage: python tools/traffic_probe.py [--nodes 4000] [--batches 4]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4000)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--per-batch", type=int, default=30)
    ap.add_argument("--capacity", type=int, default=2048)
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--material", action="store_true", default=True,
                    help="use the material-correlated net (default)")
    ap.add_argument("--random-net", dest="material", action="store_false")
    ap.add_argument("--pin-budget", type=int, default=-1,
                    help="pin the speculation budget (mirrors a slow link's "
                    "operating point, where AIMD settles near 6)")
    args = ap.parse_args()

    import bench  # repo-root bench.py: workload + net builders
    from fishnet_tpu.nnue.weights import NnueWeights
    from fishnet_tpu.search.service import SearchService

    weights = (
        bench.material_weights() if args.material
        else NnueWeights.random(seed=7)
    )
    svc = SearchService(
        weights=weights,
        pool_slots=args.slots,
        batch_capacity=args.capacity,
        eval_sizes=[args.capacity],
    )
    try:
        if args.pin_budget >= 0:
            svc.set_prefetch(args.pin_budget, adaptive=False)
        svc.warmup()
        jobs = bench.make_workload(args.batches, args.per_batch)
        total, _, _ = asyncio.run(
            bench.run_searches(svc, jobs, args.nodes, concurrency=len(jobs))
        )
        c = svc.counters()
    finally:
        svc.close()

    searches = len(jobs)
    evals = max(1, c["evals_shipped"])
    report = {
        "searches": searches,
        "total_nodes": total,
        "nodes_per_eval": round(c["nodes"] / evals, 3),
        "evals_shipped": c["evals_shipped"],
        "delta_coverage": round(c["delta_evals"] / evals, 3),
        "anchor_rate": round(c.get("anchor_deltas", 0) / evals, 3),
        "prefetch_roi": round(
            c["prefetch_hits"] / max(1, c["prefetch_shipped"]), 3
        ),
        "prefetch_share": round(c["prefetch_shipped"] / evals, 3),
        "demand_evals": c["demand_evals"],
        "tt_eval_hits": c["tt_eval_hits"],
        "suspensions_per_search": round(c["suspensions"] / searches, 1),
        "block_avg": round(evals / max(1, c["suspensions"]), 2),
        "steps": c["steps"],
        "wire_bytes_per_eval": round(c["wire_bytes"] / evals, 1),
        "occupancy": round(c["evals_shipped"] / max(1, c["bucket_slots"]), 3),
        "prefetch_budget_now": c["prefetch_budget"],
    }
    for k, v in report.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
