"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,table3]
    PYTHONPATH=src python -m benchmarks.run --only pipeline --json-out BENCH_pipeline.json

Output: ``name,us_per_call,derived`` CSV rows per measurement; with
``--json-out`` the suites' structured return values are additionally written
to one JSON file (suite -> result), so the perf trajectory is tracked across
PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (
    access_patterns,
    backends,
    balance,
    batch_dist,
    breakdown,
    chaos,
    chunkable,
    dist,
    epoch_order,
    loaders,
    numpfs,
    obs,
    optim_breakdown,
    peer,
    pipeline,
    plan,
    serve_tier,
    stream,
)
from benchmarks.common import bench_meta
from repro.launch.compile_cache import use_compile_cache
from repro.obs import log as obs_log

SUITES = {
    "table3": access_patterns.run,      # access-pattern I/O microbenchmark
    "fig3": breakdown.run,              # training-time breakdown
    "fig9": loaders.run,                # loader speedups by buffer tier
    "fig10": optim_breakdown.run,       # per-optimization contribution
    "fig11": numpfs.run,                # PFS loads per iteration
    "fig12": balance.run,               # load balance across nodes
    "fig13": chunkable.run,             # chunkable fraction
    "fig16": batch_dist.run,            # batch-size distribution
    "eoo": epoch_order.run,             # path-TSP solver comparison
    "pipeline": pipeline.run,           # sync vs async executor throughput
    "backends": backends.run,           # storage-backend shoot-out
    "peer": peer.run,                   # peer-fetch tier vs PFS-only
    "plan": plan.run,                   # plan-once/train-many amortization
    "dist": dist.run,                   # multi-process runtime digest parity
    "chaos": chaos.run,                 # elastic recovery under injected faults
    "stream": stream.run,               # overlapped window planning + ingest rates
    "serve_tier": serve_tier.run,       # multi-tenant reads under live training
    "obs": obs.run,                     # flight-recorder overhead + parity
}


def _jsonable(obj):
    """Best-effort conversion of suite return values to JSON-safe data."""
    import numpy as np

    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (np.integer, np.floating)):
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--json-out", default=None,
                    help="write suite results to this JSON file (a single "
                         "suite's result is written unwrapped; multiple "
                         "suites are keyed by suite name; every file "
                         "carries a ``_meta`` provenance header)")
    obs_log.add_verbosity_args(ap)
    args = ap.parse_args()
    obs_log.configure(obs_log.verbosity_from(args))
    use_compile_cache()
    names = args.only.split(",") if args.only else list(SUITES)
    print("suite,us_per_call,derived")
    failures = 0
    collected: dict = {}
    for name in names:
        t0 = time.perf_counter()
        try:
            collected[name] = SUITES[name]()
            print(f"{name}/_elapsed,{(time.perf_counter() - t0) * 1e6:.0f},ok")
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name}/_error,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if args.json_out:
        if failures:
            # never clobber a previously-good tracking file with partial data
            print(f"_json/skipped,0,{failures} suite(s) failed")
        else:
            payload = collected.get(names[0]) if len(names) == 1 else collected
            payload = _jsonable(payload)
            if not isinstance(payload, dict):
                payload = {"result": payload}
            # provenance header: which revision/seed/config produced these
            # tracking numbers (satellite of DESIGN.md §13).
            payload["_meta"] = bench_meta(config={"suites": sorted(names)})
            with open(args.json_out, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            print(f"_json/written,0,{args.json_out}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
