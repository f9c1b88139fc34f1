#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting the limits.

    python3 bench/control.py --workload ptychonn_repo.pfs --seeds 1 2 3

Prints one JSON line per seed: the check's numbers for the bfloat16 control
and for a step that leaves out half its rows (bench/harness/control.py).
Run on the chip at the cell's size; the benchmark's runs never run it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.harness import catalog, control

    jax.config.update("jax_compilation_cache_dir", str(ROOT / "bench" / ".cache" / "jax"))
    cell = catalog.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        out = control.readings(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "seconds": time.perf_counter() - t} | out), flush=True)


if __name__ == "__main__":
    main()
