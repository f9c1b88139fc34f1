#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload ptychonn_repo.pfs --seed 7 --seconds 20 --trace 0

From the root of a checkout. The cell is an entry of ``workloads`` in
BENCHMARK.json; its files are found by name under bench/ (see
bench/harness/catalog.py). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics, read with the program's span
tracer and JAX's profiler on. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``), and last ``checks``: each number
compared with its limit, which are also the last lines of standard error.

It exits nonzero and prints no result when JAX finds no TPU, or fewer chips
than the cell needs, or when the program (src/repro) is not beside it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of the data set, its targets and the weights")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seed >= 2**64:
        ap.error("--seed must be in [0, 2**64)")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness.cell import NoChip, run_cell

    try:
        result, lines = run_cell(ROOT, args.workload, seed=args.seed, seconds=args.seconds,
                                 trace=bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
