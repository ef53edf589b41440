"""The control and the planted faults, at a cell's own size, on the chip:

    python3 -m perfbench.controls --workload <cell> --fault <name> \\
        --seeds 11,12,13 --seconds 10

Each seed runs the cell once through the harness with the timed path
broken underneath (the faults each driver names in FAULTS), and prints
its result line; `correct` has to come out false. The numbers it
compares are the upper readings the limits are set below (PERF.md).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from perfbench.harness import ROOT, emit, run_cell

    p = argparse.ArgumentParser(prog="perfbench.controls")
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run_cell(args.workload, seed, args.seconds, False,
                          t0=time.perf_counter(), fault=args.fault)
        result["control"] = {"fault": args.fault, "seed": seed}
        emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
