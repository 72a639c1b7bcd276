#!/usr/bin/env python3
"""Read the compared numbers of a cell over many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        --system program,control

For each system and seed, one run of the cell at its own size (set-up, a
short window at the cell's own load, the check), and one JSON line with
``correct`` and each compared number beside its limit.  ``program`` gives
the lower readings a limit is set from, ``control`` (the plain reference in
the program's place, one precision below the configuration's) the upper
ones.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--system", default="program,control")
    args = ap.parse_args()
    for system in args.system.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            r = cell.run(args.workload, seed, args.seconds, False,
                         t_process=time.perf_counter(), system=system,
                         log=lambda s: print(s, file=sys.stderr, flush=True))
            print(json.dumps({"system": system, "seed": seed, "correct": r["correct"],
                              "attempted": r["attempted"], "failed": r["failed"],
                              "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
