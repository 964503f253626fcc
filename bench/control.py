#!/usr/bin/env python3
"""A cell's control on the chip, at the cell's own size, on several seeds.

    python3 bench/control.py --workload g500-22.pagerank --seconds 10 \
        --seeds 11,12,13

Each seed is a whole run (set-up, window, checks) in one process, with the
control in the program's place (``bench/harness/control.py``): PageRank
in bfloat16, or a write acknowledged with an op missing.  Prints one JSON
line per seed with the compared numbers; the smallest reading of each is
the upper end its limit was set below.  Not run by the benchmark.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.harness.main import run_cell  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(args.workload, seed, args.seconds, False, control=True,
                     t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": r["correct"], "checks": r["checks"]}),
              flush=True)


if __name__ == "__main__":
    main()
