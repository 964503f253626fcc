#!/usr/bin/env python3
"""One run of one benchmark cell of the RapidStore benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and configurations are in ``BENCHMARK.json`` at the
checkout root.  The last line of standard output is the run's JSON result;
the numbers compared with the reference are also the last lines of
standard error.  Refuses to run (non-zero exit, no result) without a TPU.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
