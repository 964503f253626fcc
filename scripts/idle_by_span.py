#!/usr/bin/env python3
"""One traced run of one benchmark cell, with the device's idle time named
by the program's own spans.

    python3 scripts/idle_by_span.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py ... --trace 1`` does and prints its result
line, then one more line, ``idle {...}``: the ring's spans are moved onto
the profiler's clock through the ``repro.clock`` anchors
(:mod:`repro.obs.profile`), and each idle gap of the traced slice of 1 ms
or more is named by the program span with the most self time in it (the
harness's own ``bench.*`` annotation where no program span overlaps it).  The line
holds each anchor's clock offset, the slice's busy and idle seconds, the
longest gaps with their names and their start on the window's clock, and
the idle seconds under each name over all gaps.  A last line, ``spans
{...}``, sums the program's spans by name over the window and lists its
``view_splice`` and ``quiesce`` spans with their args.  Like
``bench/run.py``, it refuses to run without a TPU.
"""

import argparse
import json
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.harness import trace as tr  # noqa: E402
from bench.harness.device import NoChip  # noqa: E402
from bench.harness.main import run_cell  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402

MIN_GAP_S = 1e-3  # shorter gaps are summed under SHORT, not named one by one
SHORT = "gaps under 1 ms"


def idle_by_span(profile_path: str, spans, epoch_ns: int, top: int) -> dict:
    from jax.profiler import ProfileData

    from repro.obs import profile

    planes = list(ProfileData.from_file(profile_path).planes)
    ev = tr.events_of(planes)
    host = profile.host_events(planes)
    offsets = profile.clock_offsets(host, spans)
    program = profile.on_profiler_clock(host, spans)
    lo, hi = next((t0, t1) for name, t0, t1 in ev["host"] if name == tr.WINDOW)
    program = [p for p in program if p[2] > lo and p[1] < hi]
    busy = tr.union([(o[2], o[3]) for o in ev["ops"]], lo, hi)
    gaps = tr.gaps(busy, lo, hi)
    zero = sorted(offsets)[len(offsets) // 2] + epoch_ns / 1e9  # window time 0
    named, total = [], {}
    for a, b in gaps:
        if b - a < MIN_GAP_S:
            name = SHORT
        else:
            name = profile.busiest_span((a, b), program) or tr.attribute((a, b), ev["host"])
            named.append([name, b - a, a - zero])
        total[name] = total.get(name, 0.0) + (b - a)
    named.sort(key=lambda g: -g[1])
    return {
        "clock_offsets_us": [o * 1e6 for o in offsets],
        "slice_s": hi - lo,
        "busy_s": sum(b - a for a, b in busy),
        "idle_s": sum(b - a for a, b in gaps),
        "idle_gaps": named[:top],
        "idle_by_span": sorted(([k, v] for k, v in total.items()), key=lambda x: -x[1]),
    }


def window_spans(spans, epoch_ns: int, seconds: float, listed=("view_splice", "quiesce")) -> dict:
    """Per span name, [count, total s, longest s] of the spans that start in
    the first ``seconds`` of the window; and each ``listed`` span from the
    window's start on, as [start s, duration s, args]."""
    per, each = {}, {name: [] for name in listed}
    for s in spans:
        t0 = (s.start_ns - epoch_ns) / 1e9
        if t0 < 0:
            continue
        if t0 <= seconds:
            c = per.setdefault(s.name, [0, 0.0, 0.0])
            c[0] += 1
            c[1] += s.dur_ns / 1e9
            c[2] = max(c[2], s.dur_ns / 1e9)
        if s.name in each:
            each[s.name].append([t0, s.dur_ns / 1e9, s.args])
    return {"per_name": per, **each}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    from repro.obs.trace import TRACER

    kept = {}
    reduce = tr.reduce

    def keep(ev, top=10):  # runs while the traced run's ring is still live
        kept["spans"], kept["epoch_ns"] = TRACER.spans(), TRACER.epoch_ns
        return reduce(ev, top)

    tr.reduce = keep
    out_dir = Spec().dir / "out"
    try:
        result = run_cell(args.workload, args.seed, args.seconds, True, t_start=T_START,
                          out_dir=out_dir)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    try:
        idle = idle_by_span(tr.newest(out_dir / "trace"), kept["spans"], kept["epoch_ns"],
                            args.top)
    except ValueError as e:  # no anchor of the ring in the profile
        print(f"idle: {e}", file=sys.stderr)
        return 1
    print("idle " + json.dumps(idle), flush=True)
    print("spans " + json.dumps(window_spans(kept["spans"], kept["epoch_ns"], args.seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
