"""One run of one cell, start to end: the command line, the device checks,
set-up, the window, the checks and the result line."""

from __future__ import annotations

import argparse
import copy
import json
import shutil
import sys
import time
from pathlib import Path

from . import cell as C
from .device import NoChip, record, refuse_path_vars, require_tpu, use_compile_cache
from .spec import ROOT, Spec

RING = 1 << 21  # spans the traced run keeps (wal_sync spans of a whole window)


def merge(base: dict, over: dict | None) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def program_fns(reads: dict):
    """The store's own entry points, as the window drives them."""
    from repro.core.analytics import pagerank_view
    from repro.kernels.leaf_search import edge_search_view

    def analytics(view, r):
        return pagerank_view(view, iters=int(r["iters"]), damping=float(r["damping"]))

    return edge_search_view, analytics


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT,
             t_start: float | None = None, require_chip: bool = True,
             overrides: dict | None = None, fault=None, control: bool = False,
             out_dir: Path | None = None, compile_cache: bool = True) -> dict:
    """Returns the result line as a dict, with the compared numbers last
    under ``checks``.  The keywords after ``trace`` serve rehearsals and
    tests: the chip check, sizes, a planted fault or the control, where
    the WAL and trace go, and whether the persistent compile cache is on."""
    t_start = time.perf_counter() if t_start is None else t_start
    refuse_path_vars()
    spec = Spec(root)
    cell = spec.cell(name)
    config = merge(spec.config(cell["config"]), (overrides or {}).get("config"))
    traffic = merge(spec.traffic(cell["traffic"]), (overrides or {}).get("traffic"))
    chips = int(cell["chips"])
    out_dir = Path(out_dir) if out_dir is not None else spec.dir / "out"
    if require_chip:
        require_tpu(chips)
    device = record(chips)
    if compile_cache:
        use_compile_cache(root)
    from repro.obs.trace import TRACER, SpanRing

    saved = TRACER.enabled, TRACER.ring
    TRACER.enabled = bool(trace)  # the program's spans: traced run only
    if trace:
        TRACER.ring = SpanRing(RING)
    try:
        return _run(name, seed, seconds, trace, spec, cell, config, traffic, device,
                    out_dir, t_start, fault, control)
    finally:
        TRACER.enabled, TRACER.ring = saved


def _run(name, seed, seconds, trace, spec, cell, config, traffic, device, out_dir,
         t_start, fault, control) -> dict:
    reads = traffic["reads"]
    search_fn, analytics_fn = program_fns(reads)
    t_init = time.perf_counter() - t_start
    dep = C.Deployment(config, traffic, seed, seconds, out_dir)
    try:
        if control:
            from . import control as ctl

            search_fn, analytics_fn, fault = ctl.fns(traffic, search_fn, analytics_fn)
        C.warm_up(dep, reads, search_fn, analytics_fn)
        trace_dir = None
        if trace:
            trace_dir = out_dir / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
        setup_s = time.perf_counter() - t_start
        run = C.window(dep, traffic, seconds, trace_dir, fault=fault,
                       search_fn=search_fn, analytics_fn=analytics_fn)
        run.cell, run.config, run.setup_s = name, config, setup_s
        run.spec, run.device = spec, device
        run.B = int(config["store"]["leaf_width"])
        with dep.store.read_view() as v:
            run.live_edges = int(v.n_edges)
        checks = C.check(dep, run, traffic["limits"], reads)
    finally:
        dep.close()
    if trace:
        from . import trace as tr

        run.trace = tr.reduce(tr.events(tr.newest(trace_dir)))
    metrics = {}
    for m in spec.metrics(name, bool(trace)):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run.memory_peak)
    if trace:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
    if traffic.get("primary", "reads") == "writes":
        attempted = len(run.w_stamp)
        failed = int((run.w_stamp <= 0).sum())
    elif run.due is not None:
        attempted, failed = len(run.due), int(checks["unanswered"][0])
    else:
        attempted, failed = len(run.results), 0
    if run.stream_exhausted:
        checks["write_stream_exhausted"] = [1, 0]
    result = {
        "correct": all(n <= lim for n, lim in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        result["breakdown"] = {"device_ops": [list(x) for x in run.trace["device_ops"]],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    info = {
        "cell": name, "seed": seed, "setup_s": setup_s, "window_s": run.window_s,
        "compiles_in_window": run.compiles, "compile_s_in_window": run.compile_s,
        "writes_submitted": len(run.w_stamp), "wal_syncs": run.wal_syncs,
        "wal_sync_spans": run.wal_span_count, "live_edges": run.live_edges,
        "cycles": len(run.cycles), "lateness": run.lateness,
        "setup_phases": dep.phases, "import_and_init_s": t_init,
    }
    if run.cycles:
        info["first_cycle"] = run.cycles[0][:3]
    C.log("info " + json.dumps(info))
    return result


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    for k, (n, lim) in result["checks"].items():
        C.log(f"check {k}: {n} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0
