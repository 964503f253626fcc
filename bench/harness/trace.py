"""Device trace: capture one bounded slice with the JAX profiler, and
reduce it to busy time, per-op and per-program device time, and idle gaps
named by what the host was doing.

On a TPU the device's operations are the ``XLA Ops`` line of each
``/device:`` plane and its programs the ``XLA Modules`` line.  On the CPU
backend, which has no device plane, the operations are the host events
that carry an ``hlo_op`` statistic; the reduction is the same, which is
what lets it be tested without a chip.  The harness's own host spans are
``jax.profiler.TraceAnnotation`` events named ``bench.*``; the slice itself
is the ``bench.trace_window`` span.
"""

from __future__ import annotations

import glob
from pathlib import Path

WINDOW = "bench.trace_window"
PREFIX = "bench."


def start(out_dir: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def newest(out_dir: Path) -> str:
    files = sorted(glob.glob(str(Path(out_dir) / "plugins/profile/*/*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profile under {out_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def events(path: str) -> dict:
    """Device op events ``(name, program, start_s, end_s)``, program events
    ``(name, start_s, end_s)`` and host spans ``(name, start_s, end_s)``."""
    from jax.profiler import ProfileData

    return events_of(ProfileData.from_file(path).planes)


def events_of(planes) -> dict:
    """:func:`events` of a profile's planes."""
    planes = list(planes)
    ops, progs, host = [], [], []
    # a plane of a device that ran XLA programs; a loaded TPU runtime adds
    # planes with no ops (``/device:CUSTOM:...``) even on the CPU backend
    device_planes = [p for p in planes if p.name.startswith("/device:")
                     and any(line.name == "XLA Ops" for line in p.lines)]
    for plane in device_planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    st = _stats(e)
                    ops.append((e.name, str(st.get("hlo_module", "")),
                                e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9))
            elif line.name == "XLA Modules":
                for e in line.events:
                    progs.append((e.name, e.start_ns / 1e9,
                                  (e.start_ns + e.duration_ns) / 1e9))
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                t0, t1 = e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9
                if e.name.startswith(PREFIX):
                    host.append((e.name, t0, t1))
                elif not device_planes:
                    st = _stats(e)
                    if "hlo_op" in st:
                        ops.append((str(st["hlo_op"]), str(st.get("hlo_module", "")), t0, t1))
    if not device_planes:
        # the CPU backend has no program line: a program spans its ops
        by = {}
        for name, prog, t0, t1 in ops:
            lo, hi = by.get(prog, (t0, t1))
            by[prog] = (min(lo, t0), max(hi, t1))
        progs = [(k, lo, hi) for k, (lo, hi) in by.items()]
    return {"ops": ops, "programs": progs, "host": host,
            "device_planes": len(device_planes)}


def union(intervals, lo, hi):
    """Merged intervals of ``intervals`` clipped to [lo, hi]."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap, host) -> str:
    """The host span that overlaps ``gap`` most; innermost (shortest) on a
    tie.  The trace window itself does not count."""
    best, best_key = "host: none", (0.0, 0.0)
    a, b = gap
    for name, t0, t1 in host:
        if name == WINDOW:
            continue
        ov = min(b, t1) - max(a, t0)
        if ov <= 0:
            continue
        key = (ov, -(t1 - t0))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy and window seconds, device seconds per op name and per program,
    and the longest idle gaps named by the host span they fall in."""
    wins = [(t0, t1) for name, t0, t1 in ev["host"] if name == WINDOW]
    if wins:
        lo, hi = wins[0]
    else:
        lo = min((o[2] for o in ev["ops"]), default=0.0)
        hi = max((o[3] for o in ev["ops"]), default=0.0)
    busy = union([(o[2], o[3]) for o in ev["ops"]], lo, hi)
    busy_s = sum(b - a for a, b in busy)
    per_op, per_prog = {}, {}
    for name, prog, t0, t1 in ev["ops"]:
        d = min(t1, hi) - max(t0, lo)
        if d > 0:
            per_op[name] = per_op.get(name, 0.0) + d
    for name, t0, t1 in ev["programs"]:
        d = min(t1, hi) - max(t0, lo)
        if d > 0:
            per_prog[name] = per_prog.get(name, 0.0) + d
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": hi - lo,
        "busy_s": busy_s,
        "per_op": per_op,
        "per_program": per_prog,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[attribute(g, ev["host"]), g[1] - g[0]] for g in idle],
        "n_ops": len(ev["ops"]),
    }


def program_seconds(summary: dict, needle: str) -> float:
    """Device seconds of the programs whose name contains ``needle``."""
    return sum(s for name, s in summary["per_program"].items() if needle in name)
