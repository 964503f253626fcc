"""The ring's spans on the device trace's clock.

While tracing is enabled, each pinned read writes a clock anchor
(:meth:`repro.obs.trace.Tracer.anchor`): a ``repro.clock`` profiler
annotation carrying a sequence number ``n``, around one ``perf_counter_ns``
reading that the ring keeps as a ``clock`` span with the same ``n``.  A
profile recorded with ``jax.profiler`` holds the anchors written while it
ran; pairing them by ``n`` gives the offset between the two clocks, and so
the ring's spans on the profiler's clock, beside the device's operations.

The annotation starts just before the reading, so each anchor's offset
errs low by the few instructions between the two; the offsets of one
profile agree within microseconds, and their median is applied.
"""

from __future__ import annotations

import statistics
from typing import Iterable, List, Optional, Sequence, Tuple

from .trace import CLOCK, TRACER

# (name, start_s, end_s, stats) — a profile's host event
HostEvent = Tuple[str, float, float, dict]
# (name, start_s, end_s, tid, ts, args) — a ring span on the profiler's clock
ProgramSpan = Tuple[str, float, float, int, int, Optional[dict]]


def host_events(planes) -> List[HostEvent]:
    """Every event on a profile's ``/host:`` planes (``ProfileData.planes``)."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns / 1e9,
                            (e.start_ns + e.duration_ns) / 1e9, dict(e.stats)))
    return out


def clock_offsets(host: Iterable[HostEvent], spans: Sequence) -> List[float]:
    """Profiler clock minus ring clock, in seconds, for each anchor found
    in both ``host`` and the ring ``spans``, in anchor order."""
    ring = {s.args["n"]: s.start_ns for s in spans
            if s.name == "clock" and s.args and "n" in s.args}
    out = []
    for name, t0, _t1, stats in sorted(host, key=lambda e: e[1]):
        if name == CLOCK and stats.get("n") in ring:
            out.append(t0 - ring[stats["n"]] / 1e9)
    return out


def on_profiler_clock(host: Iterable[HostEvent],
                      spans: Optional[Sequence] = None) -> List[ProgramSpan]:
    """The ring's spans (default: :data:`~repro.obs.trace.TRACER`'s) moved
    onto the clock of the profile whose host events are ``host``.  Raises
    ``ValueError`` when the profile holds none of the ring's anchors."""
    spans = TRACER.spans() if spans is None else spans
    offsets = clock_offsets(host, spans)
    if not offsets:
        raise ValueError("no clock anchor of the ring in the profile")
    off = statistics.median(offsets)
    return [(s.name, s.start_ns / 1e9 + off, (s.start_ns + s.dur_ns) / 1e9 + off,
             s.tid, s.ts, s.args) for s in spans]


def _covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    total, end = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def busiest_span(gap: Tuple[float, float], spans: Iterable[ProgramSpan]) -> Optional[str]:
    """The name of the span with the most self time inside ``gap``: its
    overlap with the gap less what spans nested in it on its own thread
    cover, so an enclosing ``read`` does not hide the work inside it.  The
    shorter span wins a tie; ``None`` where no span overlaps the gap."""
    a, b = gap
    by_tid = {}
    for name, t0, t1, tid, *_ in spans:
        if t1 > a and t0 < b and t1 > t0:
            by_tid.setdefault(tid, []).append((t0, t1, name))
    best, best_key = None, (0.0, 0.0)
    for mine in by_tid.values():
        mine.sort(key=lambda x: (x[0], -x[1]))
        for i, (t0, t1, name) in enumerate(mine):
            kids = []
            for j in range(i + 1, len(mine)):
                u0, u1, _ = mine[j]
                if u0 >= t1:
                    break
                if u1 <= t1:
                    kids.append((u0, u1))
            lo, hi = max(a, t0), min(b, t1)
            own = (hi - lo) - _covered(kids, lo, hi)
            key = (own, -(t1 - t0))
            if key > best_key:
                best, best_key = name, key
    return best


__all__ = ["busiest_span", "clock_offsets", "host_events", "on_profiler_clock"]
