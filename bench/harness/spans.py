"""The program's own spans, as the metric readers in ``bench/metrics/`` read
them: from the span ring of ``repro.obs.trace.TRACER``, which the traced run
keeps, on the window's clock (``TRACER.epoch_ns`` is stamped by the
``TRACER.clear()`` just before the window's clock starts).

A program whose tracer keeps no ``epoch_ns``, or a run with tracing off, has
nothing here to read: :func:`spans` returns ``None``."""

from __future__ import annotations


def spans(name: str):
    """``(start_s, dur_s, ts, args)`` of the ring's spans named ``name``,
    seconds from the window's start; ``None`` where no span of that name
    was recorded."""
    from repro.obs.trace import TRACER

    epoch = getattr(TRACER, "epoch_ns", None)
    if not TRACER.enabled or not epoch:
        return None
    out = [((s.start_ns - epoch) / 1e9, s.dur_ns / 1e9, s.ts, s.args or {})
           for s in TRACER.spans() if s.name == name]
    return out or None


def overlap(t0: float, t1: float, lo: float, hi: float) -> float:
    """Seconds of [t0, t1] inside [lo, hi]."""
    return max(0.0, min(t1, hi) - max(t0, lo))


def mean_per_view_ms(run, name: str):
    """Mean over pinned views (spans grouped by their view timestamp) of
    the time in spans ``name`` inside the window, in ms."""
    sp = spans(name)
    if sp is None:
        return None
    per_view = {}
    for s, d, ts, _ in sp:
        inside = overlap(s, s + d, 0.0, run.window_s)
        if inside > 0:
            per_view[ts] = per_view.get(ts, 0.0) + inside
    if not per_view:
        return None
    return 1e3 * sum(per_view.values()) / len(per_view)
