"""Arithmetic shared by the metric readers in ``bench/metrics/``.  Each
returns ``None`` where the run has nothing for it to read."""

from __future__ import annotations

import numpy as np


def device_idle_pct(run):
    t = run.trace
    if t is None or t["window_s"] <= 0 or t["n_ops"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_pct(ops, nbytes, seconds, peaks):
    """Least time the chip could take, over the time taken, in percent."""
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def program_roofline(run, program: str, work_of):
    """Roofline share of the device time of ``program`` in the traced
    slice; ``work_of(cycle_extra)`` gives a traced cycle's (ops, bytes)."""
    from .trace import program_seconds

    t = run.trace
    if t is None:
        return None
    secs = program_seconds(t, program)
    ops = nbytes = 0
    for *_, extra in run.cycles:
        if extra.get("traced"):
            w = work_of(extra)
            if w is not None:
                ops, nbytes = ops + w[0], nbytes + w[1]
    if secs <= 0 or nbytes == 0:
        return None
    return roofline_pct(ops, nbytes, secs, run.spec.peaks(run.device["kind"]))


def cycle_ms(run, a: int, b: int, key: str):
    """Mean of (time ``b`` - time ``a``) over read cycles that carry ``key``."""
    d = [c[b] - c[a] for c in run.cycles if key in c[3]]
    return 1e3 * float(np.mean(d)) if d else None
