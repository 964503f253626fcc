"""Milliseconds of compactor quiesce (writes held off) between the window's
start and the first PageRank's pin."""

from bench.harness.spans import overlap, spans


def read(run):
    sp = spans("quiesce")
    if sp is None or not run.cycles:
        return None
    pin = run.cycles[0][0]
    return 1e3 * sum(overlap(s, s + d, 0.0, pin) for s, d, *_ in sp)
