"""Share of the window in which the compactor held writes off with the
pipeline already drained: each quiesce span less its drain_s (the flush),
clipped to the window."""

from bench.harness.spans import overlap, spans


def read(run):
    sp = spans("quiesce")
    if sp is None:
        return None
    paused = sum(overlap(s + a["drain_s"], s + d, 0.0, run.window_s) for s, d, _, a in sp)
    return 100.0 * paused / run.window_s
