"""Mean per pinned view of the time from begin_read until the view's
device COO arrays are ready."""

from bench.harness.readers import cycle_ms


def read(run):
    return cycle_ms(run, 0, 1, "edges")
