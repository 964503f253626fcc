"""Mean per pinned view of the program's view_splice time (the device
COO's splice of the view's dirty subgraphs, or its full build), clipped to
the window."""

from bench.harness.spans import mean_per_view_ms


def read(run):
    return mean_per_view_ms(run, "view_splice")
