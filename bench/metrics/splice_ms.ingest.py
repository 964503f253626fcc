"""Mean per pinned view of the program's view_splice time (the device
splice of the view's dirty leaf tiles, or their full build), clipped to the
window."""

from bench.harness.spans import mean_per_view_ms


def read(run):
    return mean_per_view_ms(run, "view_splice")
