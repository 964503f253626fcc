"""Peak bytes in use on the chip at the window's end, per live edge then."""


def read(run):
    if run.memory_peak is None or not run.live_edges:
        return None
    return run.memory_peak / run.live_edges
