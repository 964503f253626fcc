"""Seconds per fresh analytics result: the window, which closes when the
last result begun in it is done, over the results completed."""


def read(run):
    if not run.results:
        return None
    return run.window_s / len(run.results)
