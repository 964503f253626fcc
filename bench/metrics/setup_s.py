"""Seconds from process start to the window's start: generation, bulk
load, attach, first assembly and warm-up."""


def read(run):
    return run.setup_s
