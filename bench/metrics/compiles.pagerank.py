"""Backend compiles inside the window (jax.monitoring events), with the
persistent compile cache off there."""


def read(run):
    return float(run.compiles)
