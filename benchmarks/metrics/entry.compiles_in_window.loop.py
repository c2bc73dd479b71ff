"""Backend compiles between window open and close (jax.monitoring); has to read 0."""


def read(run):
    return float(run.compiles("close")["count"] - run.compiles("open")["count"])
