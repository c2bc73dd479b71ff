"""Seconds of backend compilation before the window opened (jax.monitoring)."""


def read(run):
    return run.compiles("open")["seconds"]
