"""The program's own span around the parameter mirror's refresh (nothing in it blocks on the copy), per burst."""

from benchmarks import scopes


def read(run):
    return scopes.span_ms_per_burst(run, "Time/publish_time")
