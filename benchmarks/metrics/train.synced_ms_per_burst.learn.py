"""Dispatch plus the fetch that ends with the burst's outputs ready: the train time the host sees, per burst."""

from benchmarks import scopes


def read(run):
    return scopes.span_ms_per_burst(run, "Time/train_dispatch_time", "Time/train_sync_time")
