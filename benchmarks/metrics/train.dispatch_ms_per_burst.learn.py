"""Host time until the burst's dispatch returns: the program's own span in ``run_train_burst``, per burst."""

from benchmarks import scopes


def read(run):
    return scopes.span_ms_per_burst(run, "Time/train_dispatch_time")
