"""What the train loop waited for its batch in ``sample_device``: sampling, the wait on the prefetch, the H2D put; per burst."""

from benchmarks import scopes


def read(run):
    return scopes.span_ms_per_burst(run, "Time/replay_sample_time")
