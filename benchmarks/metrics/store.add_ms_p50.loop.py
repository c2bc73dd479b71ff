"""Median ``rb.add`` of one policy step's rows: the program's span in the acting loop."""

from benchmarks import reduce


def read(run):
    return reduce.p50(run.span_ms("Time/replay_add_time"))
