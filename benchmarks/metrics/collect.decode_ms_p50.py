"""Median device acting step: the dispatch of two tokens through the core and the fetch of the actions."""

from benchmarks import reduce


def read(run):
    return reduce.p50(run.span_ms("Time/act_decode_time"))
