"""Median return leg of a policy step: from the end of its host step to the next step's acting work or the rollout's end."""

from benchmarks import host_time, reduce


def read(run):
    return reduce.p50(host_time.legs_ms(run)[1])
