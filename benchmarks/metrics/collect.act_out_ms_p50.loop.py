"""Median outbound leg of a policy step: from the start of its acting work to the start of its host step."""

from benchmarks import host_time, reduce


def read(run):
    return reduce.p50(host_time.legs_ms(run)[0])
