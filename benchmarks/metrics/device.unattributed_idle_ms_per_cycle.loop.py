"""Idle device time of the traced window that no host span covers, a cycle."""

from benchmarks import host_time


def read(run):
    return host_time.unattributed_idle_ms_per_cycle(run)
