"""Share of the traced window in which the first chip ran nothing but the acting program's wait for the open host step."""

from benchmarks import host_time


def read(run):
    return host_time.host_wait_pct(run)
