"""Peak bytes in use on the fullest chip (memory_stats), GiB."""


def read(run):
    return run.memory_peak / 2**30 if run.memory_peak else None
