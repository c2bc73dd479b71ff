"""The benchmark's own span around the mirror refresh, blocking on the host copy, per burst."""


def read(run):
    spans = run.mirror_ms()
    return sum(spans) / run.bursts if spans and run.bursts else None
