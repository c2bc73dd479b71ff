"""MiB of packed parameters the mirror refreshes moved to the host, per burst: the program's ``publish_bytes`` and ``publish_refreshes`` counters give the bytes of a refresh, its ``Time/publish_time`` spans the window's refreshes."""


def read(run):
    moved, refreshes = run.counter_delta("publish_bytes"), run.counter_delta("publish_refreshes")
    in_window = len(run.span_ms("Time/publish_time"))
    if not moved or not refreshes or not in_window or not run.bursts:
        return None
    # the counters' snapshots bracket the window loosely (one refresh more than its bursts)
    return moved / refreshes * in_window / run.bursts / 2**20
