"""Time a burst waited for its batch: prefetch wait plus host-to-device staging spans, per burst."""


def read(run):
    wait = run.counter_delta("prefetch_wait_ms")
    if wait is None or not run.bursts:
        return None
    return (wait + sum(run.span_ms("Time/stage_h2d_time"))) / run.bursts
