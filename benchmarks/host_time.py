"""Where the host holds the device: the legs of a policy step, the device's
wait for the host step, and idle device time that no host span covers.

A policy step's **outbound leg** runs from the start of its acting work to the
start of its ``Time/act_host_step_time``. Its acting work starts with the
enclosing ``Time/rollout_time`` (a call's first step), with the step's own
``Time/act_decode_time`` (an actor that dispatches a program a step), or else
where the previous host step of the call ended. Its **return leg** runs from
the end of its host step to the start of the next step's acting work, or to
the end of the rollout span for a call's last step. So in a burst of K > 1
steps the gaps between host steps are outbound legs, and for a burst the
three legs of its steps tile the rollout span.

The device readers take the program's spans on the trace's clock, as the
profiler recorded them (``reduce.host_spans``). The window closes inside a
host step, where the profiler is stopped, so the spans still open then (the
closing rollout's) are not in the trace: they are taken from the program's
span file, put on the trace's clock by the window's opening mark.

Readers here return ``None`` where the spans or the device plane they read
are absent: a program without the host-step span, a CPU run.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks import reduce

ROLLOUT = "Time/rollout_time"
DECODE = "Time/act_decode_time"
HOST_STEP = "Time/act_host_step_time"
WAIT_PREFIX = "recv-done"  # the device's wait for a host transfer: here, the callback's answer

Interval = Tuple[float, float]


def step_legs(spans, lo: float, hi: float) -> Tuple[List[float], List[float]]:
    """``(outbound, return)`` seconds of every policy step of the rollout spans
    that lie wholly in ``[lo, hi]``; ``spans`` are ``(name, start, end)``."""
    steps = sorted((s, e) for n, s, e in spans if n == HOST_STEP)
    decodes = sorted(s for n, s, _e in spans if n == DECODE)
    outbound, back = [], []
    for _n, r0, r1 in sorted((sp for sp in spans if sp[0] == ROLLOUT and sp[1] >= lo and sp[2] <= hi),
                             key=lambda sp: sp[1]):
        mine = [(s, e) for s, e in steps if s >= r0 and e <= r1]
        starts, prev_end = [], r0
        for s, e in mine:
            # the step's own decode if it has one, else where the previous host step ended
            own = [d for d in decodes if prev_end <= d <= s]
            starts.append(own[-1] if own else prev_end)
            prev_end = e
        for i, (s, e) in enumerate(mine):
            outbound.append(s - starts[i])
            back.append((starts[i + 1] if i + 1 < len(mine) else r1) - e)
    return outbound, back


def legs_ms(run) -> Tuple[List[float], List[float]]:
    """The window's outbound and return legs, milliseconds."""
    outbound, back = step_legs(run.spans(), run.recorder.opened_at, run.recorder.closed_at)
    return [v * 1e3 for v in outbound], [v * 1e3 for v in back]


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Overlaps of two sorted lists of disjoint intervals (sorted and disjoint again)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals: List[Interval]) -> float:
    return reduce.union_seconds(intervals)[0]


def host_wait_seconds(ops, host_steps: List[Interval], start: float, end: float) -> float:
    """Seconds in ``[start, end]`` in which a device ran nothing but a
    ``recv-done`` while a host step was open. ``ops`` are one device's
    ``(name, start, end)``; an operation that holds the whole wait (the
    ``while`` of the acting loop) is its parent and takes nothing from it."""
    ops = reduce.clip(ops, start, end)
    _, steps = reduce.union_seconds(host_steps)
    total = 0.0
    for name, w0, w1 in ops:
        if not name.startswith(WAIT_PREFIX):
            continue
        alone = _intersect([(w0, w1)], steps)
        if not alone:
            continue
        beside = [(s, e) for n, s, e in ops
                  if s < w1 and e > w0 and (s > w0 or e < w1) and not n.startswith(WAIT_PREFIX)]
        total += _length(alone) - _length(_intersect(alone, reduce.union_seconds(beside)[1]))
    return total


def traced_spans(run, start: float):
    """The program's spans on the trace's clock: those the profiler recorded,
    and those still open when the window closed (from the span file; the
    window opened at ``start`` on the trace's clock)."""
    lo, hi = run.recorder.opened_at, run.recorder.closed_at
    still_open = [(n, s - lo + start, e - lo + start) for n, s, e in run.spans() if s < hi < e]
    return reduce.host_spans(run.planes()) + still_open


def host_wait_pct(run) -> Optional[float]:
    summary = run.device_summary()
    if summary is None:
        return None
    start, window = summary["start"], summary["window_s"]
    steps = [(s, e) for n, s, e in traced_spans(run, start) if n == HOST_STEP]
    if not steps:
        return None
    planes = run.planes()
    first = sorted(p for p in planes if reduce.is_device_plane(p))[0]
    wait = host_wait_seconds(planes[first].get("XLA Ops", []), steps, start, start + window)
    return 100.0 * wait / window


def uncovered_idle_seconds(busy: List[Interval], spans, start: float, end: float) -> float:
    """Idle seconds of ``busy``'s complement in ``[start, end]`` that no host
    span covers: all of them, where ``reduce.idle_gaps_by_span`` keeps ten names."""
    gaps, cursor = [], start
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, min(s, end)))
        cursor = max(cursor, e)
    if cursor < end:
        gaps.append((cursor, end))
    gaps = [(s, e) for s, e in gaps if e > s]
    _, covered = reduce.union_seconds([(s, e) for _n, s, e in spans])
    return _length(gaps) - _length(_intersect(gaps, covered))


def unattributed_idle_ms_per_cycle(run) -> Optional[float]:
    summary = run.device_summary()
    if summary is None or not summary["busy_intervals"] or run.bursts < 1:
        return None
    start = summary["start"]
    idle = uncovered_idle_seconds(summary["busy_intervals"], traced_spans(run, start), start, start + summary["window_s"])
    return 1e3 * idle / run.bursts
