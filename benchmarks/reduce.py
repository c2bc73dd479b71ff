"""From spans, counters and the profiler's trace to numbers.

The per-layer metric readers under ``metrics/`` are a few lines each and call
into this module, which a later PR cannot change: the table of peaks, the
reduction of a device trace to busy time, self times by operation and idle
gaps, and the reading of the program's spans and counters over the window.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

#: published peaks per chip, keyed by ``device_kind`` (Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM). A device that
#: is not here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}

WINDOW_OPEN_MARK = "bench/window_open"
DEVICE_LINES = ("XLA Ops", "XLA Modules")  # the lines of a device's plane that are read
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def peak_flops(device_kind: str) -> float:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}")
    return DEVICE_PEAKS[device_kind]["bf16_flops_per_s"]


class CompileCounter:
    """Backend compiles of this process and their seconds, from
    ``jax.monitoring`` (the event the program's own counters listen to)."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    @classmethod
    def installed(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._instance._on_duration)
        return cls._instance

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += float(duration)

    def snapshot(self) -> Dict[str, float]:
        return {"count": self.count, "seconds": self.seconds}


def program_counters() -> Dict[str, Any]:
    """The program's run counters as they stand (telemetry on), else empty."""
    from sheeprl_tpu.obs import counters

    installed = counters.installed()
    return dict(installed.as_dict()) if installed is not None else {}


def span_clock_origin() -> Optional[float]:
    """Mark the program's span file with an instant and return the host clock
    at that moment, so span times can be put on ``time.perf_counter``."""
    from sheeprl_tpu.obs.spans import get_tracer

    tracer = get_tracer()
    if tracer is None:
        return None
    now = time.perf_counter()
    tracer.instant("bench/sync")
    return now


def read_spans(path: str, sync_at: float) -> List[Tuple[str, float, float]]:
    """``(name, start, end)`` of the program's spans on ``time.perf_counter``."""
    events, sync_ts = [], None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            event = json.loads(line)
            if event.get("name") == "bench/sync" and sync_ts is None:
                sync_ts = event["ts"]
            elif event.get("ph") == "X":
                events.append(event)
    if sync_ts is None:
        raise RuntimeError("the span file holds no bench/sync mark")
    origin = sync_at - sync_ts / 1e6
    return [(e["name"], origin + e["ts"] / 1e6, origin + (e["ts"] + e["dur"]) / 1e6) for e in events]


# ---------------------------------------------------------------------------
# the device trace
# ---------------------------------------------------------------------------


class DeviceTrace:
    """A ``jax.profiler`` capture into ``directory``, read back with
    ``jax.profiler.ProfileData`` and thrown away."""

    def __init__(self, directory: str):
        self.directory = directory
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # device events and the program's annotations; not every Python call
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_at = time.perf_counter()

    def mark_open(self) -> None:
        """Leave the window's opening stamp in the trace, on its clock."""
        import jax

        with jax.profiler.TraceAnnotation(WINDOW_OPEN_MARK):
            pass

    def stop(self) -> None:
        import jax

        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(glob.glob(os.path.join(self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.directory}")
        return found[-1]

    def discard(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)


def read_planes(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """``plane -> line -> [(event name, start_s, end_s)]`` of an xplane file."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if is_device_plane(plane.name) and line.name not in DEVICE_LINES:
                continue  # a device's other lines repeat its operations by step or by scope
            events = lines.setdefault(line.name, [])
            for event in line.events:
                start = event.start_ns / 1e9
                events.append((short_name(event.name), start, start + event.duration_ns / 1e9))
    return planes


def short_name(name: str) -> str:
    """A device event is named by its whole HLO text, ``%fusion.1 = f32[...]
    fusion(...)``: keep the operation's name."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") else name


def union_seconds(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total length of the union of ``intervals``, and the merged intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def self_seconds(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds by event name, each event's children taken out of it (a
    ``while`` spans the operations of its body on the same line)."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, child seconds, own duration]

    def close():
        name, _end, children, duration = stack.pop()
        out[name] = out.get(name, 0.0) + max(duration - children, 0.0)
        if stack:
            stack[-1][2] += duration

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close()
        stack.append([name, end, 0.0, end - start])
    while stack:
        close()
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def window_start(planes: dict) -> Optional[float]:
    """Where the harness marked the window's opening, on the trace's clock."""
    for name, lines in planes.items():
        if is_device_plane(name):
            continue
        for events in lines.values():
            for event in events:
                if event[0] == WINDOW_OPEN_MARK:
                    return event[1]
    return None


def clip(events, start: float, end: float):
    return [(n, max(s, start), min(e, end)) for n, s, e in events if e > start and s < end]


def device_summary(planes: dict, window_s: float, n_devices: int) -> Optional[Dict[str, Any]]:
    """Over the traced window (from the opening mark, ``window_s`` long):
    busy seconds (union of the intervals in which an operation ran, averaged
    over the chips used), self seconds by operation, collective seconds, the
    modules' seconds, and the merged busy intervals of the first chip.
    ``None`` where the trace holds no device plane: nothing ran on a chip."""
    start = window_start(planes)
    busy, by_op, collective, first_busy, modules = [], {}, [], None, {}
    for name in sorted(p for p in planes if is_device_plane(p))[:n_devices]:
        ops = planes[name].get("XLA Ops", [])
        if start is None and ops:
            start = min(s for _n, s, _e in ops)
        ops = clip(ops, start, start + window_s) if ops else ops
        seconds, merged = union_seconds([(s, e) for _n, s, e in ops])
        busy.append(seconds)
        mine = self_seconds(ops)
        collective.append(sum(v for k, v in mine.items() if k.startswith(COLLECTIVE_PREFIXES)))
        if first_busy is None:
            first_busy, by_op = merged, mine
            for module, s, e in clip(planes[name].get("XLA Modules", []), start, start + window_s):
                modules[module] = modules.get(module, 0.0) + (e - s)
    if not busy:
        return None
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "start": start,
        "top_ops": [[k, v] for k, v in top],
        "collective_s": sum(collective) / len(collective),
        "modules": modules,
        "busy_intervals": first_busy,
    }


def host_spans(planes: dict, prefix: str = "Time/") -> List[Tuple[str, float, float]]:
    """The program's spans as the profiler saw them (its annotations), on the
    trace's own clock."""
    out = []
    for name, lines in planes.items():
        if is_device_plane(name):
            continue
        for events in lines.values():
            out += [e for e in events if e[0].startswith(prefix)]
    return out


def idle_gaps_by_span(busy: List[Tuple[float, float]], spans: List[Tuple[str, float, float]],
                      start: float, end: float) -> List[list]:
    """Idle device seconds between ``start`` and ``end`` by the innermost host
    span that covered them; at most the ten largest."""
    gaps, cursor = [], start
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, min(s, end)))
        cursor = max(cursor, e)
    if cursor < end:
        gaps.append((cursor, end))
    totals: Dict[str, float] = {}
    spans = sorted(spans, key=lambda x: x[1])
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        covering = [sp for sp in spans if sp[1] < g1 and sp[2] > g0]
        cuts = sorted({g0, g1, *(t for sp in covering for t in sp[1:] if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [sp for sp in covering if sp[1] <= mid < sp[2]]
            name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "unattributed"
            totals[name] = totals.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:10]]


# ---------------------------------------------------------------------------
# one run, as the metric readers see it
# ---------------------------------------------------------------------------


@dataclass
class RunRecord:
    workload: dict
    config: dict
    traffic: dict
    chips: int
    recorder: Any
    end_to_end: Dict[str, float]
    marks: Dict[str, Any]
    memory_peak: int
    run_dir: str
    mirror_spans: List[Tuple[float, float]]
    tracer: Optional[DeviceTrace]
    device_kind: str
    n_envs: int
    _cache: Dict[str, Any] = field(default_factory=dict)

    # -- host spans and counters over the window ---------------------------------

    def spans(self) -> List[Tuple[str, float, float]]:
        if "spans" not in self._cache:
            path = os.path.join(self.run_dir, "spans.jsonl")
            sync_at = self.marks.get("span_origin")
            self._cache["spans"] = read_spans(path, sync_at) if sync_at and os.path.exists(path) else []
        return self._cache["spans"]

    def span_ms(self, name: str) -> List[float]:
        """Durations of the window's spans called ``name``, milliseconds."""
        lo, hi = self.recorder.opened_at, self.recorder.closed_at
        return [(e - s) * 1e3 for n, s, e in self.spans() if n == name and s >= lo and e <= hi]

    def counter_delta(self, name: str) -> Optional[float]:
        before, after = self.marks.get("counters_open", {}), self.marks.get("counters_close", {})
        if name not in before or name not in after:
            return None
        return after[name] - before[name]

    def compiles(self, when: str) -> Dict[str, float]:
        return self.marks[f"compiles_{when}"]

    @property
    def bursts(self) -> int:
        return self.recorder.cycles

    def mirror_ms(self) -> List[float]:
        lo, hi = self.recorder.opened_at, self.recorder.closed_at
        return [(e - s) * 1e3 for s, e in self.mirror_spans if s >= lo and e <= hi]

    # -- the device trace -----------------------------------------------------------

    def planes(self) -> dict:
        if "planes" not in self._cache:
            self._cache["planes"] = read_planes(self.tracer.path())
        return self._cache["planes"]

    def device_summary(self) -> Optional[Dict[str, Any]]:
        if "summary" not in self._cache:
            self._cache["summary"] = device_summary(self.planes(), self.recorder.window_s, self.chips)
        return self._cache["summary"]

    def train_device_seconds(self) -> Optional[float]:
        """Device seconds of the train program's module in the trace."""
        summary = self.device_summary()
        if summary is None:
            return None
        mine = [v for k, v in summary["modules"].items() if "local_burst" in k or "local_step" in k]
        return sum(mine) if mine else None

    def idle_gaps(self) -> List[list]:
        summary = self.device_summary()
        if summary is None or not summary["busy_intervals"]:
            return []
        start = summary["start"]
        return idle_gaps_by_span(
            summary["busy_intervals"], host_spans(self.planes()), start, start + summary["window_s"]
        )


def p50(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None
