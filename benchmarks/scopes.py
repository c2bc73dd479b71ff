"""The program's own spans per burst, and the train program's device time by part.

Two readings that ``reduce.py`` does not make, for the metric readers that
need them. Nothing of ``reduce.py`` is changed: this module calls into it.

- Host side: a span the program wrote (``Time/...`` in its span file), summed
  over the window and divided by its bursts.
- Device side: the seconds of the operations that ran inside the train
  program's module, each instant counted once, grouped by the ``dv3/<part>``
  scope the program gave them with ``jax.named_scope``. The profiler keeps an operation's scope in the
  ``tf_op`` stat of its *event metadata*, which ``jax.profiler.ProfileData``
  does not hand out (it gives an event's own stats only), so the metadata
  tables of the xplane file are read here, with a decoder of the protobuf
  wire format that knows the few fields it needs. A test holds it to a trace
  recorded on the chip.

A program without the spans or the scopes (the parent of the PR that brought
them, or an executable that came from a compile cache without its metadata)
reads as ``None``: the metric is left out of the line, never written as 0.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks import reduce

_PART = re.compile(r"dv3/([a-z_]+)")
_PROGRAM = re.compile(r"\((\d+)\)\s*$")
Event = Tuple[str, float, float]


# ---------------------------------------------------------------------------
# host side: the program's spans
# ---------------------------------------------------------------------------


def span_ms_per_burst(run, *names: str) -> Optional[float]:
    """Milliseconds a burst spent in the spans called ``names``, or ``None``
    where the window holds no such span."""
    spans = [ms for name in names for ms in run.span_ms(name)]
    return sum(spans) / run.bursts if spans and run.bursts else None


# ---------------------------------------------------------------------------
# device side: the scope of each operation, from the xplane file
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one protobuf message: a varint
    as an int, a length-delimited field as bytes; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _map_value(entry: bytes) -> bytes:
    """The value of one ``map<int64, message>`` entry."""
    return next((v for f, _w, v in _fields(entry) if f == 2), b"")


def op_scopes(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """``device plane -> {(program id, operation): scope}`` of an xplane file:
    every operation's ``tf_op``, the name stack it was traced under."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[Tuple[int, str], str]] = {}
    for field, _w, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, stat_names, metadata = "", {}, []
        for f, _w, value in _fields(plane):
            if f == 2:  # XPlane.name
                name = value.decode()
            elif f == 4:  # XPlane.event_metadata
                metadata.append(_map_value(value))
            elif f == 5:  # XPlane.stat_metadata: id = 1, name = 2
                stat = dict((g, v) for g, _w, v in _fields(_map_value(value)) if g in (1, 2))
                stat_names[stat.get(1, 0)] = stat.get(2, b"").decode()
        if not reduce.is_device_plane(name):
            continue
        scopes = out.setdefault(name, {})
        for meta in metadata:
            op, program, scope = "", 0, None
            for f, _w, value in _fields(meta):
                if f == 2:  # XEventMetadata.name: the operation's HLO text
                    op = reduce.short_name(value.decode())
                elif f == 5:  # XEventMetadata.stats
                    stat = list(_fields(value))
                    kind = stat_names.get(next((v for g, _w, v in stat if g == 1), 0))
                    if kind == "tf_op":
                        scope = next((v for g, _w, v in stat if g == 5), b"").decode()  # str_value
                    elif kind == "program_id":
                        program = next((v for g, _w, v in stat if g == 3), 0)  # uint64_value
            if scope is not None:
                scopes[(program, op)] = scope
    return out


def part_of(scope: Optional[str]) -> Optional[str]:
    """The first ``dv3/<part>`` of a scope: backward operations carry their
    forward's as ``transpose(jvp(dv3/<part>))``."""
    found = _PART.search(scope or "")
    return found.group(1) if found else None


def exclusive_seconds(events: List[Event]) -> Dict[str, float]:
    """Seconds by event name, every instant given once: to the operation that
    started last among those running. A ``while`` so loses its body, as in
    ``reduce.self_seconds``; and where two operations of one line overlap
    without nesting (on the chip the train module's self times summed to 5–8 %
    more than the module took, most of it in ``copy-done`` and other
    operations the compiler put beside the fusions) the overlap is counted
    for the later one only. The total is the union of the events' intervals."""
    out: Dict[str, float] = {}
    running: List[Tuple[str, float]] = []  # (name, end), the last started on top
    cursor = 0.0

    def give(name: str, until: float) -> None:
        nonlocal cursor
        if until > cursor:
            out[name] = out.get(name, 0.0) + (until - cursor)
            cursor = until

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while running and running[-1][1] <= start:
            give(*running.pop())
        if running:
            give(running[-1][0], start)
        cursor = max(cursor, start)
        running.append((name, end))
        out.setdefault(name, 0.0)
    while running:
        give(*running.pop())
    return out


def part_seconds(ops: List[Event], modules: List[Event],
                 scopes: Dict[Tuple[int, str], str]) -> Optional[Dict[str, float]]:
    """:func:`exclusive_seconds` of the operations that ran inside ``modules``
    (the train program's events of the ``XLA Modules`` line), by part; what
    carries no ``dv3/`` scope under ``"unscoped"``, the modules' whole seconds
    under ``"module"``. ``None`` where not one operation carries a scope."""
    out: Dict[str, float] = {"unscoped": 0.0, "module": 0.0}
    scoped = False
    for module, start, end in modules:
        found = _PROGRAM.search(module)
        program = int(found.group(1)) if found else 0
        out["module"] += end - start
        inside = [e for e in ops if start <= e[1] < end]
        for op, seconds in exclusive_seconds(inside).items():
            part = part_of(scopes.get((program, op)))
            scoped = scoped or part is not None
            out[part or "unscoped"] = out.get(part or "unscoped", 0.0) + seconds
    return out if scoped else None


def train_parts(run) -> Optional[Dict[str, float]]:
    """:func:`part_seconds` of a traced run's window, on its first chip."""
    if "dv3_parts" in run._cache:
        return run._cache["dv3_parts"]
    summary, parts = run.device_summary(), None
    if summary is not None:
        plane = sorted(p for p in run.planes() if reduce.is_device_plane(p))[0]
        lo, hi = summary["start"], summary["start"] + summary["window_s"]
        lines = run.planes()[plane]
        # the train program's modules, by the rule of RunRecord.train_device_seconds
        modules = [m for m in reduce.clip(lines.get("XLA Modules", []), lo, hi)
                   if "local_burst" in m[0] or "local_step" in m[0]]
        ops = reduce.clip(lines.get("XLA Ops", []), lo, hi)
        parts = part_seconds(ops, modules, op_scopes(run.tracer.path()).get(plane, {}))
        if parts is None:
            print("scopes: no operation of the train module carries a dv3/ scope", file=sys.stderr)
    run._cache["dv3_parts"] = parts
    return parts


def part_ms_per_grad_step(run, part: str) -> Optional[float]:
    parts = train_parts(run)
    if parts is None or not run.recorder.grad_steps:
        return None
    return parts.get(part, 0.0) * 1e3 / run.recorder.grad_steps
