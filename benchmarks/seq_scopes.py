"""The sequence core's parts of the train program's device time, and its two
kernels', beside ``scopes.py`` (which this calls into and does not change).

The program names the core's operations ``dv3/core/<part>`` (``gdn``, ``attn``,
``moe``, ``head``); the delta rule's own operations carry ``delta_rule`` below
``dv3/core/gdn``, and the grouped products are the device operations called
``ragged-dot...``: all of the module's, the window pass's and imagination's
one-token steps' alike, under ``kernel/ragged_dot``. The compiler makes them
and names them itself (``ragged-dot-none``), and a device trace gives the loop
round them (the expert layer's ``while`` over its sorted pairs) no scope at
all; the loop's other operations (its gather, its masks) carry the program's,
so a grouped product takes the scope of the operation before it in the same
loop, and counts under ``core/moe`` unasked only where none has one. The parts
hold the window pass alone: imagination's one-token steps carry
``dv3/imagination`` first, and stay there. A program without these scopes (the
parent of the PR that brought them) reads as ``None``.
"""

from __future__ import annotations

import importlib
import re
from typing import Callable, Dict, List, Optional

from benchmarks import reduce, scopes

_FIRST = re.compile(r"dv3/([a-z_]+)(?:/([a-z_]+))?")
_PROGRAM = re.compile(r"\((\d+)\)\s*$")
_GROUPED = "ragged-dot"


def with_scopes(events: List[scopes.Event], scope_of: Callable[[str], str]) -> List[scopes.Event]:
    """The events renamed ``operation\\0scope``. A grouped product whose own
    scope names no ``dv3/`` part takes the last one that an operation before
    it gave inside the innermost operation whose interval holds both."""
    out, running = [], []  # running: [end, the last dv3 scope seen inside], the last started on top
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while running and running[-1][0] <= start:
            running.pop()
        scope = scope_of(name)
        holder = next((r for r in reversed(running) if r[0] >= end), None)
        if holder is not None and _FIRST.search(scope):
            holder[1] = scope
        elif holder is not None and name.startswith(_GROUPED):
            scope = holder[1] or scope
        running.append([end, ""])
        out.append((f"{name}\0{scope}", start, end))
    return out


def seconds(run) -> Optional[Dict[str, float]]:
    """Exclusive seconds of the train module's operations in the traced window:
    ``core/<part>``, ``kernel/delta_rule``, ``kernel/ragged_dot``."""
    if "seq_parts" in run._cache:
        return run._cache["seq_parts"]
    out: Optional[Dict[str, float]] = None
    summary = run.device_summary()
    if summary is not None:
        plane = sorted(p for p in run.planes() if reduce.is_device_plane(p))[0]
        lo, hi = summary["start"], summary["start"] + summary["window_s"]
        lines = run.planes()[plane]
        modules = [m for m in reduce.clip(lines.get("XLA Modules", []), lo, hi)
                   if "local_burst" in m[0] or "local_step" in m[0]]
        ops = reduce.clip(lines.get("XLA Ops", []), lo, hi)
        names = scopes.op_scopes(run.tracer.path()).get(plane, {})
        found: Dict[str, float] = {}
        for module, start, end in modules:
            program = _PROGRAM.search(module)
            program = int(program.group(1)) if program else 0
            inside = with_scopes([e for e in ops if start <= e[1] < end],
                                 lambda op: names.get((program, op)) or "")
            for named, seconds in scopes.exclusive_seconds(inside).items():
                op, scope = named.split("\0")
                first = _FIRST.search(scope)
                grouped = op.startswith(_GROUPED)
                if grouped:
                    found["kernel/ragged_dot"] = found.get("kernel/ragged_dot", 0.0) + seconds
                if first and first.group(1) == "core" and first.group(2):
                    found[f"core/{first.group(2)}"] = found.get(f"core/{first.group(2)}", 0.0) + seconds
                    if "delta_rule" in scope:
                        found["kernel/delta_rule"] = found.get("kernel/delta_rule", 0.0) + seconds
                elif grouped and first is None:
                    found["core/moe"] = found.get("core/moe", 0.0) + seconds
        out = found or None
    run._cache["seq_parts"] = out
    return out


def work_counts(run):
    """The configuration's module of required operations and bytes
    (``config["flops"]``), or ``None`` where the configuration names none."""
    name = run.config.get("flops")
    return importlib.import_module(name) if name else None


def roofline_pct(run, kernel: str, flops: float, nbytes: float) -> Optional[float]:
    """The least time the chip could take for ``flops`` and ``nbytes`` over
    the device seconds of ``kernel``'s operations, in per cent."""
    found = seconds(run)
    if not found or not found.get(kernel):
        return None
    peaks = reduce.DEVICE_PEAKS[run.device_kind]
    least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / found[kernel]


def part_ms_per_grad_step(run, part: str) -> Optional[float]:
    found = seconds(run)
    if found is None or part not in found or not run.recorder.grad_steps:
        return None
    return found[part] * 1e3 / run.recorder.grad_steps


def core_counts(run) -> Optional[Dict[str, float]]:
    """The sequence core's counters over the window, or ``None`` without them."""
    before = run.marks.get("counters_open", {}).get("seq_core")
    after = run.marks.get("counters_close", {}).get("seq_core")
    if not after or not after.get("steps"):
        return None
    before = before or {}
    delta = {k: v - before.get(k, 0.0) for k, v in after.items() if k != "state_bytes_per_env"}
    delta["state_bytes_per_env"] = after.get("state_bytes_per_env")
    return delta if delta.get("steps") else None
