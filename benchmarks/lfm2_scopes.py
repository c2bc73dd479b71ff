"""The device time of the convolution-attention core's two kernels in the
train module's window pass, beside ``seq_scopes.py`` (which this calls into
and does not change).

The program names the two gates and the convolution between them
``dv3/core/conv/gate_conv`` and the blocked scores, softmax and weighted sum
(and their transposes) ``dv3/core/attn/scores``. Imagination's one-token steps
run the same gates under ``dv3/imagination`` first and are not counted: the
work functions count the window pass's tokens. A program without these scopes
(the parent of the PR that brought them, or another core) reads as ``None``.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import reduce, scopes, seq_scopes

KERNELS = {"kernel/gate_conv": "core/conv/gate_conv", "kernel/gqa_scores": "core/attn/scores"}


def seconds(run) -> Optional[Dict[str, float]]:
    """Exclusive seconds of the train module's window-pass operations under
    each of :data:`KERNELS`' scopes in the traced window."""
    if "lfm2_kernels" in run._cache:
        return run._cache["lfm2_kernels"]
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
            program = seq_scopes._PROGRAM.search(module)
            program = int(program.group(1)) if program else 0
            inside = seq_scopes.with_scopes([e for e in ops if start <= e[1] < end],
                                            lambda op: names.get((program, op)) or "")
            for named, spent in scopes.exclusive_seconds(inside).items():
                scope = named.split("\0")[1]
                first = seq_scopes._FIRST.search(scope)
                if first is None or first.group(1) != "core":
                    continue
                for kernel, mark in KERNELS.items():
                    if mark in scope:
                        found[kernel] = found.get(kernel, 0.0) + spent
        out = found or None
    run._cache["lfm2_kernels"] = out
    return out


def roofline_pct(run, kernel: str, flops: float, nbytes: float) -> Optional[float]:
    """The least time the chip could take for ``flops`` and ``nbytes`` over
    the device seconds of ``kernel``'s operations, in per cent."""
    found = seconds(run)
    if not found or not found.get(kernel):
        return None
    peaks = reduce.DEVICE_PEAKS[run.device_kind]
    least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / found[kernel]
