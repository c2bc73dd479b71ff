"""The device time of the latent-attention core's two kernels in the train
module, beside ``seq_scopes.py`` (which this calls into and does not change).

The program names the window pass's scores, softmax and weighted sum (and
their transposes) ``dv3/core/mla/scores`` and the absorbed one-token attention
over the latent cache ``.../mla/latent_decode`` wherever it runs; in the train
module that is imagination's steps (acting's program is another module and is
not counted). A program without these scopes (the parent of the PR that
brought them, or another core) reads as ``None``.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import reduce, scopes, seq_scopes

KERNELS = {"kernel/mla_scores": "mla/scores", "kernel/latent_decode": "mla/latent_decode"}
_PROGRAM = re.compile(r"\((\d+)\)\s*$")


def seconds(run) -> Optional[Dict[str, float]]:
    """Exclusive seconds of the train module's operations under each of
    :data:`KERNELS`' scopes in the traced window."""
    if "mla_kernels" in run._cache:
        return run._cache["mla_kernels"]
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
            inside = seq_scopes.with_scopes([e for e in ops if start <= e[1] < end],
                                            lambda op: names.get((program, op)) or "")
            for named, spent in scopes.exclusive_seconds(inside).items():
                scope = named.split("\0")[1]
                for kernel, mark in KERNELS.items():
                    if mark in scope:
                        found[kernel] = found.get(kernel, 0.0) + spent
        out = found or None
    run._cache["mla_kernels"] = out
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
