"""Roofline accounting: FLOPs + bytes-accessed vs measured device time.

PaLM-style MFU (Chowdhery et al., 2022) extended with the bandwidth side of
the roofline: for a profiled program we know its analytic cost
(``Compiled.cost_analysis()`` FLOPs and bytes accessed) and its measured
per-execution device time (``obs/prof/xplane.py``), so we can say — per XLA
module, per family — whether the hardware was bound by **compute** (MFU is
the ceiling), **HBM bandwidth** (achieved GB/s is the ceiling), or by
**dispatch gaps** (the device sat idle waiting on the host, and no kernel
work will help until the dispatch path does). ROADMAP item 4 needs exactly
this verdict per Dreamer family before choosing a Pallas target.

Peak numbers come from the one ``DEVICE_PEAKS`` table, keyed on
``device_kind``. A device that is not in the table (the host CPU included)
has no peak: its MFU, bandwidth utilization and ridge point are ``None`` —
not measured — never an estimate and never another device's number. An
explicit override (``metric.telemetry.peak_tflops`` /
``metric.telemetry.profile.peak_*``) always wins.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

__all__ = [
    "DEVICE_PEAKS",
    "LINK_PEAKS",
    "cost_bytes",
    "cost_of",
    "detect_link_peaks",
    "detect_peaks",
    "roofline_analyze",
]

#: device_kind pattern -> (peak TFLOP/s in bf16, peak HBM GB/s). Single-chip
#: numbers from the vendor datasheets (v5e: Google Cloud "TPU v5e" page —
#: 197 TFLOP/s bf16, 819 GB/s HBM); the MFU denominator stays the chip's
#: bf16 peak for 32-true programs too.
DEVICE_PEAKS = (
    (r"TPU v6|Trillium", {"label": "TPU v6e", "peak_tflops": 918.0, "peak_gbps": 1640.0}),
    (r"TPU v5p", {"label": "TPU v5p", "peak_tflops": 459.0, "peak_gbps": 2765.0}),
    (r"TPU v5|v5 ?lite", {"label": "TPU v5e", "peak_tflops": 197.0, "peak_gbps": 819.0}),
    (r"TPU v4", {"label": "TPU v4", "peak_tflops": 275.0, "peak_gbps": 1228.0}),
    (r"TPU v3", {"label": "TPU v3", "peak_tflops": 123.0, "peak_gbps": 900.0}),
    (r"TPU v2", {"label": "TPU v2", "peak_tflops": 46.0, "peak_gbps": 700.0}),
    (r"H100", {"label": "H100", "peak_tflops": 989.0, "peak_gbps": 3350.0}),
    (r"A100", {"label": "A100", "peak_tflops": 312.0, "peak_gbps": 2039.0}),
    (r"V100", {"label": "V100", "peak_tflops": 125.0, "peak_gbps": 900.0}),
    (r"RTX 3080|GeForce RTX 3080", {"label": "RTX 3080", "peak_tflops": 59.5, "peak_gbps": 760.0}),
)


#: device_kind pattern -> inter-chip link peak, GB/s per link per direction
#: (ICI for TPUs from the public specs; NVLink-generation numbers for the
#: GPUs). The comms instrumentation (obs/dist/comms.py) reports achieved
#: wire GB/s against this as `link_util_pct`.
LINK_PEAKS = (
    (r"TPU v6|Trillium", 90.0),
    (r"TPU v5p", 100.0),
    (r"TPU v5|v5 ?lite", 45.0),
    (r"TPU v4", 50.0),
    (r"TPU v3", 70.0),
    (r"TPU v2", 62.5),
    (r"H100", 450.0),
    (r"A100", 300.0),
    (r"V100", 150.0),
)


def _first_device(device: Any = None) -> Any:
    if device is not None:
        return device
    import jax

    return jax.devices()[0]


def detect_link_peaks(link_gbps: Optional[float] = None, device: Any = None) -> Dict[str, Any]:
    """Inter-chip link peak of ``device`` (default: the first jax device).

    Returns ``{label, device_kind, link_gbps}``; ``link_gbps`` is ``None``
    for a device that is not in ``LINK_PEAKS`` (a CPU mesh has no link to
    rate against). An explicit ``link_gbps`` override always wins."""
    kind = _first_device(device).device_kind
    out: Dict[str, Any] = {"device_kind": kind, "label": kind, "link_gbps": None}
    for pattern, gbps in LINK_PEAKS:
        if re.search(pattern, kind, re.I):
            out["link_gbps"] = gbps
            break
    if link_gbps:
        out["link_gbps"] = float(link_gbps)
    return out


def detect_peaks(
    peak_tflops: Optional[float] = None,
    peak_gbps: Optional[float] = None,
    device: Any = None,
) -> Dict[str, Any]:
    """Peak numbers of ``device`` (default: the first jax device).

    Returns ``{label, platform, device_kind, peak_tflops, peak_gbps}``. Both
    peaks are ``None`` for a device that is not in ``DEVICE_PEAKS``;
    explicit overrides win over the table."""
    dev = _first_device(device)
    peaks: Dict[str, Any] = {
        "label": dev.device_kind,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "peak_tflops": None,
        "peak_gbps": None,
    }
    for pattern, entry in DEVICE_PEAKS:
        if re.search(pattern, dev.device_kind, re.I):
            peaks.update(entry)
            break
    if peak_tflops:
        peaks["peak_tflops"] = float(peak_tflops)
    if peak_gbps:
        peaks["peak_gbps"] = float(peak_gbps)
    return peaks


# -- cost analysis ------------------------------------------------------------


def _analysis_dict(compiled) -> Dict[str, Any]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return ca


def cost_bytes(compiled) -> float:
    """Bytes accessed by a compiled XLA module per ``cost_analysis()`` (the
    HBM traffic bound; same while-loop body-once caveat as ``cost_flops``)."""
    return float(_analysis_dict(compiled).get("bytes accessed", 0.0))


def cost_of(jit_fn, *args, **kwargs) -> Optional[Dict[str, float]]:
    """``{"flops", "bytes_accessed"}`` of ``jit_fn(*args)`` via AOT
    lower+compile, or None when the backend has no cost model (tests assert
    the None path — a missing cost analysis must never break a run).

    Pass :func:`~sheeprl_tpu.obs.perf.shape_specs` of the arguments rather
    than live arrays when the call donates buffers."""
    try:
        ca = _analysis_dict(jit_fn.lower(*args, **kwargs).compile())
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
    except Exception:
        return None


# -- the verdict --------------------------------------------------------------


def roofline_analyze(
    flops_per_exec: Optional[float],
    bytes_per_exec: Optional[float],
    device_ms_per_exec: Optional[float],
    busy_frac: Optional[float] = None,
    peaks: Optional[Dict[str, Any]] = None,
    dispatch_busy_threshold: float = 0.5,
) -> Dict[str, Any]:
    """Classify one program's binding constraint from its measured roofline.

    Rules, in order:

    - no measured device time -> ``unmeasured`` (nothing else is computable);
    - the device was busy less than ``dispatch_busy_threshold`` of the
      profiled window -> ``dispatch-bound`` (the step path waits on the
      host; per-module utilization is still reported but is not the
      constraint);
    - otherwise, whichever of compute utilization (MFU) and bandwidth
      utilization is higher is the wall being pushed: ``compute-bound`` or
      ``memory-bound``. With no cost analysis available the verdict degrades
      to ``unknown``.

    Returns ``{mfu_pct, achieved_gbps, bandwidth_util_pct,
    arithmetic_intensity, ridge_intensity, verdict, peaks}``.
    """
    peaks = peaks or detect_peaks()
    out: Dict[str, Any] = {
        "mfu_pct": None,
        "achieved_gbps": None,
        "bandwidth_util_pct": None,
        "arithmetic_intensity": None,
        "ridge_intensity": None,
        "verdict": "unmeasured",
        "peaks": peaks,
    }
    peak_tflops, peak_gbps = peaks.get("peak_tflops"), peaks.get("peak_gbps")
    if peak_tflops and peak_gbps:
        out["ridge_intensity"] = round(peak_tflops * 1e12 / (peak_gbps * 1e9), 1)
    if not device_ms_per_exec or device_ms_per_exec <= 0:
        return out
    seconds = device_ms_per_exec / 1e3
    if flops_per_exec and bytes_per_exec:
        out["arithmetic_intensity"] = round(flops_per_exec / bytes_per_exec, 2)
    if flops_per_exec and peak_tflops:
        out["mfu_pct"] = round(
            flops_per_exec / seconds / (peak_tflops * 1e12) * 100.0, 3
        )
    if bytes_per_exec:
        out["achieved_gbps"] = round(bytes_per_exec / seconds / 1e9, 2)
        if peak_gbps:
            out["bandwidth_util_pct"] = round(
                out["achieved_gbps"] / peak_gbps * 100.0, 3
            )
    if busy_frac is not None and busy_frac < dispatch_busy_threshold:
        out["verdict"] = "dispatch-bound"
    elif out["mfu_pct"] is None and out["bandwidth_util_pct"] is None:
        out["verdict"] = "unknown"
    elif (out["mfu_pct"] or 0.0) >= (out["bandwidth_util_pct"] or 0.0):
        out["verdict"] = "compute-bound"
    else:
        out["verdict"] = "memory-bound"
    return out
