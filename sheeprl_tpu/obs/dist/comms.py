"""Measured collective communication: spans, counters, achieved GB/s.

Two layers, matching where a collective can actually be timed:

- **Host-level collectives** (``Fabric.all_reduce`` / ``all_gather`` /
  ``broadcast`` / ``barrier`` — cross-process, dispatched from Python):
  :func:`collective_span` wraps each call in a ``Time/comms_<kind>_time``
  span (per-kind p50/p95/p99 via the streaming histograms), counts payload
  bytes and wall milliseconds into the run counters (``comms_ms`` /
  ``comms_bytes`` / ``comms_ops`` + a per-kind breakdown in
  ``telemetry.json``), and reports achieved GB/s against the device-link
  peak registry (:func:`sheeprl_tpu.obs.prof.roofline.detect_link_peaks`).
- **In-jit collectives** (the gradient ``pmean`` inside every train
  program): a host span cannot time an op fused into an XLA program, so
  :func:`pmean`/:func:`psum` are *chokepoints*, not timers — one named
  place every algo routes its gradient sync through (enforced by
  ``tools/lint_telemetry.py``), while the measured device time comes from
  the xplane comms attribution (``obs/prof/xplane.summarize_space`` →
  ``comms_ms_per_step`` in profiled captures).

Wire-byte accounting uses the standard ring factors so the reported GB/s is
what the link actually carried, not just the payload: all-reduce moves
``2(n-1)/n × payload`` per participant, all-gather/broadcast ``(n-1)/n`` of
the gathered/broadcast bytes, a barrier ~nothing.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

__all__ = [
    "all_gather",
    "collective_span",
    "pmean",
    "psum",
    "record_collective",
    "wire_bytes",
]

#: collective kinds the counters break down by
KINDS = ("all_reduce", "all_gather", "broadcast", "barrier")


def wire_bytes(kind: str, payload_bytes: int, n: int) -> int:
    """Bytes a ring implementation moves per participant for ``payload``.

    ``n`` is the number of participants; with ``n <= 1`` nothing crosses a
    link. The factors are the textbook ring costs (the same ones
    ``tools/bench_scaling.py`` projects with): 2(n-1)/n for all-reduce
    (reduce-scatter + all-gather phases), (n-1)/n for all-gather and for a
    pipelined broadcast, 0 for a barrier."""
    if n <= 1 or payload_bytes <= 0:
        return 0
    if kind == "all_reduce":
        return int(payload_bytes * 2 * (n - 1) / n)
    if kind in ("all_gather", "broadcast"):
        return int(payload_bytes * (n - 1) / n)
    return 0


def record_collective(
    kind: str, payload_bytes: int, seconds: float, world: int = 1
) -> Optional[float]:
    """Record one completed host-level collective into the run counters.

    Returns the achieved wire GB/s (None when nothing crossed a link or the
    clock did not advance). No-op when telemetry is off."""
    from sheeprl_tpu.obs import counters as _counters

    c = _counters.installed()
    if c is None:
        return None
    wire = wire_bytes(kind, payload_bytes, world)
    gbps = (wire / seconds / 1e9) if (wire and seconds > 0) else None
    c.add_comms(kind, payload_bytes, seconds * 1e3, gbps)
    return gbps


@contextmanager
def collective_span(kind: str, payload_bytes: int = 0, world: Optional[int] = None):
    """Span + counter accounting around one host-level collective.

    The span feeds the per-kind streaming histogram and the trace timeline
    (``Time/comms_<kind>_time``, phase ``comms``); the counter side records
    payload/wire bytes, wall ms, and achieved GB/s. ``world`` defaults to
    ``jax.process_count()`` — the participants of the fabric's host-level
    collectives."""
    from sheeprl_tpu.obs.spans import span

    if world is None:
        try:
            import jax

            world = int(jax.process_count())
        except Exception:
            world = 1
    t0 = time.perf_counter()
    with span(f"Time/comms_{kind}_time", phase="comms"):
        yield
    record_collective(kind, int(payload_bytes), time.perf_counter() - t0, world)


def link_peak_gbps() -> Optional[float]:
    """This host's device-link peak GB/s (ICI for TPUs) from the roofline
    registry, or None for a device the registry does not list."""
    from sheeprl_tpu.obs.prof.roofline import detect_link_peaks

    return detect_link_peaks().get("link_gbps")


# -- in-jit chokepoints -------------------------------------------------------
#
# These are the ONLY way algo code may spell a traced collective
# (tools/lint_telemetry.py rejects raw jax.lax.* collectives in algos/).
# They cannot be host-timed — the op lowers into the XLA program — but going
# through one named seam means (a) the xplane parser's collective-op
# attribution (obs/prof) is the agreed measurement, and (b) a future
# latency-hiding rewrite (e.g. overlapping the gradient sync with the
# backward pass) is one edit, not seventeen.


def pmean(x: Any, axis_name: Optional[str]) -> Any:
    """Mean-all-reduce over a mesh axis inside a jitted program (the
    gradient sync every train step runs). Device time is attributed by the
    profiled-capture comms split, not a host span.

    ``axis_name=None`` is the identity: sharded-parameter train steps run as
    one *global* GSPMD program (no manual axis — the batch mean already spans
    the whole mesh and XLA inserts the gradient reduce-scatter itself)."""
    import jax

    if axis_name is None:
        return x
    return jax.lax.pmean(x, axis_name)


def psum(x: Any, axis_name: Optional[str]) -> Any:
    """Sum-all-reduce over a mesh axis inside a jitted program.
    ``axis_name=None`` is the identity (see :func:`pmean`)."""
    import jax

    if axis_name is None:
        return x
    return jax.lax.psum(x, axis_name)


def all_gather(x: Any, axis_name: str, **kwargs: Any) -> Any:
    """All-gather over a mesh axis inside a jitted program (DV3's Moments
    percentile gather)."""
    import jax

    return jax.lax.all_gather(x, axis_name, **kwargs)
