"""Runtime telemetry: phase spans, device/transfer/recompile counters, and a
run-health monitor.

The observability layer the ROADMAP's data-path rounds are judged against —
it *measures* the host→HBM staging path, XLA recompiles, HBM occupancy, and
per-phase wall time instead of inferring them from wall-clock deltas. Four
pieces (see ``howto/telemetry.md``):

- :mod:`~sheeprl_tpu.obs.spans` — Chrome trace-event spans layered on the
  global ``timer`` registry, mirrored into XLA profiles;
- :mod:`~sheeprl_tpu.obs.counters` — host→HBM byte accounting, a
  ``jax.monitoring`` recompile listener, and a device-memory poller;
- :mod:`~sheeprl_tpu.obs.health` — NaN/inf guards on logged losses and a
  stall watchdog for decoupled player↔trainer threads;
- :mod:`~sheeprl_tpu.obs.perf` — the shared ``Time/sps_*`` / ``Perf/mfu``
  gauge plumbing every entrypoint logs through;
- :mod:`~sheeprl_tpu.obs.hist` — mergeable log-bucket streaming histograms
  of every span duration (per-phase ``p50/p95/p99``);
- :mod:`~sheeprl_tpu.obs.live` — the live plane: periodic atomic
  ``telemetry/live.json`` snapshots, an optional Prometheus endpoint, and
  the anomaly-triggered flight recorder;
- :mod:`~sheeprl_tpu.obs.learn` — learning-health: in-jit training-dynamics
  probes (grad/param/update norms, clip fraction, non-finite counts) and the
  divergence early-warning sentinel (``howto/learning_health.md``);
- :mod:`~sheeprl_tpu.obs.prof` — device-time profiling: in-run xplane
  capture + parsing, per-module attribution, and the roofline
  (MFU / bandwidth / binding-constraint) accounting
  (``howto/profiling.md``).

Everything is configured by the ``metric.telemetry`` config group and
defaults to off; disabled, the instrumented code paths reduce to the plain
``timer`` registry with no extra file handles, threads, or device syncs.
"""

from sheeprl_tpu.obs.counters import (
    Counters,
    DevicePoller,
    add_ckpt_blocked_ms,
    add_ckpt_write,
    add_env_async_steps,
    add_env_degraded,
    add_env_worker_restart,
    add_h2d_bytes,
    add_plane_player_restart,
    add_plane_slabs,
    add_prefetch,
    add_ring_gather,
    add_rollout_burst,
    count_h2d,
    device_memory_stats,
    note_plane_policy_version,
    set_shard_footprint,
    staged_device_put,
    tree_nbytes,
)
from sheeprl_tpu.obs.dist.comms import collective_span, pmean, psum
from sheeprl_tpu.obs.dist.staleness import StalenessTracker
from sheeprl_tpu.obs.health import NonFiniteGuard, StallWatchdog
from sheeprl_tpu.obs.hist import HistogramSet, StreamingHist
from sheeprl_tpu.obs.learn import (
    LearnSentinel,
    learn_probes,
    observe_probes,
    probes_enabled,
    split_probes,
)
from sheeprl_tpu.obs.live import (
    FlightRecorder,
    LiveExporter,
    PromServer,
    profiler_capture,
    prometheus_text,
)
from sheeprl_tpu.obs.perf import (
    cost_flops,
    log_sps_metrics,
    mfu_pct,
    register_train_cost,
    shape_specs,
)
from sheeprl_tpu.obs.prof.capture import profile_tick
from sheeprl_tpu.obs.spans import TraceWriter, get_tracer, set_tracer, span
from sheeprl_tpu.obs.telemetry import (
    Telemetry,
    finalize_telemetry,
    get_telemetry,
    setup_telemetry,
)

__all__ = [
    "Counters",
    "DevicePoller",
    "FlightRecorder",
    "HistogramSet",
    "LearnSentinel",
    "LiveExporter",
    "NonFiniteGuard",
    "PromServer",
    "StalenessTracker",
    "StallWatchdog",
    "StreamingHist",
    "Telemetry",
    "TraceWriter",
    "add_ckpt_blocked_ms",
    "add_ckpt_write",
    "add_env_async_steps",
    "add_env_degraded",
    "add_env_worker_restart",
    "add_h2d_bytes",
    "add_plane_player_restart",
    "add_plane_slabs",
    "add_prefetch",
    "add_ring_gather",
    "add_rollout_burst",
    "collective_span",
    "count_h2d",
    "cost_flops",
    "device_memory_stats",
    "finalize_telemetry",
    "get_telemetry",
    "get_tracer",
    "learn_probes",
    "log_sps_metrics",
    "mfu_pct",
    "note_plane_policy_version",
    "observe_probes",
    "probes_enabled",
    "set_shard_footprint",
    "pmean",
    "profile_tick",
    "profiler_capture",
    "prometheus_text",
    "psum",
    "register_train_cost",
    "set_tracer",
    "setup_telemetry",
    "shape_specs",
    "span",
    "split_probes",
    "staged_device_put",
    "tree_nbytes",
]
