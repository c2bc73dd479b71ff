"""SPS / MFU gauges — the one implementation every entrypoint logs through.

Before this module the ``Time/sps_*`` block was copy-pasted across all 17
algorithm entrypoints and MFU lived only in ``bench_dreamer.py``; the copies
had already drifted (bare division vs ``max(..., 1e-9)`` guards).
:func:`log_sps_metrics` is now the single computation — entrypoints call it
at their log boundary and ``tools/lint_telemetry.py`` fails CI if one grows
its own ``Time/sps_`` literal again. The benches import the same FLOPs/MFU
helpers, so benchmark numbers and run telemetry cannot disagree on the
formula.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

__all__ = [
    "cost_flops",
    "log_sps_metrics",
    "mfu_pct",
    "register_train_cost",
    "shape_specs",
]


def cost_flops(compiled) -> float:
    """FLOPs of a compiled XLA module per ``Compiled.cost_analysis()``.

    Caveat inherited by every consumer: XLA counts a while-loop *body once*
    regardless of trip count, so scan-heavy programs under-report (the
    Dreamer benches add per-family scan-body corrections on top of this).
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0))


def shape_specs(tree: Any) -> Any:
    """Abstract (shape, dtype) specs of a pytree of arrays — safe to keep
    around after the concrete (possibly donated) buffers are gone."""
    import jax
    import numpy as np

    def spec(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return jax.ShapeDtypeStruct((), np.asarray(x).dtype)

    return jax.tree_util.tree_map(spec, tree)


def _reference_twin(jit_fn):
    """A reference-tier twin of ``jit_fn`` for cost analysis, or None.

    When a fused recurrent-core tier is active (``sheeprl_tpu/kernels``) the
    train program may contain Pallas custom calls, which XLA's cost model
    scores as zero FLOPs, or padded-lane matmuls (600→640), which it scores
    as *more* FLOPs than the model actually defines. Either way the
    registered cost — and with it MFU and the roofline numerators — would
    change with the kernel tier. Model FLOPs are a property of the model,
    not of the kernel strategy (the PaLM-MFU convention), so when a fused
    tier is active we lower a twin program instead: a fresh ``jax.jit`` of
    the wrapped python body, traced under
    :func:`~sheeprl_tpu.kernels.reference_cost_mode` so the kernel
    dispatchers take the reference path at trace time.
    """
    from sheeprl_tpu import kernels

    if not kernels.fused_active():
        return None
    raw = getattr(jit_fn, "__wrapped__", None)
    if raw is None:
        return None
    import jax

    def _ref(*args):
        with kernels.reference_cost_mode():
            return raw(*args)

    return jax.jit(_ref)


def register_train_cost(
    telemetry, jit_fn, *specs, world_size: int = 1, dispatches_per_step: int = 1
) -> None:
    """One AOT cost analysis of the train program, registered with the run
    telemetry per train-step *unit*.

    The step counter advances by ``world_size`` per *training block*, which
    dispatches the analyzed program ``dispatches_per_step`` times (1 for the
    fused-burst families — DV3, SAC, PPO; ``per_rank_gradient_steps`` for
    the families that loop a single-gradient-step program — DV1, DV2, P2E).
    Registered cost = program cost × dispatches / world_size, so
    ``flops_per_train_step × Δtrain_step`` is the per-device work actually
    executed — the MFU numerator against the single-chip peak, and (with
    bytes accessed) the roofline numerators for the in-run profiler
    (``obs/prof``). Entrypoints call this once, gated on
    :meth:`~sheeprl_tpu.obs.telemetry.Telemetry.needs_train_flops`; a
    backend without a cost model records the attempt and stays quiet.
    """
    if telemetry is None:
        return
    from sheeprl_tpu.obs.prof.roofline import cost_of

    cost = None
    ref_fn = _reference_twin(jit_fn)
    if ref_fn is not None:
        cost = cost_of(ref_fn, *specs)
    if not (cost and cost.get("flops")):
        # no fused tier active, or the twin couldn't lower (e.g. the train
        # callable isn't a plain jit wrapper): fall back to the program as-is
        cost = cost_of(jit_fn, *specs)
    ws = max(int(world_size), 1)
    dps = max(int(dispatches_per_step), 1)
    if cost and cost.get("flops"):
        telemetry.set_train_cost(
            cost["flops"] * dps / ws,
            (cost.get("bytes_accessed") or 0.0) * dps / ws or None,
            dispatches_per_step=dps,
        )
    else:
        telemetry.set_train_cost(None, None)


def mfu_pct(
    flops_per_step: Optional[float],
    steps: float,
    seconds: Optional[float],
    peak_tflops: Optional[float],
) -> Optional[float]:
    """Model FLOPs utilization in percent, or None when unmeasurable — which
    includes a device with no entry in ``obs.prof.roofline.DEVICE_PEAKS``
    (``peak_tflops`` None): no peak, no MFU."""
    if not flops_per_step or not seconds or seconds <= 0 or steps <= 0 or not peak_tflops:
        return None
    return round(flops_per_step * steps / seconds / (peak_tflops * 1e12) * 100.0, 3)


def log_sps_metrics(
    logger,
    *,
    policy_step: int,
    last_log: int,
    train_step: int = 0,
    last_train: int = 0,
    world_size: int = 1,
    action_repeat: int = 1,
) -> Dict[str, float]:
    """Compute the standard rate gauges from the global timer registry, log
    them, and feed the run telemetry.

    Reads-and-resets the registry (the ``timer.compute()`` contract), so call
    exactly once per log boundary. Returns the gauges that were logged:
    ``Time/sps_train`` (train steps per second of timed train wall),
    ``Time/sps_env_interaction`` (per-process env steps × action_repeat per
    second of timed interaction wall), and — when the algorithm registered
    its per-train-step FLOPs with the telemetry — ``Perf/mfu``.
    """
    from sheeprl_tpu.obs.telemetry import get_telemetry
    from sheeprl_tpu.utils.timer import timer

    telemetry = get_telemetry()
    if timer.disabled:
        # reachable only under metric.disable_timer=true (every call site is
        # log_level-gated, and log_level=0 implies disabled timers): keep the
        # telemetry step totals accurate even without rate gauges. Fully
        # quiet runs (log_level=0) never reach a log boundary at all — their
        # telemetry.json reports the step/rate fields as null by design.
        if telemetry is not None:
            telemetry.record_window(
                policy_steps=policy_step - last_log,
                train_steps=train_step - last_train,
            )
        return {}
    timer_metrics = timer.compute()
    train_s = timer_metrics.get("Time/train_time")
    env_s = timer_metrics.get("Time/env_interaction_time")
    train_steps = train_step - last_train
    policy_steps = policy_step - last_log

    gauges: Dict[str, float] = {}
    if train_s:
        gauges["Time/sps_train"] = train_steps / max(train_s, 1e-9)
    if env_s:
        gauges["Time/sps_env_interaction"] = (
            policy_steps / world_size * action_repeat
        ) / max(env_s, 1e-9)

    if telemetry is not None:
        telemetry.record_window(
            policy_steps=policy_steps,
            train_steps=train_steps,
            env_seconds=env_s or 0.0,
            train_seconds=train_s or 0.0,
            stage_seconds=timer_metrics.get("Time/stage_h2d_time", 0.0),
        )
        mfu = mfu_pct(
            telemetry.flops_per_train_step,
            train_steps,
            train_s,
            telemetry.peak_tflops,
        )
        if mfu is not None:
            gauges["Perf/mfu"] = mfu

    if logger is not None and gauges:
        logger.log_metrics(gauges, policy_step)
    return gauges
