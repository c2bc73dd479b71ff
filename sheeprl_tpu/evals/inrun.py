"""Periodic in-run evaluation, off the train-step critical path.

The trainer never runs an eval episode. Instead, rank 0 publishes the
policy every ``eval.every_n_steps`` policy steps through the plane's
:class:`~sheeprl_tpu.plane.publish.PolicyPublisher` (``async_publish=True``
— the npz write happens on the publisher's writer thread), and a separate
**eval process** polls the channel with
:class:`~sheeprl_tpu.plane.publish.PolicyPoller`, rebuilds the frozen agent
via the same builder registry the eval CLI uses, runs a few greedy
episodes, and drops the growing frozen-greedy curve into
``telemetry/sidecar_evalproc.json``. The run's own telemetry plane
(obs/dist/aggregate) folds that sidecar into ``live.json`` mid-run and
``telemetry.json`` at finalize under ``sources.evalproc`` — so eval curves
appear in the run artifacts while the train phase histograms stay
untouched (the off-critical-path evidence the subsystem is gated on).

The child pins jax to the CPU backend before importing it (eval must never
fight the trainer for the mesh) and forces a sync eval pool (a daemonic
process cannot own env worker pools). Algorithms call only
:func:`maybe_start_inrun_eval` / :meth:`InRunEval.maybe_publish` /
:meth:`InRunEval.close` — all process machinery lives here, outside
``algos/`` (tools/lint_plane.py).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

__all__ = ["InRunEval", "maybe_start_inrun_eval"]


class _ChildHalt:
    """Event-like ``stop | orphaned`` view for the child's blocking waits."""

    def __init__(self, stop, parent_pid: int):
        self._stop = stop
        self._parent_pid = int(parent_pid)

    def is_set(self) -> bool:
        if self._stop is not None and self._stop.is_set():
            return True
        # parent death without close(): getppid() re-parents to init/reaper
        return os.getppid() != self._parent_pid


def child_main(spec: Dict[str, Any]) -> None:
    """Eval-process entry point (spawned, never forked)."""
    # the evaluator must soak idle cycles, not race the trainer for them —
    # on a host whose cores the trainer saturates (CPU meshes, few-core
    # boxes) a same-priority child shows up directly in the train-phase
    # tails. SCHED_IDLE runs the child only when nothing else wants the
    # CPU; nice(19) is the portable fallback.
    try:
        os.sched_setscheduler(0, os.SCHED_IDLE, os.sched_param(0))
    except (AttributeError, OSError, PermissionError):
        try:
            os.nice(19)
        except OSError:
            pass
    import jax

    from sheeprl_tpu.utils.utils import pin_process_to_cpu

    pin_process_to_cpu()  # the eval child lives on the host CPU
    if spec.get("prng_impl"):
        jax.config.update("jax_default_prng_impl", str(spec["prng_impl"]))

    import numpy as np

    import sheeprl_tpu
    from sheeprl_tpu.obs.dist.aggregate import write_sidecar
    from sheeprl_tpu.plane.publish import PolicyPoller
    from sheeprl_tpu.plane.slabs import PlaneClosed
    from sheeprl_tpu.utils.utils import dotdict

    sheeprl_tpu.register_algorithms()

    from sheeprl_tpu.evals.service import (
        _probe_spaces,
        find_eval_builder,
        make_eval_pool,
        run_parallel_episodes,
    )

    cfg = dotdict(spec["cfg"])
    cfg.env.capture_video = False
    eval_overrides = dict(cfg.get("eval", {}) or {})
    eval_overrides["vectorization"] = "sync"  # daemonic child: no worker pools
    cfg["eval"] = eval_overrides

    halt = _ChildHalt(spec.get("stop"), spec["parent_pid"])
    episodes = max(int(spec.get("episodes", 2)), 1)
    seed0 = int(spec.get("seed0", 1000))
    tel_dir = spec["tel_dir"]
    builder = find_eval_builder(cfg.algo.name)
    if builder is None:
        write_sidecar(
            tel_dir,
            "evalproc",
            {"error": f"no eval builder for {cfg.algo.name!r}", "points": []},
        )
        return

    observation_space, action_space = _probe_spaces(cfg)
    pool, seeds = make_eval_pool(cfg, None, episodes, seed0, prefix="inrun")
    single_space = getattr(pool, "single_action_space", None)
    act_shape = tuple(single_space.shape) if single_space is not None else ()
    poller = PolicyPoller(spec["policy_root"])
    points = []
    try:
        version = -1
        while not halt.is_set():
            try:
                version, params = poller.wait_min_version(
                    version + 1, stop=halt, use_exact=False
                )
            except PlaneClosed:
                break
            import time

            t0 = time.monotonic()
            policy = builder(None, cfg, params, observation_space, action_space)
            returns, lengths = run_parallel_episodes(
                policy,
                pool,
                seeds,
                jax.random.PRNGKey(seed0),
                act_shape,
                max_steps=int(eval_overrides.get("max_steps", 0) or 0),
            )
            points.append(
                {
                    "policy_version": int(version),
                    "mean": float(np.mean(returns)),
                    "std": float(np.std(returns)),
                    "episodes": int(episodes),
                    "eval_wall_s": round(time.monotonic() - t0, 3),
                }
            )
            write_sidecar(
                tel_dir,
                "evalproc",
                {
                    "protocol": "frozen-greedy",
                    "episodes": episodes,
                    "seed0": seed0,
                    "rounds": len(points),
                    "points": points[-200:],
                    "last_mean": points[-1]["mean"],
                    "last_policy_version": points[-1]["policy_version"],
                },
            )
    finally:
        pool.close()


class InRunEval:
    """Rank-0 handle: gated async policy publication + the eval process."""

    def __init__(self, cfg, log_dir: str):
        from sheeprl_tpu.evals.service import eval_settings
        from sheeprl_tpu.plane.publish import PolicyPublisher

        settings = eval_settings(cfg)
        self.every_n_steps = int(settings.every_n_steps)
        self.policy_root = os.path.join(log_dir, "inrun_policies")
        self.tel_dir = os.path.join(log_dir, "telemetry")
        self._last_version: Optional[int] = None
        self._publisher = PolicyPublisher(
            self.policy_root,
            keep_policies=2,
            algo=str(cfg.algo.name),
            async_publish=True,
        )
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self._stop = ctx.Event()
        spec = {
            "cfg": cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg),
            "policy_root": self.policy_root,
            "tel_dir": self.tel_dir,
            "episodes": int(settings.inrun_episodes),
            "seed0": int(settings.seed0),
            "stop": self._stop,
            "parent_pid": os.getpid(),
            "prng_impl": (cfg.get("fabric", {}) or {}).get("prng_impl"),
        }
        self._child = ctx.Process(
            target=child_main, args=(spec,), daemon=True, name="inrun-eval"
        )
        self._child.start()

    def due(self, policy_step: int) -> bool:
        """Cheap pre-gate so callers can skip building the publish pytree
        (a ``device_get``, typically) when the step gate is closed."""
        policy_step = int(policy_step)
        return self._last_version is None or (
            policy_step - self._last_version >= self.every_n_steps
            and policy_step > self._last_version
        )

    def maybe_publish(self, policy_step: int, state: Any) -> bool:
        """Publish ``state`` as version ``policy_step`` when the step gate
        opens. ``state`` must be a host pytree shaped like the checkpoint
        layout the algo's eval builder expects. Returns True on publish."""
        policy_step = int(policy_step)
        if not self.due(policy_step):
            return False
        self._publisher.publish(policy_step, state)
        self._last_version = policy_step
        from sheeprl_tpu.obs.counters import add_inrun_eval_publishes

        add_inrun_eval_publishes(1)
        return True

    def close(self, timeout: float = 30.0) -> None:
        """Stop the eval process and flush pending publications."""
        self._stop.set()
        try:
            self._publisher.close(timeout=timeout)
        finally:
            self._child.join(timeout=timeout)
            if self._child.is_alive():
                self._child.terminate()
                self._child.join(timeout=5.0)


def maybe_start_inrun_eval(fabric, cfg, log_dir: Optional[str]) -> Optional[InRunEval]:
    """The one call an algorithm makes: returns a handle when in-run eval is
    enabled (``eval.every_n_steps > 0``) on global rank 0, else None."""
    from sheeprl_tpu.evals.service import eval_settings

    settings = eval_settings(cfg)
    if int(settings.every_n_steps or 0) <= 0 or not log_dir:
        return None
    if fabric is not None and not getattr(fabric, "is_global_zero", True):
        return None
    return InRunEval(cfg, log_dir)
