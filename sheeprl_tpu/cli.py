"""Command-line entry points.

Re-implementation of the reference ``sheeprl/cli.py`` (run :265-273,
run_algorithm :48-156, eval_algorithm :159-198, check_configs :201-257,
resume_from_checkpoint :22-45) on the mini-hydra config engine and the mesh
:class:`~sheeprl_tpu.fabric.Fabric`. One process drives every local device
(SPMD), so ``fabric.launch`` validates topology instead of spawning ranks.
"""

from __future__ import annotations

import importlib
import os
import sys
import warnings
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import sheeprl_tpu
from sheeprl_tpu.config.engine import compose, to_yaml
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import (
    algorithm_registry,
    evaluation_registry,
    find_algorithm,
    find_evaluation,
    registered_algorithm_names,
)
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import enable_persistent_compilation_cache, dotdict, print_config


def _load_run_config(ckpt_path: str):
    """Read the persisted config of the run that produced a checkpoint.

    Two layouts are recognized: training runs
    (``<log_dir>/checkpoint/ckpt_*`` with ``<log_dir>/.hydra/config.yaml``)
    and model-registry versions (``<registry>/<name>/v<k>/checkpoint`` with
    the config copied next to the checkpoint — utils/model_manager.py).
    Returns ``(cfg, log_dir)``."""
    import yaml

    ckpt_abs = os.path.abspath(ckpt_path)
    log_dir = os.path.dirname(os.path.dirname(ckpt_abs))
    candidates = [
        (os.path.join(log_dir, ".hydra", "config.yaml"), log_dir),
        (os.path.join(os.path.dirname(ckpt_abs), "config.yaml"), os.path.dirname(ckpt_abs)),
    ]
    for cfg_path, base in candidates:
        if os.path.isfile(cfg_path):
            with open(cfg_path) as f:
                return dotdict(yaml.safe_load(f)), base
    raise RuntimeError(
        f"Cannot use checkpoint {ckpt_path}: missing persisted config at any of "
        f"{[c for c, _ in candidates]}"
    )


def resume_from_checkpoint(cfg, overrides: Optional[Sequence[str]] = None) -> Any:
    """Merge the checkpoint run's persisted config into the current one
    (reference cli.py:22-45): the old config wins except for runtime keys.

    ``overrides`` is the raw CLI override list; the training horizon is only
    taken from the resuming command when it was *explicitly* overridden there,
    otherwise the checkpointed run's ``total_steps`` is preserved (a bare
    resume must not silently reset the horizon to the exp default)."""
    ckpt_path = cfg.checkpoint.resume_from
    old_cfg, _ = _load_run_config(ckpt_path)
    if old_cfg.env.id != cfg.env.id:
        raise ValueError(
            f"This experiment is run with a different environment from the one of the "
            f"checkpoint: got {cfg.env.id}, the checkpoint was trained on {old_cfg.env.id}"
        )
    if old_cfg.algo.name != cfg.algo.name:
        raise ValueError(
            f"This experiment is run with a different algorithm from the one of the "
            f"checkpoint: got {cfg.algo.name}, the checkpoint was trained with {old_cfg.algo.name}"
        )
    # keep the old experiment config, but let the new run control runtime keys
    old_cfg.checkpoint.resume_from = ckpt_path
    old_cfg.root_dir = cfg.root_dir
    old_cfg.run_name = cfg.run_name
    old_cfg.fabric = cfg.fabric
    # Re-apply every EXPLICIT value override from the resuming command on top
    # of the restored config. The restored config defines the experiment
    # (reference cli.py:22-45 swaps the config wholesale), but silently
    # dropping overrides the user typed is a trap: a round-5 diagnostic run
    # passed `algo.train_every=1e9 metric.log_level=0` on resume, both were
    # discarded, and the "no-training" probe trained at full cadence while
    # its config print (then emitted pre-merge) showed the overridden values.
    # Group SELECTIONS (exp=..., env=dmc) cannot be re-applied onto an
    # already-composed tree and keep their swap-time semantics; bare resumes
    # keep the checkpointed horizon (the counters carry progress either way).
    from sheeprl_tpu.config.engine import yaml_load

    reapplied = []
    dropped = []
    ignored = []  # (override, reason) — every typed token is accounted for
    for o in overrides or []:
        if o.startswith("~"):
            ignored.append(
                (o, "deletions cannot be re-applied onto the restored config")
            )
            continue
        if "=" not in o:
            ignored.append((o, "not a key=value override"))
            continue
        key, value = o.split("=", 1)
        added = key.startswith("+")
        key = key.lstrip("+")
        if key in ("checkpoint.resume_from", "root_dir", "run_name") or key.startswith("fabric"):
            continue  # already carried over above (not silent: cfg wins)
        if key == "exp":
            ignored.append(
                (o, "defaults-list selection, consumed at compose time; the "
                    "checkpointed experiment defines the recipe")
            )
            continue
        if "." not in key and isinstance(old_cfg.get(key, None), dict):
            ignored.append(
                (o, "group selection / dict-valued key with swap-time "
                    "semantics; it cannot be re-applied onto the composed "
                    f"tree — pass leaf overrides ({key}.<field>=...) to "
                    "change the restored section")
            )
            continue
        if not _set_existing_path(old_cfg, key, yaml_load(value), allow_new=added):
            # unknown key (typo, or a +new key the stored tree lacks):
            # inventing it would hide the misconfiguration this merge exists
            # to prevent — surface it instead
            dropped.append(o)
            continue
        reapplied.append(o)
    if reapplied or ignored:
        lines = [
            "resume_from_checkpoint: the restored config defines the "
            "experiment; typed overrides were accounted for as follows."
        ]
        if reapplied:
            lines.append(f"re-applied: {reapplied}.")
        for o, reason in ignored:
            lines.append(f"ignored {o!r}: {reason}.")
        warnings.warn(" ".join(lines))
    if dropped:
        raise ValueError(
            "resume_from_checkpoint: these overrides name keys absent from "
            f"the checkpointed config: {dropped}. For a typo'd key, fix the "
            "key; to add a new LEAF under an existing section, prefix with "
            "'+'; new nested sections cannot be added on a resume command."
        )
    return old_cfg


def _set_existing_path(cfg, key: str, value, allow_new: bool = False) -> bool:
    """Set ``key`` (dotted) in ``cfg`` only if the full path already exists
    (or ``allow_new`` and the PARENT exists). Returns False otherwise —
    never invents intermediate nodes, so typos don't silently no-op."""
    node = cfg
    parts = key.split(".")
    for p in parts[:-1]:
        if not isinstance(node, dict) or p not in node or not isinstance(node[p], dict):
            return False
        node = node[p]
    if not isinstance(node, dict):
        return False
    if parts[-1] not in node and not allow_new:
        return False
    node[parts[-1]] = value
    return True


def check_configs(cfg) -> None:
    """Strategy validation (reference cli.py:201-257)."""
    algo_name = cfg.algo.name
    entry = find_algorithm(algo_name)
    if entry is None:
        raise RuntimeError(
            f"Given the algorithm named '{algo_name}', no algorithm has been found to be imported. "
            f"Available algorithms: {registered_algorithm_names()}"
        )
    strategy = str(cfg.fabric.get("strategy", "auto"))
    if entry["decoupled"]:
        devices = cfg.fabric.get("devices", 1)
        if devices not in ("auto", -1) and int(devices) < 2:
            raise RuntimeError(
                f"The decoupled version of {algo_name} algorithm requires at least 2 devices: "
                "one player and at least one trainer. "
                f"Please set `fabric.devices` to at least 2, got {devices}"
            )
    elif strategy not in ("auto", "ddp", "dp"):
        warnings.warn(
            f"Running an algorithm with a strategy ('{strategy}') "
            "different than 'auto'/'ddp': on TPU every strategy maps to SPMD "
            "data-parallel over the mesh",
            UserWarning,
        )
    if cfg.metric.get("log_level", 1) > 0 and len(cfg.metric.get("aggregator", {}).get("metrics", {})) == 0:
        warnings.warn(
            "No metrics defined in metric.aggregator.metrics: nothing will be aggregated",
            UserWarning,
        )

    # in-run eval (eval.every_n_steps, sheeprl_tpu/evals/inrun) is wired into
    # the coupled SAC and Dreamer loops; elsewhere the knob would silently do
    # nothing — the silent-ignore trap the resume-override accounting closes
    if int((cfg.get("eval", {}) or {}).get("every_n_steps", 0) or 0) > 0 and algo_name not in (
        "sac",
        "dreamer_v1",
        "dreamer_v2",
        "dreamer_v3",
    ):
        warnings.warn(
            f"eval.every_n_steps={cfg.eval.every_n_steps} is only consumed by "
            f"the coupled SAC and Dreamer (v1/v2/v3) entrypoints for now; "
            f"'{algo_name}' runs without in-run eval (howto/evaluation.md)",
            UserWarning,
        )

    # fused recurrent-core kernels (algo.fused_kernels, sheeprl_tpu/kernels):
    # the knob is read by the dreamer-v1/v2 families only. An explicit
    # `pallas` on a backend that cannot compile it is an error raised by
    # kernels.resolve_tier at agent-build time, never a quiet `xla`.
    from sheeprl_tpu.kernels import normalize_tier

    if normalize_tier(cfg.algo.get("fused_kernels", "off")) != "off" and algo_name not in (
        "dreamer_v1",
        "dreamer_v2",
        "p2e_dv1_exploration",
        "p2e_dv1_finetuning",
        "p2e_dv2_exploration",
        "p2e_dv2_finetuning",
    ):
        warnings.warn(
            f"algo.fused_kernels={cfg.algo.fused_kernels} is only consumed by "
            f"the dreamer-v1/v2 recurrent cores (and their P2E variants); "
            f"'{algo_name}' ignores it (howto/kernels.md)",
            UserWarning,
        )

    # the actor–learner plane (plane.*, sheeprl_tpu/plane) is consumed by the
    # decoupled entrypoints only; validate its knobs here so a multi-process
    # run can't silently degrade (mirrors the env.act_burst rule above)
    num_players = int(cfg.get("plane", {}).get("num_players", 0) or 0)
    if num_players > 0:
        if not entry["decoupled"]:
            warnings.warn(
                f"plane.num_players={num_players} is only consumed by the "
                f"decoupled entrypoints (sac_decoupled, ppo_decoupled); "
                f"'{algo_name}' runs coupled and ignores it "
                "(howto/actor_learner.md)",
                UserWarning,
            )
        elif str(cfg.env.get("vectorization", "") or "").lower() == "sync" or (
            # the legacy spelling resolves to the same sync backend when
            # vectorization is unset (envs/vector/factory.resolve_vectorization)
            cfg.env.get("vectorization", None) is None
            and bool(cfg.env.get("sync_env", None))
        ):
            raise RuntimeError(
                f"plane.num_players={num_players} with a sync env pool "
                "(env.vectorization=sync, or legacy env.sync_env=true) "
                "serializes every player's env fleet inside its own process — "
                "the degraded pool defeats the multi-process plane. Drop the "
                "sync override (players default to the shared-memory async "
                "pool) or set plane.num_players=0 (howto/actor_learner.md)."
            )
        keep = int(cfg.get("plane", {}).get("keep_policies", 4) or 4)
        if keep < 2:
            raise RuntimeError(
                f"plane.keep_policies={keep} can garbage-collect the policy "
                "version a freshly-respawned player still needs; use >= 2 "
                "(howto/actor_learner.md)"
            )

    # mixed precision is validated for everyone but currently consumed only by
    # the DreamerV3 model family — warn instead of silently training in f32
    from sheeprl_tpu.fabric import compute_dtype_from_precision

    precision = cfg.fabric.get("precision", "32-true")
    if compute_dtype_from_precision(precision) is not None and algo_name not in (
        "dreamer_v3",
        "p2e_dv3_exploration",
        "p2e_dv3_finetuning",
    ):
        warnings.warn(
            f"fabric.precision={precision} is only consumed by the DreamerV3 model "
            f"family; '{algo_name}' will train in f32",
            UserWarning,
        )


def _prune_metric_keys(cfg, algo_module: str) -> None:
    """Drop aggregator keys the algorithm never updates (reference cli.py:141-155)."""
    try:
        utils_module = importlib.import_module(f"{algo_module.rsplit('.', 1)[0]}.utils")
        keys = getattr(utils_module, "AGGREGATOR_KEYS", None)
    except ModuleNotFoundError:
        keys = None
    if keys is None:
        return
    metrics_cfg = cfg.metric.get("aggregator", {}).get("metrics", {})
    for name in list(metrics_cfg.keys()):
        if name not in keys:
            metrics_cfg.pop(name)


def _load_exploration_cfg(cfg) -> Any:
    """P2E finetuning: re-read the exploration run's persisted config and
    inherit its env settings (reference cli.py:106-137)."""
    ckpt_path = cfg.checkpoint.exploration_ckpt_path
    if not ckpt_path:
        raise ValueError(
            "P2E finetuning requires checkpoint.exploration_ckpt_path pointing at an "
            "exploration-phase checkpoint"
        )
    exploration_cfg, _ = _load_run_config(ckpt_path)
    if exploration_cfg.env.id != cfg.env.id:
        raise ValueError(
            "This experiment is run with a different environment from "
            "the one of the exploration you want to finetune. "
            f"Got '{cfg.env.id}', but the environment used during exploration was "
            f"{exploration_cfg.env.id}. Set properly the environment for finetuning "
            "the experiment."
        )
    # Take environment configs from exploration
    for k in (
        "frame_stack",
        "screen_size",
        "action_repeat",
        "grayscale",
        "clip_rewards",
        "frame_stack_dilation",
        "max_episode_steps",
        "reward_as_observation",
    ):
        if k in exploration_cfg.env:
            cfg.env[k] = exploration_cfg.env[k]
    return exploration_cfg


def run_algorithm(cfg) -> None:
    """Registry lookup → Fabric → entrypoint (reference cli.py:48-156)."""
    entry = find_algorithm(cfg.algo.name)
    if entry is None:
        raise RuntimeError(
            f"Given the algorithm named '{cfg.algo.name}', no algorithm has been found to be imported. "
            f"Available algorithms: {registered_algorithm_names()}"
        )
    module = importlib.import_module(entry["module"])
    entrypoint = getattr(module, entry["entrypoint"])

    kwargs = {}
    if "finetuning" in cfg.algo.name and "p2e" in entry["module"]:
        kwargs["exploration_cfg"] = _load_exploration_cfg(cfg)

    # parallel group → Fabric sharding knobs (the {'data','model'} mesh);
    # absent/empty group keeps the pure data-parallel defaults.
    parallel_cfg = cfg.get("parallel", None) or {}
    fabric = instantiate(
        cfg.fabric,
        model_axis=parallel_cfg.get("model_axis", 1) or 1,
        shard_min_bytes=parallel_cfg.get("shard_min_bytes", None),
        shard_overrides=parallel_cfg.get("shard_overrides", None),
    )

    # Observability gates (reference cli.py:141-155)
    _prune_metric_keys(cfg, entry["module"])
    MetricAggregator.disabled = cfg.metric.log_level == 0 or len(
        cfg.metric.get("aggregator", {}).get("metrics", {})
    ) == 0
    timer.disabled = cfg.metric.log_level == 0 or cfg.metric.get("disable_timer", False)

    # Run telemetry (metric.telemetry config group, obs/): spans, counters,
    # health guards. Owned here so the end-of-run summary/telemetry.json is
    # written even when the entrypoint raises; the run dir is attached later
    # by create_tensorboard_logger once the versioned path exists.
    from sheeprl_tpu.obs.telemetry import finalize_telemetry, setup_telemetry

    # Checkpoint subsystem (checkpoint config group, ckpt/): async saver,
    # keep-policy GC, SIGTERM/SIGINT preemption capture. Torn down in the
    # same finally so an in-flight async save is drained before the process
    # exits (and before telemetry finalizes, so its counters are complete).
    from sheeprl_tpu.ckpt import setup_checkpoint, teardown_checkpoint

    setup_telemetry(cfg, devices=list(fabric.mesh.devices.flat))
    setup_checkpoint(cfg)
    try:
        # jax.profiler trace capture around the whole run (SURVEY §5.1 — the
        # TPU superset of the reference's named-scope timers)
        profiler = cfg.metric.get("profiler", False)
        if profiler:
            import jax

            # traces land inside the run tree next to checkpoints/metrics
            trace_dir = (
                profiler
                if isinstance(profiler, str)
                else os.path.join(
                    "logs", "runs", str(cfg.root_dir), str(cfg.run_name), "jax_traces"
                )
            )
            with jax.profiler.trace(os.path.abspath(trace_dir)):
                return fabric.launch(entrypoint, cfg, **kwargs)

        fabric.launch(entrypoint, cfg, **kwargs)
    finally:
        # the TensorBoard writer flushes on a timer: close it so what the run
        # logged is on disk when run() returns (in-process callers read it)
        logger = getattr(fabric, "logger", None)
        if logger is not None:
            logger.close()
        teardown_checkpoint()
        # inside a finally, exc_info() sees the in-flight exception (if any):
        # a crashed run's telemetry.json records `"crashed": true` plus the
        # exception type next to the partial counters
        finalize_telemetry(error=sys.exc_info()[1])


def eval_algorithm(cfg) -> None:
    """Load checkpoint state and dispatch the evaluation fn (cli.py:159-198)."""
    entry = find_evaluation(cfg.algo.name)
    if entry is None:
        raise RuntimeError(
            f"Given the algorithm named '{cfg.algo.name}', no evaluation function has been found"
        )
    module = importlib.import_module(entry["module"])
    entrypoint = getattr(module, entry["entrypoint"])

    cfg.fabric.devices = 1
    fabric = instantiate(cfg.fabric)
    state = fabric.load(cfg.checkpoint_path)
    fabric.launch(entrypoint, cfg, state)


def _compose_from_argv(args: Optional[Sequence[str]], **kwargs) -> Any:
    overrides = list(args) if args is not None else sys.argv[1:]
    return compose("config", overrides=overrides, **kwargs)


def run(args: Optional[Sequence[str]] = None) -> None:
    """Train entrypoint (reference cli.py:265-273).

    ``-m``/``--multirun`` enables the Hydra-basic-sweeper subset (reference
    CLI inherits it from ``@hydra.main``, hydra 1.3): comma-separated
    override values expand to the cartesian product and the jobs run
    sequentially in-process, like Hydra's default launcher. Distinct output
    dirs come from the logger's ``version_k`` auto-increment.
    """
    overrides = list(args) if args is not None else sys.argv[1:]
    if "-m" in overrides or "--multirun" in overrides:
        from sheeprl_tpu.config.engine import expand_multirun

        overrides = [o for o in overrides if o not in ("-m", "--multirun")]
        jobs = expand_multirun(overrides)
        if len(jobs) > 1:
            for i, job in enumerate(jobs):
                print(f"[multirun] job {i + 1}/{len(jobs)}: {' '.join(job)}", flush=True)
                run(job)
            return
        # single job: fall through to the normal path with the cleaned argv
        args = overrides
    enable_persistent_compilation_cache()
    cfg = _compose_from_argv(args)
    if int(cfg.fabric.get("num_nodes", 1)) > 1:
        # must precede any backend initialization (fabric device queries,
        # algorithm imports that build jit caches, ...)
        from sheeprl_tpu.fabric import init_distributed

        init_distributed()
    sheeprl_tpu.register_algorithms()
    if cfg.checkpoint.resume_from:
        # `latest` (or a run-dir path) resolves to the newest manifest-valid
        # checkpoint BEFORE the config merge, which needs a concrete path
        from sheeprl_tpu.ckpt import resolve_resume_from

        cfg.checkpoint.resume_from = resolve_resume_from(cfg)
        cfg = resume_from_checkpoint(cfg, overrides)
    # print AFTER the resume merge so the tree shown is the effective config
    # (printing pre-merge showed override values the merge then discarded)
    if cfg.metric.log_level > 0:
        print_config(cfg)
    check_configs(cfg)
    run_algorithm(cfg)


def evaluation(args: Optional[Sequence[str]] = None) -> None:
    """Eval entrypoint (reference cli.py:276-312): re-reads the run's persisted
    config, forces a single-device single-env setup, and keeps the seed."""
    enable_persistent_compilation_cache()
    sheeprl_tpu.register_algorithms()
    overrides = list(args) if args is not None else sys.argv[1:]
    # the eval CLI takes checkpoint_path=... plus optional fabric overrides
    eval_cfg = compose(
        "eval_config",
        overrides=overrides,
        allow_missing=("checkpoint_path",),
    )
    ckpt_path = eval_cfg.get("checkpoint_path")
    if not ckpt_path or ckpt_path == "???":
        raise ValueError("You must specify the checkpoint path: checkpoint_path=/path/to/ckpt")
    # `registry:best:<algo>:<env id>` → the model registry's best record
    # (evals/registry.py; deterministic mean/n/append-order resolution).
    # Same resolver the serving gateway uses (sheeprl_tpu/serve).
    from sheeprl_tpu.evals.registry import resolve_checkpoint_ref

    ckpt_path, record = resolve_checkpoint_ref(
        ckpt_path,
        str((eval_cfg.get("eval", {}) or {}).get("registry_dir", "logs/registry")),
    )
    if record is not None:
        print(
            f"[registry] best {record.get('algo')} on {record.get('env')}: "
            f"{ckpt_path} (mean {record.get('metrics', {}).get('mean')})"
        )
    cfg, log_dir = _load_run_config(ckpt_path)
    # eval-time service knobs come from the eval CLI's composed `eval` group
    # (the run's persisted knobs configured its own in-run eval, not this
    # re-score); missing keys fall back to the shipped defaults
    from sheeprl_tpu.evals.service import eval_settings

    cfg["eval"] = eval_settings(eval_cfg)

    cfg.run_name = os.path.join(
        os.path.basename(log_dir), f"evaluation_{np.random.randint(0, 2**16)}"
    )
    cfg.env.num_envs = 1
    cfg.env.capture_video = bool(eval_cfg.get("env", {}).get("capture_video", cfg.env.capture_video))
    # keep the run's PRNG implementation at eval time (a threefry-trained
    # run should not sample under the constructor-default rbg)
    run_fabric = cfg.get("fabric", {}) or {}
    cfg.fabric = dotdict(
        {
            "_target_": "sheeprl_tpu.fabric.Fabric",
            "devices": 1,
            "num_nodes": 1,
            "strategy": "auto",
            "accelerator": eval_cfg.get("fabric", {}).get("accelerator", "auto"),
            "precision": eval_cfg.get("fabric", {}).get("precision", "32-true"),
            "prng_impl": run_fabric.get("prng_impl", "rbg"),
            "callbacks": [],
        }
    )
    cfg.checkpoint_path = ckpt_path
    eval_algorithm(cfg)


def serve(args: Optional[Sequence[str]] = None) -> None:
    """Serving entrypoint (sheeprl_tpu/serve, howto/serving.md): load a
    checkpoint (or ``registry:best:`` ref) through the eval-builder registry
    and serve batched ``act(obs)`` inference with request coalescing,
    hot-swap, and a SIGTERM drain."""
    enable_persistent_compilation_cache()
    sheeprl_tpu.register_algorithms()
    overrides = list(args) if args is not None else sys.argv[1:]
    serve_cfg = compose(
        "serve_config",
        overrides=overrides,
        allow_missing=("checkpoint_path",),
    )
    ckpt_path = serve_cfg.get("checkpoint_path")
    if not ckpt_path or ckpt_path == "???":
        raise ValueError("You must specify the checkpoint path: checkpoint_path=/path/to/ckpt")
    from sheeprl_tpu.serve.gateway import run_serve_entrypoint

    run_serve_entrypoint(serve_cfg)


def registration(args: Optional[Sequence[str]] = None) -> None:
    """Model-registration entrypoint (upstream sheeprl's
    ``sheeprl_model_manager.py`` → ``cli.registration``): publish a training
    checkpoint into the filesystem model registry."""
    from sheeprl_tpu.utils.model_manager import ModelManager

    overrides = list(args) if args is not None else sys.argv[1:]
    cfg = compose(
        "model_manager_config",
        overrides=overrides,
        allow_missing=("checkpoint_path", "model_name"),
    )
    ckpt_path = cfg.get("checkpoint_path")
    model_name = cfg.get("model_name")
    if not ckpt_path or ckpt_path == "???":
        raise ValueError("You must specify the checkpoint path: checkpoint_path=/path/to/ckpt")
    if not model_name or model_name == "???":
        raise ValueError("You must specify the model name: model_name=my_agent")
    manager = ModelManager(cfg.get("registry_dir", "models"))
    version = manager.register_model(
        model_name, ckpt_path, description=cfg.get("description", "")
    )
    print(f"Registered '{model_name}' v{version} in {manager.registry_dir}")


if __name__ == "__main__":
    run()
