"""SAC, coupled — off-policy continuous control.

Behavioral contract from the reference ``sheeprl/algos/sac/sac.py``
(train :33-81, main :84-398): vector observations only, twin-Q ensemble with
EMA targets, tanh-Gaussian actor, alpha autotuning against
``target_entropy = -act_dim``; per update one env step, then
``per_rank_gradient_steps`` SGD batches sampled from the replay buffer (with a
catch-up burst of ``learning_starts`` batches on the first training update).

TPU-native design:

- The reference's per-update pipeline (sample → all_gather → DistributedSampler
  → per-batch train() with three backward/allreduce passes) is ONE jitted
  ``shard_map`` program: the host samples ``G×B×world`` transitions, ships
  them sharded over the mesh, and the device scans over G gradient steps —
  critic, actor, and alpha updates each with ``pmean``-ed grads, plus the
  conditional target-EMA folded in as a ``jnp.where`` on the parameter trees.
- Collection goes through the rollout engine (``envs/rollout``,
  ``howto/rollout_engine.md``): with ``env.backend=jax`` the whole burst —
  act, env step, auto-reset, device-ring add — is one ``lax.scan`` under
  jit (zero host involvement); on the Python backend the acting loop body
  lives in a host callback that a ``BurstActor`` scans ``env.act_burst``
  times per device dispatch (K=1 = the exact per-step reference path), and
  one train program covers the burst's gradient steps.
- The critic ensemble is vmapped stacked params (see ``agent.py``) — the
  twin-Q min and per-critic MSE sum are single batched ops.
- The whole agent state (actor/critics/targets/log_alpha + 3 optimizer
  states) is one pytree: replication, donation, and checkpointing are
  single calls.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.algos.sac.agent import (
    SACActor,
    SACCritic,
    action_bounds,
    build_agent_state,
    ensemble_q,
    greedy_action,
    squash_sample,
)
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import concat_obs, test
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.data.device_ring import DeviceRingTransitions
from sheeprl_tpu.data.staging import RingStaging, make_replay_staging
from sheeprl_tpu.envs.rollout import BurstActor, JaxRolloutEngine, make_jax_env
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.envs.vector.factory import resolve_backend
from sheeprl_tpu.evals.inrun import maybe_start_inrun_eval
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import (
    get_telemetry,
    learn_probes,
    log_sps_metrics,
    observe_probes,
    probes_enabled,
    profile_tick,
    register_train_cost,
    set_shard_footprint,
    shape_specs,
    span,
)
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of, get_lr, set_lr
from sheeprl_tpu.parallel.shard import measured_bytes_per_device
from sheeprl_tpu.utils.utils import fetch_losses_if_observed, save_configs


def build_train_fn(
    actor: SACActor,
    critic: SACCritic,
    actor_tx,
    qf_tx,
    alpha_tx,
    cfg,
    fabric,
    action_scale: np.ndarray,
    action_bias: np.ndarray,
    target_entropy: float,
    donate: bool = True,
    state_plan=None,
    opt_plan=None,
    emit_td: bool = False,
):
    """Compile G gradient steps (critic → EMA → actor → alpha) as one SPMD
    program. ``batch`` leaves are ``[G, B_local, ...]``; ``do_ema`` is a
    dynamic bool so the EMA cadence never recompiles.

    ``state_plan``/``opt_plan`` (from ``fabric.shard_plan`` over the agent
    state and optimizer-state trees) switch the program onto the
    ``{'data','model'}`` mesh as ONE GSPMD program: no manual shard_map
    region (``axis=None`` makes the per-shard gradient pmean an identity —
    the loss spans the global batch, so its gradient is already the
    all-reduced one), params/opt state enter via ``in_shardings``/
    ``out_shardings`` with the plans' model-axis specs, and XLA inserts all
    collectives. The jax-0.4-era partitioner CHECK-fails on ``lax.scan``
    inside a partially-manual (``auto=``) shard_map, so the sharded path
    avoids shard_map entirely. ``None`` is the byte-identical manual
    data-parallel program.

    ``emit_td=True`` (the prioritized-replay writeback path,
    ``replay.strategy=td_priority``) additionally returns the per-row TD
    residual ``min_i Q_i(s,a) − y`` of the *pre-update* critics, stacked
    ``[G, B, 1]`` in the staged batch's row order, as the LAST output — the
    aux of the same critic-loss evaluation, so the extra cost is one output,
    not a second forward pass. With ``emit_td=False`` (the default, and
    every uniform-replay path) the built program is byte-identical to
    before the flag existed."""
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    n_critics = int(cfg.algo.critic.n)
    data_axis = fabric.data_axis
    axis = data_axis if state_plan is None else None
    scale = jnp.asarray(action_scale)
    bias = jnp.asarray(action_bias)
    tgt_entropy = jnp.float32(target_entropy)
    # learning-health probes (obs/learn): build-time gate — with the sentinel
    # uninstalled the program carries zero probe ops and its outputs (and
    # params) are bitwise those of a probes-off build
    learn_on = probes_enabled(cfg)
    learn_clips = {
        "actor": clip_norm_of(actor_tx),
        "critic": clip_norm_of(qf_tx),
        "alpha": clip_norm_of(alpha_tx),
    }

    def one_step(carry, batch_and_key):
        state, opt_states, do_ema = carry
        batch, key = batch_and_key
        a_key, c_key = jax.random.split(key)

        # ---- critic update (reference train :47-55)
        alpha = jax.lax.stop_gradient(jnp.exp(state["log_alpha"]))
        next_mean, next_std = actor.apply({"params": state["actor"]}, batch["next_observations"])
        next_actions, next_logprob = squash_sample(next_mean, next_std, c_key, scale, bias)
        target_q = ensemble_q(critic, state["target_critics"], batch["next_observations"], next_actions)
        min_target = jnp.min(target_q, axis=-1, keepdims=True) - alpha * next_logprob
        td_target = batch["rewards"] + (1.0 - batch["dones"]) * gamma * min_target
        td_target = jax.lax.stop_gradient(td_target)

        if emit_td:

            def qf_loss_td_fn(critic_params):
                q = ensemble_q(critic, critic_params, batch["observations"], batch["actions"])
                return critic_loss(q, td_target, n_critics), q

            (qf_loss, q_pre), qf_grads = jax.value_and_grad(qf_loss_td_fn, has_aux=True)(
                state["critics"]
            )
            td = jnp.min(q_pre, axis=-1, keepdims=True) - td_target
        else:

            def qf_loss_fn(critic_params):
                q = ensemble_q(critic, critic_params, batch["observations"], batch["actions"])
                return critic_loss(q, td_target, n_critics)

            qf_loss, qf_grads = jax.value_and_grad(qf_loss_fn)(state["critics"])
            td = None
        qf_grads = pmean(qf_grads, axis)
        qf_updates, qf_opt = qf_tx.update(qf_grads, opt_states["qf"], state["critics"])
        critics = optax.apply_updates(state["critics"], qf_updates)

        # ---- target EMA (reference train :57-59), gated without recompiling
        ema = jax.tree_util.tree_map(
            lambda p, t: tau * p + (1.0 - tau) * t, critics, state["target_critics"]
        )
        targets = jax.tree_util.tree_map(
            lambda e, t: jnp.where(do_ema, e, t), ema, state["target_critics"]
        )

        # ---- actor update (reference train :61-68), against the fresh critics
        def actor_loss_fn(actor_params):
            mean, std = actor.apply({"params": actor_params}, batch["observations"])
            actions, logprob = squash_sample(mean, std, a_key, scale, bias)
            q = ensemble_q(critic, critics, batch["observations"], actions)
            min_q = jnp.min(q, axis=-1, keepdims=True)
            return policy_loss(alpha, logprob, min_q), logprob

        (actor_loss, logprob), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            state["actor"]
        )
        actor_grads = pmean(actor_grads, axis)
        actor_updates, actor_opt = actor_tx.update(actor_grads, opt_states["actor"], state["actor"])
        actor_params = optax.apply_updates(state["actor"], actor_updates)

        # ---- alpha update (reference train :70-75; grad all-reduced)
        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, jax.lax.stop_gradient(logprob), tgt_entropy)

        alpha_loss, alpha_grad = jax.value_and_grad(alpha_loss_fn)(state["log_alpha"])
        alpha_grad = pmean(alpha_grad, axis)
        alpha_updates, alpha_opt = alpha_tx.update(alpha_grad, opt_states["alpha"], state["log_alpha"])
        log_alpha = optax.apply_updates(state["log_alpha"], alpha_updates)

        new_state = {
            "actor": actor_params,
            "critics": critics,
            "target_critics": targets,
            "log_alpha": log_alpha,
        }
        new_opts = {"actor": actor_opt, "qf": qf_opt, "alpha": alpha_opt}
        metrics = jnp.stack([qf_loss, actor_loss, alpha_loss])
        if learn_on:
            # grads are already pmean'd above, so every shard computes the
            # identical probe values — no extra collective needed
            probes = learn_probes(
                {
                    "actor": actor_grads,
                    "critic": qf_grads,
                    "alpha": alpha_grad,
                },
                params={
                    "actor": state["actor"],
                    "critic": state["critics"],
                    "alpha": state["log_alpha"],
                },
                updates={
                    "actor": actor_updates,
                    "critic": qf_updates,
                    "alpha": alpha_updates,
                },
                losses=(qf_loss, actor_loss, alpha_loss),
                clip_norms=learn_clips,
            )
            ys = (metrics, probes, td) if emit_td else (metrics, probes)
            return (new_state, new_opts, do_ema), ys
        if emit_td:
            return (new_state, new_opts, do_ema), (metrics, td)
        return (new_state, new_opts, do_ema), metrics

    def local_train(state, opt_states, batch, key, do_ema):
        g = jax.tree_util.tree_leaves(batch)[0].shape[0]
        keys = jax.random.split(key, g)
        (state, opt_states, _), ys = jax.lax.scan(
            one_step, (state, opt_states, do_ema), (batch, keys)
        )
        td = None
        if learn_on and emit_td:
            metrics, probes, td = ys
        elif learn_on:
            metrics, probes = ys
        elif emit_td:
            metrics, td = ys
            probes = None
        else:
            metrics, probes = ys, None
        metrics = pmean(jnp.mean(metrics, axis=0), axis)
        out = (state, opt_states, metrics)
        if learn_on:
            # probes ride the scan ys stacked [G]: per-gradient-step samples
            out = out + (probes,)
        if emit_td:
            # td residuals ride the same ys, stacked [G, B, 1] — always LAST
            out = out + (td,)
        return out

    # decoupled mode keeps the old actor params alive for the player
    # thread, so donation must be off there
    donate_argnums = (0, 1) if donate else ()
    n_learn = 1 if learn_on else 0
    # td residuals are [G, B, 1] with the batch axis data-sharded, like the
    # staged batch itself
    td_specs = (P(None, data_axis),) if emit_td else ()
    if state_plan is None:
        shmapped = jax.shard_map(
            local_train,
            mesh=fabric.mesh,
            in_specs=(P(), P(), P(None, data_axis), P(), P()),
            out_specs=(P(), P(), P()) + (P(),) * n_learn + td_specs,
            check_vma=False,
        )
        return jax.jit(shmapped, donate_argnums=donate_argnums)
    rep = fabric.replicated
    td_shardings = (fabric.sharding(None, data_axis),) if emit_td else ()
    return jax.jit(
        local_train,
        in_shardings=(
            state_plan.shardings(),
            opt_plan.shardings(),
            fabric.sharding(None, data_axis),
            rep,
            rep,
        ),
        out_shardings=(state_plan.shardings(), opt_plan.shardings(), rep)
        + (rep,) * n_learn
        + td_shardings,
        donate_argnums=donate_argnums,
    )


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if "minedojo" in (cfg.env.wrapper._target_ or "").lower():
        raise ValueError("MineDojo is not currently supported by SAC agent")

    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    if len(cfg.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.cnn_keys.encoder = []

    state = None
    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    n_envs = int(cfg.env.num_envs) * world_size
    # execution plane picked by env.backend (envs/vector/factory.py): the
    # Python vector-env plane, or the pure-JAX rollout engine (tier a) where
    # whole collection bursts run on device (howto/rollout_engine.md)
    backend = resolve_backend(cfg)
    envs = None
    jax_env = None
    if backend == "jax":
        if world_size > 1:
            raise ValueError(
                "env.backend=jax currently supports single-device SAC runs "
                "(the jitted-scan collection owns one device's ring shard); "
                f"got fabric world_size={world_size}"
            )
        jax_env = make_jax_env(cfg.env.id, cfg.env.max_episode_steps)
        action_space = jax_env.action_space
        observation_space = jax_env.observation_space
    else:
        # vector backend picked by env.vectorization (envs/vector/factory.py)
        envs = make_vector_env(cfg, fabric, log_dir)
        action_space = envs.single_action_space
        observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the SAC agent. "
                f"Provided environment: {cfg.env.id}"
            )
    if cfg.metric.log_level > 0:
        fabric.print("Encoder MLP keys:", cfg.mlp_keys.encoder)

    act_dim = int(np.prod(action_space.shape))
    obs_dim = int(sum(np.prod(observation_space[k].shape) for k in cfg.mlp_keys.encoder))
    action_scale, action_bias = action_bounds(action_space)

    actor = SACActor(action_dim=act_dim, hidden_size=cfg.algo.actor.hidden_size)
    critic = SACCritic(hidden_size=cfg.algo.critic.hidden_size, num_critics=1)
    target_entropy = -float(act_dim)

    root_key, init_key = jax.random.split(root_key)
    agent_state = build_agent_state(
        actor, critic, init_key, int(cfg.algo.critic.n), obs_dim, act_dim, cfg.algo.alpha.alpha
    )

    qf_tx = instantiate(cfg.algo.critic.optimizer)
    actor_tx = instantiate(cfg.algo.actor.optimizer)
    alpha_tx = instantiate(cfg.algo.alpha.optimizer)
    opt_states = {
        "actor": actor_tx.init(agent_state["actor"]),
        "qf": qf_tx.init(agent_state["critics"]),
        "alpha": alpha_tx.init(agent_state["log_alpha"]),
    }

    if cfg.checkpoint.resume_from:
        template = {
            "agent": agent_state,
            "opt_states": opt_states,
            "update": 0,
            "batch_size": 0,
            "last_log": 0,
            "last_checkpoint": 0,
        }
        state = fabric.load(cfg.checkpoint.resume_from, template)
        agent_state = state["agent"]
        opt_states = state["opt_states"]
        cfg.per_rank_batch_size = int(np.asarray(state["batch_size"])) // world_size
    # Parameter sharding (parallel.model_axis>1): spec-assign params and
    # optimizer state over the 'model' axis and place them sharded. Resumed
    # checkpoints arrive as full host arrays, so restoring onto a different
    # model_axis than they were saved under is the same respec-and-reshard
    # path. model_axis=1 keeps the replicated placement untouched.
    state_plan = fabric.shard_plan(agent_state)
    opt_plan = fabric.shard_plan(opt_states)
    if state_plan is None:
        agent_state = jax.device_put(agent_state, fabric.replicated)
        opt_states = jax.device_put(opt_states, fabric.replicated)
    else:
        agent_state = state_plan.place(agent_state)
        opt_states = opt_plan.place(opt_states)
    set_shard_footprint(
        measured_bytes_per_device(agent_state),
        measured_bytes_per_device(opt_states),
        fabric.model_axis_size,
    )

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    rb = make_replay_buffer(
        cfg,
        fabric,
        log_dir,
        n_envs=n_envs,
        obs_keys=("observations",),
        dry_run_size=1,
    )

    # ------------------------------------------------------------------
    # jitted programs
    # ------------------------------------------------------------------

    scale_j, bias_j = jnp.asarray(action_scale), jnp.asarray(action_bias)

    actor_mirror = HostParamMirror.from_cfg(agent_state["actor"], fabric, cfg)
    play_actor = actor_mirror(agent_state["actor"])

    # in-run eval (howto/evaluation.md): rank 0 publishes the actor through
    # the policy channel every eval.every_n_steps; a separate process scores
    # it, so nothing below touches the train-step critical path
    inrun = maybe_start_inrun_eval(fabric, cfg, log_dir)

    needs_writeback = bool(getattr(rb, "needs_writeback", False))
    train_fn = build_train_fn(
        actor, critic, actor_tx, qf_tx, alpha_tx, cfg, fabric, action_scale, action_bias, target_entropy,
        state_plan=state_plan, opt_plan=opt_plan, emit_td=needs_writeback,
    )
    batch_sharding = fabric.sharding(None, fabric.data_axis)
    if backend == "jax" and hasattr(rb, "plan_burst"):
        raise ValueError(
            "env.backend=jax collects straight into the device ring, which "
            "needs the plain replay buffer — run prioritized/sharded replay "
            "(replay.strategy/replay.shards) on the python backend"
        )
    if backend == "jax":
        # the jitted-scan collection writes straight into the device ring —
        # the ring IS the collection target on this backend, so it is always
        # on regardless of buffer.device_ring
        if not cfg.buffer.get("device_ring", False):
            warnings.warn(
                "env.backend=jax collects straight into the device ring; "
                "enabling it (buffer.device_ring was off)"
            )
        ring = DeviceRingTransitions(
            rb, device=getattr(fabric, "device", None), seed=cfg.seed
        )
        staging = RingStaging(ring)
        rb = ring
    else:
        # TPU-first replay staging (data/staging.py): device-ring gathers when
        # buffer.device_ring=True, double-buffered host prefetch otherwise
        staging = make_replay_staging(
            cfg, fabric, rb, batch_sharding=batch_sharding, seed=cfg.seed
        )
        rb = staging.rb

    if backend == "jax":
        # tier (a): act -> step -> ring-add inside one lax.scan under jit
        def engine_policy(actor_params, e_obs, key):
            mean, std = actor.apply({"params": actor_params}, e_obs)
            actions, _ = squash_sample(mean, std, key, scale_j, bias_j)
            return actions

        root_key, engine_key = jax.random.split(root_key)
        engine = JaxRolloutEngine(
            jax_env,
            n_envs,
            engine_key,
            policy=engine_policy,
            ring=rb,
            store_next_obs=not cfg.buffer.sample_next_obs,
        )

    # Global counters (reference sac.py:206-215)
    last_train = 0
    train_step = 0
    start_step = int(np.asarray(state["update"])) // world_size if state is not None else 1
    policy_step = int(np.asarray(state["update"])) * cfg.env.num_envs if state is not None else 0
    last_log = int(np.asarray(state["last_log"])) if state is not None else 0
    last_checkpoint = int(np.asarray(state["last_checkpoint"])) if state is not None else 0
    policy_steps_per_update = int(n_envs)
    num_updates = int(cfg.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    if cfg.checkpoint.resume_from and not cfg.buffer.get("checkpoint", False):
        learning_starts += start_step

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_update != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_update value ({policy_steps_per_update}), so "
            "the metrics will be logged at the nearest greater multiple of the "
            "policy_steps_per_update value."
        )
    warn_checkpoint_rounding(cfg, policy_steps_per_update)

    if backend == "python":
        o = envs.reset(seed=cfg.seed)[0]
        obs = concat_obs(o, cfg.mlp_keys.encoder, n_envs)
        root_key, play_key = jax.random.split(root_key)
        play_key = actor_mirror.put_key(play_key)

    per_rank_gradient_steps = int(cfg.algo.per_rank_gradient_steps)
    ema_every = int(cfg.algo.critic.target_network_frequency) // policy_steps_per_update + 1
    # fault injection (metric.telemetry.learn.inject_lr_spike_*): multiply
    # every optimizer's LR once at the configured update — drives the
    # divergence-sentinel acceptance tests, never enabled in a real run
    lr_spike_at = None
    lr_spike_factor = 0.0
    try:
        _lcfg = (cfg.metric.get("telemetry", {}) or {}).get("learn", {}) or {}
        if _lcfg.get("inject_lr_spike_at") is not None:
            lr_spike_at = int(_lcfg["inject_lr_spike_at"])
            lr_spike_factor = float(_lcfg.get("inject_lr_spike_factor", 0) or 0)
    except AttributeError:
        pass
    # burst acting (tier b, howto/rollout_engine.md): K env steps per device
    # dispatch; 1 reproduces the per-step path exactly
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)

    if backend == "python":
        # The acting loop body as one host function: env step (against the
        # PR-5 vector plane), SAME_STEP final_obs fixup, episode logging,
        # buffer add — the old per-step block verbatim. The BurstActor scans
        # it K times per dispatch through an ordered io_callback; the random
        # prefill phase calls it directly (no policy, no dispatch at all).
        state_box = {"obs": obs, "policy_step": policy_step}

        def _host_env_step(actions):
            actions = np.asarray(actions)
            state_box["policy_step"] += n_envs
            with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
                next_o, rewards, terminated, truncated, infos = envs.step(
                    actions.reshape(envs.action_space.shape)
                )
            dones = np.logical_or(terminated, truncated)

            if cfg.metric.log_level > 0 and "final_info" in infos:
                fi = infos["final_info"]
                if isinstance(fi, dict) and "episode" in fi:
                    mask = np.asarray(fi.get("_episode", []), dtype=bool)
                    for i in np.nonzero(mask)[0]:
                        ep_rew = float(fi["episode"]["r"][i])
                        ep_len = float(fi["episode"]["l"][i])
                        if aggregator and not aggregator.disabled:
                            aggregator.update("Rewards/rew_avg", ep_rew)
                            aggregator.update("Game/ep_len_avg", ep_len)
                        fabric.print(
                            f"Rank-0: policy_step={state_box['policy_step']}, reward_env_{i}={ep_rew}"
                        )

            # Real next obs: under SAME_STEP autoreset the terminal obs lands
            # in final_obs while next_o holds the reset obs (reference
            # sac.py:268-274)
            next_obs = concat_obs(next_o, cfg.mlp_keys.encoder, n_envs)
            real_next_obs = next_obs.copy()
            if "final_obs" in infos:
                for idx, final_obs in enumerate(infos["final_obs"]):
                    if final_obs is not None:
                        real_next_obs[idx] = concat_obs(final_obs, cfg.mlp_keys.encoder, 1)[0]

            step_data = {
                "observations": state_box["obs"][None],
                "actions": np.asarray(actions, np.float32).reshape(1, n_envs, -1),
                "rewards": np.asarray(rewards, np.float32).reshape(1, n_envs, 1),
                "dones": np.asarray(dones, np.float32).reshape(1, n_envs, 1),
            }
            if not cfg.buffer.sample_next_obs:
                step_data["next_observations"] = real_next_obs[None]
            rb.add(step_data)
            state_box["obs"] = next_obs
            return next_obs

        def _act_fn(actor_params, a_obs, key):
            # key advances inside the jitted burst: same discipline as the
            # old per-step policy_fn, so K=1 is bitwise the per-step path
            key, sub = jax.random.split(key)
            mean, std = actor.apply({"params": actor_params}, a_obs)
            actions, _ = squash_sample(mean, std, sub, scale_j, bias_j)
            return (actions,), key

        burst_actor = BurstActor(_act_fn, _host_env_step, obs)

    update = start_step
    while update <= num_updates:
        if backend == "jax":
            # tier (a): the whole burst (act, step, auto-reset, ring add)
            # is ONE device program; random bursts clamp at the
            # learning-starts boundary so the catch-up train runs on time
            # (and at num_updates, so learning_starts > num_updates can't
            # collect past total_steps or skip the final log/ckpt gates)
            random_phase = update <= learning_starts
            boundary = min(learning_starts, num_updates) if random_phase else num_updates
            n_act = max(min(act_burst, boundary - update + 1), 1)
            with span("Time/rollout_time", SumMetric(sync_on_compute=False), phase="rollout"):
                stats = engine.collect(
                    agent_state["actor"], n_act, random_actions=random_phase
                )
            if cfg.metric.log_level > 0:
                _, done_b, ep_ret_b, ep_len_b = (np.asarray(s) for s in stats)
                for t_i, env_i in zip(*np.nonzero(done_b)):
                    ep_rew = float(ep_ret_b[t_i, env_i])
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                        aggregator.update("Game/ep_len_avg", float(ep_len_b[t_i, env_i]))
                    fabric.print(
                        f"Rank-0: policy_step={policy_step + (int(t_i) + 1) * n_envs}, "
                        f"reward_env_{int(env_i)}={ep_rew}"
                    )
            policy_step += n_envs * n_act
        elif update <= learning_starts:
            n_act = 1
            _host_env_step(envs.action_space.sample())
            policy_step = state_box["policy_step"]
        else:
            n_act = max(min(act_burst, num_updates - update + 1), 1)
            with span("Time/rollout_time", SumMetric(sync_on_compute=False), phase="rollout"):
                _, play_key = burst_actor.rollout(
                    play_actor, state_box["obs"], play_key, n_act
                )
            policy_step = state_box["policy_step"]

        first = update
        update += n_act
        last = update - 1

        if last >= learning_starts and per_rank_gradient_steps > 0:
            # one gradient burst covering every update index this burst
            # collected (the reference per-step cadence for K=1; K>1 trades
            # interleaving granularity for one dispatch per K steps)
            training_steps = last - max(first, learning_starts) + 1
            if first <= learning_starts <= last:
                # the catch-up burst the reference runs at learning_starts
                training_steps += learning_starts - 1
            g_total = training_steps * per_rank_gradient_steps
            # [G, B*world, ...] device arrays: ring-gathered from HBM, or
            # host-sampled + device_put overlapped with the previous burst
            batch = staging.sample_device(
                world_size * cfg.per_rank_batch_size,
                n_samples=g_total,
                sample_next_obs=cfg.buffer.sample_next_obs,
            )

            telemetry = get_telemetry()
            train_specs = None
            with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
                root_key, train_key = jax.random.split(root_key)
                # EMA cadence: fires when any update index covered by this
                # burst hits it (K=1 reduces to the reference per-update gate)
                do_ema = jnp.bool_(
                    any(u % ema_every == 0 for u in range(first, last + 1))
                )
                if lr_spike_at is not None and lr_spike_factor and first <= lr_spike_at <= last:
                    lr_spike_at = None  # fires exactly once
                    opt_states = {
                        k: set_lr(v, jnp.float32(get_lr(v) * lr_spike_factor))
                        for k, v in opt_states.items()
                    }
                train_args = (agent_state, opt_states, batch, train_key, do_ema)
                if telemetry is not None and telemetry.needs_train_flops():
                    # specs captured pre-call: the train step donates its state
                    train_specs = shape_specs(train_args)
                outs = train_fn(*train_args)
                agent_state, opt_states, losses = outs[0], outs[1], outs[2]
                # [G]-stacked learn probes (4th output when probes are on):
                # one cadence-gated device_get inside observe_probes
                observe_probes(
                    outs[3] if probes_enabled(cfg) and len(outs) > 3 else None,
                    step=policy_step,
                )
                losses = fetch_losses_if_observed(losses, aggregator)
            if needs_writeback:
                # PER writeback (replay.strategy=td_priority): the [G, B, 1]
                # td residuals flatten in the last plan's row order
                staging.update_priorities(
                    np.abs(np.asarray(jax.device_get(outs[-1]))).reshape(-1)
                )
            if train_specs is not None:
                # per train-step UNIT (FLOPs + bytes accessed): the counter
                # advances by world_size per dispatched program (which runs
                # g_total gradient steps)
                register_train_cost(
                    telemetry, train_fn, *train_specs, world_size=world_size
                )
            if backend == "python":
                play_actor = actor_mirror(agent_state["actor"])
            train_step += world_size

            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/value_loss", losses[0])
                aggregator.update("Loss/policy_loss", losses[1])
                aggregator.update("Loss/alpha_loss", losses[2])

        if inrun is not None and last >= learning_starts and inrun.due(policy_step):
            # versioned by policy_step; the npz write runs on the publisher's
            # writer thread, so the cost here is one actor-sized device_get
            inrun.maybe_publish(
                policy_step, {"agent": {"actor": jax.device_get(agent_state["actor"])}}
            )

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or last == num_updates
        ):
            if aggregator and not aggregator.disabled:
                metrics_dict = aggregator.compute()
                if logger is not None:
                    logger.log_metrics(metrics_dict, policy_step)
                aggregator.reset()
            log_sps_metrics(
                logger,
                policy_step=policy_step,
                last_log=last_log,
                train_step=train_step,
                last_train=last_train,
                world_size=world_size,
                action_repeat=cfg.env.action_repeat,
            )
            profile_tick(policy_step=policy_step, world_size=world_size)
            last_log = policy_step
            last_train = train_step

        if should_checkpoint(cfg, policy_step, last_checkpoint, last, num_updates):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": jax.device_get(agent_state),
                "opt_states": jax.device_get(opt_states),
                "update": last * world_size,
                "batch_size": cfg.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{fabric.global_rank}")
            with span("Time/checkpoint_time", phase="checkpoint"):
                fabric.call(
                    "on_checkpoint_coupled",
                    ckpt_path=ckpt_path,
                    state=ckpt_state,
                    replay_buffer=rb if cfg.buffer.get("checkpoint", False) else None,
                    sharding_meta=state_plan.describe() if state_plan is not None else None,
                )
            if preemption_requested():
                # SIGTERM/SIGINT: the final checkpoint is saved (the CLI
                # drains the in-flight write) — leave the train loop cleanly
                break

    if inrun is not None:
        inrun.close()
    staging.close()
    if envs is not None:
        envs.close()
    if fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        if backend == "jax":
            # evaluation runs the GYMNASIUM env of the same id (a dynamics
            # parity statement for the native envs) — pure-JAX-only ids
            # (brax/*) have no gymnasium counterpart, so a failed eval must
            # not crash the completed training run
            try:
                test(actor, agent_state["actor"], scale_j, bias_j, fabric, cfg, log_dir)
            except Exception as exc:
                warnings.warn(
                    f"run_test skipped for env.backend=jax: the evaluation "
                    f"env {cfg.env.id!r} could not be built/run through the "
                    f"gymnasium pipeline ({exc!r}); set algo.run_test=False "
                    "for pure-JAX-only envs"
                )
        else:
            test(actor, agent_state["actor"], scale_j, bias_j, fabric, cfg, log_dir)
