"""Plan2Explore-DV3, finetuning phase.

Behavioral contract from the reference
``sheeprl/algos/p2e_dv3/p2e_dv3_finetuning.py`` (main :30-527): reload the
exploration checkpoint, inherit every model hyper-parameter from the
exploration run's config (:48-74, done by the CLI here — see
``cli.py run()``), then train with the **plain DV3 step** (world model +
task actor-critic). The player initially acts with the exploration actor and
switches to the task actor once ``learning_starts`` is reached
(:379-381); the final test and evaluation always use the task actor.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_train_fn
from sheeprl_tpu.algos.dreamer_v3.utils import (
    init_moments,
    normalize_obs_jnp,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.p2e_dv3.agent import acting_params, build_agent, build_player_fns
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.data.staging import make_replay_staging
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.plane import train_gated_burst_plan
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import log_sps_metrics, profile_tick, span
from sheeprl_tpu.train import metric_fetch_gate, run_train_burst, tau_schedule
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


def _as_jnp_tree(tree):
    return jax.tree_util.tree_map(jnp.asarray, tree)


@register_algorithm()
def main(fabric, cfg: Dict[str, Any], exploration_cfg: Dict[str, Any]):
    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    resume_from_checkpoint = bool(cfg.checkpoint.resume_from)
    ckpt_path = cfg.checkpoint.resume_from or cfg.checkpoint.exploration_ckpt_path
    from sheeprl_tpu.utils.utils import migrate_dv3_checkpoint

    state = migrate_dv3_checkpoint(fabric.load(ckpt_path))

    # All the models must be equal to the ones of the exploration phase
    # (reference :48-74)
    for k in (
        "gamma", "lmbda", "horizon", "layer_norm", "dense_units", "mlp_layers",
        "dense_act", "cnn_act", "unimix", "hafner_initialization",
    ):
        cfg.algo[k] = exploration_cfg.algo[k]
    cfg.algo.world_model = exploration_cfg.algo.world_model
    cfg.algo.actor = exploration_cfg.algo.actor
    cfg.algo.critic = exploration_cfg.algo.critic
    cfg.algo.ensembles = exploration_cfg.algo.ensembles
    cfg.algo.critics_exploration = exploration_cfg.algo.critics_exploration
    cfg.env.clip_rewards = exploration_cfg.env.clip_rewards
    cfg.cnn_keys = exploration_cfg.cnn_keys
    cfg.mlp_keys = exploration_cfg.mlp_keys
    cfg.env.frame_stack = -1
    # Seeding finetuning with the exploration buffer requires matching env
    # counts (reference :66-70)
    if cfg.buffer.get("load_from_exploration", False) and exploration_cfg.buffer.checkpoint:
        cfg.env.num_envs = exploration_cfg.env.num_envs
    if resume_from_checkpoint:
        cfg.per_rank_batch_size = int(np.asarray(state["batch_size"])) // world_size

    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    n_envs = int(cfg.env.num_envs) * world_size
    # each env fault-tolerant via RestartOnException; vector backend
    # picked by env.vectorization (envs/vector/factory.py)
    envs = make_vector_env(cfg, fabric, log_dir, restart_on_exception=True)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    cnn_keys = list(cfg.cnn_keys.encoder)
    mlp_keys = list(cfg.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    root_key, build_key = jax.random.split(root_key)
    world_model, actor, critic, _, _ = build_agent(
        cfg, actions_dim, is_continuous, observation_space, build_key
    )

    # Restore the exploration (or resumed-finetuning) weights
    if resume_from_checkpoint:
        loaded = state["agent"]
        params = _as_jnp_tree(loaded["params"])
        moments = _as_jnp_tree(loaded["moments"])
        actor_expl_params = _as_jnp_tree(state["actor_exploration"])
        expl_decay_steps = int(np.asarray(state["expl_decay_steps"]))
    else:
        expl = state["agent"]["params"]
        params = _as_jnp_tree(
            {
                "world_model": expl["world_model"],
                "actor": expl["actor_task"],
                "critic": expl["critic_task"],
                "target_critic": expl["target_critic_task"],
            }
        )
        moments = _as_jnp_tree(state["agent"]["moments"]["task"])
        actor_expl_params = _as_jnp_tree(expl["actor_exploration"])
        expl_decay_steps = int(np.asarray(state["expl_decay_steps"]))

    world_tx = instantiate(
        cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients
    )
    actor_tx = instantiate(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients)
    critic_tx = instantiate(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients)
    agent_state = {
        "params": params,
        "opt": {
            "world_model": world_tx.init(params["world_model"]),
            "actor": actor_tx.init(params["actor"]),
            "critic": critic_tx.init(params["critic"]),
        },
        "moments": moments if moments else init_moments(),
    }
    if resume_from_checkpoint:
        # conform the raw restore to the freshly-initialized optax structures
        from sheeprl_tpu.utils.utils import conform_pytree

        agent_state["opt"] = _as_jnp_tree(
            conform_pytree(jax.device_get(agent_state["opt"]), state["agent"]["opt"])
        )
    agent_state = jax.device_put(agent_state, fabric.replicated)
    actor_expl_params = jax.device_put(actor_expl_params, fabric.replicated)

    train_fn = build_train_fn(
        world_model, actor, critic, world_tx, actor_tx, critic_tx,
        cfg, fabric, actions_dim, is_continuous,
    )
    player_fns = build_player_fns(world_model, actor, cfg, actions_dim, is_continuous)
    # host-mirrored acting snapshots (utils/host.py) of the leaves acting
    # reads; the frozen exploration actor is mirrored once
    wm_mirror = HostParamMirror.from_cfg(
        acting_params(agent_state["params"]["world_model"]), fabric, cfg
    )
    actor_mirror = HostParamMirror.from_cfg(agent_state["params"]["actor"], fabric, cfg)
    play_wm = wm_mirror(acting_params(agent_state["params"]["world_model"]))
    play_actor = actor_mirror(agent_state["params"]["actor"])
    play_actor_expl = HostParamMirror.from_cfg(actor_expl_params, fabric, cfg)(
        actor_expl_params
    )

    player_actor_type = str(cfg.algo.player.actor_type)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    rb = make_replay_buffer(
        cfg,
        fabric,
        log_dir,
        n_envs=n_envs,
        kind="sequential",
        obs_keys=obs_keys,
        min_size=4,
        dry_run_size=4,
    )
    # Restore the replay buffer: from the resumed finetuning run, or seeded
    # from the exploration phase (reference buffer.load_from_exploration)
    if "rb" in state and (
        (resume_from_checkpoint and cfg.buffer.get("checkpoint", False))
        or (not resume_from_checkpoint and cfg.buffer.get("load_from_exploration", False))
    ):
        rb.load_state_dict(state["rb"])

    train_step = 0
    last_train = 0
    start_step = (
        int(np.asarray(state["update"])) // world_size if resume_from_checkpoint else 1
    )
    policy_step = (
        int(np.asarray(state["update"])) * cfg.env.num_envs if resume_from_checkpoint else 0
    )
    last_log = int(np.asarray(state["last_log"])) if resume_from_checkpoint else 0
    last_checkpoint = int(np.asarray(state["last_checkpoint"])) if resume_from_checkpoint else 0
    policy_steps_per_update = int(n_envs)
    updates_before_training = (
        cfg.algo.train_every // policy_steps_per_update if not cfg.dry_run else 0
    )
    num_updates = int(cfg.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    if resume_from_checkpoint and not cfg.buffer.checkpoint:
        learning_starts += start_step
    max_step_expl_decay = cfg.algo.actor.max_step_expl_decay // (
        cfg.algo.per_rank_gradient_steps * world_size
    ) if cfg.algo.actor.max_step_expl_decay else 0
    expl_amount = float(cfg.algo.actor.expl_amount)
    if resume_from_checkpoint:
        expl_amount = polynomial_decay(
            expl_decay_steps,
            initial=cfg.algo.actor.expl_amount,
            final=cfg.algo.actor.expl_min,
            max_decay_steps=max_step_expl_decay,
        )

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_update != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_update value ({policy_steps_per_update})."
        )
    warn_checkpoint_rounding(cfg, policy_steps_per_update)

    # TPU-first replay staging (data/staging.py): device-ring gathers when
    # buffer.device_ring=True, double-buffered host prefetch otherwise; the
    # whole [n, L, B, ...] burst arrives on device in one step, and the
    # per-gradient-step loop below slices device arrays (no H2D per step)
    staging = make_replay_staging(
        cfg,
        fabric,
        rb,
        sequence_length=int(cfg.per_rank_sequence_length),
        batch_sharding=fabric.sharding(None, None, fabric.data_axis),
        seed=cfg.seed,
    )
    rb = staging.rb

    o = envs.reset(seed=cfg.seed)[0]
    obs = prepare_obs(o, cnn_keys, mlp_keys, n_envs)
    step_data = {k: obs[k][None] for k in obs_keys}
    step_data["dones"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["rewards"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, n_envs, 1), np.float32)
    player_state = player_fns["init_states"](play_wm, n_envs)


    def player_actor_params():
        if player_actor_type == "exploration":
            return play_actor_expl
        return play_actor

    per_rank_gradient_steps = 0

    # Burst acting (tier b, howto/rollout_engine.md): K env steps per device
    # dispatch, K = env.act_burst; 1 reproduces the per-step path exactly.
    # The RSSM player state rides the burst carry next to the observation;
    # the host callback is the whole old loop body and applies episode
    # resets with the same mask * fresh + (1 - mask) * state arithmetic as
    # player_fns["reset_states"], against a host copy of the fresh init
    # state refreshed once per params version (DV3's fresh state has a
    # nonzero, params-dependent initial posterior). The finetuning wrinkle
    # is the actor switch: the player acts with the frozen exploration actor
    # until ``learning_starts``, then with the task actor — the switch is
    # re-checked once per burst and the burst plan is clamped so no burst
    # ever spans it.
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)
    n_sub = len(actions_dim)
    state_box = {
        "carry": {
            "obs": obs,
            "player": {k: np.asarray(v) for k, v in player_state.items()},
        },
        "policy_step": policy_step,
        "fresh": None,
    }

    def _fresh_player():
        if state_box["fresh"] is None:
            with span("Time/act_fresh_state_time", phase="rollout"):
                fresh = player_fns["init_states"](play_wm, n_envs)
                state_box["fresh"] = {k: np.asarray(v) for k, v in fresh.items()}
        return state_box["fresh"]

    def _host_step_core(actions, real_actions, player_np):
        state_box["policy_step"] += n_envs
        step_data["actions"] = actions.reshape(1, n_envs, -1).astype(np.float32)
        rb.add(step_data)
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            o, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
        dones = np.logical_or(terminated, truncated).astype(np.float32)

        step_data["is_first"] = np.zeros_like(step_data["dones"])
        if cfg.metric.log_level > 0 and "final_info" in infos:
            fi = infos["final_info"]
            if isinstance(fi, dict) and "episode" in fi:
                mask = np.asarray(fi.get("_episode", []), dtype=bool)
                for i in np.nonzero(mask)[0]:
                    ep_rew = float(fi["episode"]["r"][i])
                    ep_len = float(fi["episode"]["l"][i])
                    if aggregator and "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                    if aggregator and "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", ep_len)
                    fabric.print(
                        f"Rank-0: policy_step={state_box['policy_step']}, reward_env_{i}={ep_rew}"
                    )

        next_obs_np = {k: np.asarray(o[k]) for k in o}
        dones_idxes = np.nonzero(dones.reshape(-1))[0].tolist()
        real_next_obs = {k: v.copy() for k, v in next_obs_np.items()}
        if "final_obs" in infos and len(dones_idxes) > 0:
            for idx in dones_idxes:
                fo = infos["final_obs"][idx]
                if fo is not None:
                    for k in real_next_obs:
                        if k in fo:
                            real_next_obs[k][idx] = np.asarray(fo[k])

        new_obs = prepare_obs(next_obs_np, cnn_keys, mlp_keys, n_envs)
        for k in obs_keys:
            step_data[k] = new_obs[k][None]

        rewards = np.asarray(rewards, np.float32).reshape(n_envs, 1)
        step_data["dones"] = dones.reshape(1, n_envs, 1)
        step_data["rewards"] = clip_rewards_fn(rewards)[None]

        if len(dones_idxes) > 0:
            reset_obs = prepare_obs(
                {k: real_next_obs[k][dones_idxes] for k in real_next_obs},
                cnn_keys, mlp_keys, len(dones_idxes),
            )
            reset_data = {k: reset_obs[k][None] for k in obs_keys}
            reset_data["dones"] = np.ones((1, len(dones_idxes), 1), np.float32)
            reset_data["actions"] = np.zeros((1, len(dones_idxes), int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["dones"])
            rb.add(reset_data, dones_idxes)

            step_data["rewards"][:, dones_idxes] = 0.0
            step_data["dones"][:, dones_idxes] = 0.0
            step_data["is_first"][:, dones_idxes] = 1.0
            reset_mask = np.zeros((n_envs, 1), np.float32)
            reset_mask[dones_idxes] = 1.0
            # same arithmetic as player_fns["reset_states"], applied
            # host-side against the cached fresh init state
            fresh = _fresh_player()
            keep = np.float32(1.0) - reset_mask
            player_np = {
                k: reset_mask * fresh[k] + keep * v for k, v in player_np.items()
            }

        carry = {"obs": new_obs, "player": player_np}
        state_box["carry"] = carry
        return carry

    def _host_env_step(*args):
        actions_j = [np.asarray(a) for a in args[:n_sub]]
        player_np = {
            "actions": np.asarray(args[n_sub]),
            "recurrent": np.asarray(args[n_sub + 1]),
            "stochastic": np.asarray(args[n_sub + 2]),
        }
        actions = np.concatenate(actions_j, -1)
        if is_continuous:
            real_actions = actions
        else:
            real_actions = np.stack([np.argmax(a, axis=-1) for a in actions_j], axis=-1)
        return _host_step_core(actions, real_actions, player_np)

    def _act_fn(p, carry, key):
        # the key advances inside the jitted burst with the same split order
        # the per-step loop used (carried key first, act key second), so the
        # K=1 key stream is bitwise the per-step stream
        key, act_key = jax.random.split(key)
        norm_obs = normalize_obs_jnp(carry["obs"], cnn_keys)
        actions_j, new_player = player_fns["exploration_action"](
            p["wm"], p["actor"], carry["player"], norm_obs, act_key, p["expl"]
        )
        cb_args = tuple(actions_j) + (
            new_player["actions"],
            new_player["recurrent"],
            new_player["stochastic"],
        )
        return cb_args, key

    burst_actor = BurstActor(_act_fn, _host_env_step, state_box["carry"])

    # in-run eval (howto/evaluation.md): rank 0 publishes the frozen params
    # through the policy channel every eval.every_n_steps; a separate process
    # scores the task actor, so nothing below touches the train-step
    # critical path
    from sheeprl_tpu.evals.inrun import maybe_start_inrun_eval

    inrun = maybe_start_inrun_eval(fabric, cfg, log_dir)

    update = start_step
    while update <= num_updates:
        # no random prefill here (resuming=True mirrors the per-step loop,
        # which acts with the frozen exploration actor from step one)
        n_act, _ = train_gated_burst_plan(
            update,
            act_burst,
            learning_starts,
            num_updates,
            updates_before_training,
            resuming=True,
        )
        if update < learning_starts:
            # the acting actor flips exploration → task at learning_starts;
            # clamp so the burst never spans the switch (reference :379-381)
            n_act = max(min(n_act, learning_starts - update), 1)
        if update >= learning_starts and player_actor_type == "exploration":
            player_actor_type = "task"

        if not wm_mirror.enabled:
            # acting runs on the device that holds the trained leaves, and its
            # program waits for the host callback: the callback has to find the
            # fresh player state made, not ask it of the device it holds
            _fresh_player()
        with span("Time/rollout_time", SumMetric(sync_on_compute=False), phase="rollout"):
            _, root_key = burst_actor.rollout(
                {
                    "wm": play_wm,
                    "actor": player_actor_params(),
                    "expl": jnp.float32(expl_amount),
                },
                state_box["carry"],
                root_key,
                n_act,
            )
        # the burst program commits its inputs to the player's device;
        # pull the carried key back to host numpy (uncommitted) so the
        # possibly multi-device train program keeps accepting it
        root_key = np.asarray(root_key)
        policy_step = state_box["policy_step"]

        update += n_act
        last = update - 1
        updates_before_training -= n_act

        if last >= learning_starts and updates_before_training <= 0:
            n_samples = (
                cfg.algo.per_rank_pretrain_steps
                if last == learning_starts
                else cfg.algo.per_rank_gradient_steps
            )
            metrics = None
            if n_samples > 0:
                local_data = staging.sample_device(
                    cfg.per_rank_batch_size * world_size,
                    sequence_length=cfg.per_rank_sequence_length,
                    n_samples=n_samples,
                )
                # EMA targets: soft tau on the cadence, the run's very first
                # gradient step hard-copies
                taus = tau_schedule(
                    n_samples,
                    per_rank_gradient_steps,
                    cfg.algo.critic.target_network_update_freq,
                    tau=cfg.algo.critic.tau,
                    first_hard=True,
                )
                fetch_metrics = metric_fetch_gate(
                    cfg,
                    aggregator,
                    policy_step=policy_step,
                    last_log=last_log,
                    train_step=train_step,
                    update=last,
                    num_updates=num_updates,
                    policy_steps_per_update=policy_steps_per_update,
                    world_size=world_size,
                )
                with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
                    # the whole burst (n_samples gradient steps) is ONE
                    # scanned dispatch (sheeprl_tpu/train)
                    root_key, train_key = jax.random.split(root_key)
                    agent_state, metrics = run_train_burst(
                        train_fn,
                        agent_state,
                        local_data,
                        (jax.random.split(train_key, n_samples), jnp.asarray(taus)),
                        world_size=world_size,
                        fetch_metrics=fetch_metrics,
                    )
                    per_rank_gradient_steps += n_samples
                    play_wm = wm_mirror(acting_params(agent_state["params"]["world_model"]))
                    play_actor = actor_mirror(agent_state["params"]["actor"])
                    # cached fresh player state belongs to the previous
                    # params version — recompute on next episode reset
                    state_box["fresh"] = None
                    train_step += world_size
            updates_before_training = cfg.algo.train_every // policy_steps_per_update
            if cfg.algo.actor.expl_decay:
                expl_decay_steps += 1
                expl_amount = polynomial_decay(
                    expl_decay_steps,
                    initial=cfg.algo.actor.expl_amount,
                    final=cfg.algo.actor.expl_min,
                    max_decay_steps=max_step_expl_decay,
                )
            if aggregator and not aggregator.disabled:
                if metrics is not None:
                    for k, v in metrics.items():
                        if k in aggregator:
                            aggregator.update(k, float(np.asarray(v)))
                if "Params/exploration_amount" in aggregator:
                    aggregator.update("Params/exploration_amount", expl_amount)

        if inrun is not None and last >= learning_starts and inrun.due(policy_step):
            # versioned by policy_step; the npz write runs on the publisher's
            # writer thread, so the cost here is one params-sized device_get
            inrun.maybe_publish(
                policy_step,
                {"agent": {"params": jax.device_get(agent_state["params"])}},
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
                "actor_exploration": jax.device_get(actor_expl_params),
                "expl_decay_steps": expl_decay_steps,
                "update": last * world_size,
                "batch_size": cfg.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path_out = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{fabric.global_rank}")
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path_out,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.get("checkpoint", False) else None,
            )
            if preemption_requested():
                # SIGTERM/SIGINT: the final checkpoint is saved (the CLI
                # drains the in-flight write) — leave the train loop cleanly
                break

    if inrun is not None:
        inrun.close()
    staging.close()
    envs.close()
    if fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        final = jax.device_get(agent_state["params"])
        test(
            player_fns,
            {"world_model": final["world_model"], "actor": final["actor"]},
            fabric, cfg, log_dir, sample_actions=True,
        )
