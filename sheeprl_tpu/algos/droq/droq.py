"""DroQ — SAC with a dropout-regularized Q ensemble and high replay ratio.

Behavioral contract from the reference ``sheeprl/algos/droq/droq.py``
(train :33-128, main :131-409): per update, ``per_rank_gradient_steps`` (20)
critic batches each update every ensemble member against a freshly sampled
dropout-perturbed TD target with a target-EMA after each member's step; the
actor and alpha update once per update from a *separate* batch, the actor
against the ensemble **mean** Q (reference :112 — not the min).

TPU-native notes (one jitted shard_map program per update, as in SAC):

- The reference steps each ensemble member with its own backward/step inside a
  Python loop (sharing one Adam across members, so each step also nudges the
  other members through stale momenta — an implementation quirk, not DroQ
  Algorithm 2). Here every member computes its loss with an independent
  dropout mask and the summed loss updates all members jointly; the target
  EMA runs once per gradient step, giving each member the same EMA cadence
  as the reference.
- Dropout keys thread through ``lax.scan`` so every gradient step and every
  member uses fresh masks, exactly one compiled program.
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

from sheeprl_tpu.algos.droq.agent import DROQCritic, droq_ensemble_q, init_droq_ensemble
from sheeprl_tpu.algos.sac.agent import SACActor, action_bounds, squash_sample
from sheeprl_tpu.algos.sac.loss import entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import concat_obs, test
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.data.staging import make_replay_staging
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import (
    learn_probes,
    log_sps_metrics,
    observe_probes,
    probes_enabled,
    profile_tick,
    span,
)
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of
from sheeprl_tpu.utils.utils import fetch_losses_if_observed, save_configs


def build_train_fn(
    actor: SACActor,
    critic: DROQCritic,
    actor_tx,
    qf_tx,
    alpha_tx,
    cfg,
    fabric,
    action_scale: np.ndarray,
    action_bias: np.ndarray,
    target_entropy: float,
):
    """G dropout-critic steps + one actor/alpha step, compiled as one SPMD
    program. ``critic_batch`` leaves are ``[G, B_local, ...]``;
    ``actor_batch`` leaves are ``[B_local, ...]``."""
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    n_critics = int(cfg.algo.critic.n)
    axis = fabric.data_axis
    scale = jnp.asarray(action_scale)
    bias = jnp.asarray(action_bias)
    tgt_entropy = jnp.float32(target_entropy)
    # learning-health probes (obs/learn): build-time gate, zero ops when off
    learn_on = probes_enabled(cfg)
    learn_clips = {
        "actor": clip_norm_of(actor_tx),
        "critic": clip_norm_of(qf_tx),
        "alpha": clip_norm_of(alpha_tx),
    }

    def critic_step(carry, batch_and_key):
        state, qf_opt = carry
        batch, key = batch_and_key
        next_key, tgt_key, drop_key = jax.random.split(key, 3)

        alpha = jax.lax.stop_gradient(jnp.exp(state["log_alpha"]))
        next_mean, next_std = actor.apply({"params": state["actor"]}, batch["next_observations"])
        next_actions, next_logprob = squash_sample(next_mean, next_std, next_key, scale, bias)
        target_q = droq_ensemble_q(
            critic, state["target_critics"], batch["next_observations"], next_actions, tgt_key
        )
        min_target = jnp.min(target_q, axis=-1, keepdims=True) - alpha * next_logprob
        td_target = jax.lax.stop_gradient(
            batch["rewards"] + (1.0 - batch["dones"]) * gamma * min_target
        )

        def qf_loss_fn(critic_params):
            q = droq_ensemble_q(critic, critic_params, batch["observations"], batch["actions"], drop_key)
            # per-member MSE against the shared target (Algorithm 2, line 8)
            return sum(((q[..., i : i + 1] - td_target) ** 2).mean() for i in range(n_critics))

        qf_loss, qf_grads = jax.value_and_grad(qf_loss_fn)(state["critics"])
        qf_grads = pmean(qf_grads, axis)
        qf_updates, qf_opt = qf_tx.update(qf_grads, qf_opt, state["critics"])
        critics = optax.apply_updates(state["critics"], qf_updates)
        targets = jax.tree_util.tree_map(
            lambda p, t: tau * p + (1.0 - tau) * t, critics, state["target_critics"]
        )
        new_state = {**state, "critics": critics, "target_critics": targets}
        if learn_on:
            probes = learn_probes(
                {"critic": qf_grads},
                params={"critic": state["critics"]},
                updates={"critic": qf_updates},
                losses=qf_loss,
                clip_norms=learn_clips,
            )
            return (new_state, qf_opt), (qf_loss, probes)
        return (new_state, qf_opt), qf_loss

    def local_train(state, opt_states, critic_batch, actor_batch, key):
        g = jax.tree_util.tree_leaves(critic_batch)[0].shape[0]
        keys = jax.random.split(key, g + 2)
        (state, qf_opt), qf_ys = jax.lax.scan(
            critic_step, (state, opt_states["qf"]), (critic_batch, keys[:g])
        )
        qf_losses, critic_probes = qf_ys if learn_on else (qf_ys, None)

        # ---- actor update from the separate batch, mean over the ensemble
        alpha = jax.lax.stop_gradient(jnp.exp(state["log_alpha"]))

        def actor_loss_fn(actor_params):
            mean, std = actor.apply({"params": actor_params}, actor_batch["observations"])
            actions, logprob = squash_sample(mean, std, keys[g], scale, bias)
            q = droq_ensemble_q(critic, state["critics"], actor_batch["observations"], actions, keys[g + 1])
            mean_q = jnp.mean(q, axis=-1, keepdims=True)
            return policy_loss(alpha, logprob, mean_q), logprob

        (actor_loss, logprob), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            state["actor"]
        )
        actor_grads = pmean(actor_grads, axis)
        actor_updates, actor_opt = actor_tx.update(actor_grads, opt_states["actor"], state["actor"])
        actor_params = optax.apply_updates(state["actor"], actor_updates)

        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, jax.lax.stop_gradient(logprob), tgt_entropy)

        alpha_loss, alpha_grad = jax.value_and_grad(alpha_loss_fn)(state["log_alpha"])
        alpha_grad = pmean(alpha_grad, axis)
        alpha_updates, alpha_opt = alpha_tx.update(alpha_grad, opt_states["alpha"], state["log_alpha"])
        log_alpha = optax.apply_updates(state["log_alpha"], alpha_updates)

        new_state = {**state, "actor": actor_params, "log_alpha": log_alpha}
        opt_states = {"actor": actor_opt, "qf": qf_opt, "alpha": alpha_opt}
        metrics = pmean(
            jnp.stack([jnp.mean(qf_losses), actor_loss, alpha_loss]), axis
        )
        if learn_on:
            actor_probes = learn_probes(
                {"actor": actor_grads, "alpha": alpha_grad},
                params={"actor": state["actor"], "alpha": state["log_alpha"]},
                updates={"actor": actor_updates, "alpha": alpha_updates},
                losses=(actor_loss, alpha_loss),
                clip_norms=learn_clips,
            )
            # the critic scan yields [G]-stacked samples, the actor/alpha
            # update one more — concatenate per key (the sentinel ravels)
            probes = {}
            for d in (critic_probes, actor_probes):
                for k, v in d.items():
                    v = jnp.ravel(v)
                    probes[k] = (
                        v if k not in probes else jnp.concatenate([probes[k], v])
                    )
            return new_state, opt_states, metrics, probes
        return new_state, opt_states, metrics

    shmapped = jax.shard_map(
        local_train,
        mesh=fabric.mesh,
        in_specs=(P(), P(), P(None, axis), P(axis), P()),
        out_specs=(P(), P(), P()) + ((P(),) if learn_on else ()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0, 1))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if "minedojo" in (cfg.env.wrapper._target_ or "").lower():
        raise ValueError("MineDojo is not currently supported by DroQ agent")

    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    if len(cfg.cnn_keys.encoder) > 0:
        warnings.warn("DroQ algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.cnn_keys.encoder = []

    state = None
    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    n_envs = int(cfg.env.num_envs) * world_size
    # vector backend picked by env.vectorization (envs/vector/factory.py)
    envs = make_vector_env(cfg, fabric, log_dir)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the DroQ agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the DroQ agent. "
                f"Provided environment: {cfg.env.id}"
            )
    if cfg.metric.log_level > 0:
        fabric.print("Encoder MLP keys:", cfg.mlp_keys.encoder)

    act_dim = int(np.prod(action_space.shape))
    obs_dim = int(sum(np.prod(observation_space[k].shape) for k in cfg.mlp_keys.encoder))
    action_scale, action_bias = action_bounds(action_space)

    actor = SACActor(action_dim=act_dim, hidden_size=cfg.algo.actor.hidden_size)
    critic = DROQCritic(
        hidden_size=cfg.algo.critic.hidden_size, num_critics=1, dropout=cfg.algo.critic.dropout
    )
    target_entropy = -float(act_dim)

    root_key, a_key, c_key = jax.random.split(root_key, 3)
    actor_params = actor.init(a_key, jnp.zeros((1, obs_dim), jnp.float32))["params"]
    critic_params = init_droq_ensemble(critic, c_key, int(cfg.algo.critic.n), obs_dim, act_dim)
    agent_state = {
        "actor": actor_params,
        "critics": critic_params,
        "target_critics": jax.tree_util.tree_map(jnp.copy, critic_params),
        "log_alpha": jnp.log(jnp.asarray([cfg.algo.alpha.alpha], jnp.float32)),
    }

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
    agent_state = jax.device_put(agent_state, fabric.replicated)
    opt_states = jax.device_put(opt_states, fabric.replicated)

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

    scale_j, bias_j = jnp.asarray(action_scale), jnp.asarray(action_bias)

    actor_mirror = HostParamMirror.from_cfg(agent_state["actor"], fabric, cfg)
    play_actor = actor_mirror(agent_state["actor"])

    train_fn = build_train_fn(
        actor, critic, actor_tx, qf_tx, alpha_tx, cfg, fabric, action_scale, action_bias, target_entropy
    )
    critic_sharding = fabric.sharding(None, fabric.data_axis)
    # TPU-first replay staging (data/staging.py): device-ring gathers when
    # buffer.device_ring=True, double-buffered host prefetch otherwise; the
    # actor batch is the [0] slice of a [1, B, ...] burst, so both batches
    # flow through the same facade (its burst sharding matches
    # critic_sharding, and slicing yields the actor's fabric.data_sharding)
    staging = make_replay_staging(
        cfg, fabric, rb, batch_sharding=critic_sharding, seed=cfg.seed
    )
    rb = staging.rb

    last_train = 0
    train_step = 0
    start_step = int(np.asarray(state["update"])) // world_size if state is not None else 1
    policy_step = int(np.asarray(state["update"])) * cfg.env.num_envs if state is not None else 0
    last_log = int(np.asarray(state["last_log"])) if state is not None else 0
    last_checkpoint = int(np.asarray(state["last_checkpoint"])) if state is not None else 0
    policy_steps_per_update = int(n_envs)
    warn_checkpoint_rounding(cfg, policy_steps_per_update)
    num_updates = int(cfg.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    if cfg.checkpoint.resume_from and not cfg.buffer.get("checkpoint", False):
        learning_starts += start_step

    o = envs.reset(seed=cfg.seed)[0]
    obs = concat_obs(o, cfg.mlp_keys.encoder, n_envs)
    per_rank_gradient_steps = int(cfg.algo.per_rank_gradient_steps)
    root_key, play_key = jax.random.split(root_key)
    play_key = actor_mirror.put_key(play_key)
    # burst acting (envs/rollout, howto/rollout_engine.md): K env steps per
    # device dispatch; 1 (the default) reproduces the per-step path exactly
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)

    # The acting loop body as one host function — env step, SAME_STEP
    # final_obs fixup, episode logging, buffer add: the old per-step block
    # verbatim. The BurstActor scans it K times per dispatch through an
    # ordered io_callback; the random prefill calls it directly.
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
        # key advances inside the jitted burst: same discipline as the old
        # per-step policy_fn, so K=1 is bitwise the per-step path
        key, sub = jax.random.split(key)
        mean, std = actor.apply({"params": actor_params}, a_obs)
        actions, _ = squash_sample(mean, std, sub, scale_j, bias_j)
        return (actions,), key

    burst_actor = BurstActor(_act_fn, _host_env_step, obs)

    update = start_step
    while update <= num_updates:
        if update <= learning_starts:
            n_act = 1
            _host_env_step(envs.action_space.sample())
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

        # one train round per update index the burst covered (K=1 reduces to
        # the reference per-update cadence; the per-update actor batch and
        # target-EMA semantics stay exact for every K)
        for u in range(first, last + 1):
            if u <= learning_starts:
                continue
            # both bursts arrive as device arrays: ring-gathered from HBM, or
            # host-sampled + device_put overlapped with the previous burst
            critic_batch = staging.sample_device(
                world_size * cfg.per_rank_batch_size,
                n_samples=per_rank_gradient_steps,
                sample_next_obs=cfg.buffer.sample_next_obs,
            )
            actor_batch = {
                k: v[0]
                for k, v in staging.sample_device(
                    world_size * cfg.per_rank_batch_size
                ).items()
            }

            with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
                root_key, train_key = jax.random.split(root_key)
                outs = train_fn(
                    agent_state, opt_states, critic_batch, actor_batch, train_key
                )
                agent_state, opt_states, losses = outs[0], outs[1], outs[2]
                observe_probes(outs[3] if len(outs) > 3 else None, step=policy_step)
                losses = fetch_losses_if_observed(losses, aggregator)
                play_actor = actor_mirror(agent_state["actor"])
            train_step += world_size

            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/value_loss", losses[0])
                aggregator.update("Loss/policy_loss", losses[1])
                aggregator.update("Loss/alpha_loss", losses[2])

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
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.get("checkpoint", False) else None,
            )
            if preemption_requested():
                # SIGTERM/SIGINT: the final checkpoint is saved (the CLI
                # drains the in-flight write) — leave the train loop cleanly
                break

    staging.close()
    envs.close()
    if fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        test(actor, agent_state["actor"], scale_j, bias_j, fabric, cfg, log_dir)
