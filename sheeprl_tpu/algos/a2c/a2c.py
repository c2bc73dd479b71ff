"""A2C — synchronous advantage actor-critic on the PPO chassis.

Behavioral contract from the upstream sheeprl ``algos/a2c`` (the package
snapshot mounted at /root/reference predates it — only its tests reference
``exp=a2c``, tests/test_algos/test_algos.py:146-161): PPO's rollout/GAE
machinery with the *unclipped* policy gradient ``-(A · log π)`` and an MSE
value loss, one optimization pass per rollout.

TPU-native design: identical to ``ppo/ppo.py`` — one ``shard_map``-ped jit
per update (minibatch scan, ``pmean`` grads), rollout data sharded env-major
over the mesh.
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

from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.ppo.agent import (
    PPOAgent,
    build_agent,
    evaluate_actions,
    sample_actions,
)
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import (
    count_h2d,
    learn_probes,
    log_sps_metrics,
    observe_probes,
    probes_enabled,
    profile_tick,
    span,
)
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of
from sheeprl_tpu.utils.utils import fetch_losses_if_observed, gae, normalize_tensor, save_configs


def build_update_fn(
    agent: PPOAgent,
    tx: optax.GradientTransformation,
    cfg,
    fabric,
    n_local: int,
):
    """One SPMD program: minibatch scan with the A2C losses."""
    bs = min(int(cfg.per_rank_batch_size), n_local)
    n_mb = n_local // bs
    if n_local % bs != 0:
        warnings.warn(
            f"per_rank_batch_size ({bs}) does not divide the per-device sample count "
            f"({n_local}); the {n_local % bs} samples at the shuffle tail are dropped"
        )
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    obs_keys = tuple(cfg.mlp_keys.encoder) + cnn_keys
    reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.vf_coef)
    ent_coef = float(cfg.algo.ent_coef)
    norm_adv = bool(cfg.algo.normalize_advantages)
    axis = fabric.data_axis
    # learning-health probes (obs/learn): build-time gate, zero ops when off
    learn_on = probes_enabled(cfg)
    learn_clips = {"agent": clip_norm_of(tx)}

    def loss_fn(params, batch):
        obs = normalize_obs(batch, cnn_keys, obs_keys)
        pre_dist, new_values = agent.apply({"params": params}, obs)
        adv = batch["advantages"]
        if norm_adv:
            adv = normalize_tensor(adv)
        new_logprobs, entropy = evaluate_actions(
            pre_dist, batch["actions"], agent.actions_dim, agent.is_continuous
        )
        pg_loss = policy_loss(new_logprobs, adv, reduction)
        v_loss = value_loss(new_values, batch["returns"], reduction)
        loss = pg_loss + vf_coef * v_loss - ent_coef * entropy.mean()
        return loss, jnp.stack([pg_loss, v_loss])

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def local_update(params, opt_state, data, key):
        rank = jax.lax.axis_index(axis)
        perm = jax.random.permutation(jax.random.fold_in(key, rank), n_local)
        mb_idx = perm[: n_mb * bs].reshape(n_mb, bs)

        def mb_step(carry, idx):
            params, opt_state = carry
            batch = jax.tree_util.tree_map(lambda x: x[idx], data)
            (_, metrics), grads = grad_fn(params, batch)
            grads = pmean(grads, axis)
            updates, opt_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if learn_on:
                probes = learn_probes(
                    {"agent": grads},
                    params={"agent": params},
                    updates={"agent": updates},
                    losses=metrics,
                    clip_norms=learn_clips,
                )
                return (new_params, opt_state), (metrics, probes)
            return (new_params, opt_state), metrics

        (params, opt_state), ys = jax.lax.scan(mb_step, (params, opt_state), mb_idx)
        metrics, probes = ys if learn_on else (ys, None)
        metrics = pmean(jnp.mean(metrics, axis=0), axis)
        if learn_on:
            return params, opt_state, metrics, probes
        return params, opt_state, metrics

    shmapped = jax.shard_map(
        local_update,
        mesh=fabric.mesh,
        in_specs=(P(), P(), P(axis), P()),
        out_specs=(P(), P(), P()) + ((P(),) if learn_on else ()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0, 1))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    state = None
    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    n_envs = int(cfg.env.num_envs) * world_size
    envs = make_vector_env(cfg, fabric, log_dir)
    observation_space = envs.single_observation_space

    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.cnn_keys.encoder) + len(cfg.mlp_keys.encoder) == 0:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`cnn_keys.encoder=[rgb]` or `mlp_keys.encoder=[state]`"
        )
    if cfg.metric.log_level > 0:
        fabric.print("Encoder CNN keys:", cfg.cnn_keys.encoder)
        fabric.print("Encoder MLP keys:", cfg.mlp_keys.encoder)
    cnn_keys = list(cfg.cnn_keys.encoder)
    mlp_keys = list(cfg.mlp_keys.encoder)
    obs_keys = mlp_keys + cnn_keys

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (
            envs.single_action_space.nvec.tolist()
            if is_multidiscrete
            else [envs.single_action_space.n]
        )
    )

    agent = build_agent(cfg, actions_dim, is_continuous, cnn_keys, mlp_keys)

    root_key, init_key = jax.random.split(root_key)
    dummy_obs = {}
    for k in obs_keys:
        shape = observation_space[k].shape
        if k in cnn_keys:
            dummy_obs[k] = jnp.zeros((1, int(np.prod(shape[:-2])), *shape[-2:]), jnp.float32)
        else:
            dummy_obs[k] = jnp.zeros((1, int(np.prod(shape))), jnp.float32)
    params = agent.init(init_key, dummy_obs)["params"]

    tx = instantiate(cfg.algo.optimizer, max_grad_norm=cfg.algo.max_grad_norm or None)
    opt_state = tx.init(params)

    if cfg.checkpoint.resume_from:
        template = {
            "params": params,
            "opt_state": opt_state,
            "update": 0,
            "batch_size": 0,
            "last_log": 0,
            "last_checkpoint": 0,
        }
        state = fabric.load(cfg.checkpoint.resume_from, template)
        params = state["params"]
        opt_state = state["opt_state"]
        cfg.per_rank_batch_size = int(np.asarray(state["batch_size"])) // world_size
    params = jax.device_put(params, fabric.replicated)
    opt_state = jax.device_put(opt_state, fabric.replicated)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    to_host = HostParamMirror.from_cfg(params, fabric, cfg)

    rollout_steps = int(cfg.algo.rollout_steps)
    rb = make_replay_buffer(
        cfg,
        fabric,
        log_dir,
        n_envs=n_envs,
        obs_keys=obs_keys,
        size=int(cfg.buffer.size),
        min_size=rollout_steps,
        sampled=False,
    )

    def _act_fn(params, obs, key):
        # the key advances INSIDE the jitted burst (one dispatch per
        # env.act_burst env steps); the body is the old per-step
        # policy_step_fn verbatim, so act_burst=1 reproduces it bitwise
        key, sub = jax.random.split(key)
        norm = normalize_obs(obs, cnn_keys, obs_keys)
        pre_dist, values = agent.apply({"params": params}, norm)
        actions, real_actions, _logprob = sample_actions(pre_dist, is_continuous, sub)
        return (actions, real_actions, values), key

    @jax.jit
    def value_fn(params, obs):
        norm = normalize_obs(obs, cnn_keys, obs_keys)
        return agent.apply({"params": params}, norm, method=agent.get_value)

    gamma, gae_lambda = float(cfg.algo.gamma), float(cfg.algo.gae_lambda)

    @jax.jit
    def gae_fn(rewards, values, dones, next_values):
        return gae(rewards, values, dones, next_values, gamma, gae_lambda)

    n_local = rollout_steps * int(cfg.env.num_envs)
    update_fn = build_update_fn(agent, tx, cfg, fabric, n_local)

    last_train = 0
    train_step = 0
    start_step = int(np.asarray(state["update"])) // world_size if state is not None else 1
    policy_step = (
        int(np.asarray(state["update"])) * cfg.env.num_envs * rollout_steps
        if state is not None
        else 0
    )
    last_log = int(np.asarray(state["last_log"])) if state is not None else 0
    last_checkpoint = int(np.asarray(state["last_checkpoint"])) if state is not None else 0
    policy_steps_per_update = int(n_envs * rollout_steps)
    num_updates = int(cfg.total_steps) // policy_steps_per_update if not cfg.dry_run else 1

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_update != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_update value ({policy_steps_per_update})."
        )
    warn_checkpoint_rounding(cfg, policy_steps_per_update)

    obs = envs.reset(seed=cfg.seed)[0]
    next_obs = prepare_obs(obs, cnn_keys, n_envs)
    play_params = to_host(params)
    root_key, play_key = jax.random.split(root_key)
    play_key = to_host.put_key(play_key)

    # Burst acting (envs/rollout, howto/rollout_engine.md): the acting loop
    # body below is the old per-step block moved into a host callback; the
    # BurstActor scans it env.act_burst times per device dispatch.
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)
    state_box = {"obs": next_obs, "policy_step": policy_step}
    #: (ring row, truncated env ids, prepared final obs) per truncation —
    #: the V(s') bootstrap is patched into the stored rewards after the
    #: burst returns (the jitted burst cannot re-enter the device)
    trunc_events = []

    def _host_env_step(actions, real_actions, values):
        state_box["policy_step"] += n_envs
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            real_actions = np.asarray(real_actions)
            obs, rewards, terminated, truncated, info = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )

        truncated_envs = np.nonzero(truncated)[0]
        if len(truncated_envs) > 0:
            # bootstrap V(s') into the reward on truncation, deferred to the
            # end of the burst
            final_obs = info["final_obs"]
            t_obs = {
                k: np.stack([np.asarray(final_obs[te][k]) for te in truncated_envs])
                for k in obs_keys
            }
            t_obs = prepare_obs(t_obs, cnn_keys, len(truncated_envs))
            trunc_events.append((int(rb._pos), truncated_envs, t_obs))

        dones = np.logical_or(terminated, truncated).astype(np.float32)
        rewards = np.asarray(rewards, dtype=np.float32)

        step_data = {
            **{k: np.asarray(state_box["obs"][k])[None] for k in obs_keys},
            "dones": dones.reshape(1, n_envs, 1),
            "values": np.asarray(values).reshape(1, n_envs, 1),
            "actions": np.asarray(actions).reshape(1, n_envs, -1),
            "rewards": rewards.reshape(1, n_envs, 1),
        }
        rb.add(step_data)

        state_box["obs"] = prepare_obs(obs, cnn_keys, n_envs)

        if cfg.metric.log_level > 0 and "final_info" in info:
            fi = info["final_info"]
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
        return state_box["obs"]

    burst_actor = BurstActor(_act_fn, _host_env_step, next_obs)

    for update in range(start_step, num_updates + 1):
        remaining = rollout_steps
        while remaining > 0:
            n_act = min(act_burst, remaining)
            with span("Time/rollout_time", SumMetric(sync_on_compute=False), phase="rollout"):
                _, play_key = burst_actor.rollout(
                    play_params, state_box["obs"], play_key, n_act
                )
            remaining -= n_act
        policy_step = state_box["policy_step"]

        # patch the deferred V(s') truncation bootstraps into the stored
        # rewards (play_params were frozen for the whole rollout, so the
        # values match what the per-step path computed inline)
        for row, tr_envs, t_obs in trunc_events:
            vals = np.asarray(value_fn(play_params, t_obs)).reshape(-1)
            rewards_buf = rb["rewards"]
            rewards_buf[row, tr_envs, 0] = rewards_buf[row, tr_envs, 0] + vals
        trunc_events.clear()
        next_obs = state_box["obs"]

        next_values = value_fn(play_params, next_obs)
        returns, advantages = gae_fn(
            np.asarray(rb["rewards"]), np.asarray(rb["values"]), np.asarray(rb["dones"]), next_values
        )

        def flat(x):
            x = jnp.asarray(x)
            return jnp.swapaxes(x, 0, 1).reshape((n_envs * x.shape[0],) + x.shape[2:])

        local_np = {
            **{k: rb[k] for k in obs_keys},
            "actions": rb["actions"],
            "returns": returns,
            "advantages": advantages,
        }
        with span("Time/stage_h2d_time", phase="stage_h2d"):
            local_data = jax.device_put(
                {k: flat(v) for k, v in local_np.items()}, fabric.data_sharding
            )
        count_h2d(local_np)

        with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
            root_key, update_key = jax.random.split(root_key)
            outs = update_fn(params, opt_state, local_data, update_key)
            params, opt_state, losses = outs[0], outs[1], outs[2]
            observe_probes(outs[3] if len(outs) > 3 else None, step=policy_step)
            losses = fetch_losses_if_observed(losses, aggregator)
        play_params = to_host(params)
        train_step += world_size

        if aggregator and not aggregator.disabled:
            aggregator.update("Loss/policy_loss", losses[0])
            aggregator.update("Loss/value_loss", losses[1])

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == num_updates
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

        if should_checkpoint(cfg, policy_step, last_checkpoint, update, num_updates):
            last_checkpoint = policy_step
            ckpt_state = {
                "params": jax.device_get(params),
                "opt_state": jax.device_get(opt_state),
                "update": update * world_size,
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

    envs.close()
    if fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        test(agent, jax.device_get(params), fabric, cfg, log_dir)
