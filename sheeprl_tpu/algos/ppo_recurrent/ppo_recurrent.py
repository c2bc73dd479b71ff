"""Recurrent PPO — LSTM policy trained on replayed sequences.

Behavioral contract from the reference
``sheeprl/algos/ppo_recurrent/ppo_recurrent.py`` (train :33-107, main
:110-499): on-policy rollouts carrying LSTM state (reset on done when
``reset_recurrent_state_on_done``), GAE, then epochs × minibatches of
*sequences* with the stored initial hidden state per sequence and losses
over every step.

TPU-native design: ``rollout_steps`` must be a multiple of
``per_rank_sequence_length`` (also asserted by the reference :226-228), so
the rollout splits into fixed-shape ``[L, N_seq, ...]`` chunks — no episode
splitting, padding, or masks: the training scan zeroes the carried state at
the stored per-step ``is_first`` flags, which reproduces the reference's
split-at-done semantics branchlessly. The whole update (epochs × random
sequence minibatches) is one ``shard_map``-ped jit with ``pmean`` grads.
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

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs
from sheeprl_tpu.algos.ppo_recurrent.agent import (
    RecurrentPPOAgent,
    build_agent,
    evaluate_actions,
    init_agent_params,
    sample_actions,
)
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
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
from sheeprl_tpu.utils.optim import clip_norm_of, set_lr
from sheeprl_tpu.utils.utils import fetch_losses_if_observed, gae, normalize_tensor, polynomial_decay, save_configs


def build_update_fn(
    agent: RecurrentPPOAgent,
    tx: optax.GradientTransformation,
    cfg,
    fabric,
    n_seq_local: int,
):
    """One SPMD program for the full recurrent-PPO update.

    ``seq_data`` leaves are ``[L, N_seq_local(*world), ...]``; ``init_hc`` is
    ``{"c","h"}: [N_seq, H]``; minibatches index the sequence axis.
    """
    epochs = int(cfg.algo.update_epochs)
    num_batches = int(cfg.get("per_rank_num_batches", 1) or 1)
    bs = max(n_seq_local // num_batches, 1)
    n_mb = n_seq_local // bs
    if n_seq_local % bs != 0:
        warnings.warn(
            f"per_rank_num_batches ({num_batches}) does not evenly divide the per-device "
            f"sequence count ({n_seq_local}); each epoch drops the tail of its shuffle"
        )
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    obs_keys = tuple(cfg.mlp_keys.encoder) + cnn_keys
    reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.vf_coef)
    clip_vloss = bool(cfg.algo.clip_vloss)
    norm_adv = bool(cfg.algo.normalize_advantages)
    axis = fabric.data_axis
    # learning-health probes (obs/learn): build-time gate, zero ops when off
    learn_on = probes_enabled(cfg)
    learn_clips = {"agent": clip_norm_of(tx)}

    def loss_fn(params, batch, hc, clip_coef, ent_coef):
        obs = normalize_obs(batch, cnn_keys, obs_keys)
        pre_dist, new_values, _ = agent.apply(
            {"params": params}, obs, batch["prev_actions"], batch["is_first"], hc
        )
        adv = batch["advantages"]
        if norm_adv:
            adv = normalize_tensor(adv)
        new_logprobs, entropy = evaluate_actions(
            pre_dist, batch["actions"], agent.actions_dim, agent.is_continuous
        )
        pg_loss = policy_loss(new_logprobs, batch["logprobs"], adv, clip_coef, reduction)
        v_loss = value_loss(
            new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, reduction
        )
        ent_loss = entropy_loss(entropy, reduction)
        loss = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        return loss, jnp.stack([pg_loss, v_loss, ent_loss])

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def local_update(params, opt_state, seq_data, init_hc, key, clip_coef, ent_coef):
        rank = jax.lax.axis_index(axis)
        ep_keys = jax.random.split(jax.random.fold_in(key, rank), epochs)

        def epoch_step(carry, ep_key):
            params, opt_state = carry
            perm = jax.random.permutation(ep_key, n_seq_local)
            mb_idx = perm[: n_mb * bs].reshape(n_mb, bs)

            def mb_step(carry, idx):
                params, opt_state = carry
                batch = jax.tree_util.tree_map(lambda x: x[:, idx], seq_data)
                hc = (init_hc["c"][idx], init_hc["h"][idx])
                (_, metrics), grads = grad_fn(params, batch, hc, clip_coef, ent_coef)
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

            carry, metrics = jax.lax.scan(mb_step, (params, opt_state), mb_idx)
            return carry, metrics

        (params, opt_state), ys = jax.lax.scan(epoch_step, (params, opt_state), ep_keys)
        metrics, probes = ys if learn_on else (ys, None)
        metrics = pmean(jnp.mean(metrics, axis=(0, 1)), axis)
        if learn_on:
            return params, opt_state, metrics, probes
        return params, opt_state, metrics

    shmapped = jax.shard_map(
        local_update,
        mesh=fabric.mesh,
        in_specs=(P(), P(), P(None, axis), P(axis), P(), P(), P()),
        out_specs=(P(), P(), P()) + ((P(),) if learn_on else ()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0, 1))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if "minedojo" in (cfg.env.wrapper._target_ or "").lower():
        raise ValueError(
            "MineDojo is not currently supported by PPO Recurrent agent, since it does not "
            "take into consideration the action masks provided by the environment."
        )

    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)

    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    # rollout must split evenly into sequences (reference :226-228)
    seq_len = int(cfg.get("per_rank_sequence_length") or cfg.algo.rollout_steps)
    if cfg.algo.rollout_steps % seq_len != 0:
        raise ValueError(
            f"The rollout steps ({cfg.algo.rollout_steps}) must be a multiple of the "
            f"sequence length ({seq_len})"
        )

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
    act_dim = int(sum(actions_dim))
    reset_on_done = bool(cfg.algo.get("reset_recurrent_state_on_done", True))

    agent = build_agent(cfg, actions_dim, is_continuous, cnn_keys, mlp_keys)
    root_key, init_key = jax.random.split(root_key)
    params = init_agent_params(agent, observation_space, cnn_keys, mlp_keys, init_key)

    tx = instantiate(cfg.algo.optimizer, max_grad_norm=cfg.algo.max_grad_norm or None)
    opt_state = tx.init(params)

    if cfg.checkpoint.resume_from:
        template = {
            "params": params,
            "opt_state": opt_state,
            "update": 0,
            "num_batches": 0,
            "last_log": 0,
            "last_checkpoint": 0,
        }
        state = fabric.load(cfg.checkpoint.resume_from, template)
        params = state["params"]
        opt_state = state["opt_state"]
        cfg.per_rank_num_batches = int(np.asarray(state["num_batches"]))
    params = jax.device_put(params, fabric.replicated)
    opt_state = jax.device_put(opt_state, fabric.replicated)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

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

    # ------------------------------------------------------------------
    # jitted programs
    # ------------------------------------------------------------------

    def _act_fn(params, carry, key):
        # the key advances INSIDE the jitted burst (one dispatch per
        # env.act_burst env steps); the policy body is the old per-step
        # policy_step_fn verbatim. The LSTM state rides in the carry pytree:
        # the host callback gets both the pre-step hidden state (recorded as
        # the sequence-chunk initials) and the post-step one, applies the
        # done mask exactly where the per-step loop did, and returns the
        # masked state for the next in-scan act.
        key, sub = jax.random.split(key)
        obs = {k: carry[k] for k in obs_keys}
        hc_in = (carry["hc_c"], carry["hc_h"])
        norm = normalize_obs(obs, cnn_keys, obs_keys)
        seq_obs = {k: v[None] for k, v in norm.items()}
        pre_dist, values, hc_out = agent.apply(
            {"params": params}, seq_obs, carry["prev_actions"][None], carry["is_first"][None], hc_in
        )
        pre_dist = [p[0] for p in pre_dist]
        actions, real_actions, logprob = sample_actions(pre_dist, is_continuous, sub)
        cb_args = (
            actions,
            real_actions,
            logprob,
            values[0],
            hc_in[0],
            hc_in[1],
            hc_out[0],
            hc_out[1],
            carry["prev_actions"],
            carry["is_first"],
        )
        return cb_args, key

    @jax.jit
    def value_fn(params, obs, prev_actions, is_first, hc):
        norm = normalize_obs(obs, cnn_keys, obs_keys)
        seq_obs = {k: v[None] for k, v in norm.items()}
        _, values, _ = agent.apply(
            {"params": params}, seq_obs, prev_actions[None], is_first[None], hc
        )
        return values[0]

    gamma, gae_lambda = float(cfg.algo.gamma), float(cfg.algo.gae_lambda)

    @jax.jit
    def gae_fn(rewards, values, dones, next_values):
        return gae(rewards, values, dones, next_values, gamma, gae_lambda)

    n_seq_local = (rollout_steps // seq_len) * int(cfg.env.num_envs)
    update_fn = build_update_fn(agent, tx, cfg, fabric, n_seq_local)
    seq_sharding = fabric.sharding(None, fabric.data_axis)
    hc_sharding = fabric.data_sharding

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
    hc0 = agent.initial_hc(n_envs)
    carry = {
        **next_obs,
        "prev_actions": np.zeros((n_envs, act_dim), np.float32),
        "is_first": np.ones((n_envs, 1), np.float32),
        "hc_c": np.asarray(hc0[0], np.float32),
        "hc_h": np.asarray(hc0[1], np.float32),
    }
    root_key, play_key = jax.random.split(root_key)

    # Burst acting (envs/rollout, howto/rollout_engine.md): the acting loop
    # body below is the old per-step block moved into a host callback; the
    # BurstActor scans it env.act_burst times per device dispatch. The host
    # keeps the recurrent bookkeeping it has always owned — hidden-state
    # recording, done masking, prev_action/is_first resets — and threads
    # everything back through the burst carry.
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)
    state_box = {"carry": carry, "policy_step": policy_step, "t": 0, "cx": None, "hx": None}
    #: (ring row, truncated env ids, prepared final obs, actions, unmasked
    #: hc) per truncation — the V(s') bootstrap is patched into the stored
    #: rewards after the burst returns (the jitted burst cannot re-enter the
    #: device)
    trunc_events = []

    def _host_env_step(
        actions, real_actions, logprob, values, hc_in_c, hc_in_h, hc_out_c, hc_out_h, prev_actions, is_first
    ):
        t = state_box["t"]
        state_box["t"] = t + 1
        state_box["policy_step"] += n_envs
        state_box["cx"][t] = np.asarray(hc_in_c)
        state_box["hx"][t] = np.asarray(hc_in_h)
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            real_actions = np.asarray(real_actions)
            obs, rewards, terminated, truncated, info = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )

        actions = np.asarray(actions)
        hc_out = (np.asarray(hc_out_c), np.asarray(hc_out_h))
        truncated_envs = np.nonzero(truncated)[0]
        if len(truncated_envs) > 0:
            # bootstrap V(s') into the reward on truncation, deferred to the
            # end of the burst (the pre-mask hidden state and this step's
            # actions are what the per-step path fed value_fn inline)
            final_obs = info["final_obs"]
            t_obs = {
                k: np.stack([np.asarray(final_obs[te][k]) for te in truncated_envs])
                for k in obs_keys
            }
            t_obs = prepare_obs(t_obs, cnn_keys, len(truncated_envs))
            t_hc = (hc_out[0][truncated_envs].copy(), hc_out[1][truncated_envs].copy())
            t_actions = actions[truncated_envs].reshape(len(truncated_envs), -1).copy()
            trunc_events.append((int(rb._pos), truncated_envs, t_obs, t_actions, t_hc))

        dones = np.logical_or(terminated, truncated).astype(np.float32)
        rewards = np.asarray(rewards, dtype=np.float32)

        prev_actions = np.asarray(prev_actions)
        is_first = np.asarray(is_first)
        step_data = {
            **{k: np.asarray(state_box["carry"][k])[None] for k in obs_keys},
            "dones": dones.reshape(1, n_envs, 1),
            "values": np.asarray(values).reshape(1, n_envs, 1),
            "actions": actions.reshape(1, n_envs, -1),
            "prev_actions": prev_actions[None].copy(),
            "is_first": is_first[None].copy(),
            "logprobs": np.asarray(logprob).reshape(1, n_envs, 1),
            "rewards": rewards.reshape(1, n_envs, 1),
        }
        rb.add(step_data)

        next_prev_actions = np.array(actions, np.float32).reshape(n_envs, -1)
        if reset_on_done:
            next_is_first = dones.reshape(n_envs, 1).copy()
            next_prev_actions[dones.reshape(-1) > 0] = 0.0
            if np.any(dones):
                mask = (1.0 - dones.reshape(n_envs, 1)).astype(np.float32)
                hc_out = (hc_out[0] * mask, hc_out[1] * mask)
        else:
            next_is_first = np.zeros((n_envs, 1), np.float32)

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

        new_carry = {
            **prepare_obs(obs, cnn_keys, n_envs),
            "prev_actions": next_prev_actions,
            "is_first": next_is_first,
            "hc_c": hc_out[0],
            "hc_h": hc_out[1],
        }
        state_box["carry"] = new_carry
        return new_carry

    burst_actor = BurstActor(_act_fn, _host_env_step, carry)

    for update in range(start_step, num_updates + 1):
        if cfg.algo.anneal_lr:
            lr = polynomial_decay(
                update - 1,
                initial=cfg.algo.optimizer.lr,
                final=0.0,
                max_decay_steps=num_updates,
                power=1.0,
            )
            opt_state = set_lr(opt_state, lr)
        else:
            lr = cfg.algo.optimizer.lr

        state_box["hx"] = np.empty((rollout_steps, n_envs, agent.rnn_hidden_size), np.float32)
        state_box["cx"] = np.empty((rollout_steps, n_envs, agent.rnn_hidden_size), np.float32)
        state_box["t"] = 0

        remaining = rollout_steps
        while remaining > 0:
            n_act = min(act_burst, remaining)
            with span("Time/rollout_time", SumMetric(sync_on_compute=False), phase="rollout"):
                _, play_key = burst_actor.rollout(
                    params, state_box["carry"], play_key, n_act
                )
            remaining -= n_act
        policy_step = state_box["policy_step"]
        hx_steps, cx_steps = state_box["hx"], state_box["cx"]

        # patch the deferred V(s') truncation bootstraps into the stored
        # rewards (params were frozen for the whole rollout, so the values
        # match what the per-step path computed inline)
        for row, tr_envs, t_obs, t_actions, t_hc in trunc_events:
            vals = np.asarray(
                value_fn(
                    params,
                    t_obs,
                    jnp.asarray(t_actions),
                    jnp.zeros((len(tr_envs), 1), jnp.float32),
                    (jnp.asarray(t_hc[0]), jnp.asarray(t_hc[1])),
                )
            ).reshape(-1)
            rewards_buf = rb["rewards"]
            rewards_buf[row, tr_envs, 0] = rewards_buf[row, tr_envs, 0] + vals
        trunc_events.clear()

        carry = state_box["carry"]
        next_obs = {k: carry[k] for k in obs_keys}
        prev_actions = carry["prev_actions"]
        is_first = carry["is_first"]
        hc = (jnp.asarray(carry["hc_c"]), jnp.asarray(carry["hc_h"]))

        # GAE over the rollout
        next_values = value_fn(
            params, next_obs, jnp.asarray(prev_actions), jnp.asarray(is_first), hc
        )
        returns, advantages = gae_fn(
            np.asarray(rb["rewards"]), np.asarray(rb["values"]), np.asarray(rb["dones"]), next_values
        )

        # Chunk the rollout into [L, N_seq, ...] sequences: [T, E] → env-major
        # [(T/L)*E sequences] so device shards own whole envs.
        n_chunks = rollout_steps // seq_len

        def to_seq(x):
            x = np.asarray(x)[:rollout_steps]
            # [T, E, ...] → [n_chunks, L, E, ...] → [L, E, n_chunks, ...] → [L, E*n_chunks, ...]
            x = x.reshape((n_chunks, seq_len) + x.shape[1:])
            x = np.moveaxis(x, 0, 2)
            return x.reshape((seq_len, n_envs * n_chunks) + x.shape[3:])

        seq_data = {
            **{k: to_seq(rb[k]) for k in obs_keys},
            "actions": to_seq(rb["actions"]),
            "prev_actions": to_seq(rb["prev_actions"]),
            "is_first": to_seq(rb["is_first"]),
            "logprobs": to_seq(rb["logprobs"]),
            "values": to_seq(rb["values"]),
            "returns": to_seq(np.asarray(returns)),
            "advantages": to_seq(np.asarray(advantages)),
        }
        # initial hidden state of every chunk: [E, n_chunks, H] → [E*n_chunks, H]
        def to_hc(x):
            x = x[::seq_len]  # [n_chunks, E, H]
            return np.moveaxis(x, 0, 1).reshape(n_envs * n_chunks, -1)

        init_hc = {"c": to_hc(cx_steps), "h": to_hc(hx_steps)}

        count_h2d(seq_data)
        count_h2d(init_hc)
        with span("Time/stage_h2d_time", phase="stage_h2d"):
            seq_data = jax.device_put(seq_data, seq_sharding)
            init_hc = jax.device_put(init_hc, hc_sharding)

        with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
            root_key, update_key = jax.random.split(root_key)
            outs = update_fn(
                params,
                opt_state,
                seq_data,
                init_hc,
                update_key,
                jnp.float32(cfg.algo.clip_coef),
                jnp.float32(cfg.algo.ent_coef),
            )
            params, opt_state, losses = outs[0], outs[1], outs[2]
            observe_probes(outs[3] if len(outs) > 3 else None, step=policy_step)
            losses = fetch_losses_if_observed(losses, aggregator)
        train_step += world_size

        if aggregator and not aggregator.disabled:
            aggregator.update("Loss/policy_loss", losses[0])
            aggregator.update("Loss/value_loss", losses[1])
            aggregator.update("Loss/entropy_loss", losses[2])

        if cfg.metric.log_level > 0 and logger is not None:
            logger.log_metrics({"Info/learning_rate": lr}, policy_step)
            logger.log_metrics({"Info/clip_coef": cfg.algo.clip_coef}, policy_step)
            logger.log_metrics({"Info/ent_coef": cfg.algo.ent_coef}, policy_step)

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

        if cfg.algo.anneal_clip_coef:
            cfg.algo.clip_coef = polynomial_decay(
                update, initial=initial_clip_coef, final=0.0, max_decay_steps=num_updates, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            cfg.algo.ent_coef = polynomial_decay(
                update, initial=initial_ent_coef, final=0.0, max_decay_steps=num_updates, power=1.0
            )

        if should_checkpoint(cfg, policy_step, last_checkpoint, update, num_updates):
            last_checkpoint = policy_step
            ckpt_state = {
                "params": jax.device_get(params),
                "opt_state": jax.device_get(opt_state),
                "update": update * world_size,
                "num_batches": int(cfg.get("per_rank_num_batches", 1) or 1),
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
        from sheeprl_tpu.algos.ppo_recurrent.utils import test

        test(agent, jax.device_get(params), fabric, cfg, log_dir)
