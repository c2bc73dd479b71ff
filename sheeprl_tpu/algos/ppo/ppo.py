"""PPO, coupled — the framework's first end-to-end vertical slice.

Behavioral contract from the reference ``sheeprl/algos/ppo/ppo.py``
(train :32-105, main :108-454): on-policy rollout → GAE → epochs×minibatch
clipped-surrogate SGD, with truncation bootstrapping (:291-310), annealed
lr/clip/entropy coefficients (:425-433), metric aggregation, checkpointing,
and a final greedy test on rank 0.

TPU-native design (NOT a translation):

- **One jitted update per rollout.** The reference runs a Python loop of
  epochs × minibatches with per-minibatch ``fabric.backward`` allreduces; here
  the whole update (shuffle → minibatch scan → grad → psum → optimizer) is a
  single ``shard_map``-ped, jit-compiled program: ``lax.scan`` over epochs and
  minibatches, `optax` update inline, gradients ``pmean``-ed over the mesh's
  ``data`` axis. XLA fuses the lot; the host dispatches once per update.
- **SPMD instead of DDP ranks.** One process drives all devices. The
  reference's per-rank envs/data become per-device shards of a single
  ``[n_envs_total]`` batch (``n_envs_total = env.num_envs × world_size``), so
  the reference's step accounting (`policy_steps_per_update = num_envs ×
  rollout_steps × world_size`) holds identically.
- ``buffer.share_data`` (reference ppo.py:42-52) keeps its meaning: instead of
  per-device independent shuffles, every device sees the same global
  permutation and takes its `DistributedSampler` slice — expressed inside the
  same shard_map with the data replicated instead of sharded.
- Annealing (lr / clip / entropy) is host-side state threaded into the jitted
  step as dynamic scalars — no recompilation.
- Rollout collection goes through the burst actor (``envs/rollout``,
  ``howto/rollout_engine.md``): the per-step loop body (policy → env step →
  buffer add → episode bookkeeping) runs as a host callback scanned
  ``env.act_burst`` times per device dispatch; truncation V(s') bootstraps
  are patched into the stored rewards after the burst (the acting params
  are frozen across the rollout, so the values are identical).
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

from sheeprl_tpu.algos.ppo.agent import PPOAgent, build_agent, evaluate_actions, sample_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.obs import (
    count_h2d,
    get_telemetry,
    learn_probes,
    log_sps_metrics,
    observe_probes,
    probes_enabled,
    profile_tick,
    register_train_cost,
    shape_specs,
    span,
)
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.optim import clip_norm_of, set_lr
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.utils import fetch_losses_if_observed, gae, normalize_tensor, polynomial_decay, save_configs


def build_update_fn(
    agent: PPOAgent,
    tx: optax.GradientTransformation,
    cfg,
    fabric,
    n_local: int,
    donate: bool = True,
):
    """Compile the full PPO update as one SPMD program.

    ``n_local``: per-device sample count (rollout_steps × env.num_envs).
    Returns ``update(params, opt_state, data, key, clip_coef, ent_coef) ->
    (params, opt_state, metrics)`` where data leaves are ``[N, ...]`` arrays
    (sharded over the mesh unless ``buffer.share_data``).
    """
    share = bool(cfg.buffer.share_data)
    world = fabric.world_size
    epochs = int(cfg.algo.update_epochs)
    bs = min(int(cfg.per_rank_batch_size), n_local)
    n_mb = n_local // bs
    if n_local % bs != 0:
        warnings.warn(
            f"per_rank_batch_size ({bs}) does not divide the per-device sample count "
            f"({n_local}); each epoch drops the {n_local % bs} samples at the tail of "
            "its shuffle (static shapes are required under jit)"
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

    def loss_fn(params, batch, clip_coef, ent_coef):
        obs = normalize_obs(batch, cnn_keys, obs_keys)
        pre_dist, new_values = agent.apply({"params": params}, obs)
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

    def local_update(params, opt_state, data, key, clip_coef, ent_coef):
        rank = jax.lax.axis_index(axis)
        # per-device shuffle by default; identical global permutation +
        # DistributedSampler slice under share_data
        ep_keys = jax.random.split(key if share else jax.random.fold_in(key, rank), epochs)
        data_len = n_local * world if share else n_local

        def epoch_step(carry, ep_key):
            params, opt_state = carry
            perm = jax.random.permutation(ep_key, data_len)
            if share:
                perm = jax.lax.dynamic_slice(perm, (rank * n_local,), (n_local,))
            mb_idx = perm[: n_mb * bs].reshape(n_mb, bs)

            def mb_step(carry, idx):
                params, opt_state = carry
                batch = jax.tree_util.tree_map(lambda x: x[idx], data)
                (_, metrics), grads = grad_fn(params, batch, clip_coef, ent_coef)
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
            # probes stacked [epochs, n_mb]: every minibatch gradient step is
            # a sentinel sample (the host ravels them in order)
            return params, opt_state, metrics, probes
        return params, opt_state, metrics

    data_spec = P() if share else P(axis)
    shmapped = jax.shard_map(
        local_update,
        mesh=fabric.mesh,
        in_specs=(P(), P(), data_spec, P(), P(), P()),
        out_specs=(P(), P(), P()) + ((P(),) if learn_on else ()),
        check_vma=False,
    )
    # decoupled mode keeps the old params alive for the player thread, so
    # donation must be off there (donated buffers are invalidated mid-use)
    return jax.jit(shmapped, donate_argnums=(0, 1) if donate else ())


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if "minedojo" in (cfg.env.wrapper._target_ or "").lower():
        raise ValueError(
            "MineDojo is not currently supported by PPO agent, since it does not take "
            "into consideration the action masks provided by the environment. "
            "As an alternative you can use one of the Dreamers' agents."
        )

    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)

    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    # Resume state is restored against full templates once params/opt_state
    # exist (single checkpoint read); `state` carries the restored counters.
    state = None

    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    # Environment setup: the reference runs `env.num_envs` per DDP rank; here
    # one process drives all devices, so the vector env holds the whole batch.
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

    # Parameter init from a dummy observation batch
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
        # restore against a full template so optax NamedTuple states come back
        # with their original structure (orbax needs the exact tree)
        template = {
            "params": params,
            "opt_state": opt_state,
            "update": 0,
            "batch_size": 0,
            "last_log": 0,
            "last_checkpoint": 0,
        }
        state = fabric.load(cfg.checkpoint.resume_from, template)
        params = jax.device_put(state["params"], fabric.replicated)
        opt_state = jax.device_put(state["opt_state"], fabric.replicated)
        cfg.per_rank_batch_size = int(np.asarray(state["batch_size"])) // world_size
    else:
        params = jax.device_put(params, fabric.replicated)
        opt_state = jax.device_put(opt_state, fabric.replicated)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = make_replay_buffer(
        cfg,
        fabric,
        log_dir,
        n_envs=n_envs,
        obs_keys=obs_keys,
        size=int(cfg.buffer.size),
        sampled=False,
    )

    # ------------------------------------------------------------------
    # jitted programs
    # ------------------------------------------------------------------

    # The player runs on the CPU host with a mirrored parameter snapshot
    # (one pytree transfer per update) instead of dispatching one device
    # program per env step (SURVEY §5.8 — players pinned to CPU hosts
    # feeding the trainer mesh; algo.player_on_host=False acts on the mesh).
    to_host = HostParamMirror.from_cfg(params, fabric, cfg)

    def _act_fn(params, obs, key):
        # the key advances INSIDE the jitted burst: the rollout costs one
        # dispatch per env.act_burst env steps (a host-side jax.random.split
        # per step would be a second one); the body is the old per-step policy_step_fn verbatim, so
        # act_burst=1 reproduces the per-step path bitwise
        key, sub = jax.random.split(key)
        norm = normalize_obs(obs, cnn_keys, obs_keys)
        pre_dist, values = agent.apply({"params": params}, norm)
        actions, real_actions, logprob = sample_actions(pre_dist, is_continuous, sub)
        return (actions, real_actions, logprob, values), key

    @jax.jit
    def value_fn(params, obs):
        norm = normalize_obs(obs, cnn_keys, obs_keys)
        return agent.apply({"params": params}, norm, method=agent.get_value)

    gamma, gae_lambda = float(cfg.algo.gamma), float(cfg.algo.gae_lambda)

    @jax.jit
    def gae_fn(rewards, values, dones, next_values):
        return gae(rewards, values, dones, next_values, gamma, gae_lambda)

    n_local = int(cfg.algo.rollout_steps) * int(cfg.env.num_envs)
    update_fn = build_update_fn(agent, tx, cfg, fabric, n_local)
    data_sharding = fabric.replicated if cfg.buffer.share_data else fabric.data_sharding

    # Global counters (reference ppo.py:227-232)
    last_train = 0
    train_step = 0
    start_step = int(np.asarray(state["update"])) // world_size if state is not None else 1
    policy_step = (
        int(np.asarray(state["update"])) * cfg.env.num_envs * cfg.algo.rollout_steps
        if state is not None
        else 0
    )
    last_log = int(np.asarray(state["last_log"])) if state is not None else 0
    last_checkpoint = int(np.asarray(state["last_checkpoint"])) if state is not None else 0
    policy_steps_per_update = int(n_envs * cfg.algo.rollout_steps)
    num_updates = int(cfg.total_steps) // policy_steps_per_update if not cfg.dry_run else 1

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_update != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_update value ({policy_steps_per_update}), so "
            "the metrics will be logged at the nearest greater multiple of the "
            "policy_steps_per_update value."
        )
    warn_checkpoint_rounding(cfg, policy_steps_per_update)

    # First observation
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

    def _host_env_step(actions, real_actions, logprobs, values):
        state_box["policy_step"] += n_envs
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            real_actions = np.asarray(real_actions)
            obs, rewards, terminated, truncated, info = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )

        truncated_envs = np.nonzero(truncated)[0]
        if len(truncated_envs) > 0:
            # bootstrap V(s') into the reward on truncation (ppo.py:291-310),
            # deferred to the end of the burst
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
            "logprobs": np.asarray(logprobs).reshape(1, n_envs, 1),
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

        remaining = int(cfg.algo.rollout_steps)
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

        # GAE over the whole rollout (ppo.py:350-368), one fused scan on device
        next_values = value_fn(play_params, next_obs)
        returns, advantages = gae_fn(
            np.asarray(rb["rewards"]), np.asarray(rb["values"]), np.asarray(rb["dones"]), next_values
        )

        # Assemble the flat update batch: [T, n_envs, ...] → [n_envs*T, ...]
        # (env-major so device shards own whole envs), then stage to the mesh.
        def flat(x):
            x = jnp.asarray(x)
            return jnp.swapaxes(x, 0, 1).reshape((n_envs * x.shape[0],) + x.shape[2:])

        local_np = {
            **{k: rb[k] for k in obs_keys},
            "actions": rb["actions"],
            "logprobs": rb["logprobs"],
            "values": rb["values"],
            "returns": returns,
            "advantages": advantages,
        }
        with span("Time/stage_h2d_time", phase="stage_h2d"):
            local_data = jax.device_put(
                {k: flat(v) for k, v in local_np.items()}, data_sharding
            )
        count_h2d(local_np)

        telemetry = get_telemetry()
        update_specs = None
        with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
            root_key, update_key = jax.random.split(root_key)
            update_args = (
                params,
                opt_state,
                local_data,
                update_key,
                jnp.float32(cfg.algo.clip_coef),
                jnp.float32(cfg.algo.ent_coef),
            )
            if telemetry is not None and telemetry.needs_train_flops():
                # abstract specs captured pre-call: the update donates its
                # params/opt_state buffers, so the live arrays are gone after
                update_specs = shape_specs(update_args)
            outs = update_fn(*update_args)
            params, opt_state, losses = outs[0], outs[1], outs[2]
            observe_probes(outs[3] if len(outs) > 3 else None, step=policy_step)
            losses = fetch_losses_if_observed(losses, aggregator)
        if update_specs is not None:
            # per train-step UNIT (FLOPs + bytes accessed): the counter
            # advances by world_size per dispatched update program
            register_train_cost(
                telemetry, update_fn, *update_specs, world_size=world_size
            )
        play_params = to_host(params)
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

        # Anneal coefficients (ppo.py:425-433)
        if cfg.algo.anneal_clip_coef:
            cfg.algo.clip_coef = polynomial_decay(
                update, initial=initial_clip_coef, final=0.0, max_decay_steps=num_updates, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            cfg.algo.ent_coef = polynomial_decay(
                update, initial=initial_ent_coef, final=0.0, max_decay_steps=num_updates, power=1.0
            )

        # Checkpoint (ppo.py:435-450)
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
            with span("Time/checkpoint_time", phase="checkpoint"):
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
        test(agent, params, fabric, cfg, log_dir)
