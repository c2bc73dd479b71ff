"""Plan2Explore-DV1, exploration phase.

Behavioral contract from the reference
``sheeprl/algos/p2e_dv1/p2e_dv1_exploration.py`` (train :38-390, main
:393-800): DV1 world-model learning, plus

- **ensemble learning** (:200-222): members regress the next *observation
  embedding* with a unit-Gaussian NLL;
- **exploration behaviour** (:224-330): DV1-style H-step imagination with
  the exploration actor; intrinsic reward = ensemble disagreement ×
  multiplier; pure dynamics-backprop actor loss
  ``-mean(discount · λ-values)``; Gaussian exploration critic (V1 has no
  target critics);
- **task behaviour** (:332-390): the plain DV1 actor-critic update.

TPU-native: one fused ``shard_map``-ped jit per gradient step; the shared
behaviour closure is instantiated twice (intrinsic / extrinsic reward).
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v1.agent import (
    Actor,
    WorldModel,
    build_actor_dists,
    resolve_actor_distribution,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v1.loss import gaussian_independent, reconstruction_loss
from sheeprl_tpu.algos.dreamer_v1.utils import (
    compute_lambda_values,
    normalize_obs_jnp,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.p2e_dv1.agent import apply_ensemble, build_agent, build_player_fns
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.data.staging import make_replay_staging
from sheeprl_tpu.distributions import Bernoulli, Independent, Normal
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.plane import train_gated_burst_plan
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import learn_probes, log_sps_metrics, probes_enabled, profile_tick, span
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of
from sheeprl_tpu.train import build_train_burst, metric_fetch_gate, run_train_burst
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

sg = jax.lax.stop_gradient


def build_train_fn(
    world_model: WorldModel,
    actor: Actor,
    critic,
    ensemble_member,
    txs: Dict[str, optax.GradientTransformation],
    cfg,
    fabric,
    actions_dim: Sequence[int],
    is_continuous: bool,
):
    """``train_step(agent_state, data, key) -> (agent_state, metrics)``."""
    axis = fabric.data_axis
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    mlp_keys = tuple(cfg.mlp_keys.encoder)
    learn_on = probes_enabled(cfg)
    learn_clips = {name: clip_norm_of(tx) for name, tx in txs.items()}
    wm_cfg = cfg.algo.world_model
    stoch_size = int(wm_cfg.stochastic_size)
    rec_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    use_continues = bool(wm_cfg.use_continues)
    intrinsic_mult = float(cfg.algo.intrinsic_reward_multiplier)
    distribution = resolve_actor_distribution(
        cfg.distribution.get("type", "auto"), is_continuous
    )
    init_std = float(cfg.algo.actor.init_std)
    min_std = float(cfg.algo.actor.min_std)

    def wm_apply(params, method, *args):
        return world_model.apply({"params": params}, *args, method=method)

    # -- world model loss: identical to DV1, but the embeddings are also
    # returned for ensemble training (reference :200-222) ------------------

    def wm_loss_fn(wm_params, data, key):
        T, B = data["rewards"].shape[:2]
        batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})
        embedded = wm_apply(wm_params, WorldModel.encode, batch_obs)

        def step(carry, inp):
            posterior, recurrent = carry
            action, embed, eps = inp
            recurrent, posterior, post_ms = world_model.apply(
                {"params": wm_params},
                posterior, recurrent, action, embed, None, eps,
                method=WorldModel.dynamic_posterior,
            )
            return (posterior, recurrent), (recurrent, posterior, post_ms)

        # pre-drawn sampling noise + batched prior stats (same as DV1/DV3)
        noise = jax.random.normal(key, (T, B, stoch_size))
        (_, _), (recurrents, posteriors, post_ms) = jax.lax.scan(
            step,
            (jnp.zeros((B, stoch_size)), jnp.zeros((B, rec_size))),
            (data["actions"], embedded, noise),
        )
        prior_ms = wm_apply(wm_params, WorldModel.prior_stats, recurrents)
        latents = jnp.concatenate([posteriors, recurrents], -1)
        recon = wm_apply(wm_params, WorldModel.decode, latents)
        qo = {k: gaussian_independent(recon[k], 1.0, 3 if k in cnn_keys else 1) for k in recon}
        qr = gaussian_independent(wm_apply(wm_params, WorldModel.reward, latents), 1.0, 1)
        if use_continues:
            qc = Independent(Bernoulli(logits=wm_apply(wm_params, WorldModel.continues, latents)), 1)
            continue_targets = 1.0 - data["dones"]
        else:
            qc = continue_targets = None
        posteriors_dist = Independent(Normal(post_ms[0], post_ms[1]), 1)
        priors_dist = Independent(Normal(prior_ms[0], prior_ms[1]), 1)
        loss, metrics = reconstruction_loss(
            qo, batch_obs, qr, data["rewards"],
            posteriors_dist, priors_dist,
            float(wm_cfg.kl_free_nats), float(wm_cfg.kl_regularizer),
            qc, continue_targets, float(wm_cfg.continue_scale_factor),
        )
        return loss, (metrics, sg(posteriors), sg(recurrents), sg(embedded))

    # -- ensemble loss (reference :200-222) --------------------------------

    def ensemble_loss_fn(ens_params, posteriors, recurrents, actions, embedded):
        inp = jnp.concatenate([posteriors, recurrents, actions], -1)
        out = apply_ensemble(ensemble_member, ens_params, inp)[:, :-1]
        target = embedded[1:][None]
        dist = Independent(Normal(out, jnp.ones_like(out)), 1)
        return -jnp.sum(jnp.mean(dist.log_prob(target), axis=tuple(range(1, out.ndim - 1))))

    # -- DV1 imagination with recorded actions (reference :224-245) --------

    def imagination_rollout(wm_params, actor_params, posteriors, recurrents, key):
        prior = posteriors.reshape(-1, stoch_size)
        recurrent = recurrents.reshape(-1, rec_size)
        latent = jnp.concatenate([prior, recurrent], -1)

        def policy(latent, k):
            pre = actor.apply({"params": actor_params}, sg(latent))
            dists = build_actor_dists(pre, is_continuous, distribution, init_std, min_std, unimix=0.0)
            return jnp.concatenate(sample_actor_actions(dists, is_continuous, k, True), -1)

        def step(carry, inp):
            prior, recurrent, latent = carry
            eps_img, k_act = inp
            action = policy(latent, k_act)
            prior, recurrent = world_model.apply(
                {"params": wm_params}, prior, recurrent, action, None, eps_img,
                method=WorldModel.imagination,
            )
            latent = jnp.concatenate([prior, recurrent], -1)
            return (prior, recurrent, latent), (latent, action)

        k_eps, key = jax.random.split(key)
        noise = jax.random.normal(k_eps, (horizon, prior.shape[0], stoch_size))
        keys = jax.random.split(key, horizon)
        _, (latents, acts) = jax.lax.scan(step, (prior, recurrent, latent), (noise, keys))
        return latents, acts

    # -- shared behaviour-learning actor loss (reference :224-330 / :332-390)

    def behaviour_actor_loss(actor_params, wm_params, critic_params,
                             posteriors, recurrents, key, reward_fn):
        traj, imagined_actions = imagination_rollout(
            wm_params, actor_params, posteriors, recurrents, key
        )
        predicted_values = critic.apply({"params": critic_params}, traj)
        reward = reward_fn(traj, imagined_actions)
        if use_continues:
            continues = jax.nn.sigmoid(wm_apply(wm_params, WorldModel.continues, traj)) * gamma
        else:
            continues = jnp.ones_like(sg(reward)) * gamma

        lambda_values = compute_lambda_values(
            reward, predicted_values, continues,
            last_values=predicted_values[-1], lmbda=lmbda,
        )
        discount = sg(
            jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
        )
        policy_loss = -jnp.mean(discount * lambda_values)
        aux = {
            "trajectories": sg(traj),
            "lambda_values": sg(lambda_values),
            "discount": discount,
            "reward_mean": jnp.mean(sg(reward)),
            "values_mean": jnp.mean(sg(predicted_values)),
        }
        return policy_loss, aux

    def critic_loss_fn(critic_params, traj, lambda_values, discount):
        qv = Independent(Normal(critic.apply({"params": critic_params}, traj[:-1]), 1.0), 1)
        return -jnp.mean(discount[..., 0] * qv.log_prob(lambda_values))

    # ----------------------------------------------------------------------

    def local_step(agent_state, data, key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        params = agent_state["params"]
        opt = agent_state["opt"]

        k_wm, k_expl, k_task = jax.random.split(key, 3)

        (wm_loss, (wm_metrics, posteriors, recurrents, embedded)), wm_grads = jax.value_and_grad(
            wm_loss_fn, has_aux=True
        )(params["world_model"], data, k_wm)
        wm_grads = pmean(wm_grads, axis)
        wm_updates, wm_opt = txs["world_model"].update(wm_grads, opt["world_model"], params["world_model"])
        wm_params = optax.apply_updates(params["world_model"], wm_updates)

        ens_loss, ens_grads = jax.value_and_grad(ensemble_loss_fn)(
            params["ensembles"], posteriors, recurrents, data["actions"], embedded
        )
        ens_grads = pmean(ens_grads, axis)
        ens_updates, ens_opt = txs["ensembles"].update(ens_grads, opt["ensembles"], params["ensembles"])
        ens_params = optax.apply_updates(params["ensembles"], ens_updates)

        def intrinsic_reward_fn(traj, imagined_actions):
            ens_in = jnp.concatenate([sg(traj), sg(imagined_actions)], -1)
            pred = apply_ensemble(ensemble_member, ens_params, ens_in)
            return jnp.var(pred, axis=0).mean(-1, keepdims=True) * intrinsic_mult

        def extrinsic_reward_fn(traj, imagined_actions):
            del imagined_actions
            return wm_apply(wm_params, WorldModel.reward, traj)

        # exploration actor + critic
        (pl_expl, aux_expl), a_expl_grads = jax.value_and_grad(
            behaviour_actor_loss, has_aux=True
        )(
            params["actor_exploration"], wm_params, params["critic_exploration"],
            posteriors, recurrents, k_expl, intrinsic_reward_fn,
        )
        a_expl_grads = pmean(a_expl_grads, axis)
        a_expl_updates, a_expl_opt = txs["actor_exploration"].update(
            a_expl_grads, opt["actor_exploration"], params["actor_exploration"]
        )
        actor_expl_params = optax.apply_updates(params["actor_exploration"], a_expl_updates)

        ce_loss, ce_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic_exploration"],
            aux_expl["trajectories"], aux_expl["lambda_values"], aux_expl["discount"],
        )
        ce_grads = pmean(ce_grads, axis)
        ce_updates, ce_opt = txs["critic_exploration"].update(
            ce_grads, opt["critic_exploration"], params["critic_exploration"]
        )
        critic_expl_params = optax.apply_updates(params["critic_exploration"], ce_updates)

        # task actor + critic
        (pl_task, aux_task), a_task_grads = jax.value_and_grad(
            behaviour_actor_loss, has_aux=True
        )(
            params["actor_task"], wm_params, params["critic_task"],
            posteriors, recurrents, k_task, extrinsic_reward_fn,
        )
        a_task_grads = pmean(a_task_grads, axis)
        a_task_updates, a_task_opt = txs["actor_task"].update(
            a_task_grads, opt["actor_task"], params["actor_task"]
        )
        actor_task_params = optax.apply_updates(params["actor_task"], a_task_updates)

        ct_loss, ct_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic_task"],
            aux_task["trajectories"], aux_task["lambda_values"], aux_task["discount"],
        )
        ct_grads = pmean(ct_grads, axis)
        ct_updates, ct_opt = txs["critic_task"].update(ct_grads, opt["critic_task"], params["critic_task"])
        critic_task_params = optax.apply_updates(params["critic_task"], ct_updates)

        metrics = dict(wm_metrics)
        metrics["Loss/ensemble_loss"] = ens_loss
        metrics["Loss/policy_loss_exploration"] = pl_expl
        metrics["Loss/value_loss_exploration"] = ce_loss
        metrics["Loss/policy_loss_task"] = pl_task
        metrics["Loss/value_loss_task"] = ct_loss
        metrics["Rewards/intrinsic"] = aux_expl["reward_mean"]
        metrics["Values_exploration/predicted_values"] = aux_expl["values_mean"]
        metrics["Values_exploration/lambda_values"] = jnp.mean(aux_expl["lambda_values"])
        metrics["Grads/world_model"] = optax.global_norm(wm_grads)
        metrics["Grads/ensemble"] = optax.global_norm(ens_grads)
        metrics["Grads/actor_exploration"] = optax.global_norm(a_expl_grads)
        metrics["Grads/critic_exploration"] = optax.global_norm(ce_grads)
        metrics["Grads/actor_task"] = optax.global_norm(a_task_grads)
        metrics["Grads/critic_task"] = optax.global_norm(ct_grads)
        metrics = pmean(metrics, axis)
        if learn_on:
            # grads are already pmean'd, so the probe scalars are identical
            # on every shard — the learn plane adds no collectives
            metrics.update(
                learn_probes(
                    {
                        "world_model": wm_grads,
                        "ensembles": ens_grads,
                        "actor_exploration": a_expl_grads,
                        "critic_exploration": ce_grads,
                        "actor_task": a_task_grads,
                        "critic_task": ct_grads,
                    },
                    params={
                        "world_model": params["world_model"],
                        "ensembles": params["ensembles"],
                        "actor_exploration": params["actor_exploration"],
                        "critic_exploration": params["critic_exploration"],
                        "actor_task": params["actor_task"],
                        "critic_task": params["critic_task"],
                    },
                    updates={
                        "world_model": wm_updates,
                        "ensembles": ens_updates,
                        "actor_exploration": a_expl_updates,
                        "critic_exploration": ce_updates,
                        "actor_task": a_task_updates,
                        "critic_task": ct_updates,
                    },
                    losses=(wm_loss, ens_loss, pl_expl, ce_loss, pl_task, ct_loss),
                    clip_norms=learn_clips,
                )
            )

        new_state = {
            "params": {
                "world_model": wm_params,
                "actor_task": actor_task_params,
                "critic_task": critic_task_params,
                "actor_exploration": actor_expl_params,
                "critic_exploration": critic_expl_params,
                "ensembles": ens_params,
            },
            "opt": {
                "world_model": wm_opt,
                "ensembles": ens_opt,
                "actor_task": a_task_opt,
                "critic_task": ct_opt,
                "actor_exploration": a_expl_opt,
                "critic_exploration": ce_opt,
            },
        }
        return new_state, metrics

    # step + fused-burst programs (scanned per-step input: key); the
    # ensemble params/optimizer state ride the burst carry with the rest
    return build_train_burst(local_step, fabric, n_scanned=1)


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    cfg.algo.player.actor_type = "exploration"
    cfg.env.screen_size = 64
    cfg.env.frame_stack = 1

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
    if cfg.cnn_keys.encoder == [] and cfg.mlp_keys.encoder == []:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`cnn_keys.encoder=[rgb]` or `mlp_keys.encoder=[state]`"
        )
    cnn_keys = list(cfg.cnn_keys.encoder)
    mlp_keys = list(cfg.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    root_key, build_key = jax.random.split(root_key)
    world_model, actor, critic, ensemble_member, params = build_agent(
        cfg, actions_dim, is_continuous, observation_space, build_key
    )
    txs = {
        "world_model": instantiate(
            cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients
        ),
        "ensembles": instantiate(
            cfg.algo.ensembles.optimizer, max_grad_norm=cfg.algo.ensembles.clip_gradients
        ),
        "actor_task": instantiate(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
        "critic_task": instantiate(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
        "actor_exploration": instantiate(
            cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients
        ),
        "critic_exploration": instantiate(
            cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients
        ),
    }
    agent_state = {
        "params": params,
        "opt": {
            "world_model": txs["world_model"].init(params["world_model"]),
            "ensembles": txs["ensembles"].init(params["ensembles"]),
            "actor_task": txs["actor_task"].init(params["actor_task"]),
            "critic_task": txs["critic_task"].init(params["critic_task"]),
            "actor_exploration": txs["actor_exploration"].init(params["actor_exploration"]),
            "critic_exploration": txs["critic_exploration"].init(params["critic_exploration"]),
        },
    }

    expl_decay_steps = 0
    state = None
    if cfg.checkpoint.resume_from:
        template = {
            "agent": agent_state,
            "expl_decay_steps": 0,
            "update": 0,
            "batch_size": 0,
            "last_log": 0,
            "last_checkpoint": 0,
        }
        state = fabric.load(cfg.checkpoint.resume_from, template)
        agent_state = state["agent"]
        expl_decay_steps = int(np.asarray(state["expl_decay_steps"]))
        cfg.per_rank_batch_size = int(np.asarray(state["batch_size"])) // world_size
    agent_state = jax.device_put(agent_state, fabric.replicated)

    train_fn = build_train_fn(
        world_model, actor, critic, ensemble_member, txs, cfg, fabric, actions_dim, is_continuous
    )
    player_fns = build_player_fns(world_model, actor, cfg, actions_dim, is_continuous)

    # host-mirrored acting snapshots (utils/host.py)
    wm_mirror = HostParamMirror.from_cfg(agent_state["params"]["world_model"], fabric, cfg)
    actor_expl_mirror = HostParamMirror.from_cfg(
        agent_state["params"]["actor_exploration"], fabric, cfg
    )
    actor_task_mirror = HostParamMirror.from_cfg(
        agent_state["params"]["actor_task"], fabric, cfg
    )
    play_wm = wm_mirror(agent_state["params"]["world_model"])
    play_actor_expl = actor_expl_mirror(agent_state["params"]["actor_exploration"])
    play_actor_task = actor_task_mirror(agent_state["params"]["actor_task"])

    def player_actor_params():
        if cfg.algo.player.actor_type == "exploration":
            return play_actor_expl
        return play_actor_task

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
        min_size=8,
        dry_run_size=8,
    )
    if state is not None and cfg.buffer.get("checkpoint", False) and "rb" in state:
        rb.load_state_dict(state["rb"])

    train_step = 0
    last_train = 0
    start_step = int(np.asarray(state["update"])) // world_size if state is not None else 1
    policy_step = int(np.asarray(state["update"])) * cfg.env.num_envs if state is not None else 0
    last_log = int(np.asarray(state["last_log"])) if state is not None else 0
    last_checkpoint = int(np.asarray(state["last_checkpoint"])) if state is not None else 0
    policy_steps_per_update = int(n_envs)
    updates_before_training = (
        cfg.algo.train_every // policy_steps_per_update if not cfg.dry_run else 0
    )
    num_updates = int(cfg.total_steps // policy_steps_per_update) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_update if not cfg.dry_run else 0
    if cfg.checkpoint.resume_from and not cfg.buffer.checkpoint:
        learning_starts += start_step
    max_step_expl_decay = cfg.algo.actor.max_step_expl_decay // (
        cfg.algo.per_rank_gradient_steps * world_size
    ) if cfg.algo.actor.max_step_expl_decay else 0
    expl_amount = float(cfg.algo.actor.expl_amount)
    if cfg.checkpoint.resume_from:
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
    step_data["actions"] = np.zeros((1, n_envs, int(np.sum(actions_dim))), np.float32)
    step_data["rewards"] = np.zeros((1, n_envs, 1), np.float32)
    rb.add(step_data)
    player_state = player_fns["init_states"](play_wm, n_envs)

    per_rank_gradient_steps = 0

    # Burst acting (tier b, howto/rollout_engine.md): K env steps per device
    # dispatch, K = env.act_burst; 1 reproduces the per-step path exactly.
    # The RSSM player state rides the burst carry next to the observation —
    # the host callback is the whole old loop body (env step, episode
    # bookkeeping, buffer adds) and applies episode resets with the same
    # (1 - mask) * state arithmetic the jitted reset path computes, so
    # trajectories do not depend on K. The acting actor (exploration vs
    # task, cfg.algo.player.actor_type) is a rollout() parameter.
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)
    n_sub = len(actions_dim)
    state_box = {
        "carry": {
            "obs": obs,
            "player": {k: np.asarray(v) for k, v in player_state.items()},
        },
        "policy_step": policy_step,
    }

    def _host_step_core(actions, real_actions, player_np):
        state_box["policy_step"] += n_envs
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            o, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
        dones = np.logical_or(terminated, truncated).astype(np.float32)

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

        obs_row = prepare_obs(real_next_obs, cnn_keys, mlp_keys, n_envs)
        for k in obs_keys:
            step_data[k] = obs_row[k][None]
        rewards = np.asarray(rewards, np.float32).reshape(n_envs, 1)
        step_data["dones"] = dones.reshape(1, n_envs, 1)
        step_data["actions"] = actions.reshape(1, n_envs, -1).astype(np.float32)
        step_data["rewards"] = clip_rewards_fn(rewards)[None]
        rb.add(step_data)

        new_obs = prepare_obs(next_obs_np, cnn_keys, mlp_keys, n_envs)

        if len(dones_idxes) > 0:
            reset_obs = prepare_obs(
                {k: next_obs_np[k][dones_idxes] for k in next_obs_np},
                cnn_keys, mlp_keys, len(dones_idxes),
            )
            reset_data = {k: reset_obs[k][None] for k in obs_keys}
            reset_data["dones"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            reset_data["actions"] = np.zeros((1, len(dones_idxes), int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            rb.add(reset_data, dones_idxes)

            step_data["dones"][:, dones_idxes] = 0.0
            reset_mask = np.zeros((n_envs, 1), np.float32)
            reset_mask[dones_idxes] = 1.0
            # same arithmetic as player_fns["reset_states"], applied host-side
            keep = np.float32(1.0) - reset_mask
            player_np = {k: keep * v for k, v in player_np.items()}

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
    # scores them (the task actor — the eval builder picks actor_task), so
    # nothing below touches the train-step critical path
    from sheeprl_tpu.evals.inrun import maybe_start_inrun_eval

    inrun = maybe_start_inrun_eval(fabric, cfg, log_dir)

    update = start_step
    while update <= num_updates:
        n_act, random_phase = train_gated_burst_plan(
            update,
            act_burst,
            learning_starts,
            num_updates,
            updates_before_training,
            resuming=cfg.checkpoint.resume_from is not None,
        )
        if random_phase:
            real_actions = actions = np.array(envs.action_space.sample())
            if not is_continuous:
                actions = np.concatenate(
                    [
                        np.eye(act_dim, dtype=np.float32)[act]
                        for act, act_dim in zip(
                            actions.reshape(len(actions_dim), -1), actions_dim
                        )
                    ],
                    axis=-1,
                )
            _host_step_core(actions, real_actions, state_box["carry"]["player"])
        else:
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
            n_samples = cfg.algo.per_rank_gradient_steps
            metrics = None
            if n_samples > 0:
                local_data = staging.sample_device(
                    cfg.per_rank_batch_size * world_size,
                    sequence_length=cfg.per_rank_sequence_length,
                    n_samples=n_samples,
                )
                # metrics are pulled at most once per burst behind the
                # shared fetch gate (sheeprl_tpu/train)
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
                        (jax.random.split(train_key, n_samples),),
                        world_size=world_size,
                        fetch_metrics=fetch_metrics,
                    )
                    per_rank_gradient_steps += n_samples
                    play_wm = wm_mirror(agent_state["params"]["world_model"])
                    play_actor_expl = actor_expl_mirror(agent_state["params"]["actor_exploration"])
                    play_actor_task = actor_task_mirror(agent_state["params"]["actor_task"])
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
                "expl_decay_steps": expl_decay_steps,
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

    if inrun is not None:
        inrun.close()
    staging.close()
    envs.close()
    if fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        final = jax.device_get(agent_state["params"])
        test(
            player_fns,
            {"world_model": final["world_model"], "actor": final["actor_task"]},
            fabric, cfg, log_dir, sample_actions=False,
            normalize_fn=normalize_obs_jnp,
        )
