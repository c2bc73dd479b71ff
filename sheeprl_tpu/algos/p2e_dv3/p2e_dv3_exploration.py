"""Plan2Explore-DV3, exploration phase.

Behavioral contract from the reference
``sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py`` (train :45-560, main
:563-1125): DV3 world-model learning, plus

- **ensemble learning** (:246-271): every member regresses the *next*
  stochastic state from ``(posterior, recurrent, action)`` with an MSE
  objective;
- **exploration behaviour** (:276-421): imagination with the exploration
  actor; per-critic rewards — ``intrinsic`` = ensemble-disagreement
  (variance over members of the predicted next state, :318-333) ×
  ``intrinsic_reward_multiplier``, ``task`` = the world-model reward head —
  each with its own two-hot critic, EMA target, and Moments normalizer;
  the actor objective sums the per-critic normalized advantages weighted by
  ``weight / Σweights`` (:306-350);
- **task behaviour** (:426-540): the plain DV3 actor-critic update so the
  task policy is ready for finetuning.

TPU-native design: ONE fused ``shard_map``-ped jit per gradient step covering
all six updates (world model, ensembles, exploration actor, N exploration
critics, task actor, task critic); the ensemble runs as a single vmapped
apply (see ``agent.py``); batch dim sharded over the mesh with ``pmean``
grads; Moments all-gather per critic.
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

from sheeprl_tpu.algos.dreamer_v3.agent import (
    Actor,
    WorldModel,
    actor_entropy,
    build_actor_dists,
    resolve_actor_distribution,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v3.loss import continue_distribution, reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    compute_lambda_values,
    init_moments,
    normalize_obs_jnp,
    prepare_obs,
    test,
    update_moments,
)
from sheeprl_tpu.algos.p2e_dv3.agent import (
    acting_params,
    apply_ensemble,
    build_agent,
    build_player_fns,
)
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.data.staging import make_replay_staging
from sheeprl_tpu.distributions import MSEDistribution, SymlogDistribution, TwoHotEncodingDistribution
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.models.hoist import scan_hoisting_dense_grads
from sheeprl_tpu.plane import train_gated_burst_plan
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import learn_probes, log_sps_metrics, probes_enabled, profile_tick, span
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of
from sheeprl_tpu.train import build_train_burst, metric_fetch_gate, run_train_burst, tau_schedule
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
    """One fused SPMD gradient step for the exploration phase.

    ``train_step(agent_state, data, key, tau) -> (agent_state, metrics)``.
    """
    axis = fabric.data_axis
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    mlp_keys = tuple(cfg.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.mlp_keys.decoder)
    learn_on = probes_enabled(cfg)
    learn_clips = {name: clip_norm_of(tx) for name, tx in txs.items()}
    wm_cfg = cfg.algo.world_model
    stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    rec_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    intrinsic_mult = float(cfg.algo.intrinsic_reward_multiplier)
    distribution = resolve_actor_distribution(
        cfg.distribution.get("type", "auto"), is_continuous
    )
    init_std = float(cfg.algo.actor.init_std)
    min_std = float(cfg.algo.actor.min_std)
    unimix = float(cfg.algo.unimix)
    moments_cfg = cfg.algo.actor.moments
    m_args = (
        float(moments_cfg.decay),
        float(moments_cfg.max),
        float(moments_cfg.percentile.low),
        float(moments_cfg.percentile.high),
    )
    dims = tuple(int(d) for d in actions_dim)
    splits = list(np.cumsum(dims)[:-1])
    critics_cfg = {
        k: {"weight": float(v["weight"]), "reward_type": str(v["reward_type"])}
        for k, v in cfg.algo.critics_exploration.items()
    }
    weights_sum = sum(c["weight"] for c in critics_cfg.values())

    def wm_apply(params, method, *args):
        return world_model.apply({"params": params}, *args, method=method)

    # -- world model loss: identical to DV3 (reference train :121-245) -----

    S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)

    def wm_loss_fn(wm_params, data, key):
        T, B = data["rewards"].shape[:2]
        batch_obs = {k: data[k] / 255.0 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})
        is_first = data["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        )
        embedded = wm_apply(wm_params, WorldModel.encode, batch_obs)
        # hoist the non-sequential work out of the time scan (same
        # optimization as dreamer_v3.py wm_loss_fn): embed projection and
        # prior logits are batched over [T, B]; the is_first reset posterior
        # is the constant prior mode at a zeroed recurrent state
        embed_proj = wm_apply(wm_params, WorldModel.project_embed, embedded)
        init_post = wm_apply(
            wm_params, WorldModel.initial_posterior, jnp.zeros((1, rec_size))
        )

        def step(params, init_post, carry, inp):
            posterior, recurrent = carry
            action, eproj, first, g = inp
            recurrent, posterior, post_logits = world_model.apply(
                {"params": params},
                posterior, recurrent, action, eproj, first, init_post, None, g,
                method=WorldModel.dynamic_posterior,
            )
            return (posterior, recurrent), (recurrent, posterior, post_logits)

        # posterior sampling noise for the whole sequence drawn in one call;
        # the scan's Dense kernels get their gradients after the backward
        # loop (models/hoist.py), as in dreamer_v3.py
        gumbels = jax.random.gumbel(key, (T, B, S, D))
        (_, _), (recurrents, posteriors, post_logits) = scan_hoisting_dense_grads(
            step,
            {"rssm": wm_params["rssm"]},
            init_post,
            (jnp.zeros((B, stoch_flat)), jnp.zeros((B, rec_size))),
            (batch_actions, embed_proj, is_first, gumbels),
        )
        prior_logits = wm_apply(wm_params, WorldModel.prior_logits, recurrents)
        latents = jnp.concatenate([posteriors, recurrents], -1)
        recon = wm_apply(wm_params, WorldModel.decode, latents)
        po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_dec_keys}
        po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_dec_keys})
        pr = TwoHotEncodingDistribution(
            wm_apply(wm_params, WorldModel.reward_logits, latents), dims=1
        )
        pc = continue_distribution(wm_apply(wm_params, WorldModel.continue_logits, latents))
        loss, metrics = reconstruction_loss(
            po, batch_obs, pr, data["rewards"],
            prior_logits.reshape(T, B, S, D), post_logits.reshape(T, B, S, D),
            float(wm_cfg.kl_dynamic), float(wm_cfg.kl_representation),
            float(wm_cfg.kl_free_nats), float(wm_cfg.kl_regularizer),
            pc, 1.0 - data["dones"], float(wm_cfg.continue_scale_factor),
        )
        return loss, (metrics, sg(posteriors), sg(recurrents))

    # -- ensemble loss (reference train :246-271) --------------------------

    def ensemble_loss_fn(ens_params, posteriors, recurrents, actions):
        inp = jnp.concatenate([posteriors, recurrents, actions], -1)
        out = apply_ensemble(ensemble_member, ens_params, inp)[:, :-1]
        target = posteriors[1:][None]
        dist = MSEDistribution(out, dims=1)
        return -jnp.sum(jnp.mean(dist.log_prob(target), axis=tuple(range(1, out.ndim - 1))))

    # -- imagination with a given actor (reference :276-303 / :426-455) ----

    def imagination_rollout(wm_params, actor_params, posteriors, recurrents, key):
        prior = posteriors.reshape(-1, stoch_flat)
        recurrent = recurrents.reshape(-1, rec_size)
        latent0 = jnp.concatenate([prior, recurrent], -1)

        def policy(latent, k):
            pre = actor.apply({"params": actor_params}, sg(latent))
            dists = build_actor_dists(pre, is_continuous, distribution, init_std, min_std, unimix)
            return jnp.concatenate(sample_actor_actions(dists, is_continuous, k, True), -1)

        k0, key = jax.random.split(key)
        a0 = policy(latent0, k0)

        def step(carry, inp):
            prior, recurrent, action = carry
            g_img, k_act = inp
            prior, recurrent = world_model.apply(
                {"params": wm_params}, prior, recurrent, action, None, g_img,
                method=WorldModel.imagination,
            )
            latent = jnp.concatenate([prior, recurrent], -1)
            action = policy(latent, k_act)
            return (prior, recurrent, action), (latent, action)

        # prior-sampling noise for the whole horizon drawn in one call
        k_gum, key = jax.random.split(key)
        gumbels = jax.random.gumbel(k_gum, (horizon, prior.shape[0], S, D))
        keys = jax.random.split(key, horizon)
        _, (latents, acts) = jax.lax.scan(step, (prior, recurrent, a0), (gumbels, keys))
        return (
            jnp.concatenate([latent0[None], latents], 0),
            jnp.concatenate([a0[None], acts], 0),
        )

    def _discrete_objective(policies, imagined_actions, advantage):
        per_head = [
            p.log_prob(sg(a))[..., None][:-1]
            for p, a in zip(policies, jnp.split(imagined_actions, splits, axis=-1))
        ]
        return sum(per_head) * sg(advantage)

    # -- exploration actor loss (reference :276-395) ------------------------

    def actor_expl_loss_fn(actor_params, wm_params, ens_params, critics_params,
                           posteriors, recurrents, true_continue, moments_expl, key):
        traj, imagined_actions = imagination_rollout(
            wm_params, actor_params, posteriors, recurrents, key
        )
        continues = continue_distribution(
            wm_apply(wm_params, WorldModel.continue_logits, traj)
        ).base.mode
        continues = jnp.concatenate([true_continue[None], continues[1:]], 0)
        discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)

        # intrinsic reward: variance over members of the predicted next state
        ens_in = jnp.concatenate([sg(traj), sg(imagined_actions)], -1)
        next_state_pred = apply_ensemble(ensemble_member, ens_params, ens_in)
        intrinsic_reward = (
            jnp.var(next_state_pred, axis=0).mean(-1, keepdims=True) * intrinsic_mult
        )

        advantage = 0.0
        new_moments = {}
        aux_critic = {}
        metrics = {}
        for k, ccfg in critics_cfg.items():
            values = TwoHotEncodingDistribution(
                critic.apply({"params": critics_params[k]["module"]}, traj), dims=1
            ).mean
            if ccfg["reward_type"] == "intrinsic":
                reward = intrinsic_reward
                metrics[f"Rewards/intrinsic_{k}"] = jnp.mean(sg(reward))
            else:
                reward = TwoHotEncodingDistribution(
                    wm_apply(wm_params, WorldModel.reward_logits, traj), dims=1
                ).mean
            lambda_values = compute_lambda_values(
                reward[1:], values[1:], continues[1:] * gamma, lmbda
            )
            nm, offset, invscale = update_moments(
                moments_expl[k], lambda_values, *m_args, axis_name=axis
            )
            new_moments[k] = nm
            advantage = advantage + (
                (lambda_values - offset) / invscale - (values[:-1] - offset) / invscale
            ) * (ccfg["weight"] / weights_sum)
            aux_critic[k] = {"lambda_values": sg(lambda_values)}
            metrics[f"Values_exploration/predicted_values_{k}"] = jnp.mean(sg(values))
            metrics[f"Values_exploration/lambda_values_{k}"] = jnp.mean(sg(lambda_values))

        pre = actor.apply({"params": actor_params}, sg(traj))
        policies = build_actor_dists(pre, is_continuous, distribution, init_std, min_std, unimix)
        if is_continuous:
            objective = advantage
        else:
            objective = _discrete_objective(policies, imagined_actions, advantage)
        entropy = ent_coef * actor_entropy(policies, distribution)
        policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
        aux = {
            "trajectories": sg(traj),
            "discount": discount,
            "critics": aux_critic,
            "moments": new_moments,
            "metrics": metrics,
            "Loss/policy_loss_exploration": policy_loss,
        }
        return policy_loss, aux

    # -- task actor loss: plain DV3 (reference :426-521) ---------------------

    def actor_task_loss_fn(actor_params, wm_params, critic_params, posteriors, recurrents,
                           true_continue, moments_task, key):
        traj, imagined_actions = imagination_rollout(
            wm_params, actor_params, posteriors, recurrents, key
        )
        values = TwoHotEncodingDistribution(
            critic.apply({"params": critic_params}, traj), dims=1
        ).mean
        rewards = TwoHotEncodingDistribution(
            wm_apply(wm_params, WorldModel.reward_logits, traj), dims=1
        ).mean
        continues = continue_distribution(
            wm_apply(wm_params, WorldModel.continue_logits, traj)
        ).base.mode
        continues = jnp.concatenate([true_continue[None], continues[1:]], 0)

        lambda_values = compute_lambda_values(
            rewards[1:], values[1:], continues[1:] * gamma, lmbda
        )
        discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)
        new_moments, offset, invscale = update_moments(
            moments_task, lambda_values, *m_args, axis_name=axis
        )
        advantage = (lambda_values - offset) / invscale - (values[:-1] - offset) / invscale

        pre = actor.apply({"params": actor_params}, sg(traj))
        policies = build_actor_dists(pre, is_continuous, distribution, init_std, min_std, unimix)
        if is_continuous:
            objective = advantage
        else:
            objective = _discrete_objective(policies, imagined_actions, advantage)
        entropy = ent_coef * actor_entropy(policies, distribution)
        policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
        aux = {
            "trajectories": sg(traj),
            "lambda_values": sg(lambda_values),
            "discount": discount,
            "moments": new_moments,
            "Loss/policy_loss_task": policy_loss,
        }
        return policy_loss, aux

    # -- two-hot critic loss with EMA-target regularizer (reference :396-560)

    def critic_loss_fn(critic_params, target_params, traj, lambda_values, discount):
        qv = TwoHotEncodingDistribution(
            critic.apply({"params": critic_params}, traj[:-1]), dims=1
        )
        target_values = TwoHotEncodingDistribution(
            critic.apply({"params": target_params}, traj[:-1]), dims=1
        ).mean
        value_loss = -qv.log_prob(lambda_values) - qv.log_prob(sg(target_values))
        return jnp.mean(value_loss * discount[:-1, ..., 0])

    # ----------------------------------------------------------------------

    def local_step(agent_state, data, key, tau):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        params = agent_state["params"]
        opt = agent_state["opt"]
        ema = lambda c, t: jax.tree_util.tree_map(  # noqa: E731
            lambda a, b: tau * a + (1.0 - tau) * b, c, t
        )

        target_task = ema(params["critic_task"], params["target_critic_task"])
        targets_expl = {
            k: ema(params["critics_exploration"][k]["module"], params["critics_exploration"][k]["target"])
            for k in critics_cfg
        }

        k_wm, k_expl, k_task = jax.random.split(key, 3)

        # 1. world model
        (wm_loss, (wm_metrics, posteriors, recurrents)), wm_grads = jax.value_and_grad(
            wm_loss_fn, has_aux=True
        )(params["world_model"], data, k_wm)
        wm_grads = pmean(wm_grads, axis)
        wm_updates, wm_opt = txs["world_model"].update(
            wm_grads, opt["world_model"], params["world_model"]
        )
        wm_params = optax.apply_updates(params["world_model"], wm_updates)

        # 2. ensembles (actions unshifted: action[t] leads out of state t)
        ens_loss, ens_grads = jax.value_and_grad(ensemble_loss_fn)(
            params["ensembles"], posteriors, recurrents, data["actions"]
        )
        ens_grads = pmean(ens_grads, axis)
        ens_updates, ens_opt = txs["ensembles"].update(
            ens_grads, opt["ensembles"], params["ensembles"]
        )
        ens_params = optax.apply_updates(params["ensembles"], ens_updates)

        true_continue = (1.0 - data["dones"]).reshape(-1, 1)

        # 3. exploration actor
        (pl_expl, aux_expl), a_expl_grads = jax.value_and_grad(
            actor_expl_loss_fn, has_aux=True
        )(
            params["actor_exploration"], wm_params, ens_params,
            params["critics_exploration"], posteriors, recurrents,
            true_continue, agent_state["moments"]["exploration"], k_expl,
        )
        a_expl_grads = pmean(a_expl_grads, axis)
        a_expl_updates, a_expl_opt = txs["actor_exploration"].update(
            a_expl_grads, opt["actor_exploration"], params["actor_exploration"]
        )
        actor_expl_params = optax.apply_updates(params["actor_exploration"], a_expl_updates)

        # 4. exploration critics
        new_critics_expl = {}
        critics_expl_opt = {}
        critic_metrics = {}
        critics_expl_grads = {}
        critics_expl_updates = {}
        critics_expl_losses = []
        for k in critics_cfg:
            c_loss, c_grads = jax.value_and_grad(critic_loss_fn)(
                params["critics_exploration"][k]["module"],
                targets_expl[k],
                aux_expl["trajectories"],
                aux_expl["critics"][k]["lambda_values"],
                aux_expl["discount"],
            )
            c_grads = pmean(c_grads, axis)
            c_updates, c_opt = txs["critics_exploration"].update(
                c_grads, opt["critics_exploration"][k],
                params["critics_exploration"][k]["module"],
            )
            new_critics_expl[k] = {
                "module": optax.apply_updates(params["critics_exploration"][k]["module"], c_updates),
                "target": targets_expl[k],
            }
            critics_expl_opt[k] = c_opt
            critic_metrics[f"Loss/value_loss_exploration_{k}"] = c_loss
            critics_expl_grads[k] = c_grads
            critics_expl_updates[k] = c_updates
            critics_expl_losses.append(c_loss)

        # 5. task actor
        (pl_task, aux_task), a_task_grads = jax.value_and_grad(
            actor_task_loss_fn, has_aux=True
        )(
            params["actor_task"], wm_params, params["critic_task"],
            posteriors, recurrents, true_continue,
            agent_state["moments"]["task"], k_task,
        )
        a_task_grads = pmean(a_task_grads, axis)
        a_task_updates, a_task_opt = txs["actor_task"].update(
            a_task_grads, opt["actor_task"], params["actor_task"]
        )
        actor_task_params = optax.apply_updates(params["actor_task"], a_task_updates)

        # 6. task critic
        ct_loss, ct_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic_task"], target_task,
            aux_task["trajectories"], aux_task["lambda_values"], aux_task["discount"],
        )
        ct_grads = pmean(ct_grads, axis)
        ct_updates, ct_opt = txs["critic_task"].update(
            ct_grads, opt["critic_task"], params["critic_task"]
        )
        critic_task_params = optax.apply_updates(params["critic_task"], ct_updates)

        metrics = dict(wm_metrics)
        metrics.update(aux_expl["metrics"])
        metrics.update(critic_metrics)
        metrics["Loss/ensemble_loss"] = ens_loss
        metrics["Loss/policy_loss_exploration"] = pl_expl
        metrics["Loss/policy_loss_task"] = pl_task
        metrics["Loss/value_loss_task"] = ct_loss
        metrics["Grads/world_model"] = optax.global_norm(wm_grads)
        metrics["Grads/ensemble"] = optax.global_norm(ens_grads)
        metrics["Grads/actor_exploration"] = optax.global_norm(a_expl_grads)
        metrics["Grads/actor_task"] = optax.global_norm(a_task_grads)
        metrics["Grads/critic_task"] = optax.global_norm(ct_grads)
        metrics = pmean(metrics, axis)
        if learn_on:
            # grads are already pmean'd, so the probe scalars are identical
            # on every shard — the learn plane adds no collectives; the per-k
            # exploration critics fold into ONE module (dict of per-k grads)
            metrics.update(
                learn_probes(
                    {
                        "world_model": wm_grads,
                        "ensembles": ens_grads,
                        "actor_exploration": a_expl_grads,
                        "critics_exploration": critics_expl_grads,
                        "actor_task": a_task_grads,
                        "critic_task": ct_grads,
                    },
                    params={
                        "world_model": params["world_model"],
                        "ensembles": params["ensembles"],
                        "actor_exploration": params["actor_exploration"],
                        "critics_exploration": {
                            k: params["critics_exploration"][k]["module"] for k in critics_cfg
                        },
                        "actor_task": params["actor_task"],
                        "critic_task": params["critic_task"],
                    },
                    updates={
                        "world_model": wm_updates,
                        "ensembles": ens_updates,
                        "actor_exploration": a_expl_updates,
                        "critics_exploration": critics_expl_updates,
                        "actor_task": a_task_updates,
                        "critic_task": ct_updates,
                    },
                    losses=(wm_loss, ens_loss, pl_expl, pl_task, ct_loss, *critics_expl_losses),
                    clip_norms=learn_clips,
                )
            )

        new_state = {
            "params": {
                "world_model": wm_params,
                "actor_task": actor_task_params,
                "critic_task": critic_task_params,
                "target_critic_task": target_task,
                "actor_exploration": actor_expl_params,
                "critics_exploration": new_critics_expl,
                "ensembles": ens_params,
            },
            "opt": {
                "world_model": wm_opt,
                "ensembles": ens_opt,
                "actor_task": a_task_opt,
                "critic_task": ct_opt,
                "actor_exploration": a_expl_opt,
                "critics_exploration": critics_expl_opt,
            },
            "moments": {"task": aux_task["moments"], "exploration": aux_expl["moments"]},
        }
        return new_state, metrics

    # step + fused-burst programs (scanned per-step inputs: key, tau); the
    # ensemble params/optimizer state ride the burst carry with the rest
    return build_train_burst(local_step, fabric, n_scanned=2)


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    # The exploration phase always acts with the exploration actor
    # (reference main :570)
    cfg.algo.player.actor_type = "exploration"
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

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
        "critics_exploration": instantiate(
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
            "critics_exploration": {
                k: txs["critics_exploration"].init(params["critics_exploration"][k]["module"])
                for k in params["critics_exploration"]
            },
        },
        "moments": {
            "task": init_moments(),
            "exploration": {k: init_moments() for k in params["critics_exploration"]},
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

    # host-mirrored acting snapshots (utils/host.py) of the leaves acting reads
    wm_mirror = HostParamMirror.from_cfg(
        acting_params(agent_state["params"]["world_model"]), fabric, cfg
    )
    actor_expl_mirror = HostParamMirror.from_cfg(
        agent_state["params"]["actor_exploration"], fabric, cfg
    )
    actor_task_mirror = HostParamMirror.from_cfg(
        agent_state["params"]["actor_task"], fabric, cfg
    )
    play_wm = wm_mirror(acting_params(agent_state["params"]["world_model"]))
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
        min_size=4,
        dry_run_size=4,
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
    step_data["rewards"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, n_envs, 1), np.float32)
    player_state = player_fns["init_states"](play_wm, n_envs)

    per_rank_gradient_steps = 0

    # Burst acting (tier b, howto/rollout_engine.md): K env steps per device
    # dispatch, K = env.act_burst; 1 reproduces the per-step path exactly.
    # The RSSM player state rides the burst carry next to the observation;
    # the host callback is the whole old loop body and applies episode
    # resets with the same mask * fresh + (1 - mask) * state arithmetic as
    # player_fns["reset_states"], against a host copy of the fresh init
    # state refreshed once per params version (DV3's fresh state has a
    # nonzero, params-dependent initial posterior).
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
        # the per-step loop used, so the K=1 key stream is bitwise the
        # per-step stream
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
                # EMA target updates on the host-computed cadence (first
                # gradient step hard-copies); metrics are pulled at most
                # once per burst behind the shared gate
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
                    play_actor_expl = actor_expl_mirror(agent_state["params"]["actor_exploration"])
                    play_actor_task = actor_task_mirror(agent_state["params"]["actor_task"])
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
    # Final greedy test runs the *task* policy (reference main :1124)
    if fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        final = jax.device_get(agent_state["params"])
        test(
            player_fns,
            {"world_model": final["world_model"], "actor": final["actor_task"]},
            fabric, cfg, log_dir, sample_actions=True,
        )
