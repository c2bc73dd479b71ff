"""DreamerV2 — discrete-latent world model with KL balancing.

Behavioral contract from the reference ``sheeprl/algos/dreamer_v2/dreamer_v2.py``
(train :43-426, main :429-870): sequence-replay world-model learning with
KL-balanced categorical state loss, 15-step imagination with the action
computed inside the rollout, reinforce/dynamics-mixed actor objective
(``objective_mix``), Gaussian critic regressed on bootstrapped TD(λ) returns,
and a hard-copied target critic every ``target_network_update_freq`` steps.

TPU-native design: identical chassis to ``dreamer_v3.py`` — one
``shard_map``-ped jit per gradient step, ``lax.scan`` over T and H,
``lax.pmean`` gradients, dynamic tau (here 0/1: hard copy) — with the V2
losses. Data layout note (reference main :572-745): row *t* of the buffer
holds the action that *led to* observation *t*, so the dynamic-learning scan
consumes ``data["actions"]`` unshifted (unlike V3).
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

from sheeprl_tpu.algos.dreamer_v2.agent import (
    Actor,
    WorldModel,
    actor_entropy,
    build_actor_dists,
    build_agent,
    build_player_fns,
    resolve_actor_distribution,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v2.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v2.utils import (
    compute_lambda_values,
    normalize_obs_jnp,
    prepare_obs,
    test,
)
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.data.staging import make_replay_staging
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.distributions import Bernoulli, Independent, Normal
from sheeprl_tpu.envs.rollout import BurstActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.plane import train_gated_burst_plan
from sheeprl_tpu.train import build_train_burst, metric_fetch_gate, run_train_burst, tau_schedule
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import learn_probes, log_sps_metrics, probes_enabled, profile_tick, span
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

sg = jax.lax.stop_gradient


def build_train_fn(
    world_model: WorldModel,
    actor: Actor,
    critic,
    world_tx: optax.GradientTransformation,
    actor_tx: optax.GradientTransformation,
    critic_tx: optax.GradientTransformation,
    cfg,
    fabric,
    actions_dim: Sequence[int],
    is_continuous: bool,
):
    """Compile one full DreamerV2 gradient step as a single SPMD program.

    Returns a :class:`~sheeprl_tpu.train.TrainProgram`: callable as
    ``train_step(agent_state, data, key, tau) -> (agent_state, metrics)``
    (``tau`` is 1.0 on hard-copy steps, 0.0 otherwise), with ``.burst``
    scanning the step over a staged ``[n_samples, ...]`` block as ONE
    dispatch.
    """
    axis = fabric.data_axis
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    mlp_keys = tuple(cfg.mlp_keys.encoder)
    learn_on = probes_enabled(cfg)
    learn_clips = {
        "world_model": clip_norm_of(world_tx),
        "actor": clip_norm_of(actor_tx),
        "critic": clip_norm_of(critic_tx),
    }
    wm_cfg = cfg.algo.world_model
    stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    rec_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    kl_balancing_alpha = float(wm_cfg.kl_balancing_alpha)
    kl_free_nats = float(wm_cfg.kl_free_nats)
    kl_free_avg = bool(wm_cfg.kl_free_avg)
    kl_regularizer = float(wm_cfg.kl_regularizer)
    discount_scale = float(wm_cfg.discount_scale_factor)
    use_continues = bool(wm_cfg.use_continues)
    ent_coef = float(cfg.algo.actor.ent_coef)
    objective_mix = float(cfg.algo.actor.objective_mix)
    distribution = resolve_actor_distribution(
        cfg.distribution.get("type", "auto"), is_continuous
    )
    init_std = float(cfg.algo.actor.init_std)
    min_std = float(cfg.algo.actor.min_std)
    dims = tuple(int(d) for d in actions_dim)
    splits = list(np.cumsum(dims)[:-1])

    S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)

    def wm_apply(params, method, *args):
        return world_model.apply({"params": params}, *args, method=method)

    # ------------------------------------------------------------------
    # world-model loss (reference train :104-240)
    # ------------------------------------------------------------------

    def wm_loss_fn(wm_params, data, key):
        T, B = data["rewards"].shape[:2]
        batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})
        is_first = data["is_first"].at[0].set(1.0)
        embedded = wm_apply(wm_params, WorldModel.encode, batch_obs)

        def step(carry, inp):
            posterior, recurrent = carry
            action, embed, first, g = inp
            recurrent, posterior, post_logits = world_model.apply(
                {"params": wm_params},
                posterior,
                recurrent,
                action,
                embed,
                first,
                None,
                g,
                method=WorldModel.dynamic_posterior,
            )
            return (posterior, recurrent), (recurrent, posterior, post_logits)

        # posterior sampling noise for the whole sequence in one draw; the
        # prior (transition) logits never feed back into the loop and are
        # batched over [T, B] after the scan (same optimization as DV3)
        gumbels = jax.random.gumbel(key, (T, B, S, D))
        (_, _), (recurrents, posteriors, post_logits) = jax.lax.scan(
            step,
            (jnp.zeros((B, stoch_flat)), jnp.zeros((B, rec_size))),
            (data["actions"], embedded, is_first, gumbels),
        )
        prior_logits = wm_apply(wm_params, WorldModel.prior_logits, recurrents)
        latents = jnp.concatenate([posteriors, recurrents], -1)
        recon = wm_apply(wm_params, WorldModel.decode, latents)
        po = {
            k: Independent(Normal(recon[k], jnp.ones_like(recon[k])), 3 if k in cnn_keys else 1)
            for k in recon
        }
        pr = Independent(Normal(wm_apply(wm_params, WorldModel.reward, latents), 1.0), 1)
        if use_continues:
            pc = Independent(Bernoulli(logits=wm_apply(wm_params, WorldModel.continues, latents)), 1)
            continue_targets = (1.0 - data["dones"]) * gamma
        else:
            pc = continue_targets = None
        loss, metrics = reconstruction_loss(
            po,
            batch_obs,
            pr,
            data["rewards"],
            prior_logits.reshape(T, B, S, D),
            post_logits.reshape(T, B, S, D),
            kl_balancing_alpha,
            kl_free_nats,
            kl_free_avg,
            kl_regularizer,
            pc,
            continue_targets,
            discount_scale,
        )
        return loss, (metrics, sg(posteriors), sg(recurrents))

    # ------------------------------------------------------------------
    # actor loss via imagination (reference train :253-398)
    # ------------------------------------------------------------------

    def imagination_rollout(wm_params, actor_params, posteriors, recurrents, key):
        """H-step prior rollout with the action computed inside the loop
        (reference :299-320). Returns ``(trajectories [H+1, BT, L],
        actions [H+1, BT, A])`` with ``actions[0] = 0``."""
        prior = posteriors.reshape(-1, stoch_flat)
        recurrent = recurrents.reshape(-1, rec_size)
        latent0 = jnp.concatenate([prior, recurrent], -1)

        def policy(latent, k):
            pre = actor.apply({"params": actor_params}, sg(latent))
            dists = build_actor_dists(
                pre, is_continuous, distribution, init_std, min_std, unimix=0.0
            )
            return jnp.concatenate(
                sample_actor_actions(dists, is_continuous, k, True), -1
            )

        def step(carry, inp):
            prior, recurrent, latent = carry
            g_img, k_act = inp
            action = policy(latent, k_act)
            prior, recurrent = world_model.apply(
                {"params": wm_params},
                prior,
                recurrent,
                action,
                None,
                g_img,
                method=WorldModel.imagination,
            )
            latent = jnp.concatenate([prior, recurrent], -1)
            return (prior, recurrent, latent), (latent, action)

        # prior-sampling noise for the whole horizon in one draw
        k_gum, key = jax.random.split(key)
        gumbels = jax.random.gumbel(k_gum, (horizon, prior.shape[0], S, D))
        keys = jax.random.split(key, horizon)
        _, (latents, acts) = jax.lax.scan(step, (prior, recurrent, latent0), (gumbels, keys))
        trajectories = jnp.concatenate([latent0[None], latents], 0)
        actions = jnp.concatenate([jnp.zeros_like(acts[:1]), acts], 0)
        return trajectories, actions

    def actor_loss_fn(actor_params, wm_params, target_params, posteriors, recurrents,
                      true_continue, key):
        traj, imagined_actions = imagination_rollout(
            wm_params, actor_params, posteriors, recurrents, key
        )
        # values from the *target* critic (reference :322-327)
        predicted_values = critic.apply({"params": target_params}, traj)
        predicted_rewards = wm_apply(wm_params, WorldModel.reward, traj)
        if use_continues:
            continues = jax.nn.sigmoid(wm_apply(wm_params, WorldModel.continues, traj))
            continues = jnp.concatenate([true_continue[None] * gamma, continues[1:]], 0)
        else:
            continues = jnp.ones_like(sg(predicted_rewards)) * gamma

        lambda_values = compute_lambda_values(
            predicted_rewards[:-1],
            predicted_values[:-1],
            continues[:-1],
            bootstrap=predicted_values[-1:],
            lmbda=lmbda,
        )
        discount = sg(
            jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], 0), 0)
        )

        pre = actor.apply({"params": actor_params}, sg(traj[:-2]))
        policies = build_actor_dists(
            pre, is_continuous, distribution, init_std, min_std, unimix=0.0
        )

        # dynamics backprop vs reinforce, mixed (reference :366-383)
        dynamics = lambda_values[1:]
        advantage = sg(lambda_values[1:] - predicted_values[:-2])
        per_head = [
            p.log_prob(sg(a[1:-1]))[..., None]
            for p, a in zip(policies, jnp.split(imagined_actions, splits, axis=-1))
        ]
        reinforce = sum(per_head) * advantage
        objective = objective_mix * reinforce + (1 - objective_mix) * dynamics
        entropy = ent_coef * actor_entropy(policies, distribution)
        policy_loss = -jnp.mean(discount[:-2] * (objective + entropy[..., None]))
        aux = {
            "trajectories": sg(traj),
            "lambda_values": sg(lambda_values),
            "discount": discount,
            "Loss/policy_loss": policy_loss,
            "User/PredictedRewards": jnp.mean(sg(predicted_rewards)),
            "User/LambdaValues": jnp.mean(sg(lambda_values)),
        }
        return policy_loss, aux

    # ------------------------------------------------------------------
    # critic loss (reference train :399-418)
    # ------------------------------------------------------------------

    def critic_loss_fn(critic_params, traj, lambda_values, discount):
        qv = Independent(Normal(critic.apply({"params": critic_params}, traj[:-1]), 1.0), 1)
        return -jnp.mean(discount[:-1, ..., 0] * qv.log_prob(lambda_values))

    # ------------------------------------------------------------------
    # the fused step
    # ------------------------------------------------------------------

    def local_step(agent_state, data, key, tau):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        params = agent_state["params"]
        opt = agent_state["opt"]

        # hard target copy on tau=1 steps (reference main :779-785)
        target = jax.tree_util.tree_map(
            lambda c, t: tau * c + (1.0 - tau) * t,
            params["critic"],
            params["target_critic"],
        )

        k_wm, k_img = jax.random.split(key)

        (wm_loss, (wm_metrics, posteriors, recurrents)), wm_grads = jax.value_and_grad(
            wm_loss_fn, has_aux=True
        )(params["world_model"], data, k_wm)
        wm_grads = pmean(wm_grads, axis)
        wm_updates, wm_opt = world_tx.update(wm_grads, opt["world_model"], params["world_model"])
        wm_params = optax.apply_updates(params["world_model"], wm_updates)

        true_continue = (1.0 - data["dones"]).reshape(-1, 1)
        (actor_loss, aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"],
            wm_params,
            target,
            posteriors,
            recurrents,
            true_continue,
            k_img,
        )
        actor_grads = pmean(actor_grads, axis)
        actor_updates, actor_opt = actor_tx.update(actor_grads, opt["actor"], params["actor"])
        actor_params = optax.apply_updates(params["actor"], actor_updates)

        critic_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic"],
            aux["trajectories"],
            aux["lambda_values"],
            aux["discount"],
        )
        critic_grads = pmean(critic_grads, axis)
        critic_updates, critic_opt = critic_tx.update(critic_grads, opt["critic"], params["critic"])
        critic_params = optax.apply_updates(params["critic"], critic_updates)

        metrics = dict(wm_metrics)
        metrics["Loss/policy_loss"] = aux["Loss/policy_loss"]
        metrics["User/PredictedRewards"] = aux["User/PredictedRewards"]
        metrics["User/LambdaValues"] = aux["User/LambdaValues"]
        metrics["Loss/value_loss"] = critic_loss
        metrics["Grads/world_model"] = optax.global_norm(wm_grads)
        metrics["Grads/actor"] = optax.global_norm(actor_grads)
        metrics["Grads/critic"] = optax.global_norm(critic_grads)
        metrics = pmean(metrics, axis)
        if learn_on:
            # grads are already pmean'd, so the probe scalars are identical
            # on every shard — the learn plane adds no collectives
            metrics.update(
                learn_probes(
                    {
                        "world_model": wm_grads,
                        "actor": actor_grads,
                        "critic": critic_grads,
                    },
                    params={
                        "world_model": params["world_model"],
                        "actor": params["actor"],
                        "critic": params["critic"],
                    },
                    updates={
                        "world_model": wm_updates,
                        "actor": actor_updates,
                        "critic": critic_updates,
                    },
                    losses=(wm_loss, actor_loss, critic_loss),
                    clip_norms=learn_clips,
                )
            )

        new_state = {
            "params": {
                "world_model": wm_params,
                "actor": actor_params,
                "critic": critic_params,
                "target_critic": target,
            },
            "opt": {"world_model": wm_opt, "actor": actor_opt, "critic": critic_opt},
        }
        return new_state, metrics

    # step + fused-burst programs (scanned per-step inputs: key, tau)
    return build_train_burst(local_step, fabric, n_scanned=2)


def build_optimizers_and_state(cfg, params):
    """The three labeled optimizers + the initial agent-state pytree
    (shared with bench_dreamer.py so benchmarks can't drift from the real
    training wiring)."""
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
    }
    return world_tx, actor_tx, critic_tx, agent_state


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    # These arguments cannot be changed (reference main :436-438)
    cfg.env.screen_size = 64
    cfg.env.frame_stack = 1

    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    # Environment setup — one process drives all devices (SPMD)
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
    if (
        len(set(cfg.cnn_keys.encoder).intersection(set(cfg.cnn_keys.decoder))) == 0
        and len(set(cfg.mlp_keys.encoder).intersection(set(cfg.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if len(set(cfg.cnn_keys.decoder) - set(cfg.cnn_keys.encoder)) > 0:
        raise RuntimeError(
            "The CNN keys of the decoder must be contained in the encoder ones. "
            f"Those keys are decoded without being encoded: {list(set(cfg.cnn_keys.decoder))}"
        )
    if len(set(cfg.mlp_keys.decoder) - set(cfg.mlp_keys.encoder)) > 0:
        raise RuntimeError(
            "The MLP keys of the decoder must be contained in the encoder ones. "
            f"Those keys are decoded without being encoded: {list(set(cfg.mlp_keys.decoder))}"
        )
    if cfg.metric.log_level > 0:
        fabric.print("Encoder CNN keys:", cfg.cnn_keys.encoder)
        fabric.print("Encoder MLP keys:", cfg.mlp_keys.encoder)
        fabric.print("Decoder CNN keys:", cfg.cnn_keys.decoder)
        fabric.print("Decoder MLP keys:", cfg.mlp_keys.decoder)
    cnn_keys = list(cfg.cnn_keys.encoder)
    mlp_keys = list(cfg.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    root_key, build_key = jax.random.split(root_key)
    world_model, actor, critic, params = build_agent(
        cfg, actions_dim, is_continuous, observation_space, build_key
    )
    world_tx, actor_tx, critic_tx, agent_state = build_optimizers_and_state(cfg, params)

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
        world_model,
        actor,
        critic,
        world_tx,
        actor_tx,
        critic_tx,
        cfg,
        fabric,
        actions_dim,
        is_continuous,
    )
    player_fns = build_player_fns(world_model, actor, cfg, actions_dim, is_continuous)

    # the player acts on the CPU host with mirrored snapshots (utils/host.py)
    wm_mirror = HostParamMirror.from_cfg(agent_state["params"]["world_model"], fabric, cfg)
    actor_mirror = HostParamMirror.from_cfg(agent_state["params"]["actor"], fabric, cfg)
    play_wm = wm_mirror(agent_state["params"]["world_model"])
    play_actor = actor_mirror(agent_state["params"]["actor"])

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # Buffer: sequential (per-env sub-buffers) or whole-episode storage
    # (reference main :545-564)
    rb = make_replay_buffer(
        cfg,
        fabric,
        log_dir,
        n_envs=n_envs,
        kind="dreamer",
        obs_keys=obs_keys,
        min_size=8,
        dry_run_size=8,
        sequence_length=int(cfg.per_rank_sequence_length),
    )
    episode_buffer = str(cfg.buffer.get("type", "sequential")).lower() == "episode"
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
            f"policy_steps_per_update value ({policy_steps_per_update}), so "
            "the metrics will be logged at the nearest greater multiple of the "
            "policy_steps_per_update value."
        )
    warn_checkpoint_rounding(cfg, policy_steps_per_update)

    # TPU-first replay staging (data/staging.py): device-ring gathers when
    # buffer.device_ring=True (sequential buffers; the episode buffer falls
    # back), double-buffered host prefetch otherwise; the [n, L, B, ...]
    # burst arrives on device in one step and the per-gradient-step loop
    # below slices device arrays (no H2D per step)
    staging = make_replay_staging(
        cfg,
        fabric,
        rb,
        sequence_length=int(cfg.per_rank_sequence_length),
        batch_sharding=fabric.sharding(None, None, fabric.data_axis),
        seed=cfg.seed,
    )
    rb = staging.rb

    # First observation: a zero-action is_first row (reference main :614-632)
    o = envs.reset(seed=cfg.seed)[0]
    obs = prepare_obs(o, cnn_keys, mlp_keys, n_envs)
    step_data = {k: obs[k][None] for k in obs_keys}
    step_data["dones"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["actions"] = np.zeros((1, n_envs, int(np.sum(actions_dim))), np.float32)
    step_data["rewards"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, n_envs, 1), np.float32)
    rb.add(step_data)
    player_state = player_fns["init_states"](play_wm, n_envs)

    per_rank_gradient_steps = 0

    # Burst acting (tier b, howto/rollout_engine.md): K env steps per device
    # dispatch, K = env.act_burst; 1 reproduces the per-step path exactly.
    # The RSSM player state rides the burst carry next to the observation —
    # the host callback is the whole old loop body (env step, episode
    # bookkeeping, buffer adds) and applies episode resets with the same
    # (1 - mask) * state arithmetic the jitted reset path computes, so
    # trajectories do not depend on K.
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
        # The next row's is_first mirrors the previous dones
        # (reference main :675)
        step_data["is_first"] = step_data["dones"].copy()
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            o, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
        dones = np.logical_or(terminated, truncated).astype(np.float32)

        if "restart_on_exception" in infos:
            for i, env_roe in enumerate(infos["restart_on_exception"]):
                if env_roe and not dones[i]:
                    if not episode_buffer:
                        # both the host copy and (when the ring is on) the
                        # HBM mirror are patched by the staging facade
                        staging.force_done_last(i)
                    step_data["is_first"][0, i] = 1.0

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

        # Save the real next observation (reference main :692-708)
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

        # Row t holds the action that led to observation t (reference :705-720)
        obs_row = prepare_obs(real_next_obs, cnn_keys, mlp_keys, n_envs)
        for k in obs_keys:
            step_data[k] = obs_row[k][None]
        rewards = np.asarray(rewards, np.float32).reshape(n_envs, 1)
        step_data["dones"] = dones.reshape(1, n_envs, 1)
        step_data["actions"] = actions.reshape(1, n_envs, -1).astype(np.float32)
        step_data["rewards"] = clip_rewards_fn(rewards)[None]
        rb.add(step_data)

        # The *player* continues from the autoreset observation
        new_obs = prepare_obs(next_obs_np, cnn_keys, mlp_keys, n_envs)

        if len(dones_idxes) > 0:
            reset_obs = prepare_obs(
                {k: next_obs_np[k][dones_idxes] for k in next_obs_np},
                cnn_keys,
                mlp_keys,
                len(dones_idxes),
            )
            reset_data = {k: reset_obs[k][None] for k in obs_keys}
            reset_data["dones"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            reset_data["actions"] = np.zeros((1, len(dones_idxes), int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = np.zeros((1, len(dones_idxes), 1), np.float32)
            reset_data["is_first"] = np.ones_like(reset_data["dones"])
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
    # scores them, so nothing below touches the train-step critical path
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
                    {"wm": play_wm, "actor": play_actor, "expl": jnp.float32(expl_amount)},
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

        # Train the agent (reference main :756-800)
        if last >= learning_starts and updates_before_training <= 0:
            n_samples = (
                cfg.algo.per_rank_pretrain_steps
                if last == learning_starts
                else cfg.algo.per_rank_gradient_steps
            )
            metrics = None
            if n_samples > 0:
                # a length-0 scan over the burst would fail at trace time;
                # n_samples<=0 degrades to "no training this window"
                local_data = staging.sample_device(
                    cfg.per_rank_batch_size * world_size,
                    sequence_length=cfg.per_rank_sequence_length,
                    n_samples=n_samples,
                )
                # hard target copies on the host-computed cadence; metrics
                # are pulled at most once per burst behind the shared gate
                taus = tau_schedule(
                    n_samples,
                    per_rank_gradient_steps,
                    cfg.algo.critic.target_network_update_freq,
                    tau=1.0,
                    first_hard=False,
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
                    play_wm = wm_mirror(agent_state["params"]["world_model"])
                    play_actor = actor_mirror(agent_state["params"]["actor"])
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

        # Log metrics
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

        # Checkpoint
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
        test(
            player_fns,
            jax.device_get(agent_state["params"]),
            fabric,
            cfg,
            log_dir,
            sample_actions=False,
            normalize_fn=normalize_obs_jnp,
        )
