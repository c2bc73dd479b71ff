"""DreamerV3 — the flagship model-based algorithm.

Behavioral contract from the reference ``sheeprl/algos/dreamer_v3/dreamer_v3.py``
(train :49-378, main :381-832): sequence-replay world-model learning
(posterior scan over T=64), 15-step imagination for actor-critic learning with
percentile-normalized λ-returns, two-hot critic with EMA target regularizer,
ε-greedy env interaction gated by ``learning_starts``/``train_every``.

TPU-native design (NOT a translation):

- **One jitted SPMD program per gradient step.** The reference runs three
  separate backward/step passes plus a Python GRU loop per batch; here the
  target-EMA, world-model update, imagination rollout, actor update, critic
  update, and Moments state all live in a single ``shard_map``-ped jit with
  the batch dim sharded over the mesh's ``data`` axis. Sequence (T) and
  horizon (H) loops are ``lax.scan``: each is one ``while`` whose body is one
  step's fused operations (nothing is fused *across* steps). What does not
  feed the recurrence stays out of the T loop — the embed projection, the
  prior logits, the Gumbel noise run over ``[T, B]`` at once, before or
  after it — and so do the gradients of the loop's ``Dense`` kernels
  (``models/hoist.py``): a scan's transpose would add each to a kernel-sized
  float32 buffer once an iteration (the GRU's is 252 MB at XL), so the
  backward loop keeps ``dx_t = dy_t @ W^T``, the recurrence, and hands out
  ``dy_t`` stacked for one ``[T*B, in]^T x [T*B, out]`` product after it.
- **Gradient psum via shardings.** Each of the three losses takes
  ``lax.pmean`` on its grads over the data axis — the DDP allreduce —
  and the Moments percentile EMA all-gathers λ-returns across the mesh
  (reference utils.py:61), keeping bitwise 1-vs-N invariance of the math.
- **Stateless cadences.** Target-EMA cadence (tau ∈ {0, τ, 1}) and
  exploration amount enter as dynamic scalars: no recompiles.
- The whole agent (3 param trees + target + 3 optax states + moments) is one
  pytree, donated through the step: params stay resident in HBM.
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
    acting_params,
    build_actor_dists,
    build_agent,
    build_player_fns,
    actor_entropy,
    sample_actor_actions,
)
from sheeprl_tpu.algos.dreamer_v3 import seq_agent
from sheeprl_tpu.algos.dreamer_v3.loss import continue_distribution, reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    compute_lambda_values,
    init_moments,
    prepare_obs,
    test,
    update_moments,
)
from sheeprl_tpu.ckpt import preemption_requested, should_checkpoint, warn_checkpoint_rounding
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.utils.host import HostParamMirror
from sheeprl_tpu.replay import make_replay_buffer
from sheeprl_tpu.data.staging import make_replay_staging
from sheeprl_tpu.distributions import MSEDistribution, SymlogDistribution, TwoHotEncodingDistribution
from sheeprl_tpu.envs.rollout import BurstActor, DeviceActor
from sheeprl_tpu.envs.vector import make_vector_env
from sheeprl_tpu.models.hoist import scan_hoisting_dense_grads
from sheeprl_tpu.plane import train_gated_burst_plan
from sheeprl_tpu.utils.logger import create_tensorboard_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.obs import (
    learn_probes,
    log_sps_metrics,
    probes_enabled,
    profile_tick,
    set_shard_footprint,
    span,
)
from sheeprl_tpu.obs.counters import (
    add_seq_core,
    installed as counters_installed,
    set_seq_core_gauges,
    set_seq_core_state_bytes,
)
from sheeprl_tpu.obs.dist import pmean
from sheeprl_tpu.utils.optim import clip_norm_of
from sheeprl_tpu.parallel.shard import measured_bytes_per_device
from sheeprl_tpu.train import (
    TrainProgram,
    build_train_burst,
    metric_fetch_gate,
    run_train_burst,
    tau_schedule,
)
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
    plan=None,
):
    """Compile one full DreamerV3 gradient step as a single SPMD program.

    Returns ``train_step(agent_state, data, key, tau) -> (agent_state,
    metrics)`` where ``data`` leaves are ``[T, B_total, ...]`` (B sharded over
    the mesh) and ``tau`` is the dynamic target-EMA coefficient (0 = skip).

    ``plan`` (a :class:`~sheeprl_tpu.parallel.shard.ShardingPlan` over the
    agent-state tree, from ``fabric.shard_plan``) switches the program onto
    the ``{'data','model'}`` mesh as ONE GSPMD program: no manual shard_map
    region at all — ``axis=None`` turns the per-shard gradient pmean and the
    rank-decorrelating fold_in into identities (the loss already spans the
    global batch, so its gradient IS the all-reduced gradient), params and
    optimizer state enter via ``in_shardings``/``out_shardings`` with the
    plan's model-axis specs, and XLA inserts every collective (batch-dim
    all-reduces on the data axis, all-gather/reduce-scatter on the model
    axis). This sidesteps the jax-0.4-era partitioner, which CHECK-fails on
    ``lax.scan`` inside a partially-manual (``auto=``) shard_map region.
    ``plan=None`` keeps the manual data-parallel shard_map program
    byte-identical to the pure data-parallel runtime.
    """
    data_axis = fabric.data_axis
    axis = data_axis if plan is None else None
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    mlp_keys = tuple(cfg.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.mlp_keys.decoder)
    wm_cfg = cfg.algo.world_model
    stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    rec_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    kl_dynamic = float(wm_cfg.kl_dynamic)
    kl_representation = float(wm_cfg.kl_representation)
    kl_free_nats = float(wm_cfg.kl_free_nats)
    kl_regularizer = float(wm_cfg.kl_regularizer)
    continue_scale = float(wm_cfg.continue_scale_factor)
    ent_coef = float(cfg.algo.actor.ent_coef)
    from sheeprl_tpu.algos.dreamer_v3.agent import resolve_actor_distribution

    distribution = resolve_actor_distribution(
        cfg.distribution.get("type", "auto"), is_continuous
    )
    init_std = float(cfg.algo.actor.init_std)
    min_std = float(cfg.algo.actor.min_std)
    unimix = float(cfg.algo.unimix)
    moments_cfg = cfg.algo.actor.moments
    m_decay = float(moments_cfg.decay)
    m_max = float(moments_cfg.max)
    m_low = float(moments_cfg.percentile.low)
    m_high = float(moments_cfg.percentile.high)
    dims = tuple(int(d) for d in actions_dim)
    splits = list(np.cumsum(dims)[:-1])
    learn_on = probes_enabled(cfg)
    learn_clips = {
        "world_model": clip_norm_of(world_tx),
        "actor": clip_norm_of(actor_tx),
        "critic": clip_norm_of(critic_tx),
    }

    def wm_apply(params, method, *args):
        return world_model.apply({"params": params}, *args, method=method)

    # ------------------------------------------------------------------
    # world-model loss (reference train :104-194)
    # ------------------------------------------------------------------

    def wm_loss_fn(wm_params, data, key):
        T, B = data["rewards"].shape[:2]
        with jax.named_scope("dv3/encoder"):
            batch_obs = {k: data[k] / 255.0 for k in cnn_keys}
            batch_obs.update({k: data[k] for k in mlp_keys})
            is_first = data["is_first"].at[0].set(1.0)
            # shift: the action column becomes "action that led here"
            batch_actions = jnp.concatenate(
                [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
            )
            embedded = wm_apply(wm_params, WorldModel.encode, batch_obs)
            # hoist the embed half of the posterior trunk out of the time scan:
            # one [T*B, E]×[E, H] matmul here instead of T sequential [B, E]×[E, H]
            embed_proj = wm_apply(wm_params, WorldModel.project_embed, embedded)
        # the is_first reset posterior is the prior mode at a zeroed recurrent
        # state — a constant, computed once (broadcast over B inside the scan)
        with jax.named_scope("dv3/rssm"):
            init_post = wm_apply(
                wm_params, WorldModel.initial_posterior, jnp.zeros((1, rec_size))
            )

        def step(params, init_post, carry, inp):
            posterior, recurrent = carry
            action, eproj, first, g = inp
            recurrent, posterior, post_logits = world_model.apply(
                {"params": params},
                posterior,
                recurrent,
                action,
                eproj,
                first,
                init_post,
                None,
                g,
                method=WorldModel.dynamic_posterior,
            )
            return (posterior, recurrent), (recurrent, posterior, post_logits)

        # pre-draw the posterior sampling noise for the whole sequence in one
        # vectorized call; the scan body is left with add+argmax only. The
        # scan's Dense kernels (the GRU's joint [h, x] -> 3H above all) get
        # their gradients from one [T*B, in]^T x [T*B, out] product after the
        # backward loop, not from a kernel-sized buffer added to T times
        with jax.named_scope("dv3/rssm"):
            gumbels = jax.random.gumbel(key, (T, B, S, D))
            (_, _), (recurrents, posteriors, post_logits) = scan_hoisting_dense_grads(
                step,
                {"rssm": wm_params["rssm"]},
                init_post,
                (jnp.zeros((B, stoch_flat)), jnp.zeros((B, rec_size))),
                (batch_actions, embed_proj, is_first, gumbels),
            )
        with jax.named_scope("dv3/heads"):
            # prior (transition) logits never feed back into the loop: batch
            # them over the whole [T, B] recurrent-state sequence after the scan
            prior_logits = wm_apply(wm_params, WorldModel.prior_logits, recurrents)
            latents = jnp.concatenate([posteriors, recurrents], -1)
            recon = wm_apply(wm_params, WorldModel.decode, latents)
            po = {k: MSEDistribution(recon[k], dims=3) for k in cnn_dec_keys}
            po.update({k: SymlogDistribution(recon[k], dims=1) for k in mlp_dec_keys})
            pr = TwoHotEncodingDistribution(
                wm_apply(wm_params, WorldModel.reward_logits, latents), dims=1
            )
            pc = continue_distribution(
                wm_apply(wm_params, WorldModel.continue_logits, latents)
            )
            loss, metrics = reconstruction_loss(
                po,
                batch_obs,
                pr,
                data["rewards"],
                prior_logits.reshape(T, B, S, D),
                post_logits.reshape(T, B, S, D),
                kl_dynamic,
                kl_representation,
                kl_free_nats,
                kl_regularizer,
                pc,
                1.0 - data["dones"],
                continue_scale,
            )
        return loss, (metrics, sg(posteriors), sg(recurrents))

    # ------------------------------------------------------------------
    # actor loss via imagination (reference train :230-345)
    # ------------------------------------------------------------------

    # A fused Pallas rollout kernel lived here through round 3 (VMEM-resident
    # weights over the whole horizon; 1.6x over the lax scan standalone) but
    # never beat the lax path in-graph: the custom-call scheduling barrier —
    # XLA cannot overlap async weight prefetches across a pallas region —
    # plus per-step pack gathers cost more than the kernel saved (14.67 vs
    # 14.55 ms at the S preset, bf16). Retired in round 4; the lax scan IS
    # the fast path. History: ops/imagination.py before commit 5430c2d.
    S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)

    def imagination_rollout(wm_params, actor_params, posteriors, recurrents, key):
        """15-step prior rollout from every (t, b) posterior. Returns
        ``(trajectories [H+1, BT, L], actions [H+1, BT, A])``.

        Gradients flow through the actor's straight-through / rsample
        actions (needed by the continuous dynamics-backprop objective)."""
        prior = posteriors.reshape(-1, stoch_flat)
        recurrent = recurrents.reshape(-1, rec_size)
        latent0 = jnp.concatenate([prior, recurrent], -1)

        def policy(latent, k):
            pre = actor.apply({"params": actor_params}, sg(latent))
            dists = build_actor_dists(
                pre, is_continuous, distribution, init_std, min_std, unimix
            )
            return jnp.concatenate(
                sample_actor_actions(dists, is_continuous, k, True), -1
            )

        k0, key = jax.random.split(key)
        a0 = policy(latent0, k0)

        def step(carry, inp):
            prior, recurrent, action = carry
            g_img, k_act = inp
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
            action = policy(latent, k_act)
            return (prior, recurrent, action), (latent, action)

        # prior-sampling noise for the whole horizon drawn in one call; only
        # the actor's (distribution-dependent) sampling still consumes keys
        k_gum, key = jax.random.split(key)
        gumbels = jax.random.gumbel(k_gum, (horizon, prior.shape[0], S, D))
        keys = jax.random.split(key, horizon)
        _, (latents, acts) = jax.lax.scan(step, (prior, recurrent, a0), (gumbels, keys))
        trajectories = jnp.concatenate([latent0[None], latents], 0)
        actions = jnp.concatenate([a0[None], acts], 0)
        return trajectories, actions

    def actor_loss_fn(actor_params, wm_params, critic_params, posteriors, recurrents,
                      true_continue, moments_state, key):
        with jax.named_scope("dv3/imagination"):
            traj, imagined_actions = imagination_rollout(
                wm_params, actor_params, posteriors, recurrents, key
            )
        with jax.named_scope("dv3/behavior"):
            predicted_values = TwoHotEncodingDistribution(
                critic.apply({"params": critic_params}, traj), dims=1
            ).mean
            predicted_rewards = TwoHotEncodingDistribution(
                wm_apply(wm_params, WorldModel.reward_logits, traj), dims=1
            ).mean
            continues = continue_distribution(
                wm_apply(wm_params, WorldModel.continue_logits, traj)
            ).base.mode
            continues = jnp.concatenate([true_continue[None], continues[1:]], 0)

            lambda_values = compute_lambda_values(
                predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda
            )
            discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)

            pre = actor.apply({"params": actor_params}, sg(traj))
            policies = build_actor_dists(
                pre, is_continuous, distribution, init_std, min_std, unimix
            )

            baseline = predicted_values[:-1]
            new_moments, offset, invscale = update_moments(
                moments_state, lambda_values, m_decay, m_max, m_low, m_high, axis_name=axis
            )
            advantage = (lambda_values - offset) / invscale - (baseline - offset) / invscale

            if is_continuous:
                objective = advantage
            else:
                per_head = [
                    p.log_prob(sg(a))[..., None][:-1]
                    for p, a in zip(policies, jnp.split(imagined_actions, splits, axis=-1))
                ]
                objective = sum(per_head) * sg(advantage)
            entropy = ent_coef * actor_entropy(policies, distribution)
            policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
            aux = {
                "trajectories": sg(traj),
                "lambda_values": sg(lambda_values),
                "discount": discount,
                "moments": new_moments,
                "Loss/policy_loss": policy_loss,
                "User/LambdaValues": jnp.mean(sg(lambda_values)),
                "User/Advantages": jnp.mean(sg(advantage)),
                "User/Entropy": jnp.mean(sg(entropy)),
                "User/PredictedRewards": jnp.mean(sg(predicted_rewards)),
                "User/PredictedValues": jnp.mean(sg(predicted_values)),
            }
        return policy_loss, aux

    # ------------------------------------------------------------------
    # critic loss (reference train :348-370)
    # ------------------------------------------------------------------

    def critic_loss_fn(critic_params, target_params, traj, lambda_values, discount):
        with jax.named_scope("dv3/behavior"):
            qv = TwoHotEncodingDistribution(
                critic.apply({"params": critic_params}, traj[:-1]), dims=1
            )
            target_values = TwoHotEncodingDistribution(
                critic.apply({"params": target_params}, traj[:-1]), dims=1
            ).mean
            value_loss = -qv.log_prob(lambda_values) - qv.log_prob(sg(target_values))
            return jnp.mean(value_loss * discount[:-1, ..., 0])

    # ------------------------------------------------------------------
    # the fused step
    # ------------------------------------------------------------------

    def local_step(agent_state, data, key, tau):
        # de-correlate sampling noise across shards: each device works on a
        # different slice of the batch and must draw different latents
        if axis is not None:
            # manual data-parallel program: decorrelate the per-shard noise
            # (the global GSPMD program draws [B_total] noise from one key)
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        params = agent_state["params"]
        opt = agent_state["opt"]

        # target critic EMA, dynamic cadence (reference main :731-735)
        with jax.named_scope("dv3/optimizer"):
            target = jax.tree_util.tree_map(
                lambda c, t: tau * c + (1.0 - tau) * t,
                params["critic"],
                params["target_critic"],
            )

        k_wm, k_img = jax.random.split(key)

        # -- world model update
        (wm_loss, (wm_metrics, posteriors, recurrents)), wm_grads = jax.value_and_grad(
            wm_loss_fn, has_aux=True
        )(params["world_model"], data, k_wm)
        with jax.named_scope("dv3/optimizer"):
            wm_grads = pmean(wm_grads, axis)
            wm_updates, wm_opt = world_tx.update(wm_grads, opt["world_model"], params["world_model"])
            wm_params = optax.apply_updates(params["world_model"], wm_updates)

        # -- actor update (imagination from the *updated* world model, as the
        # reference's in-place optimizer.step implies)
        true_continue = (1.0 - data["dones"]).reshape(-1, 1)
        (actor_loss, aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"],
            wm_params,
            params["critic"],
            posteriors,
            recurrents,
            true_continue,
            agent_state["moments"],
            k_img,
        )
        with jax.named_scope("dv3/optimizer"):
            actor_grads = pmean(actor_grads, axis)
            actor_updates, actor_opt = actor_tx.update(actor_grads, opt["actor"], params["actor"])
            actor_params = optax.apply_updates(params["actor"], actor_updates)

        # -- critic update
        critic_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic"],
            target,
            aux["trajectories"],
            aux["lambda_values"],
            aux["discount"],
        )
        with jax.named_scope("dv3/optimizer"):
            critic_grads = pmean(critic_grads, axis)
            critic_updates, critic_opt = critic_tx.update(critic_grads, opt["critic"], params["critic"])
            critic_params = optax.apply_updates(params["critic"], critic_updates)

        metrics = dict(wm_metrics)
        metrics.update(
            {
                k: v
                for k, v in aux.items()
                if k not in ("trajectories", "lambda_values", "discount", "moments")
            }
        )
        metrics["Loss/value_loss"] = critic_loss
        with jax.named_scope("dv3/optimizer"):
            metrics["Grads/world_model"] = optax.global_norm(wm_grads)
            metrics["Grads/actor"] = optax.global_norm(actor_grads)
            metrics["Grads/critic"] = optax.global_norm(critic_grads)
            metrics = pmean(metrics, axis)
        if learn_on:
            # grads are already pmean'd above, so every shard computes the
            # same probe scalars — no extra collectives for the learn plane
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
            "moments": aux["moments"],
        }
        return new_state, metrics

    # step + fused-burst programs (scanned per-step inputs: key, tau): one
    # dispatch per training burst through the shared engine (train/burst.py)
    return build_train_burst(local_step, fabric, n_scanned=2, plan=plan)


def build_optimizers_and_state(cfg, params):
    """The three labeled optimizers + the initial agent-state pytree
    (shared with bench_dreamer.py so benchmarks can't drift from the real
    train-state layout)."""
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
        "moments": init_moments(),
    }
    return world_tx, actor_tx, critic_tx, agent_state


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    # These arguments cannot be changed (reference main :394-396)
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    logger, log_dir = create_tensorboard_logger(cfg)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    # Environment setup — one process drives all devices (SPMD), so the vector
    # env holds num_envs × world_size environments, each fault-tolerant via
    # RestartOnException (reference main :408-423).
    n_envs = int(cfg.env.num_envs) * world_size
    # each env fault-tolerant via RestartOnException; vector backend picked
    # by env.vectorization — env.vectorization=async keeps simulator CPU burn
    # in worker processes (the shared-memory pool, howto/async_envs.md)
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
    is_minedojo = "minedojo" in str(cfg.env.wrapper.get("_target_", "") or "").lower()
    mask_keys = (
        ("mask_action_type", "mask_craft_smelt", "mask_equip_place", "mask_destroy")
        if is_minedojo
        else ()
    )
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

    # Agent + optimizers + train program
    root_key, build_key = jax.random.split(root_key)
    world_model, actor, critic, params = build_agent(
        cfg, actions_dim, is_continuous, observation_space, build_key
    )
    # a sequence core (algo.world_model.sequence_model, howto/sequence_core.md)
    # trains through the same loop; its acting state lives on the device
    seq_core = isinstance(world_model, seq_agent.SeqWorldModel)
    if seq_core and HostParamMirror.enabled_for(fabric, cfg):
        raise ValueError(
            "the sequence core acts on the device (its acting set is gigabytes, and the host mirror "
            "would copy it after every burst): set algo.player_on_host=False"
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
    # Parameter sharding (parallel.model_axis>1): spec-assign the whole agent
    # state — optax mu/nu mirror the param shapes, so one plan covers params
    # and optimizer state — and place it model-sharded. A resumed checkpoint
    # arrives here as full host arrays, so re-planning onto a *different*
    # model_axis than it was saved under is the same code path (respec +
    # reshard on load). model_axis=1 keeps the replicated placement untouched.
    plan = fabric.shard_plan(agent_state)
    if plan is None:
        agent_state = jax.device_put(agent_state, fabric.replicated)
    else:
        agent_state = plan.place(agent_state)
    set_shard_footprint(
        measured_bytes_per_device(agent_state["params"]),
        measured_bytes_per_device(agent_state["opt"]),
        fabric.model_axis_size,
    )

    train_fn = (seq_agent.build_seq_train_fn if seq_core else build_train_fn)(
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
        plan=plan,
    )
    player_fns = None if seq_core else build_player_fns(
        world_model, actor, cfg, actions_dim, is_continuous
    )

    # Acting is handed parameter trees: the leaves it reads
    # (agent.acting_params and the actor), the trained leaves themselves where
    # the host mirror is off (the recipe's default, and every CPU run: a
    # disabled mirror is the identity), CPU snapshots refreshed per burst where
    # it is on (player_on_host=True on an accelerator mesh, utils/host.py).
    wm_mirror = HostParamMirror.from_cfg(
        acting_params(agent_state["params"]["world_model"]), fabric, cfg
    )
    actor_mirror = HostParamMirror.from_cfg(agent_state["params"]["actor"], fabric, cfg)
    play_wm = wm_mirror(acting_params(agent_state["params"]["world_model"]))
    play_actor = actor_mirror(agent_state["params"]["actor"])

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # Buffer: per-env sequential sub-buffers (reference main :515-523)
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
    # TPU-first replay staging, shared with every off-policy algo
    # (data/staging.py): with buffer.device_ring=True transitions stream to
    # HBM once at collection and train bursts are gathered on device — no
    # per-gradient-step host→device pixel upload; on a multi-device mesh the
    # ring shards itself env-wise over the data axis (each device keeps a
    # private ring shard and gathers exactly the batch slice it consumes).
    # Multi-process runs (and ring off) get the double-buffered host
    # prefetch pipeline instead.
    staging = make_replay_staging(
        cfg,
        fabric,
        rb,
        sequence_length=int(cfg.per_rank_sequence_length),
        batch_sharding=fabric.sharding(None, None, fabric.data_axis),
        seed=cfg.seed,
    )
    rb = staging.rb
    if state is not None and cfg.buffer.get("checkpoint", False) and "rb" in state:
        rb.load_state_dict(state["rb"])

    # Global counters (reference main :534-545)
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

    # First observation (reference main :574-590)
    o = envs.reset(seed=cfg.seed)[0]
    obs = prepare_obs(o, cnn_keys, mlp_keys, n_envs)
    if os.environ.get("SHEEPRL_ACT_DUMP"):
        import pickle

        _dump_file = os.environ["SHEEPRL_ACT_DUMP"]
        if os.path.exists(_dump_file):
            # appending a second stream onto a previous run's dump would
            # silently interleave two incompatible acting traces; start fresh
            # and say so (the dump exists to be diffed against external
            # tooling — a mixed file is worse than a missing one)
            print(
                f"SHEEPRL_ACT_DUMP: {_dump_file} already exists from a "
                "previous run — truncating it; this run's acting stream "
                "starts at row 0",
                flush=True,
            )
            open(_dump_file, "wb").close()
        with open(_dump_file, "ab") as _f:
            pickle.dump(
                {"step": -1, **{k: np.asarray(obs[k]) for k in obs_keys}}, _f
            )
    step_data = {k: obs[k][None] for k in obs_keys}
    step_data["dones"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["rewards"] = np.zeros((1, n_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, n_envs, 1), np.float32)
    if seq_core:
        # the per-env state stays on the device (DeviceActor below); what rides
        # the host loop is the flag that resets it at an episode's end
        player_state = {"reset": np.zeros((n_envs, 1), np.float32)}
    else:
        player_state = player_fns["init_states"](play_wm, n_envs)

    per_rank_gradient_steps = 0
    dumped_rows = 0
    dump_path = os.environ.get("SHEEPRL_ACT_DUMP")

    # Burst acting (tier b, howto/rollout_engine.md): K env steps per device
    # dispatch, K = env.act_burst; 1 reproduces the per-step path exactly.
    # The RSSM player state rides the burst carry next to the observation
    # (and the MineDojo validity masks when present); the host callback is
    # the whole old loop body — env step, episode bookkeeping, buffer adds —
    # and applies episode resets with the same mask * fresh + (1 - mask) *
    # state arithmetic as player_fns["reset_states"], against a host copy of
    # the fresh init state refreshed once per params version (unlike
    # DV1/DV2's zeros, DV3's fresh state has a nonzero initial posterior
    # that depends on the current world-model params).
    act_burst = max(int(cfg.env.get("act_burst", 1) or 1), 1)
    n_sub = len(actions_dim)
    carry0 = {
        "obs": obs,
        "player": {k: np.asarray(v) for k, v in player_state.items()},
    }
    if is_minedojo:
        carry0["masks"] = {k: np.asarray(o[k]) for k in mask_keys}
    state_box = {
        "carry": carry0,
        "policy_step": policy_step,
        "update": start_step,
        "fresh": None,
    }

    def _fresh_player():
        # host copy of init_states under the CURRENT acting params; the
        # train block clears it whenever the params version advances
        if state_box["fresh"] is None and seq_core:
            state_box["fresh"] = {"reset": np.ones((n_envs, 1), np.float32)}
        if state_box["fresh"] is None:
            with span("Time/act_fresh_state_time", phase="rollout"):
                fresh = player_fns["init_states"](play_wm, n_envs)
                state_box["fresh"] = {k: np.asarray(v) for k, v in fresh.items()}
        return state_box["fresh"]

    def _host_step_core(actions, real_actions, player_np, key_data=None):
        nonlocal dumped_rows
        cur_update = state_box["update"]
        state_box["update"] += 1
        state_box["policy_step"] += n_envs
        step_data["actions"] = actions.reshape(1, n_envs, -1).astype(np.float32)
        with span("Time/replay_add_time", phase="store"):
            rb.add(step_data)
        with span("Time/env_interaction_time", SumMetric(sync_on_compute=False), phase="env"):
            o, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
        dones = np.logical_or(terminated, truncated).astype(np.float32)

        step_data["is_first"] = np.zeros_like(step_data["dones"])
        if "restart_on_exception" in infos:
            for i, env_roe in enumerate(infos["restart_on_exception"]):
                if env_roe and not dones[i]:
                    # both the host copy and (when the ring is on) the HBM
                    # mirror are patched by the staging facade
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

        # Save the real next observation: on autoreset steps the terminal
        # observation lives in final_obs (reference main :663-668)
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

        # SHEEPRL_ACT_DUMP=<path>: append (o_{t+1}, action_t, reward_t,
        # done_t) rows for the first 1000 POLICY-acting steps — ground truth
        # for comparing the in-loop acting stream against external eval
        # tooling (random-prefill steps bind no act_key and are not dumped;
        # the window counts dumped rows, not loop iterations, so fresh runs
        # with a long prefill still capture their first 1000 policy steps)
        acted_with_policy = (
            cur_update > learning_starts or cfg.checkpoint.resume_from is not None
        )
        if dump_path and acted_with_policy and key_data is not None and dumped_rows < 1000:
            import pickle

            dumped_rows += 1
            with open(dump_path, "ab") as _f:
                pickle.dump(
                    {
                        "step": cur_update,
                        "actions": np.asarray(actions),
                        "act_key": np.asarray(key_data),
                        "rewards": rewards.copy(),
                        "dones": dones.copy(),
                        "rec_norm": float(np.linalg.norm(player_np["recurrent"])),
                        **{k: np.asarray(new_obs[k]) for k in obs_keys},
                    },
                    _f,
                )

        if len(dones_idxes) > 0:
            reset_obs = prepare_obs(
                {k: real_next_obs[k][dones_idxes] for k in real_next_obs},
                cnn_keys,
                mlp_keys,
                len(dones_idxes),
            )
            reset_data = {k: reset_obs[k][None] for k in obs_keys}
            reset_data["dones"] = np.ones((1, len(dones_idxes), 1), np.float32)
            reset_data["actions"] = np.zeros((1, len(dones_idxes), int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["dones"])
            rb.add(reset_data, dones_idxes)

            # Reset already-inserted step data (reference main :708-712)
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
        if is_minedojo:
            carry["masks"] = {k: np.asarray(o[k]) for k in mask_keys}
        state_box["carry"] = carry
        return carry

    def _host_env_step(*args):
        actions_j = [np.asarray(a) for a in args[:n_sub]]
        player_np = {
            "actions": np.asarray(args[n_sub]),
            "recurrent": np.asarray(args[n_sub + 1]),
            "stochastic": np.asarray(args[n_sub + 2]),
        }
        key_data = np.asarray(args[n_sub + 3])
        actions = np.concatenate(actions_j, -1)
        if is_continuous:
            real_actions = actions
        else:
            real_actions = np.stack([np.argmax(a, axis=-1) for a in actions_j], axis=-1)
        return _host_step_core(actions, real_actions, player_np, key_data)

    def _act_fn(p, carry, key):
        # the key advances inside the jitted burst with the same split order
        # the per-step loop used (carried key first, act key second), so the
        # K=1 key stream is bitwise the per-step stream
        key, act_key = jax.random.split(key)
        masks = carry["masks"] if is_minedojo else None
        player = carry["player"]
        # raw-obs variant: uint8 pixels cross the host→device link and are
        # normalized inside the jit
        actions_j, new_player = player_fns["exploration_action_raw"](
            p["wm"], p["actor"], player, carry["obs"], act_key, p["expl"], masks=masks
        )
        cb_args = tuple(actions_j) + (
            new_player["actions"],
            new_player["recurrent"],
            new_player["stochastic"],
            jax.random.key_data(act_key),
        )
        return cb_args, key

    if seq_core:
        init_player, player_step = seq_agent.build_player(world_model, actor, cfg, n_envs)

        def _device_step(p, state, carry, key):
            key, act_key = jax.random.split(key)
            action, computed, state = player_step(
                {"wm": p["wm"], "actor": p["actor"]}, state, carry["obs"], carry["player"]["reset"],
                act_key, p["expl"],
            )
            return action, computed, state, key

        def _host_device_step(actions):
            actions = np.asarray(actions)
            return _host_step_core(
                actions, np.argmax(actions, -1)[:, None], {"reset": np.zeros((n_envs, 1), np.float32)}
            )

        with jax.default_device(fabric.device):
            burst_actor = DeviceActor(_device_step, _host_device_step, jax.jit(init_player)())
        set_seq_core_state_bytes(
            sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(burst_actor.state)) // n_envs
        )
    else:
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
            with span("Time/act_prepare_time", phase="rollout"):
                # the exploration amount's upload: an eager dispatch of its own
                burst_params = {"wm": play_wm, "actor": play_actor, "expl": jnp.float32(expl_amount)}
            if not wm_mirror.enabled:
                # acting runs on the device that holds the trained leaves, and its
                # program waits for the host callback: the callback has to find the
                # fresh player state made, not ask it of the device it holds
                _fresh_player()
            with span("Time/rollout_time", SumMetric(sync_on_compute=False), phase="rollout"):
                _, root_key = burst_actor.rollout(
                    burst_params, state_box["carry"], root_key, n_act
                )
            # the burst program commits its inputs to the player's device;
            # pull the carried key back to host numpy (uncommitted) so the
            # possibly multi-device train program keeps accepting it
            with span("Time/act_key_fetch_time", phase="rollout"):
                root_key = np.asarray(root_key)
        policy_step = state_box["policy_step"]

        update += n_act
        last = update - 1
        updates_before_training -= n_act

        # Train the agent (reference main :719-765)
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
                # EMA targets: soft tau on the cadence, the run's very first
                # gradient step hard-copies
                taus = tau_schedule(
                    n_samples,
                    per_rank_gradient_steps,
                    cfg.algo.critic.target_network_update_freq,
                    tau=cfg.algo.critic.tau,
                    first_hard=True,
                )
                with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
                    with span("Time/train_prepare_time", phase="train"):
                        root_key, train_key = jax.random.split(root_key)
                        scanned = (jax.random.split(train_key, n_samples), jnp.asarray(taus))
                    # two values; the `*_` is for the benchmark's sequence adapter, which
                    # returns a triple in this call's place (benchmarks/dv3_seq_adapter.py:169)
                    agent_state, metrics, *_ = run_train_burst(
                        train_fn,
                        agent_state,
                        local_data,
                        scanned,
                        world_size=world_size,
                        # the sequence core's counters ride the step's metrics
                        fetch_metrics=fetch_metrics or (seq_core and counters_installed() is not None),
                    )
                    per_rank_gradient_steps += n_samples
                    # the burst donated the state acting read: hand it the new leaves
                    play_wm = wm_mirror(acting_params(agent_state["params"]["world_model"]))
                    play_actor = actor_mirror(agent_state["params"]["actor"])
                    if seq_core and metrics is not None:
                        add_seq_core(
                            steps=n_samples,
                            **{k: float(metrics[f"Core/{k}"]) * n_samples
                               for k in seq_agent.CORE_COUNTERS if f"Core/{k}" in metrics},
                        )
                        set_seq_core_gauges(
                            **{k: float(metrics[f"Core/{k}"]) for k in seq_agent.CORE_GAUGES if f"Core/{k}" in metrics}
                        )
                    # the cached fresh player state (episode resets) belongs
                    # to the previous params version
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

        # Log metrics (reference main :768-800)
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

        # Checkpoint (reference main :803-830)
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
            with span("Time/checkpoint_time", phase="checkpoint"):
                fabric.call(
                    "on_checkpoint_coupled",
                    ckpt_path=ckpt_path,
                    state=ckpt_state,
                    replay_buffer=rb if cfg.buffer.get("checkpoint", False) else None,
                    sharding_meta=plan.describe() if plan is not None else None,
                )
            if preemption_requested():
                # SIGTERM/SIGINT: the final checkpoint is saved (the CLI
                # drains the in-flight write) — leave the train loop cleanly
                break

    if inrun is not None:
        inrun.close()
    staging.close()
    envs.close()
    if seq_core:
        if cfg.algo.get("run_test", True):
            fabric.print("the sequence core has no test episode yet (algo.run_test is skipped)")
    elif fabric.is_global_zero and cfg.algo.get("run_test", True) and not preemption_requested():
        test(player_fns, jax.device_get(agent_state["params"]), fabric, cfg, log_dir, sample_actions=True)
