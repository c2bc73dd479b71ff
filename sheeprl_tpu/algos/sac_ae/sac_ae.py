"""SAC-AE — pixel SAC with a convolutional autoencoder.

Behavioral contract from the reference ``sheeprl/algos/sac_ae/sac_ae.py``
(train :46-133, main :136-428): per update one env step, then (every
``update``) a soft-critic update that also trains the encoder; EMA of the
target Q heads (``algo.tau``) and target encoder (``algo.encoder.tau``) every
``critic.target_network_frequency``; actor + alpha updates on *detached*
conv features every ``actor.network_frequency``; an autoencoder update
(5-bit-quantized pixel targets + latent L2 penalty) every
``decoder.update_freq``.

TPU-native design (same chassis as ``sac/sac.py``): ONE jitted ``shard_map``
program scans the G gradient steps; the cadence gates enter as dynamic bools
applied via ``jnp.where`` on parameter/optimizer pytrees, so no cadence ever
recompiles; the twin-Q ensemble is a vmapped stacked-params apply.
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

from sheeprl_tpu.algos.sac.agent import action_bounds, squash_sample
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac_ae.agent import build_agent, ensemble_q, preprocess_obs
from sheeprl_tpu.algos.sac_ae.utils import normalize_obs_jnp, prepare_obs, test
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

sg = jax.lax.stop_gradient


def build_train_fn(
    encoder,
    decoder,
    qf,
    actor_trunk,
    txs: Dict[str, Any],
    cfg,
    fabric,
    action_scale: np.ndarray,
    action_bias: np.ndarray,
    target_entropy: float,
):
    """``train(state, opts, batch, key, gates) -> (state, opts, metrics)``;
    ``batch`` leaves are ``[G, B_local, ...]``, ``gates`` is a dict of
    dynamic bools {do_ema, do_actor, do_decoder}."""
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    encoder_tau = float(cfg.algo.encoder.tau)
    l2_lambda = float(cfg.algo.decoder.l2_lambda)
    n_critics = int(cfg.algo.critic.n)
    axis = fabric.data_axis
    cnn_keys = tuple(cfg.cnn_keys.encoder)
    mlp_keys = tuple(cfg.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.mlp_keys.decoder)
    scale = jnp.asarray(action_scale)
    bias = jnp.asarray(action_bias)
    tgt_entropy = jnp.float32(target_entropy)
    # learning-health probes (obs/learn): build-time gate, zero ops when off
    learn_on = probes_enabled(cfg)
    learn_clips = {
        "critic": clip_norm_of(txs["qf"]),
        "actor": clip_norm_of(txs["actor"]),
        "alpha": clip_norm_of(txs["alpha"]),
        "decoder": clip_norm_of(txs["decoder"]),
    }

    def normalize(batch, prefix=""):
        out = {}
        for k in cnn_keys:
            out[k] = batch[prefix + k] / 255.0
        for k in mlp_keys:
            out[k] = batch[prefix + k]
        return out

    def encode(enc_params, obs, detach_conv=False):
        return encoder.apply({"params": enc_params}, obs, detach_conv)

    def where_tree(flag, a, b):
        return jax.tree_util.tree_map(lambda x, y: jnp.where(flag, x, y), a, b)

    def one_step(carry, batch_and_key):
        state, opts, gates = carry
        batch, key = batch_and_key
        c_key, a_key, d_key = jax.random.split(key, 3)
        obs = normalize(batch)
        next_obs = normalize(batch, "next_")

        # ---- soft critic (trains encoder too; reference train :77-86)
        alpha = sg(jnp.exp(state["log_alpha"]))
        next_feat = encode(state["target_encoder"], next_obs)
        mean, std = actor_trunk.apply({"params": state["actor"]}, encode(state["encoder"], next_obs))
        next_actions, next_logprob = squash_sample(mean, std, c_key, scale, bias)
        target_q = ensemble_q(qf, state["target_qfs"], next_feat, next_actions)
        min_target = jnp.min(target_q, axis=-1, keepdims=True) - alpha * next_logprob
        td_target = sg(batch["rewards"] + (1.0 - batch["dones"]) * gamma * min_target)

        def qf_loss_fn(p):
            feat = encode(p["encoder"], obs)
            q = ensemble_q(qf, p["qfs"], feat, batch["actions"])
            return critic_loss(q, td_target, n_critics)

        qf_loss, qf_grads = jax.value_and_grad(qf_loss_fn)(
            {"encoder": state["encoder"], "qfs": state["qfs"]}
        )
        qf_grads = pmean(qf_grads, axis)
        qf_updates, qf_opt = txs["qf"].update(
            qf_grads, opts["qf"], {"encoder": state["encoder"], "qfs": state["qfs"]}
        )
        new_enc_qfs = optax.apply_updates(
            {"encoder": state["encoder"], "qfs": state["qfs"]}, qf_updates
        )
        enc_params, qfs = new_enc_qfs["encoder"], new_enc_qfs["qfs"]

        # ---- dual-tau target EMA, gated (reference train :89-92)
        target_qfs = where_tree(
            gates["do_ema"],
            jax.tree_util.tree_map(lambda p, t: tau * p + (1 - tau) * t, qfs, state["target_qfs"]),
            state["target_qfs"],
        )
        target_enc = where_tree(
            gates["do_ema"],
            jax.tree_util.tree_map(
                lambda p, t: encoder_tau * p + (1 - encoder_tau) * t,
                enc_params,
                state["target_encoder"],
            ),
            state["target_encoder"],
        )

        # ---- actor + alpha on detached conv features, gated (reference :94-113)
        def actor_loss_fn(actor_params):
            feat = encode(enc_params, obs, detach_conv=True)
            mean, std = actor_trunk.apply({"params": actor_params}, feat)
            actions, logprob = squash_sample(mean, std, a_key, scale, bias)
            q = ensemble_q(qf, qfs, feat, actions)
            min_q = jnp.min(q, axis=-1, keepdims=True)
            return policy_loss(alpha, logprob, min_q), logprob

        (actor_loss, logprob), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            state["actor"]
        )
        actor_grads = pmean(actor_grads, axis)
        actor_updates, actor_opt = txs["actor"].update(actor_grads, opts["actor"], state["actor"])
        actor_params = where_tree(
            gates["do_actor"], optax.apply_updates(state["actor"], actor_updates), state["actor"]
        )
        actor_opt = where_tree(gates["do_actor"], actor_opt, opts["actor"])

        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, sg(logprob), tgt_entropy)

        alpha_loss, alpha_grad = jax.value_and_grad(alpha_loss_fn)(state["log_alpha"])
        alpha_grad = pmean(alpha_grad, axis)
        alpha_updates, alpha_opt = txs["alpha"].update(alpha_grad, opts["alpha"], state["log_alpha"])
        log_alpha = jnp.where(
            gates["do_actor"], optax.apply_updates(state["log_alpha"], alpha_updates), state["log_alpha"]
        )
        alpha_opt = where_tree(gates["do_actor"], alpha_opt, opts["alpha"])

        # ---- autoencoder, gated (reference train :115-131)
        def recon_loss_fn(p):
            hidden = encode(p["encoder"], obs)
            recon = decoder.apply({"params": p["decoder"]}, hidden)
            loss = 0.0
            keys = jax.random.split(d_key, max(len(cnn_dec_keys), 1))
            for i, k in enumerate(cnn_dec_keys):
                target = preprocess_obs(batch[k], bits=5, key=keys[i])
                loss += jnp.mean((target - recon[k]) ** 2) + l2_lambda * jnp.mean(
                    0.5 * jnp.sum(hidden**2, -1)
                )
            for k in mlp_dec_keys:
                loss += jnp.mean((batch[k] - recon[k]) ** 2) + l2_lambda * jnp.mean(
                    0.5 * jnp.sum(hidden**2, -1)
                )
            return loss

        recon_loss, recon_grads = jax.value_and_grad(recon_loss_fn)(
            {"encoder": enc_params, "decoder": state["decoder"]}
        )
        recon_grads = pmean(recon_grads, axis)
        enc_updates, enc_opt = txs["encoder"].update(
            recon_grads["encoder"], opts["encoder"], enc_params
        )
        dec_updates, dec_opt = txs["decoder"].update(
            recon_grads["decoder"], opts["decoder"], state["decoder"]
        )
        enc_params = where_tree(
            gates["do_decoder"], optax.apply_updates(enc_params, enc_updates), enc_params
        )
        dec_params = where_tree(
            gates["do_decoder"],
            optax.apply_updates(state["decoder"], dec_updates),
            state["decoder"],
        )
        enc_opt = where_tree(gates["do_decoder"], enc_opt, opts["encoder"])
        dec_opt = where_tree(gates["do_decoder"], dec_opt, opts["decoder"])

        new_state = {
            "encoder": enc_params,
            "target_encoder": target_enc,
            "qfs": qfs,
            "target_qfs": target_qfs,
            "actor": actor_params,
            "decoder": dec_params,
            "log_alpha": log_alpha,
        }
        new_opts = {
            "qf": qf_opt,
            "actor": actor_opt,
            "alpha": alpha_opt,
            "encoder": enc_opt,
            "decoder": dec_opt,
        }
        metrics = jnp.stack([qf_loss, actor_loss, alpha_loss, recon_loss])
        if learn_on:
            probes = learn_probes(
                {
                    "critic": qf_grads,
                    "actor": actor_grads,
                    "alpha": alpha_grad,
                    "decoder": recon_grads,
                },
                params={
                    "critic": {"encoder": state["encoder"], "qfs": state["qfs"]},
                    "actor": state["actor"],
                    "alpha": state["log_alpha"],
                    "decoder": state["decoder"],
                },
                updates={
                    "critic": qf_updates,
                    "actor": actor_updates,
                    "alpha": alpha_updates,
                    "decoder": dec_updates,
                },
                losses=(qf_loss, actor_loss, alpha_loss, recon_loss),
                clip_norms=learn_clips,
            )
            return (new_state, new_opts, gates), (metrics, probes)
        return (new_state, new_opts, gates), metrics

    def local_train(state, opts, batch, key, gates):
        g = jax.tree_util.tree_leaves(batch)[0].shape[0]
        keys = jax.random.split(key, g)
        (state, opts, _), ys = jax.lax.scan(one_step, (state, opts, gates), (batch, keys))
        metrics, probes = ys if learn_on else (ys, None)
        metrics = pmean(jnp.mean(metrics, axis=0), axis)
        if learn_on:
            return state, opts, metrics, probes
        return state, opts, metrics

    shmapped = jax.shard_map(
        local_train,
        mesh=fabric.mesh,
        in_specs=(P(), P(), P(None, axis), P(), P()),
        out_specs=(P(), P(), P()) + ((P(),) if learn_on else ()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0, 1))


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    if "minedojo" in (cfg.env.wrapper._target_ or "").lower():
        raise ValueError("MineDojo is not currently supported by SAC-AE agent")

    world_size = fabric.world_size
    root_key = fabric.seed_everything(cfg.seed)

    # These arguments cannot be changed (reference main :157)
    cfg.env.screen_size = 64

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
        raise ValueError("Only continuous action space is supported for the SAC-AE agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if (
        len(set(cfg.cnn_keys.encoder).intersection(set(cfg.cnn_keys.decoder))) == 0
        and len(set(cfg.mlp_keys.encoder).intersection(set(cfg.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjoint")
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

    act_dim = int(np.prod(action_space.shape))
    action_scale, action_bias = action_bounds(action_space)
    target_entropy = -float(act_dim)

    root_key, build_key = jax.random.split(root_key)
    encoder, decoder, qf, actor_trunk, params = build_agent(
        cfg, act_dim, observation_space, build_key
    )

    txs = {
        "qf": instantiate(cfg.algo.critic.optimizer),
        "actor": instantiate(cfg.algo.actor.optimizer),
        "alpha": instantiate(cfg.algo.alpha.optimizer),
        "encoder": instantiate(cfg.algo.encoder.optimizer),
        "decoder": instantiate(cfg.algo.decoder.optimizer),
    }
    opt_states = {
        "qf": txs["qf"].init({"encoder": params["encoder"], "qfs": params["qfs"]}),
        "actor": txs["actor"].init(params["actor"]),
        "alpha": txs["alpha"].init(params["log_alpha"]),
        "encoder": txs["encoder"].init(params["encoder"]),
        "decoder": txs["decoder"].init(params["decoder"]),
    }

    if cfg.checkpoint.resume_from:
        template = {
            "agent": params,
            "opt_states": opt_states,
            "update": 0,
            "batch_size": 0,
            "last_log": 0,
            "last_checkpoint": 0,
        }
        state = fabric.load(cfg.checkpoint.resume_from, template)
        params = state["agent"]
        opt_states = state["opt_states"]
        cfg.per_rank_batch_size = int(np.asarray(state["batch_size"])) // world_size
    agent_state = jax.device_put(params, fabric.replicated)
    opt_states = jax.device_put(opt_states, fabric.replicated)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    rb = make_replay_buffer(
        cfg,
        fabric,
        log_dir,
        n_envs=n_envs,
        obs_keys=tuple(obs_keys),
        dry_run_size=1,
    )
    if state is not None and cfg.buffer.get("checkpoint", False) and "rb" in state:
        rb.load_state_dict(state["rb"])

    scale_j, bias_j = jnp.asarray(action_scale), jnp.asarray(action_bias)

    def _acting_subtree(p):
        return {"encoder": p["encoder"], "actor": p["actor"]}

    actor_mirror = HostParamMirror.from_cfg(_acting_subtree(agent_state), fabric, cfg)
    play_params = actor_mirror(_acting_subtree(agent_state))

    train_fn = build_train_fn(
        encoder, decoder, qf, actor_trunk, txs, cfg, fabric,
        action_scale, action_bias, target_entropy,
    )
    batch_sharding = fabric.sharding(None, fabric.data_axis)
    # TPU-first replay staging (data/staging.py): device-ring gathers when
    # buffer.device_ring=True, double-buffered host prefetch otherwise
    staging = make_replay_staging(
        cfg, fabric, rb, batch_sharding=batch_sharding, seed=cfg.seed
    )
    rb = staging.rb

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
            f"policy_steps_per_update value ({policy_steps_per_update})."
        )
    warn_checkpoint_rounding(cfg, policy_steps_per_update)

    o = envs.reset(seed=cfg.seed)[0]
    obs = prepare_obs(o, cnn_keys, mlp_keys, n_envs)
    root_key, play_key = jax.random.split(root_key)
    play_key = actor_mirror.put_key(play_key)

    per_rank_gradient_steps = int(cfg.algo.per_rank_gradient_steps)
    ema_every = int(cfg.algo.critic.target_network_frequency) // policy_steps_per_update + 1
    actor_every = int(cfg.algo.actor.network_frequency) // policy_steps_per_update + 1
    decoder_every = int(cfg.algo.decoder.update_freq) // policy_steps_per_update + 1

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

        next_obs_np = {k: np.asarray(next_o[k]) for k in next_o}
        real_next_obs = {k: v.copy() for k, v in next_obs_np.items()}
        if "final_obs" in infos:
            for idx, final_obs in enumerate(infos["final_obs"]):
                if final_obs is not None:
                    for k in real_next_obs:
                        if k in final_obs:
                            real_next_obs[k][idx] = np.asarray(final_obs[k])

        next_obs = prepare_obs(next_obs_np, cnn_keys, mlp_keys, n_envs)
        real_next = prepare_obs(real_next_obs, cnn_keys, mlp_keys, n_envs)

        step_data = {k: state_box["obs"][k][None] for k in obs_keys}
        step_data["actions"] = np.asarray(actions, np.float32).reshape(1, n_envs, -1)
        step_data["rewards"] = np.asarray(rewards, np.float32).reshape(1, n_envs, 1)
        step_data["dones"] = np.asarray(dones, np.float32).reshape(1, n_envs, 1)
        if not cfg.buffer.sample_next_obs:
            for k in obs_keys:
                step_data[f"next_{k}"] = real_next[k][None]
        rb.add(step_data)
        state_box["obs"] = next_obs
        return next_obs

    def _act_fn(agent_params, a_obs, key):
        # key advances inside the jitted burst (same discipline as the old
        # per-step policy_fn, so K=1 is bitwise the per-step path); the
        # uint8→[0,1] normalize moved inside the traced program — same math
        key, sub = jax.random.split(key)
        norm_obs = normalize_obs_jnp(a_obs, cnn_keys)
        feat = encoder.apply({"params": agent_params["encoder"]}, norm_obs)
        mean, std = actor_trunk.apply({"params": agent_params["actor"]}, feat)
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
                    play_params, state_box["obs"], play_key, n_act
                )
        policy_step = state_box["policy_step"]
        first = update
        update += n_act
        last = update - 1

        # one train round per update index the burst covered (K=1 reduces to
        # the reference per-update cadence; the ema/actor/decoder gates use
        # the exact per-update index, so the cadences stay bitwise for any K)
        for u in range(first, last + 1):
            if u < learning_starts:
                continue
            training_steps = learning_starts if u == learning_starts else 1
            g_total = training_steps * per_rank_gradient_steps
            # [G, B*world, ...] device arrays: ring-gathered from HBM, or
            # host-sampled + device_put overlapped with the previous burst
            # (native dtypes either way: uint8 pixels are 4x cheaper over
            # the host->HBM link; the train step normalizes on device)
            batch = staging.sample_device(
                world_size * cfg.per_rank_batch_size,
                n_samples=g_total,
                sample_next_obs=cfg.buffer.sample_next_obs,
            )

            with span("Time/train_time", SumMetric(sync_on_compute=cfg.metric.sync_on_compute), phase="train"):
                root_key, train_key = jax.random.split(root_key)
                gates = {
                    "do_ema": jnp.bool_(u % ema_every == 0),
                    "do_actor": jnp.bool_(u % actor_every == 0),
                    "do_decoder": jnp.bool_(u % decoder_every == 0),
                }
                outs = train_fn(agent_state, opt_states, batch, train_key, gates)
                agent_state, opt_states, losses = outs[0], outs[1], outs[2]
                observe_probes(outs[3] if len(outs) > 3 else None, step=policy_step)
                losses = fetch_losses_if_observed(losses, aggregator)
            play_params = actor_mirror(_acting_subtree(agent_state))
            train_step += world_size

            if aggregator and not aggregator.disabled:
                aggregator.update("Loss/value_loss", losses[0])
                aggregator.update("Loss/policy_loss", losses[1])
                aggregator.update("Loss/alpha_loss", losses[2])
                aggregator.update("Loss/reconstruction_loss", losses[3])

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
        test(
            encoder, actor_trunk, jax.device_get(agent_state), scale_j, bias_j,
            fabric, cfg, log_dir,
        )
