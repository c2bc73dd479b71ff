"""DreamerV3 with an autoregressive sequence model as its world-model core
(``algo.world_model.sequence_model``, one of :data:`CORES`; howto/sequence_core.md).

What TransDreamer, IRIS and STORM do to DreamerV3: the recurrent core gives
way to a decoder over observation and action tokens, and everything else of
the agent stays. The decoder is the module :data:`CORES` names for the
configured model; nothing below knows which. A core module answers ``Config``
(with ``from_mapping``, ``hidden_size``, ``vocab_size``, ``chunk``,
``experts_held``, ``moe_layers``, ``balance_loss``), ``init_params``,
``window``, ``decode``, ``boundary_state``, ``init_state``, ``reset_state``,
``embed``, ``head_logits``, ``balance_step`` (what training moves outside the
gradient, after the optimiser's step, from the window pass's load of every
expert: a router's selection bias, or nothing), and names the statistics it
counts (``DECODE_COUNTS``, ``WINDOW_COUNTS``).

- The stochastic state is *one* categorical over ``discrete_size`` observation
  codes, its posterior from the encoder alone. Token ids below
  ``discrete_size`` are the codes, the ids after them the actions.
- A window of ``T`` env steps is the token row ``o_1, a_1, o_2, a_2, ...`` of
  length ``2T`` through the model's own embedding. The head's output at an
  action position is the prior over the next observation code. Reward,
  continue, decoder, actor and critic read ``[embedded code, final-norm
  output]`` at the observation position.
- An episode's first step resets the core inside the window.
- Imagination is one-token decoding, forward only (discrete actions: the
  actor learns by REINFORCE), from the state the training pass had at every
  ``core.chunk``-th token of every row.
- Acting is the same one-token decoding against per-env state kept on the
  device (:func:`build_player`); the parameters are read where training left
  them, no copy is made.
"""

from __future__ import annotations

import importlib
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import (
    ACTOR_UNIFORM_HEADS,
    CRITIC_UNIFORM_HEADS,
    Actor,
    CNNDecoder,
    MLPWithHead,
    MultiEncoderDV3,
    actor_entropy,
    add_exploration_noise,
    build_actor_dists,
    hafner_initialization,
    resolve_actor_distribution,
    sample_actor_actions,
    uniform_mix,
)
from sheeprl_tpu.algos.dreamer_v3.loss import categorical_kl, continue_distribution
from sheeprl_tpu.algos.dreamer_v3.utils import compute_lambda_values, normalize_obs_jnp, update_moments
from sheeprl_tpu.distributions import (
    Independent,
    MSEDistribution,
    OneHotCategorical,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.obs.dist import pmean, psum

sg = jax.lax.stop_gradient
f32 = jnp.float32

#: ``algo.world_model.sequence_model`` -> the module that is the core
CORES = {
    "qwen3_next": "sheeprl_tpu.models.qwen3_next",
    "deepseek_v2": "sheeprl_tpu.models.deepseek_v2",
    "lfm2_moe": "sheeprl_tpu.models.lfm2_moe",
}
SEQUENCE_MODELS = ("gru", *CORES)
#: the scope the core's parts are named under, beside ``dv3/encoder``, ``dv3/heads``, ...
CORE_SCOPE = "dv3/core"
WM_UNIFORM_HEADS = ((r"reward_model/head/", 0.0), (r"continue_model/head/", 1.0), (r"cnn_decoder/head/", 1.0))


class SeqWorldModel:
    """The modules round the core, and the core's configuration."""

    def __init__(self, cfg, observation_space, n_actions: int, dtype):
        wm = cfg.algo.world_model
        self.cnn_keys = tuple(cfg.cnn_keys.encoder)
        if tuple(cfg.cnn_keys.decoder) != self.cnn_keys or list(cfg.mlp_keys.encoder) or list(cfg.mlp_keys.decoder):
            raise ValueError(
                "the sequence core encodes and decodes the same image keys and no vector keys: "
                f"got cnn {list(cfg.cnn_keys.encoder)}/{list(cfg.cnn_keys.decoder)}, "
                f"mlp {list(cfg.mlp_keys.encoder)}/{list(cfg.mlp_keys.decoder)}"
            )
        if int(wm.stochastic_size) != 1:
            raise ValueError("the sequence core's stochastic state is one categorical: algo.world_model.stochastic_size=1")
        self.codes = int(wm.discrete_size)
        self.n_actions = int(n_actions)
        self.core_module = importlib.import_module(CORES[str(wm.sequence_model)])
        core = dict(wm.core)
        held = core.pop("held", {"index": 0, "of": 1})
        self.core = self.core_module.Config.from_mapping(
            {**core, "held_index": int(held["index"]), "held_of": int(held["of"])}
        )
        if self.core.vocab_size < self.codes + self.n_actions:
            raise ValueError(
                f"vocab_size {self.core.vocab_size} holds no room for {self.codes} codes and {self.n_actions} actions"
            )
        self.dtype = dtype
        self.unimix = float(cfg.algo.unimix)
        screen = int(cfg.env.screen_size)
        stages = int(np.log2(screen)) - 2
        self.screen = screen
        self.cnn_channels = [int(np.prod(observation_space[k].shape[:-2])) for k in self.cnn_keys]
        self.encoder = MultiEncoderDV3(
            cnn_keys=self.cnn_keys, mlp_keys=(), channels_multiplier=int(wm.encoder.cnn_channels_multiplier),
            stages=stages, mlp_layers=int(wm.encoder.mlp_layers), dense_units=int(wm.encoder.dense_units),
            layer_norm=bool(cfg.algo.layer_norm), cnn_act=cfg.algo.cnn_act, dense_act=cfg.algo.dense_act, dtype=dtype,
        )
        head = dict(layer_norm=bool(cfg.algo.layer_norm), activation=cfg.algo.dense_act, dtype=dtype)
        self.posterior = MLPWithHead(
            output_dim=self.codes, mlp_layers=1, dense_units=int(wm.representation_model.hidden_size), **head
        )
        self.cnn_decoder = CNNDecoder(
            output_channels=self.cnn_channels, channels_multiplier=int(wm.observation_model.cnn_channels_multiplier),
            stages=stages, image_size=(screen, screen), layer_norm=bool(cfg.algo.layer_norm),
            activation=cfg.algo.cnn_act, dtype=dtype,
        )
        self.reward_model = MLPWithHead(
            output_dim=int(wm.reward_model.bins), mlp_layers=int(wm.reward_model.mlp_layers),
            dense_units=int(wm.reward_model.dense_units), **head
        )
        self.continue_model = MLPWithHead(
            output_dim=1, mlp_layers=int(wm.discount_model.mlp_layers),
            dense_units=int(wm.discount_model.dense_units), **head
        )
        self.feature_size = 2 * self.core.hidden_size

    # -- pieces of the forward pass -------------------------------------------

    def posterior_logits(self, p, obs):
        """``obs`` normalised images -> unimixed log-probabilities over codes."""
        embedded = self.encoder.apply({"params": p["encoder"]}, obs)
        logits = self.posterior.apply({"params": p["posterior"]}, embedded)
        return uniform_mix(logits, self.codes, self.unimix)

    def prior_logits(self, p, h):
        """The head at an action position, over the codes."""
        logits = self.core_module.head_logits(p["core"], h, self.dtype, scope=CORE_SCOPE)
        return uniform_mix(logits[..., : self.codes], self.codes, self.unimix)

    def code_embedding(self, p, onehot):
        """A (straight-through) one-hot over codes through the model's own embedding."""
        return jnp.dot(onehot.astype(self.dtype), p["core"]["embed"][: self.codes].astype(self.dtype),
                       preferred_element_type=f32)

    def decode_image(self, p, feat):
        recon = self.cnn_decoder.apply({"params": p["cnn_decoder"]}, feat)
        out, at = {}, 0
        for k, ch in zip(self.cnn_keys, self.cnn_channels):
            out[k] = recon[..., at : at + ch, :, :]
            at += ch
        return out

    def reward_logits(self, p, feat):
        return self.reward_model.apply({"params": p["reward_model"]}, feat)

    def continue_logits(self, p, feat):
        return self.continue_model.apply({"params": p["continue_model"]}, feat)


def build_seq_agent(cfg, actions_dim: Sequence[int], is_continuous: bool, observation_space, key):
    """``(world_model, actor, critic, params)`` as :func:`agent.build_agent`
    returns them, for the sequence core."""
    from sheeprl_tpu.fabric import compute_dtype_from_precision

    if is_continuous or len(actions_dim) != 1:
        raise ValueError(
            f"algo.world_model.sequence_model={cfg.algo.world_model.sequence_model} takes one discrete action a step "
            f"(an action is a token); got actions_dim={tuple(actions_dim)}, continuous={is_continuous}"
        )
    dtype = compute_dtype_from_precision(cfg.fabric.get("precision", "32-true"))
    wm = SeqWorldModel(cfg, observation_space, int(actions_dim[0]), dtype)
    actor = Actor(
        actions_dim=tuple(actions_dim), is_continuous=False,
        distribution=resolve_actor_distribution(cfg.distribution.get("type", "auto"), False),
        dense_units=int(cfg.algo.actor.dense_units), mlp_layers=int(cfg.algo.actor.mlp_layers),
        layer_norm=bool(cfg.algo.actor.layer_norm), activation=cfg.algo.actor.dense_act, dtype=dtype,
    )
    critic = MLPWithHead(
        output_dim=int(cfg.algo.critic.bins), mlp_layers=int(cfg.algo.critic.mlp_layers),
        dense_units=int(cfg.algo.critic.dense_units), layer_norm=bool(cfg.algo.critic.layer_norm),
        activation=cfg.algo.critic.dense_act, dtype=dtype,
    )
    keys = jax.random.split(key, 11)
    obs = {k: jnp.zeros((1, ch, wm.screen, wm.screen), f32) for k, ch in zip(wm.cnn_keys, wm.cnn_channels)}
    feat = jnp.zeros((1, wm.feature_size), f32)
    enc = wm.encoder.init(keys[0], obs)["params"]
    embedded = wm.encoder.apply({"params": enc}, obs)
    wm_params = {
        "encoder": enc,
        "posterior": wm.posterior.init(keys[1], embedded)["params"],
        "cnn_decoder": wm.cnn_decoder.init(keys[2], feat)["params"],
        "reward_model": wm.reward_model.init(keys[3], feat)["params"],
        "continue_model": wm.continue_model.init(keys[4], feat)["params"],
    }
    actor_params = actor.init(keys[5], feat)["params"]
    critic_params = critic.init(keys[6], feat)["params"]
    if bool(cfg.algo.hafner_initialization):
        wm_params = hafner_initialization(wm_params, keys[7], WM_UNIFORM_HEADS)
        actor_params = hafner_initialization(actor_params, keys[8], ACTOR_UNIFORM_HEADS)
        critic_params = hafner_initialization(critic_params, keys[9], CRITIC_UNIFORM_HEADS)
    wm_params["core"] = jax.jit(lambda k: wm.core_module.init_params(k, wm.core))(keys[10])
    params = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
        "target_critic": jax.tree_util.tree_map(jnp.copy, critic_params),
    }
    return wm, actor, critic, params


# ---------------------------------------------------------------------------
# the train program
# ---------------------------------------------------------------------------


def build_seq_train_fn(world_model: SeqWorldModel, actor, critic, world_tx, actor_tx, critic_tx, cfg, fabric,
                       actions_dim, is_continuous, plan=None):
    """One DreamerV3 gradient step over the sequence core, as
    ``dreamer_v3.build_train_fn`` builds the GRU's: the same three updates,
    the same burst engine."""
    from sheeprl_tpu.obs import learn_probes, probes_enabled
    from sheeprl_tpu.train import build_train_burst
    from sheeprl_tpu.utils.optim import clip_norm_of

    learn_on = probes_enabled(cfg)
    learn_clips = {"world_model": clip_norm_of(world_tx), "actor": clip_norm_of(actor_tx), "critic": clip_norm_of(critic_tx)}
    if plan is not None:
        raise ValueError("the sequence core has no model-axis sharding plan yet (parallel.model_axis=1)")
    wm, c, core_module = world_model, world_model.core, world_model.core_module
    decode_counts = tuple(core_module.DECODE_COUNTS)
    axis = fabric.data_axis
    cnn_keys = wm.cnn_keys
    codes, n_act, dtype = wm.codes, wm.n_actions, wm.dtype
    wm_cfg = cfg.algo.world_model
    horizon = int(cfg.algo.horizon)
    gamma, lmbda = float(cfg.algo.gamma), float(cfg.algo.lmbda)
    kl_dynamic, kl_representation = float(wm_cfg.kl_dynamic), float(wm_cfg.kl_representation)
    kl_free_nats, kl_regularizer = float(wm_cfg.kl_free_nats), float(wm_cfg.kl_regularizer)
    continue_scale = float(wm_cfg.continue_scale_factor)
    ent_coef = float(cfg.algo.actor.ent_coef)
    distribution = resolve_actor_distribution(cfg.distribution.get("type", "auto"), False)
    init_std, min_std, unimix = float(cfg.algo.actor.init_std), float(cfg.algo.actor.min_std), float(cfg.algo.unimix)
    moments_cfg = cfg.algo.actor.moments
    m_decay, m_max = float(moments_cfg.decay), float(moments_cfg.max)
    m_low, m_high = float(moments_cfg.percentile.low), float(moments_cfg.percentile.high)

    def policy(actor_params, feat, key):
        pre = actor.apply({"params": actor_params}, sg(feat))
        dists = build_actor_dists(pre, False, distribution, init_std, min_std, unimix)
        return jnp.concatenate(sample_actor_actions(dists, False, key, True), -1)

    def wm_loss_fn(p, data, key):
        T, B = data["rewards"].shape[:2]
        with jax.named_scope("dv3/encoder"):
            obs = {k: data[k] / 255.0 for k in cnn_keys}
            is_first = data["is_first"][..., 0].at[0].set(1.0)  # [T, B]
            post_logits = wm.posterior_logits(p, obs)  # [T, B, codes]
            gumbel = jax.random.gumbel(key, (T, B, codes))
            z = jnp.argmax(post_logits + gumbel, -1)  # [T, B]
            probs = jnp.exp(post_logits)
            onehot = jax.nn.one_hot(z, codes, dtype=f32) + probs - sg(probs)
            z_emb = wm.code_embedding(p, onehot)  # [T, B, D]
        a = codes + jnp.argmax(data["actions"], -1)  # [T, B]
        tokens = jnp.stack([z.T, a.T], -1).reshape(B, 2 * T).astype(jnp.int32)
        reset = jnp.stack([is_first.T, jnp.zeros_like(is_first.T)], -1).reshape(B, 2 * T).astype(jnp.int32)
        h, states, stats = core_module.window(p["core"], tokens, reset, c, dtype, scope=CORE_SCOPE)
        h = h.reshape(B, T, 2, -1)
        h_obs, h_act = jnp.moveaxis(h[:, :, 0], 0, 1), jnp.moveaxis(h[:, :, 1], 0, 1)  # [T, B, D]
        prior_logits = wm.prior_logits(p, h_act[:-1])  # the prior of steps 1..T-1
        with jax.named_scope("dv3/heads"):
            feat = jnp.concatenate([z_emb, h_obs], -1)

            @jax.checkpoint  # the decoder's activations are the agent's widest: recomputed
            def observation_nll(decoder_params, feat):
                recon = wm.decode_image({"cnn_decoder": decoder_params}, feat)
                return -sum(MSEDistribution(recon[k], dims=3).log_prob(obs[k]) for k in cnn_keys)

            observation_loss = observation_nll(p["cnn_decoder"], feat)
            reward_loss = -TwoHotEncodingDistribution(wm.reward_logits(p, feat), dims=1).log_prob(data["rewards"])
            continue_loss = continue_scale * -continue_distribution(wm.continue_logits(p, feat)).log_prob(
                1.0 - data["dones"]
            )
            # a step has a prior where the step before it is of the same episode
            has_prior = 1.0 - is_first[1:]
            post = post_logits[1:][..., None, :]
            prior = prior_logits[..., None, :]
            kl = categorical_kl(sg(post), prior)
            dyn_loss = kl_dynamic * jnp.maximum(kl, kl_free_nats)
            repr_loss = kl_representation * jnp.maximum(categorical_kl(post, sg(prior)), kl_free_nats)
            kl_loss = jnp.concatenate([jnp.zeros((1, B), f32), (dyn_loss + repr_loss) * has_prior], 0)
            aux = stats["aux"] / c.moe_layers
            loss = jnp.mean(kl_regularizer * kl_loss + observation_loss + reward_loss + continue_loss) \
                + c.balance_loss(stats["aux"])
            metrics = {
                "Loss/world_model_loss": loss,
                "Loss/observation_loss": jnp.mean(observation_loss),
                "Loss/reward_loss": jnp.mean(reward_loss),
                "Loss/state_loss": jnp.mean(kl_loss),
                "Loss/continue_loss": jnp.mean(continue_loss),
                "State/kl": jnp.sum(kl * has_prior) / jnp.maximum(jnp.sum(has_prior), 1.0),
                "State/post_entropy": jnp.mean(Independent(OneHotCategorical(logits=sg(post_logits[..., None, :])), 1).entropy()),
                "State/prior_entropy": jnp.mean(Independent(OneHotCategorical(logits=sg(prior)), 1).entropy()),
                "Core/router_aux_loss": aux,
                "Core/held_pairs": stats["held_pairs"],
                "Core/max_load": stats["max_load"],
                "Core/experts_hit": stats["experts_hit"],
                "Core/dropped_pairs": stats["dropped_pairs"],
                "Core/episode_ends": jnp.sum(is_first[1:]),
                **{f"Core/{name}": stats[name] for name in core_module.WINDOW_COUNTS},
            }
        carry = {"states": sg(states), "tokens": tokens, "reset": reset}
        return loss, (metrics, carry, sg(stats["load"]))

    def imagine(p, actor_params, carry, key):
        """``horizon`` imagined steps of two tokens from every ``chunk``-th
        token of every row. Returns ``(features [H+1, N, F], actions [H+1, N,
        A], start indices into the row's env steps, the one-token steps' counts
        (the core's ``DECODE_COUNTS``: pairs sent to held experts, held experts
        hit, ...), summed over steps and layers)``."""
        B, L = carry["tokens"].shape
        state, context = core_module.boundary_state(
            carry["states"], carry["reset"], c, own_len=2 * horizon + 2, dtype=dtype
        )
        at = jnp.arange(L // c.chunk) * c.chunk
        core = jax.tree_util.tree_map(lambda x: x.astype(dtype) if x.ndim >= 2 else x, p["core"])
        pc = {**p, "core": core}

        def feed(state, routed, tokens):
            h, state, stats = core_module.decode(core, state, tokens, c, dtype, context=context, scope=CORE_SCOPE)
            return h, state, routed + jnp.stack([stats[name] for name in decode_counts])

        z0 = carry["tokens"][:, at]  # the observation token at each start
        h0, state, routed = feed(state, jnp.zeros((len(decode_counts),), f32), z0)
        feat0 = jnp.concatenate([core["embed"][z0].astype(f32), h0], -1)
        k0, key = jax.random.split(key)
        a0 = policy(actor_params, feat0, k0)

        def step(inp, keys):
            state, routed, action = inp
            k_z, k_a = keys
            h_a, state, routed = feed(state, routed, codes + jnp.argmax(action, -1).astype(jnp.int32))
            z = jax.random.categorical(k_z, wm.prior_logits(pc, h_a), -1).astype(jnp.int32)
            h, state, routed = feed(state, routed, z)
            feat = jnp.concatenate([core["embed"][z].astype(f32), h], -1)
            action = policy(actor_params, feat, k_a)
            return (state, routed, action), (feat, action)

        keys = jax.random.split(key, 2 * horizon).reshape(horizon, 2, -1)
        (_, routed, _), (feats, acts) = jax.lax.scan(step, (state, routed, a0), (keys[:, 0], keys[:, 1]))
        flat = lambda x: x.reshape((x.shape[0], -1) + x.shape[3:])
        feats, acts = flat(jnp.concatenate([feat0[None], feats], 0)), flat(jnp.concatenate([a0[None], acts], 0))
        return feats, acts, at // 2, routed

    def actor_loss_fn(actor_params, p, critic_params, carry, continues_true, moments_state, key):
        with jax.named_scope("dv3/imagination"):
            traj, imagined_actions, starts, routed = imagine(p, actor_params, carry, key)
            traj, imagined_actions, routed = sg(traj), sg(imagined_actions), sg(routed)
        with jax.named_scope("dv3/behavior"):
            predicted_values = TwoHotEncodingDistribution(critic.apply({"params": critic_params}, traj), dims=1).mean
            predicted_rewards = TwoHotEncodingDistribution(wm.reward_logits(p, traj), dims=1).mean
            continues = continue_distribution(wm.continue_logits(p, traj)).base.mode
            true_continue = continues_true[starts].T.reshape(-1, 1)  # [T, B, 1] -> [B * starts, 1]
            continues = jnp.concatenate([true_continue[None], continues[1:]], 0)
            lambda_values = compute_lambda_values(
                predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda
            )
            discount = sg(jnp.cumprod(continues * gamma, axis=0) / gamma)
            pre = actor.apply({"params": actor_params}, traj)
            policies = build_actor_dists(pre, False, distribution, init_std, min_std, unimix)
            baseline = predicted_values[:-1]
            new_moments, offset, invscale = update_moments(
                moments_state, lambda_values, m_decay, m_max, m_low, m_high, axis_name=axis
            )
            advantage = (lambda_values - offset) / invscale - (baseline - offset) / invscale
            objective = policies[0].log_prob(imagined_actions)[..., None][:-1] * sg(advantage)
            entropy = ent_coef * actor_entropy(policies, distribution)
            policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[..., None][:-1]))
            aux = {
                "trajectories": traj,
                "lambda_values": sg(lambda_values),
                "discount": discount,
                "moments": new_moments,
                "Loss/policy_loss": policy_loss,
                "User/LambdaValues": jnp.mean(sg(lambda_values)),
                "User/Advantages": jnp.mean(sg(advantage)),
                "User/Entropy": jnp.mean(sg(entropy)),
                "User/PredictedRewards": jnp.mean(sg(predicted_rewards)),
                "User/PredictedValues": jnp.mean(sg(predicted_values)),
                "Core/imagination_starts": jnp.float32(traj.shape[1]),
                "Core/decode_steps": jnp.float32(2 * horizon + 1),
                **{f"Core/{counter}": count for counter, count in zip(core_module.DECODE_COUNTS.values(), routed)},
            }
        return policy_loss, aux

    def critic_loss_fn(critic_params, target_params, traj, lambda_values, discount):
        with jax.named_scope("dv3/behavior"):
            qv = TwoHotEncodingDistribution(critic.apply({"params": critic_params}, traj[:-1]), dims=1)
            target_values = TwoHotEncodingDistribution(critic.apply({"params": target_params}, traj[:-1]), dims=1).mean
            value_loss = -qv.log_prob(lambda_values) - qv.log_prob(sg(target_values))
            return jnp.mean(value_loss * discount[:-1, ..., 0])

    def local_step(agent_state, data, key, tau):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        params, opt = agent_state["params"], agent_state["opt"]
        with jax.named_scope("dv3/optimizer"):
            target = jax.tree_util.tree_map(
                lambda cr, t: tau * cr + (1.0 - tau) * t, params["critic"], params["target_critic"]
            )
        k_wm, k_img = jax.random.split(key)
        (wm_loss, (wm_metrics, carry, load)), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            params["world_model"], data, k_wm
        )
        with jax.named_scope("dv3/optimizer"):
            wm_grads = pmean(wm_grads, axis)
            wm_updates, wm_opt = world_tx.update(wm_grads, opt["world_model"], params["world_model"])
            wm_params = optax.apply_updates(params["world_model"], wm_updates)
            grad_norm_wm = optax.global_norm(wm_grads)
            # what the core trains outside the gradient, from the whole step's load of every expert
            balanced, balance_report = core_module.balance_step(wm_params["core"], psum(load, axis), c)
            wm_params = {**wm_params, "core": balanced}
        if not learn_on:  # 2.7 GB each at the recipe's size: kept only for the learn probes
            del wm_grads, wm_updates
        (actor_loss, aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(
            params["actor"], wm_params, params["critic"], carry, 1.0 - data["dones"],
            agent_state["moments"], k_img,
        )
        with jax.named_scope("dv3/optimizer"):
            actor_grads = pmean(actor_grads, axis)
            actor_updates, actor_opt = actor_tx.update(actor_grads, opt["actor"], params["actor"])
            actor_params = optax.apply_updates(params["actor"], actor_updates)
        critic_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic"], target, aux["trajectories"], aux["lambda_values"], aux["discount"]
        )
        with jax.named_scope("dv3/optimizer"):
            critic_grads = pmean(critic_grads, axis)
            critic_updates, critic_opt = critic_tx.update(critic_grads, opt["critic"], params["critic"])
            critic_params = optax.apply_updates(params["critic"], critic_updates)
        metrics = dict(wm_metrics)
        metrics.update({k: v for k, v in aux.items() if k not in ("trajectories", "lambda_values", "discount", "moments")})
        metrics["Loss/value_loss"] = critic_loss
        metrics.update({f"Core/{name}": value for name, value in balance_report.items()})
        with jax.named_scope("dv3/optimizer"):
            metrics["Grads/world_model"] = grad_norm_wm
            metrics["Grads/actor"] = optax.global_norm(actor_grads)
            metrics["Grads/critic"] = optax.global_norm(critic_grads)
            metrics = pmean(metrics, axis)
        if learn_on:
            metrics.update(
                learn_probes(
                    {"world_model": wm_grads, "actor": actor_grads, "critic": critic_grads},
                    params={k: params[k] for k in ("world_model", "actor", "critic")},
                    updates={"world_model": wm_updates, "actor": actor_updates, "critic": critic_updates},
                    losses=(wm_loss, actor_loss, critic_loss),
                    clip_norms=learn_clips,
                )
            )
        new_state = {
            "params": {"world_model": wm_params, "actor": actor_params, "critic": critic_params, "target_critic": target},
            "opt": {"world_model": wm_opt, "actor": actor_opt, "critic": critic_opt},
            "moments": aux["moments"],
        }
        return new_state, metrics

    return build_train_burst(local_step, fabric, n_scanned=2, plan=None, metric_mode="mean")


#: train-step metrics that are counts of the sequence core, summed into the run counters.
#: ``held_pairs``/``experts_hit`` are the window pass's (pairs routed to held experts and
#: held experts with at least one pair, over the layers), ``imagination_*`` the same of
#: imagination's one-token steps, over steps and layers. A latent-attention core adds
#: ``attended_pairs`` (query-key pairs inside an episode's segment, a window pass, over
#: the layers), ``decode_context_tokens`` (latent positions the one-token steps attended
#: to) and ``decode_cache_tokens`` (those that had to be read: a row's shared cache once);
#: the delta-rule core adds ``delta_rule_fused_tiles`` (tiles of the chunk-local WY form
#: that the fused kernels built, the three passes of a gradient step; 0 in the XLA form)
#: and ``delta_rule_scan_fused_tiles`` (a head's chunks that the inter-chunk kernels
#: carried, likewise);
#: the convolution-attention core (``lfm2_moe``) reports ``attended_pairs`` of its attention
#: layers and adds ``router_max_load`` (the largest load among *all* the router's outputs,
#: held here or not, the routing layers' maximum, a step's window pass: summed, divide by
#: the steps); a counter the configured core does not report is not in the step's metrics
CORE_COUNTERS = (
    "held_pairs", "experts_hit", "max_load", "dropped_pairs", "episode_ends", "imagination_starts", "decode_steps",
    "imagination_pairs", "imagination_experts_hit", "attended_pairs", "decode_context_tokens", "decode_cache_tokens",
    "delta_rule_fused_tiles", "delta_rule_scan_fused_tiles", "router_max_load",
)
#: train-step metrics that are levels, not counts: the newest burst's value stands.
#: ``expert_bias_abs_max`` (``lfm2_moe``): the largest selection bias in any routing layer
CORE_GAUGES = ("expert_bias_abs_max",)


# ---------------------------------------------------------------------------
# acting on the device
# ---------------------------------------------------------------------------


def build_player(world_model: SeqWorldModel, actor, cfg, n_envs: int):
    """``(init_state, step)`` for :class:`~sheeprl_tpu.envs.rollout.DeviceActor`.

    ``step(params, state, raw_obs, reset, key, expl) -> (action, computed,
    state)``: one env step of every env, two tokens through the core.
    ``params`` is ``{"wm": acting_params(world model), "actor": ...}``, read
    where training left them. ``action`` is one-hot; ``computed`` holds the two
    tokens and the prior over the next observation code that the action
    token's step gave.
    """
    wm, c, core_module = world_model, world_model.core, world_model.core_module
    dtype = wm.dtype
    distribution = resolve_actor_distribution(cfg.distribution.get("type", "auto"), False)
    init_std, min_std, unimix = float(cfg.algo.actor.init_std), float(cfg.algo.actor.min_std), float(cfg.algo.unimix)

    def init_state():
        return core_module.init_state(c, n_envs, 1, None, dtype)

    def step(params, state, raw_obs, reset, key, expl_amount):
        p = params["wm"]
        obs = normalize_obs_jnp(raw_obs, wm.cnn_keys)
        k_z, k_a, k_e = jax.random.split(key, 3)
        post = wm.posterior_logits(p, obs)
        z = jax.random.categorical(k_z, post, -1).astype(jnp.int32)
        state = core_module.reset_state(state, reset.reshape(n_envs, 1) > 0)
        h, state, _ = core_module.decode(p["core"], state, z[:, None], c, dtype, scope=CORE_SCOPE)
        feat = jnp.concatenate([core_module.embed(p["core"], z), h[:, 0]], -1)
        pre = actor.apply({"params": params["actor"]}, feat)
        dists = build_actor_dists(pre, False, distribution, init_std, min_std, unimix)
        actions = sample_actor_actions(dists, False, k_a, True)
        actions = add_exploration_noise(actions, expl_amount, False, k_e)
        a = (wm.codes + jnp.argmax(actions[0], -1)).astype(jnp.int32)
        h_a, state, _ = core_module.decode(p["core"], state, a[:, None], c, dtype, scope=CORE_SCOPE)
        prior = wm.prior_logits(p, h_a[:, 0])
        return actions[0], {"tokens": jnp.stack([z, a], -1), "prior_logits": prior}, state

    return init_state, step
