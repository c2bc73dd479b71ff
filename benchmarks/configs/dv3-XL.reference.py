"""Plain reference for DreamerV3 (arXiv:2301.04104), one whole gradient step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no flax modules, no optax, no shard_map, no burst
engine, no bf16 — and no import of ``sheeprl_tpu``. It follows the paper's
equations and the reference implementation's layer layout (sheeprl
``algos/dreamer_v3``): CNN encoder + symlog MLP encoder, RSSM with a
LayerNorm-GRU and 32x32 categorical latents with 1 % unimix, pixel decoder,
two-hot reward head, Bernoulli continue head, KL balancing with free bits,
15-step imagination, percentile-normalised lambda-returns, REINFORCE actor
with entropy bonus, two-hot critic with an EMA target regulariser, and three
Adam optimisers behind global-norm clipping.

What it shares with the program under test is only the interface a checkpoint
has: the parameter tree's leaf names and shapes (``param_shapes``), and the
order in which a step consumes its random key (``train_step``), because the
program's batch, key and target-EMA coefficient are this reference's inputs.
The weights come from the benchmark's own generator, never from the program.

``mode`` selects the arithmetic of every matrix product and convolution:
``f32`` (the reference), ``bf16`` and ``fp8`` (operands rounded to that type,
products and sums in float32). ``fp8`` is the control of ``bf16-mixed`` cells: the
nearest precision below the one the configuration states.

Departures from the published description, each also the program's: the
recipe feeds the scalar reward to the encoder as an observation (``mlp``
encoder key) and builds a vector decoder for it that no loss term reads.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sg = jax.lax.stop_gradient
LN_EPS = 1e-3
TWOHOT_LOW, TWOHOT_HIGH = -20.0, 20.0
_TRUNC_STD_FACTOR = 0.87962566103423978  # std of a unit normal truncated at +-2
MODULES = ("world_model", "actor", "critic")


# ---------------------------------------------------------------------------
# parameter tree: names and shapes from the configuration's sizes
# ---------------------------------------------------------------------------


def param_shapes(s: dict) -> Dict[str, Tuple[int, ...]]:
    """Every parameter leaf, ``module/path/name -> shape``, from the sizes."""
    out: Dict[str, Tuple[int, ...]] = {}
    stages = int(np.log2(s["screen_size"])) - 2
    mult, units, layers = s["cnn_channels_multiplier"], s["dense_units"], s["mlp_layers"]
    rec, hid = s["recurrent_state_size"], s["hidden_size"]
    stoch = s["stochastic_size"] * s["discrete_size"]
    act = s["actions"]
    latent = stoch + rec
    chans = [mult * 2**i for i in range(stages)]
    base = s["screen_size"] >> stages
    embed = base * base * chans[-1] + units  # cnn features + the reward's mlp features

    def ln(prefix, n):
        out[f"{prefix}/scale"] = (n,)
        out[f"{prefix}/bias"] = (n,)

    def mlp(prefix, n_in, n_layers=layers, width=units):
        for i in range(n_layers):
            out[f"{prefix}/MLP_0/Dense_{i}/kernel"] = (n_in if i == 0 else width, width)
            ln(f"{prefix}/MLP_0/LayerNorm_{i}", width)

    def head(prefix, n_in, n_out):
        out[f"{prefix}/kernel"] = (n_in, n_out)
        out[f"{prefix}/bias"] = (n_out,)

    wm = "world_model"
    c_in = s["image_channels"]
    for i, c in enumerate(chans):
        out[f"{wm}/encoder/cnn_encoder/CNN_0/Conv_{i}/kernel"] = (4, 4, c_in, c)
        ln(f"{wm}/encoder/cnn_encoder/CNN_0/LayerNorm_{i}", c)
        c_in = c
    mlp(f"{wm}/encoder/mlp_encoder", 1)
    mlp(f"{wm}/rssm/recurrent_model", stoch + act, n_layers=1)
    out[f"{wm}/rssm/recurrent_model/gru/Dense_0/kernel"] = (rec + units, 3 * rec)
    ln(f"{wm}/rssm/recurrent_model/gru/LayerNorm_0", 3 * rec)
    out[f"{wm}/rssm/representation_model/trunk_kernel"] = (rec + embed, hid)
    ln(f"{wm}/rssm/representation_model/trunk_ln", hid)
    head(f"{wm}/rssm/representation_model/head", hid, stoch)
    mlp(f"{wm}/rssm/transition_model", rec, n_layers=1, width=hid)
    head(f"{wm}/rssm/transition_model/head", hid, stoch)
    head(f"{wm}/cnn_decoder/Dense_0", latent, chans[-1] * base * base)
    c_in = chans[-1]
    for i, c in enumerate(reversed(chans[:-1])):
        out[f"{wm}/cnn_decoder/DeCNN_0/ConvTranspose_{i}/kernel"] = (4, 4, c, c_in)
        ln(f"{wm}/cnn_decoder/DeCNN_0/LayerNorm_{i}", c)
        c_in = c
    out[f"{wm}/cnn_decoder/head/ConvTranspose_0/kernel"] = (4, 4, s["image_channels"], c_in)
    out[f"{wm}/cnn_decoder/head/ConvTranspose_0/bias"] = (s["image_channels"],)
    mlp(f"{wm}/mlp_decoder", latent)  # built by the recipe, read by no loss term
    head(f"{wm}/mlp_decoder/head_reward", units, 1)
    mlp(f"{wm}/reward_model", latent)
    head(f"{wm}/reward_model/head", units, s["bins"])
    mlp(f"{wm}/continue_model", latent)
    head(f"{wm}/continue_model/head", units, 1)
    mlp("actor", latent)
    head("actor/head_0", units, act)
    for c in ("critic", "target_critic"):
        mlp(c, latent)
        head(f"{c}/head", units, s["bins"])
    return out


#: output layers that start at zero, so rewards and values start at 0
ZERO_KERNELS = ("world_model/reward_model/head/kernel", "critic/head/kernel", "target_critic/head/kernel")


def _fan_mean(shape) -> float:
    if len(shape) == 4:
        space = shape[0] * shape[1]
        return space * (shape[2] + shape[3]) / 2.0
    return (shape[0] + shape[1]) / 2.0


def make_weights(shapes: Dict[str, Tuple[int, ...]], seed: int) -> Dict[str, jax.Array]:
    """The benchmark's weights from the seed (trace it inside one ``jit``).

    Kernels: normal truncated at two sigma with variance ``1 / mean(fan_in,
    fan_out)``; norm scales one, biases zero, the reward and value output
    kernels zero, the target critic a copy of the critic. Counter-based keys
    (threefry), one per leaf from a checksum of its name, so the draw depends
    on neither the leaf order nor the backend."""
    root = jax.random.key(seed, impl="threefry2x32")
    out = {}
    for name, shape in shapes.items():
        source = name.replace("target_critic/", "critic/", 1)
        if name.endswith("scale"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("bias") or name in ZERO_KERNELS:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            key = jax.random.fold_in(root, zlib.crc32(source.encode()) & 0x7FFFFFFF)
            std = (1.0 / _fan_mean(shape)) ** 0.5 / _TRUNC_STD_FACTOR
            out[name] = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _round(x, mode):
    """``x`` rounded to the mode's type, as float32; the gradient passes
    straight through, so that small cotangents do not underflow in fp8."""
    if mode == "f32":
        return x
    low = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[mode]
    return x + sg(x.astype(low).astype(jnp.float32) - x)


def matmul(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode), precision=jax.lax.Precision.HIGHEST)


def conv(x, w, mode):
    """4x4 convolution, stride 2, padding 1, NHWC."""
    return jax.lax.conv_general_dilated(
        _round(x, mode), _round(w, mode), (2, 2), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=jax.lax.Precision.HIGHEST,
    )


def conv_transpose(x, w, mode):
    """4x4 transposed convolution, stride 2, padding 1 (doubles H and W)."""
    return jax.lax.conv_transpose(
        _round(x, mode), _round(w, mode), (2, 2), ((2, 2), (2, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), transpose_kernel=True,
        precision=jax.lax.Precision.HIGHEST,
    )


def layer_norm(p, prefix, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p[f"{prefix}/scale"] + p[f"{prefix}/bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def mlp(p, prefix, x, n_layers, mode):
    for i in range(n_layers):
        x = matmul(x, p[f"{prefix}/MLP_0/Dense_{i}/kernel"], mode)
        x = silu(layer_norm(p, f"{prefix}/MLP_0/LayerNorm_{i}", x))
    return x


def head(p, prefix, x, mode):
    return matmul(x, p[f"{prefix}/kernel"], mode) + p[f"{prefix}/bias"]


def trunk_and_head(p, prefix, x, n_layers, mode):
    return head(p, f"{prefix}/head", mlp(p, prefix, x, n_layers, mode), mode)


# ---------------------------------------------------------------------------
# world model
# ---------------------------------------------------------------------------


def encode(p, s, rgb, reward_obs, mode):
    """``rgb`` [..., C, H, W] in [0, 1], ``reward_obs`` [..., 1] -> features."""
    lead = rgb.shape[:-3]
    x = jnp.transpose(rgb.reshape((-1,) + rgb.shape[-3:]), (0, 2, 3, 1))
    stages = int(np.log2(s["screen_size"])) - 2
    pre = "world_model/encoder/cnn_encoder/CNN_0"
    for i in range(stages):
        x = conv(x, p[f"{pre}/Conv_{i}/kernel"], mode)
        x = silu(layer_norm(p, f"{pre}/LayerNorm_{i}", x))
    x = x.reshape(lead + (-1,))
    y = mlp(p, "world_model/encoder/mlp_encoder", symlog(reward_obs), s["mlp_layers"], mode)
    return jnp.concatenate([x, y], -1)


def unimix_logits(logits, s):
    """Flat logits -> log of (99 % softmax + 1 % uniform), [..., S, D]."""
    logits = logits.reshape(logits.shape[:-1] + (s["stochastic_size"], s["discrete_size"]))
    probs = jax.nn.softmax(logits, -1)
    probs = (1.0 - s["unimix"]) * probs + s["unimix"] / s["discrete_size"]
    return jnp.log(probs)


def sample_latent(logits, gumbel):
    """Straight-through sample of the categorical latent, flat."""
    d = logits.shape[-1]
    one = jax.nn.one_hot(jnp.argmax(logits + gumbel, -1), d, dtype=logits.dtype)
    probs = jax.nn.softmax(logits, -1)
    out = one + probs - sg(probs)
    return out.reshape(out.shape[:-2] + (-1,))


def recurrent_step(p, s, stoch, action, h, mode):
    pre = "world_model/rssm/recurrent_model"
    feat = mlp(p, pre, jnp.concatenate([stoch, action], -1), 1, mode)
    z = matmul(jnp.concatenate([h, feat], -1), p[f"{pre}/gru/Dense_0/kernel"], mode)
    reset, cand, update = jnp.split(layer_norm(p, f"{pre}/gru/LayerNorm_0", z), 3, -1)
    cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
    update = jax.nn.sigmoid(update - 1.0)
    return update * cand + (1.0 - update) * h


def prior_logits(p, s, h, mode):
    return unimix_logits(trunk_and_head(p, "world_model/rssm/transition_model", h, 1, mode), s)


def posterior_logits(p, s, h, embed_proj, mode):
    pre = "world_model/rssm/representation_model"
    rec = s["recurrent_state_size"]
    x = matmul(h, p[f"{pre}/trunk_kernel"][:rec], mode) + embed_proj
    x = silu(layer_norm(p, f"{pre}/trunk_ln", x))
    return unimix_logits(head(p, f"{pre}/head", x, mode), s)


def decode_pixels(p, s, latent, mode):
    lead = latent.shape[:-1]
    stages = int(np.log2(s["screen_size"])) - 2
    base = s["screen_size"] >> stages
    x = head(p, "world_model/cnn_decoder/Dense_0", latent, mode)
    x = jnp.transpose(x.reshape((-1, x.shape[-1] // (base * base), base, base)), (0, 2, 3, 1))
    pre = "world_model/cnn_decoder/DeCNN_0"
    for i in range(stages - 1):
        x = conv_transpose(x, p[f"{pre}/ConvTranspose_{i}/kernel"], mode)
        x = silu(layer_norm(p, f"{pre}/LayerNorm_{i}", x))
    pre = "world_model/cnn_decoder/head/ConvTranspose_0"
    x = conv_transpose(x, p[f"{pre}/kernel"], mode) + p[f"{pre}/bias"]
    x = jnp.transpose(x, (0, 3, 1, 2))
    return x.reshape(lead + x.shape[1:]) + 0.5


def twohot_bins(n):
    return jnp.linspace(TWOHOT_LOW, TWOHOT_HIGH, n, dtype=jnp.float32)


def twohot_mean(logits):
    value = jnp.sum(jax.nn.softmax(logits, -1) * twohot_bins(logits.shape[-1]), -1, keepdims=True)
    return symexp(value)


def twohot_log_prob(logits, value):
    """Cross-entropy of ``logits`` against the two-hot code of symlog(value)."""
    n = logits.shape[-1]
    step = (TWOHOT_HIGH - TWOHOT_LOW) / (n - 1)
    pos = (jnp.clip(symlog(value)[..., 0], TWOHOT_LOW, TWOHOT_HIGH) - TWOHOT_LOW) / step
    above = jnp.clip(jnp.ceil(pos).astype(jnp.int32), 1, n - 1)
    below = above - 1
    w_above = jnp.clip(pos - below, 0.0, 1.0)
    target = (
        jax.nn.one_hot(below, n) * (1.0 - w_above)[..., None]
        + jax.nn.one_hot(above, n) * w_above[..., None]
    )
    return jnp.sum(target * jax.nn.log_softmax(logits, -1), -1)


def categorical_kl(p_logits, q_logits):
    return jnp.sum(jnp.exp(p_logits) * (p_logits - q_logits), (-2, -1))


def world_model_loss(wm, s, batch, key, mode):
    T, B = batch["rewards"].shape[:2]
    S, D, rec = s["stochastic_size"], s["discrete_size"], s["recurrent_state_size"]
    rgb = batch["rgb"].astype(jnp.float32) / 255.0
    is_first = batch["is_first"].at[0].set(1.0)
    prev_actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
    embedded = encode(wm, s, rgb, batch["reward"], mode)
    embed_proj = matmul(embedded, wm["world_model/rssm/representation_model/trunk_kernel"][rec:], mode)
    init_logits = prior_logits(wm, s, jnp.zeros((1, rec)), mode)
    init_post = jax.nn.one_hot(jnp.argmax(init_logits, -1), D).reshape(1, S * D)

    def step(carry, inp):
        post, h = carry
        action, eproj, first, g = inp
        action, h = (1.0 - first) * action, (1.0 - first) * h
        post = (1.0 - first) * post + first * init_post
        h = recurrent_step(wm, s, post, action, h, mode)
        logits = posterior_logits(wm, s, h, eproj, mode)
        post = sample_latent(logits, g)
        return (post, h), (h, post, logits)

    gumbels = jax.random.gumbel(key, (T, B, S, D))
    _, (hs, posts, post_logits) = jax.lax.scan(
        step, (jnp.zeros((B, S * D)), jnp.zeros((B, rec))), (prev_actions, embed_proj, is_first, gumbels)
    )
    pri_logits = prior_logits(wm, s, hs, mode)
    latents = jnp.concatenate([posts, hs], -1)
    recon = decode_pixels(wm, s, latents, mode)
    observation_loss = jnp.sum(jnp.square(recon - rgb), (-3, -2, -1))
    reward_logits = trunk_and_head(wm, "world_model/reward_model", latents, s["mlp_layers"], mode)
    reward_loss = -twohot_log_prob(reward_logits, batch["rewards"])
    cont_logits = trunk_and_head(wm, "world_model/continue_model", latents, s["mlp_layers"], mode)
    cont_target = 1.0 - batch["dones"]
    continue_loss = jnp.sum(
        jax.nn.softplus(-cont_logits) * cont_target + jax.nn.softplus(cont_logits) * (1.0 - cont_target), -1
    )
    dyn = s["kl_dynamic"] * jnp.maximum(categorical_kl(sg(post_logits), pri_logits), s["kl_free_nats"])
    rep = s["kl_representation"] * jnp.maximum(categorical_kl(post_logits, sg(pri_logits)), s["kl_free_nats"])
    loss = jnp.mean(
        s["kl_regularizer"] * (dyn + rep) + observation_loss + reward_loss
        + s["continue_scale_factor"] * continue_loss
    )
    return loss, (sg(posts), sg(hs))


# ---------------------------------------------------------------------------
# behaviour: imagination, actor, critic
# ---------------------------------------------------------------------------


def actor_logits(actor, s, latent, mode):
    """Log of the unimixed action probabilities."""
    logits = trunk_and_head_actor(actor, latent, s, mode)
    probs = jax.nn.softmax(logits, -1)
    probs = (1.0 - s["unimix"]) * probs + s["unimix"] / probs.shape[-1]
    return jax.nn.log_softmax(jnp.log(probs), -1)


def trunk_and_head_actor(actor, latent, s, mode):
    return head(actor, "actor/head_0", mlp(actor, "actor", latent, s["mlp_layers"], mode), mode)


def sample_action(logp, key):
    """Straight-through sample; ``key`` is split once per action head (one)."""
    k = jax.random.split(key, 1)[0]
    idx = jax.random.categorical(k, logp, axis=-1, shape=logp.shape[:-1])
    probs = jnp.exp(logp)
    return jax.nn.one_hot(idx, logp.shape[-1], dtype=logp.dtype) + probs - sg(probs)


def lambda_returns(rewards, values, continues, lmbda):
    interm = rewards + continues * values * (1.0 - lmbda)

    def step(nxt, inp):
        interm_t, cont_t = inp
        val = interm_t + cont_t * lmbda * nxt
        return val, val

    return jax.lax.scan(step, values[-1], (interm, continues), reverse=True)[1]


def imagine(wm, actor, s, posts, hs, key, mode):
    H = s["horizon"]
    S, D = s["stochastic_size"], s["discrete_size"]
    prior = posts.reshape(-1, S * D)
    h = hs.reshape(-1, s["recurrent_state_size"])
    latent0 = jnp.concatenate([prior, h], -1)

    def policy(latent, k):
        return sample_action(actor_logits(actor, s, sg(latent), mode), k)

    k0, key = jax.random.split(key)
    a0 = policy(latent0, k0)
    k_gum, key = jax.random.split(key)
    gumbels = jax.random.gumbel(k_gum, (H, prior.shape[0], S, D))
    keys = jax.random.split(key, H)

    def step(carry, inp):
        prior, h, action = carry
        g, k = inp
        h = recurrent_step(wm, s, prior, action, h, mode)
        prior = sample_latent(prior_logits(wm, s, h, mode), g)
        latent = jnp.concatenate([prior, h], -1)
        action = policy(latent, k)
        return (prior, h, action), (latent, action)

    _, (latents, acts) = jax.lax.scan(step, (prior, h, a0), (gumbels, keys))
    return jnp.concatenate([latent0[None], latents], 0), jnp.concatenate([a0[None], acts], 0)


def imagined_returns(actor, wm, critic, s, posts, hs, true_continue, key, mode):
    """Trajectories, actions, lambda-returns, baseline values and discount."""
    traj, actions = imagine(wm, actor, s, posts, hs, key, mode)
    values = twohot_mean(trunk_and_head(critic, "critic", traj, s["mlp_layers"], mode))
    rewards = twohot_mean(trunk_and_head(wm, "world_model/reward_model", traj, s["mlp_layers"], mode))
    cont_logits = trunk_and_head(wm, "world_model/continue_model", traj, s["mlp_layers"], mode)
    continues = jnp.concatenate([true_continue[None], (cont_logits > 0).astype(jnp.float32)[1:]], 0)
    lam = lambda_returns(rewards[1:], values[1:], continues[1:] * s["gamma"], s["lmbda"])
    discount = sg(jnp.cumprod(continues * s["gamma"], 0) / s["gamma"])
    return traj, actions, lam, values, discount


def actor_loss(actor, wm, critic, s, posts, hs, true_continue, low_high, key, mode):
    """``low_high``: the return percentiles after this step's moment update."""
    traj, actions, lam, values, discount = imagined_returns(
        actor, wm, critic, s, posts, hs, true_continue, key, mode
    )
    low, high = low_high
    invscale = jnp.maximum(1.0 / s["moments_max"], high - low)
    advantage = (lam - low) / invscale - (values[:-1] - low) / invscale
    logp = actor_logits(actor, s, sg(traj), mode)
    log_prob = jnp.sum(sg(actions) * logp, -1)[..., None][:-1]
    entropy = -jnp.sum(jnp.exp(logp) * logp, -1)[..., None][:-1]
    objective = log_prob * sg(advantage)
    loss = -jnp.mean(discount[:-1] * (objective + s["ent_coef"] * entropy))
    return loss, (sg(traj), sg(lam), discount)


def critic_loss(critic, target, s, traj, lam, discount, mode):
    logits = trunk_and_head(critic, "critic", traj[:-1], s["mlp_layers"], mode)
    target_logits = trunk_and_head(
        {k.replace("target_critic/", "critic/", 1): v for k, v in target.items()},
        "critic", traj[:-1], s["mlp_layers"], mode,
    )
    target_values = twohot_mean(target_logits)
    loss = -twohot_log_prob(logits, lam) - twohot_log_prob(logits, sg(target_values))
    return jnp.mean(loss * discount[:-1, ..., 0])


# ---------------------------------------------------------------------------
# optimiser
# ---------------------------------------------------------------------------


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return {k: g * scale for k, g in grads.items()}


def adam(params, grads, opt, hp):
    """One Adam step on global-norm-clipped gradients; returns the clipped
    gradients too (what the optimiser was given)."""
    grads = clip_by_global_norm(grads, hp["clip"])
    b1, b2 = hp["betas"]
    t = opt["t"] + 1
    mu = {k: b1 * opt["mu"][k] + (1.0 - b1) * g for k, g in grads.items()}
    nu = {k: b2 * opt["nu"][k] + (1.0 - b2) * jnp.square(g) for k, g in grads.items()}
    c1, c2 = 1.0 - b1**t, 1.0 - b2**t
    new = {
        k: params[k] - hp["lr"] * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + hp["eps"]) for k in params
    }
    return new, {"t": t, "mu": mu, "nu": nu}, grads


def init_state(shapes, seed) -> dict:
    params = make_weights(shapes, seed)
    opt = {}
    for m in MODULES:
        mine = {k: jnp.zeros_like(v) for k, v in params.items() if k.startswith(m + "/")}
        opt[m] = {"t": jnp.zeros((), jnp.float32), "mu": mine, "nu": dict(mine)}
    return {"params": params, "opt": opt, "low": jnp.zeros(()), "high": jnp.zeros(())}


# ---------------------------------------------------------------------------
# one gradient step, over the shards of a data-parallel batch
# ---------------------------------------------------------------------------


def freeze(tree):
    """A nested dict of sizes as a hashable tuple (a static ``jit`` argument)."""
    if isinstance(tree, dict):
        return tuple(sorted((k, freeze(v)) for k, v in tree.items()))
    if isinstance(tree, list):
        return tuple(freeze(v) for v in tree)
    return tree


def thaw(items) -> dict:
    return {
        k: thaw(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v
        for k, v in items
    }


def _mean(trees):
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, 0), trees)


@partial(jax.jit, static_argnames=("sizes", "n_shards", "mode", "exchange"), donate_argnums=(0,))
def train_step(state, batch, key_data, tau, *, sizes, n_shards=1, mode="f32", exchange=True):
    """One DreamerV3 gradient step on ``batch`` ([T, B, ...], B split evenly
    over ``n_shards`` data-parallel shards, each drawing its noise from the
    key folded with its index; gradients and return percentiles are taken
    over all shards). ``sizes`` is the configuration's hashable sizes tuple.
    ``exchange=False`` plants the fault of a left-out gradient exchange: the
    update uses shard 0's gradients alone.

    Returns ``(state, report)``; the report holds the three losses and the
    per-leaf norms of the gradients the optimisers were given."""
    s = thaw(sizes)
    params = state["params"]
    split = lambda m: {k: v for k, v in params.items() if k.startswith(m + "/")}
    wm, actor, critic, target = (split(m) for m in MODULES + ("target_critic",))
    target = {
        k: tau * critic[k.replace("target_critic/", "critic/", 1)] + (1.0 - tau) * v
        for k, v in target.items()
    }
    key = jax.random.wrap_key_data(key_data, impl=s["prng_impl"])
    shard_ids = jnp.arange(n_shards)
    shards = jax.tree_util.tree_map(
        lambda x: jnp.moveaxis(x.reshape(x.shape[:1] + (n_shards, -1) + x.shape[2:]), 1, 0), batch
    )
    shard_keys = jax.vmap(lambda i: jax.random.split(jax.random.fold_in(key, i)))(shard_ids)
    reduce = _mean if exchange else (lambda t: jax.tree_util.tree_map(lambda x: x[0], t))

    def wm_shard(args):
        b, ks = args
        (loss, (posts, hs)), g = jax.value_and_grad(world_model_loss, has_aux=True)(wm, s, b, ks[0], mode)
        return loss, posts, hs, g

    wm_losses, posts, hs, wm_grads = jax.lax.map(wm_shard, (shards, shard_keys))
    new_wm, wm_opt, wm_given = adam(wm, reduce(wm_grads), state["opt"]["world_model"], s["optim"]["world_model"])

    def returns_shard(args):
        b, ks, po, h = args
        cont = (1.0 - b["dones"]).reshape(-1, 1)
        return imagined_returns(actor, new_wm, critic, s, po, h, cont, ks[1], mode)[2]

    lam_all = jax.lax.map(returns_shard, (shards, shard_keys, posts, hs))
    decay = s["moments_decay"]
    low = decay * state["low"] + (1.0 - decay) * jnp.quantile(lam_all, s["moments_low"])
    high = decay * state["high"] + (1.0 - decay) * jnp.quantile(lam_all, s["moments_high"])

    def behaviour_shard(args):
        b, ks, po, h = args
        cont = (1.0 - b["dones"]).reshape(-1, 1)
        (a_loss, (traj, lam, disc)), a_g = jax.value_and_grad(actor_loss, has_aux=True)(
            actor, new_wm, critic, s, po, h, cont, (low, high), ks[1], mode
        )
        c_loss, c_g = jax.value_and_grad(critic_loss)(critic, target, s, traj, lam, disc, mode)
        return a_loss, a_g, c_loss, c_g

    a_losses, a_grads, c_losses, c_grads = jax.lax.map(behaviour_shard, (shards, shard_keys, posts, hs))
    new_actor, actor_opt, actor_given = adam(actor, reduce(a_grads), state["opt"]["actor"], s["optim"]["actor"])
    new_critic, critic_opt, critic_given = adam(critic, reduce(c_grads), state["opt"]["critic"], s["optim"]["critic"])

    given = {**wm_given, **actor_given, **critic_given}
    report = {
        "Loss/world_model_loss": jnp.mean(wm_losses),
        "Loss/policy_loss": jnp.mean(a_losses),
        "Loss/value_loss": jnp.mean(c_losses),
        "grad_norms": {k: jnp.sqrt(jnp.sum(jnp.square(g))) for k, g in given.items()},
    }
    new_state = {
        "params": {**new_wm, **new_actor, **new_critic, **target},
        "opt": {"world_model": wm_opt, "actor": actor_opt, "critic": critic_opt},
        "low": low,
        "high": high,
    }
    return new_state, report


@partial(jax.jit, static_argnames=("shapes_items",))
def change_norms(params, seed, shapes_items):
    """Per-leaf norm of ``params - make_weights(seed)``."""
    start = make_weights(dict(shapes_items), seed)
    return {k: jnp.sqrt(jnp.sum(jnp.square(params[k] - start[k]))) for k in params}
