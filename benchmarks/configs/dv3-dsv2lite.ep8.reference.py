"""Plain reference for DreamerV3 over a DeepSeek-V2 sequence core, one whole
gradient step, and the core's forward pass alone.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no flax, no optax, no burst engine, no kernels, no bf16,
no cache — and no import of ``sheeprl_tpu``. The core follows the published
implementation of the family (``model_type: deepseek_v2``, ``q_lora_rank``
null; source:
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json):

- stack: embedding, pre-norm residual layers (``x += Attn(norm(x))``, ``x +=
  MLP(norm(x))``; RMSNorm with a plain weight, eps 1e-6), a final norm, an
  untied head. Layer 0 (``first_k_dense_replace`` 1) has a SwiGLU MLP of
  ``intermediate_size``, the others the expert layer.
- attention (multi-head latent attention), **never absorbed**: ``q = x W_Q`` a
  head ``[q_nope | q_rope]``; ``[c | k_rope] = x W_DKV``, ``c <- RMSNorm(c)``;
  every head's ``[k_nope | v] = c W_UKV`` is built for every position; rotary
  on ``q_rope`` and on the one ``k_rope`` all heads share; ``score = (q_nope .
  k_nope + q_rope . k_rope) s``; causal softmax; ``y = concat(sum p v) W_O``.
- YaRN: the rotary frequencies are, per pair ``i``, the blend of
  ``theta^(-2i/d)`` and that over ``factor`` by the linear ramp between the
  correction dims of ``beta_fast`` and ``beta_slow`` rotations over
  ``original_max_position_embeddings``; cos and sin times
  ``m(factor, mscale) / m(factor, mscale_all_dim)``; ``s = (d_nope +
  d_rope)^-1/2 m(factor, mscale_all_dim)^2`` with ``m(f, a) = 0.1 a ln f + 1``.
  **Rotary pair layout:** pairs are ``(i, i + d/2)`` of the rotary columns as
  they stand (``rotate_half``); the published code reads ``W_Q``'s and
  ``W_DKV``'s rotary columns interleaved and permutes them to this layout
  first: a permutation of those columns, the same model on seeded weights.
- experts: softmax over all the router's outputs, the ``k`` largest (greedy)
  with their probabilities as they are (``norm_topk_prob`` false) times
  ``routed_scaling_factor``; *dense per-expert products* for the experts held
  (every token through every held expert, weighted by its routing weight or
  0); the ``n_shared_experts`` always-on experts as one MLP of their joint
  width, no gate. What absent experts would add is left out. The balance term
  is sequence-wise (``seq_aux``): per window row ``sum_e f_e P_e``, ``f_e`` the
  row's choices of ``e`` times ``E / (k T)``, ``P_e`` the row's mean
  probability; every expert layer adds ``aux_loss_alpha`` times its own.

Departures, each also the program's: ``aux_loss_alpha`` is assumed (0.001, the
family's published value; not in the catalog's config); an episode's first
token masks attention to the episode's own segment and restarts rotary
positions (packing); no multi-token prediction. Imagination keeps the per-head
keys and values its own steps have computed in a list (one slot a step) beside
the window pass's per-head keys and values: recomputing thirty prefixes of a
thousand tokens would cost the reference petaflops.
:func:`core_forward` — what ``correct`` holds acting's absorbed one-token path
to — keeps nothing: it is the full, un-absorbed forward pass of the same
tokens.

``mode`` as in the other references: ``f32``, or ``bf16``/``fp8`` (operands of
every product rounded to that type; ``fp8`` is the control of ``bf16-mixed``).
``held=False`` plants the fault of an expert layer that leaves its held
experts out.
"""

from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sg = jax.lax.stop_gradient
HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-3
TWOHOT_LOW, TWOHOT_HIGH = -20.0, 20.0
_TRUNC_STD_FACTOR = 0.87962566103423978  # std of a unit normal truncated at +-2
MODULES = ("world_model", "actor", "critic")
CORE = "world_model/core"
ROW_BLOCK = 1  # window rows the world-model pass takes at a time
#: the reference is compiled once a run and executed three times: the compiler
#: is told to spend no effort on speed or on fitting memory (a quarter of a
#: minute a program at the published widths, against four minutes)
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0, "memory_fitting_effort": -1.0}


# ---------------------------------------------------------------------------
# parameter tree
# ---------------------------------------------------------------------------


def is_dense(s: dict, layer: int) -> bool:
    return layer < s["first_k_dense_replace"]


def param_shapes(s: dict) -> Dict[str, Tuple[int, ...]]:
    out: Dict[str, Tuple[int, ...]] = {}
    stages = int(np.log2(s["screen_size"])) - 2
    mult, units, layers = s["cnn_channels_multiplier"], s["dense_units"], s["mlp_layers"]
    D, V = s["hidden_size"], s["vocab_size"]
    feat = 2 * D
    chans = [mult * 2**i for i in range(stages)]
    base = s["screen_size"] >> stages

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
    mlp(f"{wm}/posterior", base * base * chans[-1], n_layers=1, width=s["posterior_hidden_size"])
    head(f"{wm}/posterior/head", s["posterior_hidden_size"], s["discrete_size"])
    head(f"{wm}/cnn_decoder/Dense_0", feat, chans[-1] * base * base)
    c_in = chans[-1]
    for i, c in enumerate(reversed(chans[:-1])):
        out[f"{wm}/cnn_decoder/DeCNN_0/ConvTranspose_{i}/kernel"] = (4, 4, c, c_in)
        ln(f"{wm}/cnn_decoder/DeCNN_0/LayerNorm_{i}", c)
        c_in = c
    out[f"{wm}/cnn_decoder/head/ConvTranspose_0/kernel"] = (4, 4, s["image_channels"], c_in)
    out[f"{wm}/cnn_decoder/head/ConvTranspose_0/bias"] = (s["image_channels"],)
    mlp(f"{wm}/reward_model", feat)
    head(f"{wm}/reward_model/head", units, s["bins"])
    mlp(f"{wm}/continue_model", feat)
    head(f"{wm}/continue_model/head", units, 1)
    mlp("actor", feat)
    head("actor/head_0", units, s["actions"])
    for c in ("critic", "target_critic"):
        mlp(c, feat)
        head(f"{c}/head", units, s["bins"])

    H, r = s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    E, Eh, F = s["router_outputs"], s["num_experts"], s["moe_intermediate_size"]
    Fs = s["n_shared_experts"] * F
    out[f"{CORE}/embed"] = (V, D)
    out[f"{CORE}/final_norm"] = (D,)
    out[f"{CORE}/head"] = (D, V)
    for l in range(s["num_hidden_layers"]):
        pre = f"{CORE}/layers_{l}"
        out.update({
            f"{pre}/input_norm": (D,), f"{pre}/post_norm": (D,),
            f"{pre}/mla/q": (D, H * (dn + dr)), f"{pre}/mla/dkv": (D, r + dr), f"{pre}/mla/kv_norm": (r,),
            f"{pre}/mla/ukv": (r, H * (dn + dv)), f"{pre}/mla/o": (H * dv, D),
        })
        if is_dense(s, l):
            I = s["intermediate_size"]
            out.update({f"{pre}/mlp/gate": (D, I), f"{pre}/mlp/up": (D, I), f"{pre}/mlp/down": (I, D)})
        else:
            out.update({
                f"{pre}/moe/router": (D, E), f"{pre}/moe/gate": (Eh, D, F), f"{pre}/moe/up": (Eh, D, F),
                f"{pre}/moe/down": (Eh, F, D), f"{pre}/moe/shared_gate": (D, Fs), f"{pre}/moe/shared_up": (D, Fs),
                f"{pre}/moe/shared_down": (Fs, D),
            })
    return out


#: output layers that start at zero, so rewards and values start at 0
ZERO_KERNELS = ("world_model/reward_model/head/kernel", "critic/head/kernel", "target_critic/head/kernel")


def _fan_mean(shape) -> float:
    if len(shape) == 4:
        return shape[0] * shape[1] * (shape[2] + shape[3]) / 2.0
    return (shape[-2] + shape[-1]) / 2.0


def make_weights(shapes: Dict[str, Tuple[int, ...]], seed: int) -> Dict[str, jax.Array]:
    """The benchmark's weights from the seed (trace it inside one ``jit``).

    The agent round the core as in the other configurations: kernels normal
    truncated at two sigma with variance ``1 / mean(fan_in, fan_out)``, norm
    scales one, biases zero, reward and value output kernels zero, the target
    critic a copy of the critic. The core the same rule for its products (an
    expert's fans are its own) and plain norm weights of one. Counter-based
    keys, one a leaf from a checksum of its name."""
    root = jax.random.key(seed, impl="threefry2x32")
    out = {}
    for name, shape in shapes.items():
        source = name.replace("target_critic/", "critic/", 1)
        key = jax.random.fold_in(root, zlib.crc32(source.encode()) & 0x7FFFFFFF)
        if name.endswith("scale") or name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("bias") or name in ZERO_KERNELS:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = (1.0 / _fan_mean(shape)) ** 0.5 / _TRUNC_STD_FACTOR
            out[name] = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# layers of the agent round the core (as in the other reference)
# ---------------------------------------------------------------------------


def _round(x, mode):
    """``x`` rounded to the mode's type, as float32; the gradient passes
    straight through, so that small cotangents do not underflow in fp8."""
    if mode == "f32":
        return x
    low = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[mode]
    return x + sg(x.astype(low).astype(jnp.float32) - x)


def matmul(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode), precision=HI)


def conv(x, w, mode):
    return jax.lax.conv_general_dilated(
        _round(x, mode), _round(w, mode), (2, 2), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
    )


def conv_transpose(x, w, mode):
    return jax.lax.conv_transpose(
        _round(x, mode), _round(w, mode), (2, 2), ((2, 2), (2, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), transpose_kernel=True, precision=HI,
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


def unimix(logits, s):
    """Log of (99 % softmax + 1 % uniform) over the last axis."""
    probs = jax.nn.softmax(logits, -1)
    return jnp.log((1.0 - s["unimix"]) * probs + s["unimix"] / logits.shape[-1])


def posterior_logits(p, s, rgb, mode):
    """``rgb`` [..., C, H, W] in [0, 1] -> unimixed log-probabilities over the codes."""
    lead = rgb.shape[:-3]
    x = jnp.transpose(rgb.reshape((-1,) + rgb.shape[-3:]), (0, 2, 3, 1))
    stages = int(np.log2(s["screen_size"])) - 2
    pre = "world_model/encoder/cnn_encoder/CNN_0"
    for i in range(stages):
        x = conv(x, p[f"{pre}/Conv_{i}/kernel"], mode)
        x = silu(layer_norm(p, f"{pre}/LayerNorm_{i}", x))
    x = x.reshape(lead + (-1,))
    return unimix(trunk_and_head(p, "world_model/posterior", x, 1, mode), s)


def decode_pixels(p, s, feat, mode):
    lead = feat.shape[:-1]
    stages = int(np.log2(s["screen_size"])) - 2
    base = s["screen_size"] >> stages
    x = head(p, "world_model/cnn_decoder/Dense_0", feat, mode)
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
    return jnp.sum(jnp.exp(p_logits) * (p_logits - q_logits), -1)


# ---------------------------------------------------------------------------
# the core
# ---------------------------------------------------------------------------


def rms_norm(x, w, s):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + s["rms_norm_eps"]) * w


def yarn_m(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(s):
    """The 32 rotary frequencies of a 64-wide rotary part under YaRN."""
    y, d, base = s["rope_scaling"], s["qk_rope_head_dim"], float(s["rope_theta"])
    own = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(rotations):
        return d * math.log(y["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(own / y["factor"] * ramp + own * (1.0 - ramp), jnp.float32)


def score_scale(s):
    y = s["rope_scaling"]
    m = yarn_m(y["factor"], y["mscale_all_dim"])
    return (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, pos, s):
    """``x`` [..., d_rope] rotated by ``pos`` (broadcast against the leading axes)."""
    y = s["rope_scaling"]
    ang = pos.astype(jnp.float32)[..., None] * yarn_frequencies(s)
    m = yarn_m(y["factor"], y["mscale"]) / yarn_m(y["factor"], y["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def segments(reset):
    """``[R, L]`` resets -> (segment id, position inside the segment)."""
    L = reset.shape[1]
    seg = jnp.cumsum(reset.astype(jnp.int32), 1)
    idx = jnp.arange(L, dtype=jnp.int32)[None]
    start = jax.lax.cummax(jnp.where(reset > 0, idx, 0), axis=1)
    return seg, idx - start


def dense_mlp(p, pre, x, mode):
    return matmul(silu(matmul(x, p[f"{pre}/gate"], mode)) * matmul(x, p[f"{pre}/up"], mode), p[f"{pre}/down"], mode)


def experts(p, pre, x, s, mode, held=True):
    """``x`` [N, D] -> (the held experts' part plus the shared experts, the
    router's probabilities [N, E], whether each expert was chosen [N, E])."""
    probs = jax.nn.softmax(matmul(x, p[f"{pre}/router"], mode), -1)
    top_p, top_i = jax.lax.top_k(probs, s["num_experts_per_tok"])
    if s["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * s["routed_scaling_factor"]
    E, Eh = s["router_outputs"], s["num_experts"]
    weights = jnp.sum(jax.nn.one_hot(top_i, E) * top_p[..., None], 1)  # [N, E], 0 where not chosen
    chosen = jnp.sum(jax.nn.one_hot(top_i, E), 1)
    out = 0.0
    if held:  # every token through every held expert, weighted by its routing weight or 0
        lo = s["expert_share_index"] * Eh
        xr = _round(x, mode)
        gate = jnp.einsum("nd,edf->enf", xr, _round(p[f"{pre}/gate"], mode), precision=HI)
        up = jnp.einsum("nd,edf->enf", xr, _round(p[f"{pre}/up"], mode), precision=HI)
        each = jnp.einsum("enf,efd->end", _round(silu(gate) * up, mode), _round(p[f"{pre}/down"], mode), precision=HI)
        out = jnp.einsum("end,ne->nd", each, jax.lax.dynamic_slice_in_dim(weights, lo, Eh, 1), precision=HI)
    shared = matmul(silu(matmul(x, p[f"{pre}/shared_gate"], mode)) * matmul(x, p[f"{pre}/shared_up"], mode),
                    p[f"{pre}/shared_down"], mode)
    return out + shared, probs, chosen


def mla_project(p, pre, x, pos, s, mode):
    """Queries, and every head's keys and values, of ``x`` [..., D] at rotary
    positions ``pos`` [...]: ``q, k`` [..., H, d_nope + d_rope], ``v`` [..., H, d_v]."""
    H, r, dn, dv = s["num_attention_heads"], s["kv_lora_rank"], s["qk_nope_head_dim"], s["v_head_dim"]
    lead = x.shape[:-1]
    q = matmul(x, p[f"{pre}/q"], mode).reshape(lead + (H, -1))
    ckr = matmul(x, p[f"{pre}/dkv"], mode)
    c = rms_norm(ckr[..., :r], p[f"{pre}/kv_norm"], s)
    kv = matmul(c, p[f"{pre}/ukv"], mode).reshape(lead + (H, dn + dv))
    k_rope = jnp.broadcast_to(rope(ckr[..., r:], pos, s)[..., None, :], lead + (H, s["qk_rope_head_dim"]))
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos[..., None], s)], -1)
    return q, jnp.concatenate([kv[..., :dn], k_rope], -1), kv[..., dn:]


def mla_window(p, pre, x, reset, s, mode):
    R, L, _ = x.shape
    seg, pos = segments(reset)
    q, k, v = mla_project(p, pre, x, pos, s, mode)
    logits = jnp.einsum("bqhd,bkhd->bhqk", _round(q, mode), _round(k, mode), precision=HI) * score_scale(s)
    idx = jnp.arange(L)
    mask = (idx[:, None] >= idx[None, :])[None] & (seg[:, :, None] == seg[:, None, :])
    w = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(w, mode), _round(v, mode), precision=HI)
    return matmul(o.reshape(R, L, -1), p[f"{pre}/o"], mode), {"k": k, "v": v}


def feed_forward(p, pre, h, s, layer, mode, held):
    """The layer's MLP over ``h`` [N, D]; probabilities and choices where it routes."""
    if is_dense(s, layer):
        return dense_mlp(p, f"{pre}/mlp", h, mode), None, None
    return experts(p, f"{pre}/moe", h, s, mode, held)


def core_window(p, s, tokens, reset, mode="f32", held=True):
    """The whole decoder over ``tokens`` [R, L] with resets [R, L]. Returns
    the final-norm output [R, L, D], every layer's per-head keys and values
    (what imagination's steps attend to), and the balance term of each row
    [R], summed over the expert layers."""
    R, L = tokens.shape
    x = p[f"{CORE}/embed"][tokens]
    states, aux = {}, 0.0
    k, E = s["num_experts_per_tok"], s["router_outputs"]
    for l in range(s["num_hidden_layers"]):
        pre = f"{CORE}/layers_{l}"
        y, states[l] = mla_window(p, f"{pre}/mla", rms_norm(x, p[f"{pre}/input_norm"], s), reset, s, mode)
        x = x + y
        y, probs, chosen = feed_forward(p, pre, rms_norm(x, p[f"{pre}/post_norm"], s).reshape(R * L, -1), s, l, mode, held)
        x = x + y.reshape(R, L, -1)
        if probs is not None:
            f = jnp.sum(chosen.reshape(R, L, -1), 1) * (E / (k * L))
            aux = aux + jnp.sum(sg(f) * jnp.mean(probs.reshape(R, L, -1), 1), -1)
    return rms_norm(x, p[f"{CORE}/final_norm"], s), states, aux


def prior_logits(p, s, h, mode):
    """The head at an action position, over the observation codes."""
    return unimix(matmul(h, p[f"{CORE}/head"], mode)[..., : s["discrete_size"]], s)


@partial(jax.jit, static_argnames=("sizes", "mode", "held"), compiler_options=QUICK_COMPILE)
def core_forward(params, tokens, reset, *, sizes, mode="f32", held=True):
    """Full forward pass of one env's recorded token row ``[L]``: the prior
    over the next observation code at every action position ``[L / 2, codes]``
    (what ``correct`` holds acting's absorbed one-token path to)."""
    s = thaw(sizes)
    h, _, _ = core_window(params, s, tokens[None], reset[None], mode, held)
    return prior_logits(params, s, h[0, 1::2], mode)


# -- one token at a time (imagination) -------------------------------------------


def core_decode(p, s, state, tokens, context, mode):
    """One token per stream; streams ``[n]`` (``n = rows * starts``, row-major),
    taken as ``[R, S]``. ``state[l]``: the per-head keys and values ``{"k",
    "v"}`` of the stream's own steps, written at ``state["step"]``;
    ``context[l] = (k, v [R, L, H, .], mask [R, S, L])``: the window pass's,
    shared by a row's streams."""
    R, S = context[0][2].shape[:2]
    x = p[f"{CORE}/embed"][tokens.reshape(R, S)]
    step = state["step"]
    new = {"rope_pos": state["rope_pos"] + 1, "step": step + 1}
    for l in range(s["num_hidden_layers"]):
        pre = f"{CORE}/layers_{l}"
        q, k, v = mla_project(p, f"{pre}/mla", rms_norm(x, p[f"{pre}/input_norm"], s), state["rope_pos"], s, mode)
        own_k = jax.lax.dynamic_update_slice_in_dim(state[f"layer_{l}"]["k"], k[:, :, None], step, 2)
        own_v = jax.lax.dynamic_update_slice_in_dim(state[f"layer_{l}"]["v"], v[:, :, None], step, 2)
        ck, cv, cmask = context[l]
        n_own = own_k.shape[2]
        ahead = jnp.einsum("rshd,rkhd->rshk", _round(q, mode), _round(ck, mode), precision=HI)
        mine = jnp.einsum("rshd,rskhd->rshk", _round(q, mode), _round(own_k, mode), precision=HI)
        mask = jnp.concatenate([cmask, jnp.broadcast_to(jnp.arange(n_own) <= step, (R, S, n_own))], -1)
        logits = jnp.concatenate([ahead, mine], -1) * score_scale(s)
        w = _round(jax.nn.softmax(jnp.where(mask[:, :, None], logits, -1e30), -1), mode)
        o = jnp.einsum("rshk,rkhd->rshd", w[..., :-n_own], _round(cv, mode), precision=HI) \
            + jnp.einsum("rshk,rskhd->rshd", w[..., -n_own:], _round(own_v, mode), precision=HI)
        x = x + matmul(o.reshape(R, S, -1), p[f"{pre}/mla/o"], mode)
        new[f"layer_{l}"] = {"k": own_k, "v": own_v}
        y = feed_forward(p, pre, rms_norm(x, p[f"{pre}/post_norm"], s).reshape(R * S, -1), s, l, mode, True)[0]
        x = x + y.reshape(R, S, -1)
    return rms_norm(x, p[f"{CORE}/final_norm"], s).reshape(R * S, -1), new


def boundary_state(s, states, reset):
    """The token at every ``chunk``-th position of every row as a stream: the
    keys and values it may see (its own episode's, before it)."""
    R, L = reset.shape
    at = jnp.arange(L // s["chunk"]) * s["chunk"]
    seg, pos = segments(reset)
    H, S = s["num_attention_heads"], at.shape[0]
    steps = 2 * s["horizon"] + 1  # one-token steps a stream takes
    mask = (jnp.arange(L)[None, None] < at[None, :, None]) & (seg[:, None, :] == seg[:, at][..., None])
    state, context = {"rope_pos": pos[:, at], "step": jnp.zeros((), jnp.int32)}, {}
    for l in range(s["num_hidden_layers"]):
        context[l] = (states[l]["k"], states[l]["v"], mask)
        state[f"layer_{l}"] = {
            "k": jnp.zeros((R, S, steps, H, s["qk_nope_head_dim"] + s["qk_rope_head_dim"])),
            "v": jnp.zeros((R, S, steps, H, s["v_head_dim"])),
        }
    return state, context, at


# ---------------------------------------------------------------------------
# the world-model loss, one block of window rows at a time
# ---------------------------------------------------------------------------


def world_model_rows(wm, s, rows, gumbel, mode, held):
    """Sum over the block's rows of (mean over time of the per-step terms plus
    the load-balancing term). ``rows`` leaves are ``[T, R, ...]``."""
    T, R = rows["rewards"].shape[:2]
    codes = s["discrete_size"]
    rgb = rows["rgb"].astype(jnp.float32) / 255.0
    is_first = rows["is_first"][..., 0].at[0].set(1.0)
    post = posterior_logits(wm, s, rgb, mode)  # [T, R, codes]
    z = jnp.argmax(post + gumbel, -1)
    probs = jnp.exp(post)
    onehot = jax.nn.one_hot(z, codes) + probs - sg(probs)
    z_emb = matmul(onehot, wm[f"{CORE}/embed"][:codes], mode)
    a = codes + jnp.argmax(rows["actions"], -1)
    tokens = jnp.stack([z.T, a.T], -1).reshape(R, 2 * T).astype(jnp.int32)
    reset = jnp.stack([is_first.T, jnp.zeros_like(is_first.T)], -1).reshape(R, 2 * T).astype(jnp.int32)
    h, states, aux = core_window(wm, s, tokens, reset, mode, held)
    h = h.reshape(R, T, 2, -1)
    h_obs, h_act = jnp.moveaxis(h[:, :, 0], 0, 1), jnp.moveaxis(h[:, :, 1], 0, 1)
    prior = prior_logits(wm, s, h_act[:-1], mode)
    feat = jnp.concatenate([z_emb, h_obs], -1)
    observation_loss = jnp.sum(jnp.square(decode_pixels(wm, s, feat, mode) - rgb), (-3, -2, -1))
    reward_loss = -twohot_log_prob(trunk_and_head(wm, "world_model/reward_model", feat, s["mlp_layers"], mode), rows["rewards"])
    cont_logits = trunk_and_head(wm, "world_model/continue_model", feat, s["mlp_layers"], mode)
    cont_target = 1.0 - rows["dones"]
    continue_loss = jnp.sum(
        jax.nn.softplus(-cont_logits) * cont_target + jax.nn.softplus(cont_logits) * (1.0 - cont_target), -1
    )
    has_prior = 1.0 - is_first[1:]
    dyn = s["kl_dynamic"] * jnp.maximum(categorical_kl(sg(post[1:]), prior), s["kl_free_nats"])
    rep = s["kl_representation"] * jnp.maximum(categorical_kl(post[1:], sg(prior)), s["kl_free_nats"])
    kl = jnp.concatenate([jnp.zeros((1, R)), (dyn + rep) * has_prior], 0)
    per_step = s["kl_regularizer"] * kl + observation_loss + reward_loss + s["continue_scale_factor"] * continue_loss
    total = jnp.sum(jnp.mean(per_step, 0) + s["aux_loss_alpha"] * aux)
    return total, (sg(states), tokens, reset)


def world_model_grads(wm, s, batch, key, mode, held):
    """Loss and gradients over the whole batch ``[T, B, ...]``, ``ROW_BLOCK``
    rows at a time; also what imagination starts from."""
    T, B = batch["rewards"].shape[:2]
    gumbel = jax.random.gumbel(key, (T, B, s["discrete_size"]))
    block = min(ROW_BLOCK, B)
    split = lambda x: jnp.moveaxis(x.reshape((T, B // block, block) + x.shape[2:]), 1, 0)
    blocks = jax.tree_util.tree_map(split, {**batch, "_gumbel": gumbel})

    def one(carry, rows):
        loss, grads = carry
        g = rows.pop("_gumbel")
        (l, aux), gr = jax.value_and_grad(world_model_rows, has_aux=True)(wm, s, rows, g, mode, held)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, gr)), aux

    zeros = jax.tree_util.tree_map(jnp.zeros_like, wm)
    (loss, grads), (states, tokens, reset) = jax.lax.scan(one, (jnp.zeros(()), zeros), blocks)
    join = lambda x: x.reshape((B,) + x.shape[2:])
    return loss / B, jax.tree_util.tree_map(lambda g: g / B, grads), jax.tree_util.tree_map(join, (states, tokens, reset))


# ---------------------------------------------------------------------------
# behaviour
# ---------------------------------------------------------------------------


def actor_logits(actor, s, feat, mode):
    logits = head(actor, "actor/head_0", mlp(actor, "actor", feat, s["mlp_layers"], mode), mode)
    probs = jax.nn.softmax(logits, -1)
    probs = (1.0 - s["unimix"]) * probs + s["unimix"] / probs.shape[-1]
    return jax.nn.log_softmax(jnp.log(probs), -1)


def sample_action(logp, key):
    """A sample as a one-hot; ``key`` is split once per action head (one)."""
    k = jax.random.split(key, 1)[0]
    idx = jax.random.categorical(k, logp, axis=-1, shape=logp.shape[:-1])
    return jax.nn.one_hot(idx, logp.shape[-1], dtype=logp.dtype)


def lambda_returns(rewards, values, continues, lmbda):
    interm = rewards + continues * values * (1.0 - lmbda)

    def step(nxt, inp):
        interm_t, cont_t = inp
        val = interm_t + cont_t * lmbda * nxt
        return val, val

    return jax.lax.scan(step, values[-1], (interm, continues), reverse=True)[1]


def imagine(wm, actor, s, carry, key, mode):
    """Forward only: ``horizon`` steps of two tokens from every start."""
    states, tokens, reset = carry
    H, codes = s["horizon"], s["discrete_size"]
    state, context, at = boundary_state(s, states, reset)
    embed = wm[f"{CORE}/embed"]
    z0 = tokens[:, at].reshape(-1)
    h0, state = core_decode(wm, s, state, z0, context, mode)
    feat0 = jnp.concatenate([embed[z0], h0], -1)
    k0, key = jax.random.split(key)
    a0 = sample_action(actor_logits(actor, s, feat0, mode), k0)
    keys = jax.random.split(key, 2 * H).reshape(H, 2)

    def step(carry, ks):
        state, action = carry
        h_a, state = core_decode(wm, s, state, (codes + jnp.argmax(action, -1)).astype(jnp.int32), context, mode)
        z = jax.random.categorical(ks[0], prior_logits(wm, s, h_a, mode), -1).astype(jnp.int32)
        h, state = core_decode(wm, s, state, z, context, mode)
        feat = jnp.concatenate([embed[z], h], -1)
        action = sample_action(actor_logits(actor, s, feat, mode), ks[1])
        return (state, action), (feat, action)

    _, (feats, acts) = jax.lax.scan(step, (state, a0), keys)
    return sg(jnp.concatenate([feat0[None], feats], 0)), sg(jnp.concatenate([a0[None], acts], 0)), at // 2


def imagined_returns(wm, critic, s, traj, true_continue, mode):
    values = twohot_mean(trunk_and_head(critic, "critic", traj, s["mlp_layers"], mode))
    rewards = twohot_mean(trunk_and_head(wm, "world_model/reward_model", traj, s["mlp_layers"], mode))
    cont_logits = trunk_and_head(wm, "world_model/continue_model", traj, s["mlp_layers"], mode)
    continues = jnp.concatenate([true_continue[None], (cont_logits > 0).astype(jnp.float32)[1:]], 0)
    lam = lambda_returns(rewards[1:], values[1:], continues[1:] * s["gamma"], s["lmbda"])
    discount = sg(jnp.cumprod(continues * s["gamma"], 0) / s["gamma"])
    return lam, values, discount


def actor_loss(actor, s, traj, actions, lam, values, discount, low_high, mode):
    low, high = low_high
    invscale = jnp.maximum(1.0 / s["moments_max"], high - low)
    advantage = (lam - low) / invscale - (values[:-1] - low) / invscale
    logp = actor_logits(actor, s, traj, mode)
    log_prob = jnp.sum(actions * logp, -1)[..., None][:-1]
    entropy = -jnp.sum(jnp.exp(logp) * logp, -1)[..., None][:-1]
    return -jnp.mean(discount[:-1] * (log_prob * sg(advantage) + s["ent_coef"] * entropy))


def critic_loss(critic, target, s, traj, lam, discount, mode):
    logits = trunk_and_head(critic, "critic", traj[:-1], s["mlp_layers"], mode)
    target_logits = trunk_and_head(
        {k.replace("target_critic/", "critic/", 1): v for k, v in target.items()},
        "critic", traj[:-1], s["mlp_layers"], mode,
    )
    loss = -twohot_log_prob(logits, lam) - twohot_log_prob(logits, sg(twohot_mean(target_logits)))
    return jnp.mean(loss * discount[:-1, ..., 0])


# ---------------------------------------------------------------------------
# optimiser, state
# ---------------------------------------------------------------------------


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return {k: g * scale for k, g in grads.items()}


def adam(params, grads, opt, hp):
    grads = clip_by_global_norm(grads, hp["clip"])
    b1, b2 = hp["betas"]
    t = opt["t"] + 1
    mu = {k: b1 * opt["mu"][k] + (1.0 - b1) * g for k, g in grads.items()}
    nu = {k: b2 * opt["nu"][k] + (1.0 - b2) * jnp.square(g) for k, g in grads.items()}
    c1, c2 = 1.0 - b1**t, 1.0 - b2**t
    new = {k: params[k] - hp["lr"] * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + hp["eps"]) for k in params}
    norms = {k: jnp.sqrt(jnp.sum(jnp.square(g))) for k, g in grads.items()}
    return new, {"t": t, "mu": mu, "nu": nu}, norms


def init_state(shapes, seed) -> dict:
    """Parameters from the seed and fresh optimiser state. The world model's
    Adam moments are made by its first update (``None`` until then) and wait
    on the host between updates: 5 GB that the gradient pass has no room for
    beside its own accumulators on a 16 GB chip."""
    params = make_weights(shapes, seed)
    opt = {}
    for m in MODULES:
        mine = {k: jnp.zeros_like(v) for k, v in params.items() if k.startswith(m + "/")}
        moments = {"mu": None, "nu": None} if m == "world_model" else {"mu": mine, "nu": dict(mine)}
        opt[m] = {"t": jnp.zeros((), jnp.float32), **moments}
    return {"params": params, "opt": opt, "low": jnp.zeros(()), "high": jnp.zeros(())}


def freeze(tree):
    if isinstance(tree, dict):
        return tuple(sorted((k, freeze(v)) for k, v in tree.items()))
    if isinstance(tree, list):
        return tuple(freeze(v) for v in tree)
    return tree


def thaw(items) -> dict:
    return {k: thaw(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v for k, v in items}


def _split(params, module):
    return {k: v for k, v in params.items() if k.startswith(module + "/")}


def _keys(key_data, s):
    return jax.random.split(jax.random.fold_in(jax.random.wrap_key_data(key_data, impl=s["prng_impl"]), 0))


@partial(jax.jit, static_argnames=("sizes", "mode", "held"), compiler_options=QUICK_COMPILE)
def _wm_gradients(wm, batch, key_data, *, sizes, mode, held):
    s = thaw(sizes)
    return world_model_grads(wm, s, batch, _keys(key_data, s)[0], mode, held)


@partial(jax.jit, static_argnames=("sizes",), donate_argnums=(0, 1, 2), compiler_options=QUICK_COMPILE)
def _wm_update(wm, grads, opt, *, sizes):
    if opt["mu"] is None:
        zeros = {k: jnp.zeros_like(v) for k, v in wm.items()}
        opt = {"t": opt["t"], "mu": zeros, "nu": dict(zeros)}
    return adam(wm, grads, opt, thaw(sizes)["optim"]["world_model"])


@partial(jax.jit, static_argnames=("sizes", "mode"), compiler_options=QUICK_COMPILE)
def _behaviour(wm, actor, critic, target, opt, low_high, carry, dones, key_data, tau, *, sizes, mode):
    s = thaw(sizes)
    target = {k: tau * critic[k.replace("target_critic/", "critic/", 1)] + (1.0 - tau) * v for k, v in target.items()}
    traj, actions, starts = imagine(wm, actor, s, carry, _keys(key_data, s)[1], mode)
    true_continue = (1.0 - dones)[starts][..., 0].T.reshape(-1, 1)
    lam, values, discount = imagined_returns(wm, critic, s, traj, true_continue, mode)
    decay = s["moments_decay"]
    low = decay * low_high[0] + (1.0 - decay) * jnp.quantile(lam, s["moments_low"])
    high = decay * low_high[1] + (1.0 - decay) * jnp.quantile(lam, s["moments_high"])
    a_loss, a_grads = jax.value_and_grad(actor_loss)(actor, s, traj, actions, sg(lam), sg(values), discount, (low, high), mode)
    c_loss, c_grads = jax.value_and_grad(critic_loss)(critic, target, s, traj, sg(lam), discount, mode)
    new_actor, actor_opt, actor_norms = adam(actor, a_grads, opt["actor"], s["optim"]["actor"])
    new_critic, critic_opt, critic_norms = adam(critic, c_grads, opt["critic"], s["optim"]["critic"])
    return ({**new_actor, **new_critic, **target}, {"actor": actor_opt, "critic": critic_opt}, (low, high),
            a_loss, c_loss, {**actor_norms, **critic_norms})


def train_step(state, batch, key_data, tau, *, sizes, n_shards=1, mode="f32", exchange=True, held=True):
    """One gradient step on ``batch`` ([T, B, ...]) with the program's key and
    target coefficient: three compiled parts (the world model's gradients, its
    update, the behaviour), so that the world model's Adam moments can wait on
    the host while the gradients are taken. The configuration runs on one chip
    (``n_shards`` 1). Returns ``(state, report)``: the three losses and the
    per-leaf norms of the gradients the optimisers were given."""
    if n_shards != 1 or not exchange:
        raise ValueError("this configuration's cells run on one chip")
    params, opt = state["params"], state["opt"]
    wm = _split(params, "world_model")
    wm_loss, wm_grads, carry = _wm_gradients(wm, batch, key_data, sizes=sizes, mode=mode, held=held)
    wm_opt = opt["world_model"]
    if wm_opt["mu"] is not None:
        wm_opt = jax.device_put(wm_opt, jax.tree_util.tree_leaves(wm)[0].sharding)
    new_wm, wm_opt, wm_norms = _wm_update(wm, wm_grads, wm_opt, sizes=sizes)
    host_opt = jax.device_get(wm_opt)
    del wm_opt
    rest, rest_opt, low_high, a_loss, c_loss, rest_norms = _behaviour(
        new_wm, _split(params, "actor"), _split(params, "critic"), _split(params, "target_critic"),
        {"actor": opt["actor"], "critic": opt["critic"]}, (state["low"], state["high"]), carry,
        batch["dones"], key_data, tau, sizes=sizes, mode=mode,
    )
    report = {
        "Loss/world_model_loss": wm_loss,
        "Loss/policy_loss": a_loss,
        "Loss/value_loss": c_loss,
        "grad_norms": {**wm_norms, **rest_norms},
    }
    new_state = {
        "params": {**new_wm, **rest},
        "opt": {"world_model": host_opt, **rest_opt},
        "low": low_high[0],
        "high": low_high[1],
    }
    return new_state, report


@partial(jax.jit, static_argnames=("shapes_items",))
def change_norms(params, seed, shapes_items):
    """Per-leaf norm of ``params - make_weights(seed)``."""
    start = make_weights(dict(shapes_items), seed)
    return {k: jnp.sqrt(jnp.sum(jnp.square(params[k] - start[k]))) for k in params}
