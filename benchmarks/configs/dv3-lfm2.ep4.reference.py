"""Plain reference for DreamerV3 over an LFM2-MoE sequence core, one whole
gradient step with the balance step that follows it, and the core's forward
pass alone.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no flax, no optax, no burst engine, no kernels, no bf16,
no ring — and no import of ``sheeprl_tpu``. The core follows the published
implementation of the family (``model_type: lfm2_moe``; source:
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json):

- stack: embedding, pre-norm residual layers (``x += op(operator_norm(x))``,
  ``x += ff(ffn_norm(x))``; RMSNorm with a plain weight, eps 1e-5),
  ``embedding_norm``, then the head, **tied** to the embedding. ``op`` is the
  short convolution where ``layer_types[l]`` is ``conv`` and attention where
  it is ``full_attention``; ``ff`` a SwiGLU MLP of ``intermediate_size`` in the
  first ``num_dense_layers`` layers and the expert layer after.
- gated short convolution: ``[B, C, u] = x W_in`` (three blocks of the hidden
  size), ``y = (C * conv(B * u)) W_out``, ``conv`` a causal depthwise
  convolution of ``conv_L_cache`` taps, here as that many **shifted, masked
  products** (tap ``j`` reads the row ``K - 1 - j`` before the token where it
  lies in the token's episode); no bias, no activation.
- attention: 32 query heads over 8 key-value heads of 64, ``q`` and ``k``
  RMS-normalised per head (``q_layernorm``, ``k_layernorm``) and then rotated
  over the whole head (``rotate_half``: pairs ``(i, i + 32)``, ``rope_theta``
  1e6), scale ``64^-1/2``; **every query against every key of its segment**,
  causal; no bias, no gate.
- experts: ``s = sigmoid(x W_r)`` over all the router's outputs; the ``k``
  chosen are the largest of ``s + b`` (``b`` the ``expert_bias``, which no
  gradient reaches); their weights are ``s`` at the chosen, without ``b``,
  over their sum ``+ 1e-6`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; *dense per-expert products* for the experts held
  (every token through every held expert, weighted by its routing weight or
  0); no shared expert. What absent experts would add is left out.
- the balance step, after each optimiser step: per routing layer ``b_e += u
  sign(mean(load) - load_e)``, ``load_e`` the tokens of the step's window pass
  that chose expert ``e``, over all the router's outputs (loss-free balancing,
  arXiv:2408.15664); no balance term in the loss.

Departures, each also the program's: the balance step's rule and rate ``u`` are
assumed (the config gives the bias, not how it is trained; ``u`` is
``sizes["bias_update_rate"]``: the paper's 0.001 in the recipe, 0.02 in the
benchmark's configuration, whose file says why) and its load is this chip's batch alone (a deployment sums its four chips');
``tie_word_embeddings`` is assumed true (the family's published configs tie
them; the catalog's row lacks the key); an episode's first token masks the
convolution and attention to the episode's own segment and restarts rotary
positions (packing). Imagination keeps the two gated rows a convolution layer
and the keys and values its own steps have computed in a list (one slot a
step) beside the window pass's keys and values: recomputing thirty prefixes of
a thousand tokens would cost the reference petaflops. :func:`core_forward` —
what ``correct`` holds acting's one-token path to — keeps nothing: it is the
full forward pass of the same tokens.

``mode`` as in the other references: ``f32``, or ``bf16``/``fp8`` (operands of
every product rounded to that type; ``fp8`` is the control of ``bf16-mixed``).
``held=False`` plants the fault of an expert layer that leaves its held
experts out, ``bias=False`` that of a router that chooses by ``s`` alone.
"""

from __future__ import annotations

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
    return layer < s["num_dense_layers"]


def is_attention(s: dict, layer: int) -> bool:
    return s["layer_types"][layer] == "full_attention"


def routing_layers(s: dict) -> list:
    return [l for l in range(s["num_hidden_layers"]) if not is_dense(s, l)]


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

    H, Hkv, K = s["num_attention_heads"], s["num_key_value_heads"], s["conv_L_cache"]
    hd = D // H
    E, Eh, F = s["router_outputs"], s["num_experts"], s["moe_intermediate_size"]
    out[f"{CORE}/embed"] = (V, D)
    out[f"{CORE}/embedding_norm"] = (D,)
    for l in range(s["num_hidden_layers"]):
        pre = f"{CORE}/layers_{l}"
        out.update({f"{pre}/operator_norm": (D,), f"{pre}/ffn_norm": (D,)})
        if is_attention(s, l):
            out.update({
                f"{pre}/attn/q": (D, H * hd), f"{pre}/attn/k": (D, Hkv * hd), f"{pre}/attn/v": (D, Hkv * hd),
                f"{pre}/attn/q_layernorm": (hd,), f"{pre}/attn/k_layernorm": (hd,), f"{pre}/attn/o": (H * hd, D),
            })
        else:
            out.update({f"{pre}/conv/in": (D, 3 * D), f"{pre}/conv/conv": (K, D), f"{pre}/conv/out": (D, D)})
        if is_dense(s, l):
            I = s["intermediate_size"]
            out.update({f"{pre}/mlp/gate": (D, I), f"{pre}/mlp/up": (D, I), f"{pre}/mlp/down": (I, D)})
        else:
            out.update({
                f"{pre}/moe/router": (D, E), f"{pre}/moe/expert_bias": (E,), f"{pre}/moe/gate": (Eh, D, F),
                f"{pre}/moe/up": (Eh, D, F), f"{pre}/moe/down": (Eh, F, D),
            })
    return out


#: output layers that start at zero, so rewards and values start at 0
ZERO_KERNELS = ("world_model/reward_model/head/kernel", "critic/head/kernel", "target_critic/head/kernel")
#: the selection bias starts at the size of the differences between the scores it is added to (see :func:`make_weights`)
BIAS_SPAN = 0.25


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
    expert's fans are its own), plain norm weights of one, the convolution by
    its three taps. **The selection bias is not zero**: uniform in ``+-0.25``,
    of the order of the differences between the sigmoid scores it is added to
    (logits of standard deviation ~1.4 under the fan rule) — what 250 balance
    steps at the paper's rate of 0.001 reach in one direction, a dozen at the
    benchmark's 0.02 — so that the choice by ``s + b`` differs
    from the choice by ``s`` for most tokens from the first step on: with a
    zero bias a program that ignored it would pass. Counter-based keys, one a
    leaf from a checksum of its name."""
    root = jax.random.key(seed, impl="threefry2x32")
    out = {}
    for name, shape in shapes.items():
        source = name.replace("target_critic/", "critic/", 1)
        key = jax.random.fold_in(root, zlib.crc32(source.encode()) & 0x7FFFFFFF)
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "expert_bias":
            out[name] = jax.random.uniform(key, shape, jnp.float32, -BIAS_SPAN, BIAS_SPAN)
        elif name.endswith("scale") or name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("bias") or name in ZERO_KERNELS:
            out[name] = jnp.zeros(shape, jnp.float32)
        elif leaf == "conv":
            out[name] = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + s["norm_eps"]) * w


def rope(x, pos, s):
    """``x`` [..., heads, d] rotated by ``pos`` [...] over the whole head; pairs ``(i, i + d/2)``."""
    d = x.shape[-1]
    freq = 1.0 / float(s["rope_theta"]) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
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


def experts(p, pre, x, s, mode, held=True, bias=True):
    """``x`` [N, D] -> (the held experts' part, whether each of the router's
    outputs was chosen [N, E])."""
    scores = jax.nn.sigmoid(matmul(x, p[f"{pre}/router"], mode))
    k, E, Eh = s["num_experts_per_tok"], s["router_outputs"], s["num_experts"]
    _, top_i = jax.lax.top_k(scores + p[f"{pre}/expert_bias"] if bias and s["use_expert_bias"] else scores, k)
    top_s = jnp.take_along_axis(scores, top_i, -1)  # the weights never see the bias
    if s["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    top_s = top_s * s["routed_scaling_factor"]
    weights = jnp.sum(jax.nn.one_hot(top_i, E) * top_s[..., None], 1)  # [N, E], 0 where not chosen
    chosen = jnp.sum(jax.nn.one_hot(top_i, E), 1)
    out = jnp.zeros_like(x)
    if held:  # every token through every held expert, weighted by its routing weight or 0
        lo = s["expert_share_index"] * Eh
        xr = _round(x, mode)
        gate = jnp.einsum("nd,edf->enf", xr, _round(p[f"{pre}/gate"], mode), precision=HI)
        up = jnp.einsum("nd,edf->enf", xr, _round(p[f"{pre}/up"], mode), precision=HI)
        each = jnp.einsum("enf,efd->end", _round(silu(gate) * up, mode), _round(p[f"{pre}/down"], mode), precision=HI)
        out = jnp.einsum("end,ne->nd", each, jax.lax.dynamic_slice_in_dim(weights, lo, Eh, 1), precision=HI)
    return out, chosen


def gated_rows(p, pre, x, mode):
    """``x`` [..., D] -> (``B * u``, ``C``), each [..., D]."""
    D = x.shape[-1]
    bcu = matmul(x, p[f"{pre}/in"], mode)
    return bcu[..., :D] * bcu[..., 2 * D:], bcu[..., D : 2 * D]


def conv_window(p, pre, x, reset, s, mode):
    """The operator over ``x`` [R, L, D]; also the gated rows ``B * u`` (what a
    one-token stream keeps the last two of)."""
    L, K = x.shape[1], s["conv_L_cache"]
    seg, _ = segments(reset)
    gated, c = gated_rows(p, pre, x, mode)
    mixed = jnp.zeros_like(gated)
    for j in range(K):  # tap j: the row K - 1 - j before the token
        back = K - 1 - j
        rows = jnp.pad(gated, ((0, 0), (back, 0), (0, 0)))[:, :L]
        same = jnp.pad(seg, ((0, 0), (back, 0)), constant_values=-1)[:, :L] == seg
        mixed = mixed + jnp.where(same[..., None], rows, 0.0) * p[f"{pre}/conv"][j]
    return matmul(c * mixed, p[f"{pre}/out"], mode), {"gated": gated}


def attn_project(p, pre, x, pos, s, mode):
    """``q`` [..., H, d], ``k, v`` [..., Hkv, d] of ``x`` [..., D] at rotary positions ``pos`` [...]."""
    H, Hkv = s["num_attention_heads"], s["num_key_value_heads"]
    lead = x.shape[:-1]
    q = matmul(x, p[f"{pre}/q"], mode).reshape(lead + (H, -1))
    k = matmul(x, p[f"{pre}/k"], mode).reshape(lead + (Hkv, -1))
    v = matmul(x, p[f"{pre}/v"], mode).reshape(lead + (Hkv, -1))
    q = rope(rms_norm(q, p[f"{pre}/q_layernorm"], s), pos, s)
    k = rope(rms_norm(k, p[f"{pre}/k_layernorm"], s), pos, s)
    return q, k, v


def _repeat_heads(x, s):
    """Key-value heads [..., Hkv, d] -> one a query head [..., H, d]."""
    return jnp.repeat(x, s["num_attention_heads"] // s["num_key_value_heads"], -2)


def attn_window(p, pre, x, reset, s, mode):
    R, L, _ = x.shape
    seg, pos = segments(reset)
    q, k, v = attn_project(p, pre, x, pos, s, mode)
    kh, vh = _repeat_heads(k, s), _repeat_heads(v, s)
    logits = jnp.einsum("bqhd,bkhd->bhqk", _round(q, mode), _round(kh, mode), precision=HI) * q.shape[-1] ** -0.5
    idx = jnp.arange(L)
    mask = (idx[:, None] >= idx[None, :])[None] & (seg[:, :, None] == seg[:, None, :])
    w = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(w, mode), _round(vh, mode), precision=HI)
    return matmul(o.reshape(R, L, -1), p[f"{pre}/o"], mode), {"k": k, "v": v}


def feed_forward(p, pre, h, s, layer, mode, held, bias):
    """The layer's MLP over ``h`` [N, D]; the router's choices where it routes."""
    if is_dense(s, layer):
        return dense_mlp(p, f"{pre}/mlp", h, mode), None
    return experts(p, f"{pre}/moe", h, s, mode, held, bias)


def core_window(p, s, tokens, reset, mode="f32", held=True, bias=True):
    """The whole decoder over ``tokens`` [R, L] with resets [R, L]. Returns
    the final-norm output [R, L, D], every layer's state (a convolution
    layer's gated rows, an attention layer's keys and values: what
    imagination's steps go on from), and the load of every routing layer:
    tokens that chose each of the router's outputs [routing layers, E]."""
    R, L = tokens.shape
    x = p[f"{CORE}/embed"][tokens]
    states, load = {}, []
    for l in range(s["num_hidden_layers"]):
        pre = f"{CORE}/layers_{l}"
        h = rms_norm(x, p[f"{pre}/operator_norm"], s)
        if is_attention(s, l):
            y, states[l] = attn_window(p, f"{pre}/attn", h, reset, s, mode)
        else:
            y, states[l] = conv_window(p, f"{pre}/conv", h, reset, s, mode)
        x = x + y
        y, chosen = feed_forward(p, pre, rms_norm(x, p[f"{pre}/ffn_norm"], s).reshape(R * L, -1), s, l, mode, held, bias)
        x = x + y.reshape(R, L, -1)
        if chosen is not None:
            load.append(jnp.sum(chosen, 0))
    return rms_norm(x, p[f"{CORE}/embedding_norm"], s), states, jnp.stack(load)


def tied_head(p, h, mode):
    """The head over final-norm outputs: the embedding's rows as the output layer."""
    return matmul(h, p[f"{CORE}/embed"].T, mode)


def prior_logits(p, s, h, mode):
    """The head at an action position, over the observation codes."""
    return unimix(tied_head(p, h, mode)[..., : s["discrete_size"]], s)


@partial(jax.jit, static_argnames=("sizes", "mode", "held", "bias"), compiler_options=QUICK_COMPILE)
def core_forward(params, tokens, reset, *, sizes, mode="f32", held=True, bias=True):
    """Full forward pass of one env's recorded token row ``[L]``: the prior
    over the next observation code at every action position ``[L / 2, codes]``
    (what ``correct`` holds acting's one-token path to)."""
    s = thaw(sizes)
    h, _, _ = core_window(params, s, tokens[None], reset[None], mode, held, bias)
    return prior_logits(params, s, h[0, 1::2], mode)


def balance_step(params, load, s):
    """``b_e += u sign(mean(load) - load_e)`` in every routing layer; ``load`` [routing layers, E]."""
    out = dict(params)
    for i, l in enumerate(routing_layers(s)):
        name = f"{CORE}/layers_{l}/moe/expert_bias"
        out[name] = params[name] + s["bias_update_rate"] * jnp.sign(jnp.mean(load[i]) - load[i])
    return out


# -- one token at a time (imagination) -------------------------------------------


def core_decode(p, s, state, tokens, context, mode):
    """One token per stream; streams ``[n]`` (``n = rows * starts``, row-major),
    taken as ``[R, S]``. ``state[layer]``: a convolution layer's last gated
    rows ``{"gated": [R, S, K - 1, D]}``, or the keys and values ``{"k", "v"}``
    of the stream's own steps, written at ``state["step"]``; ``context[l] = (k,
    v [R, L, Hkv, d], mask [R, S, L])``: the window pass's, shared by a row's
    streams."""
    R, S = state["rope_pos"].shape
    x = p[f"{CORE}/embed"][tokens.reshape(R, S)]
    step = state["step"]
    new = {"rope_pos": state["rope_pos"] + 1, "step": step + 1}
    for l in range(s["num_hidden_layers"]):
        pre = f"{CORE}/layers_{l}"
        h = rms_norm(x, p[f"{pre}/operator_norm"], s)
        if is_attention(s, l):
            q, k, v = attn_project(p, f"{pre}/attn", h, state["rope_pos"], s, mode)
            own_k = jax.lax.dynamic_update_slice_in_dim(state[f"layer_{l}"]["k"], k[:, :, None], step, 2)
            own_v = jax.lax.dynamic_update_slice_in_dim(state[f"layer_{l}"]["v"], v[:, :, None], step, 2)
            ck, cv, cmask = context[l]
            n_own = own_k.shape[2]
            ahead = jnp.einsum("rshd,rkhd->rshk", _round(q, mode), _round(_repeat_heads(ck, s), mode), precision=HI)
            mine = jnp.einsum("rshd,rskhd->rshk", _round(q, mode), _round(_repeat_heads(own_k, s), mode), precision=HI)
            mask = jnp.concatenate([cmask, jnp.broadcast_to(jnp.arange(n_own) <= step, (R, S, n_own))], -1)
            logits = jnp.concatenate([ahead, mine], -1) * q.shape[-1] ** -0.5
            w = _round(jax.nn.softmax(jnp.where(mask[:, :, None], logits, -1e30), -1), mode)
            o = jnp.einsum("rshk,rkhd->rshd", w[..., :-n_own], _round(_repeat_heads(cv, s), mode), precision=HI) \
                + jnp.einsum("rshk,rskhd->rshd", w[..., -n_own:], _round(_repeat_heads(own_v, s), mode), precision=HI)
            x = x + matmul(o.reshape(R, S, -1), p[f"{pre}/attn/o"], mode)
            new[f"layer_{l}"] = {"k": own_k, "v": own_v}
        else:
            gated, c = gated_rows(p, f"{pre}/conv", h, mode)
            taps = jnp.concatenate([state[f"layer_{l}"]["gated"], gated[:, :, None]], 2)
            x = x + matmul(c * jnp.sum(taps * p[f"{pre}/conv/conv"], 2), p[f"{pre}/conv/out"], mode)
            new[f"layer_{l}"] = {"gated": taps[:, :, 1:]}
        y = feed_forward(p, pre, rms_norm(x, p[f"{pre}/ffn_norm"], s).reshape(R * S, -1), s, l, mode, True, True)[0]
        x = x + y.reshape(R, S, -1)
    return rms_norm(x, p[f"{CORE}/embedding_norm"], s).reshape(R * S, -1), new


def boundary_state(s, states, reset):
    """The token at every ``chunk``-th position of every row as a stream: the
    gated rows before it and the keys and values it may see (its own
    episode's, before it)."""
    R, L = reset.shape
    at = jnp.arange(L // s["chunk"]) * s["chunk"]
    seg, pos = segments(reset)
    Hkv, S, K = s["num_key_value_heads"], at.shape[0], s["conv_L_cache"]
    hd = s["hidden_size"] // s["num_attention_heads"]
    steps = 2 * s["horizon"] + 1  # one-token steps a stream takes
    mask = (jnp.arange(L)[None, None] < at[None, :, None]) & (seg[:, None, :] == seg[:, at][..., None])
    state, context = {"rope_pos": pos[:, at], "step": jnp.zeros((), jnp.int32)}, {}
    for l in range(s["num_hidden_layers"]):
        if is_attention(s, l):
            context[l] = (states[l]["k"], states[l]["v"], mask)
            state[f"layer_{l}"] = {"k": jnp.zeros((R, S, steps, Hkv, hd)), "v": jnp.zeros((R, S, steps, Hkv, hd))}
        else:
            before = at[:, None] - (K - 1) + jnp.arange(K - 1)[None]  # [S, K - 1] positions, negative before the row
            rows = states[l]["gated"][:, jnp.maximum(before, 0)]  # [R, S, K - 1, D]
            same = (before >= 0)[None] & (seg[:, jnp.maximum(before, 0)] == seg[:, at][..., None])
            state[f"layer_{l}"] = {"gated": jnp.where(same[..., None], rows, 0.0)}
    return state, context, at


# ---------------------------------------------------------------------------
# the world-model loss, one block of window rows at a time
# ---------------------------------------------------------------------------


def world_model_rows(wm, s, rows, gumbel, mode, held, bias):
    """Sum over the block's rows of the mean over time of the per-step terms (no
    balance term: the bias is balanced outside the gradient). ``rows`` leaves
    are ``[T, R, ...]``."""
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
    h, states, load = core_window(wm, s, tokens, reset, mode, held, bias)
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
    return jnp.sum(jnp.mean(per_step, 0)), (sg(states), tokens, reset, load)


def world_model_grads(wm, s, batch, key, mode, held, bias):
    """Loss and gradients over the whole batch ``[T, B, ...]``, ``ROW_BLOCK``
    rows at a time; also what imagination starts from, and the whole batch's
    load of every routing layer's experts."""
    T, B = batch["rewards"].shape[:2]
    gumbel = jax.random.gumbel(key, (T, B, s["discrete_size"]))
    block = min(ROW_BLOCK, B)
    split = lambda x: jnp.moveaxis(x.reshape((T, B // block, block) + x.shape[2:]), 1, 0)
    blocks = jax.tree_util.tree_map(split, {**batch, "_gumbel": gumbel})

    def one(carry, rows):
        loss, grads = carry
        g = rows.pop("_gumbel")
        (l, aux), gr = jax.value_and_grad(world_model_rows, has_aux=True)(wm, s, rows, g, mode, held, bias)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, gr)), aux

    zeros = jax.tree_util.tree_map(jnp.zeros_like, wm)
    (loss, grads), (states, tokens, reset, load) = jax.lax.scan(one, (jnp.zeros(()), zeros), blocks)
    join = lambda x: x.reshape((B,) + x.shape[2:])
    carry = jax.tree_util.tree_map(join, (states, tokens, reset))
    return loss / B, jax.tree_util.tree_map(lambda g: g / B, grads), carry, jnp.sum(load, 0)


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


@partial(jax.jit, static_argnames=("sizes", "mode", "held", "bias"), compiler_options=QUICK_COMPILE)
def _wm_gradients(wm, batch, key_data, *, sizes, mode, held, bias):
    s = thaw(sizes)
    return world_model_grads(wm, s, batch, _keys(key_data, s)[0], mode, held, bias)


@partial(jax.jit, static_argnames=("sizes",), donate_argnums=(0, 1, 2), compiler_options=QUICK_COMPILE)
def _wm_update(wm, grads, opt, load, *, sizes):
    """Adam's step, then the balance step on what it left."""
    s = thaw(sizes)
    if opt["mu"] is None:
        zeros = {k: jnp.zeros_like(v) for k, v in wm.items()}
        opt = {"t": opt["t"], "mu": zeros, "nu": dict(zeros)}
    new, opt, norms = adam(wm, grads, opt, s["optim"]["world_model"])
    return balance_step(new, load, s), opt, norms


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


def train_step(state, batch, key_data, tau, *, sizes, n_shards=1, mode="f32", exchange=True, held=True, bias=True):
    """One gradient step on ``batch`` ([T, B, ...]) with the program's key and
    target coefficient: three compiled parts (the world model's gradients, its
    update with the balance step, the behaviour), so that the world model's
    Adam moments can wait on the host while the gradients are taken. The
    configuration runs on one chip (``n_shards`` 1). Returns ``(state,
    report)``: the three losses, the per-leaf norms of the gradients the
    optimisers were given, and ``router_load`` [routing layers, E], the load
    the balance step acted on."""
    if n_shards != 1 or not exchange:
        raise ValueError("this configuration's cells run on one chip")
    params, opt = state["params"], state["opt"]
    wm = _split(params, "world_model")
    wm_loss, wm_grads, carry, load = _wm_gradients(wm, batch, key_data, sizes=sizes, mode=mode, held=held, bias=bias)
    wm_opt = opt["world_model"]
    if wm_opt["mu"] is not None:
        wm_opt = jax.device_put(wm_opt, jax.tree_util.tree_leaves(wm)[0].sharding)
    new_wm, wm_opt, wm_norms = _wm_update(wm, wm_grads, wm_opt, load, sizes=sizes)
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
        "router_load": load,
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
