"""The Qwen3-Next decoder as a sequence core: Gated DeltaNet and gated
softmax attention, three to one, each followed by a sparse expert layer.

Plain functions over a parameter dict (``init_params`` names every leaf), in
two entry points that must agree:

- :func:`window` — a whole packed window ``[B, L]`` at once: the chunked delta
  rule (``kernels/delta_rule.py``), causal attention masked to each token's
  own episode segment, rotary positions restarting at a segment's first token.
  Returns the final-norm output and what one-token decoding needs to go on
  from any chunk boundary (:func:`boundary_state`).
- :func:`decode` — one token per stream against per-stream state: the
  delta-rule state, the convolution tail, and a key-value cache (a ring, so a
  stream may run longer than the cache). Streams are ``[R, S]``: ``S`` streams
  share row ``r``'s *context* keys and values (imagination starts of one
  replay row); acting has ``S = 1`` and no context.

The expert layer is told which experts it holds (``held = (index, of)``) and is
``models/moe.py``'s, shared with the other cores: softmax over all the router's
outputs, the ten largest renormalised, a shared expert behind a ``sigmoid``.

Equations follow the family's published implementation (``model_type:
qwen3_next``): RMSNorm with weight ``1 + w`` (the norm inside the DeltaNet
output gate carries a plain weight), q/k L2-normalised in the delta rule,
rotary on the first quarter of each attention head, ``sigmoid`` output gate.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.kernels import delta_rule
from sheeprl_tpu.models import moe as _moe
from sheeprl_tpu.models.moe import mm as _mm

f32 = jnp.float32
#: the ``jax.named_scope`` the stack's parts (``gdn``, ``attn``, ``moe``, ``head``)
#: are named under, unless the caller gives its own
SCOPE = "core"
#: one-token statistics ``seq_agent`` sums over imagination's steps -> the run counter each feeds
DECODE_COUNTS = {"held_pairs": "imagination_pairs", "experts_hit": "imagination_experts_hit"}
#: window-pass statistics reported as run counters beside the expert layer's
WINDOW_COUNTS = ("delta_rule_fused_tiles", "delta_rule_scan_fused_tiles")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    rms_norm_eps: float = 1e-6
    num_experts: int = 512  # the router's outputs
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    vocab_size: int = 151_936
    held_index: int = 0  # this chip's share of each layer's experts: (index, of)
    held_of: int = 1
    chunk: int = 64
    cache_len: int = 1024
    router_aux_loss_coef: float = 0.001

    @property
    def moe_spec(self) -> _moe.MoESpec:
        return _moe.MoESpec(self.num_experts, self.num_experts_per_tok, self.held_index, self.held_of)

    @property
    def experts_held(self) -> int:
        return self.moe_spec.experts_held

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers

    def balance_loss(self, aux_sum):
        """The coefficient times the expert layers' mean term."""
        return self.router_aux_loss_coef * (aux_sum / self.num_hidden_layers)

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def conv_dim(self) -> int:
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim + (
            self.linear_num_value_heads * self.linear_value_head_dim
        )

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @classmethod
    def from_mapping(cls, m) -> "Qwen3NextConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: m[k] for k in m if k in names})


#: the name ``seq_agent`` asks every core module for
Config = Qwen3NextConfig


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def param_shapes(c: Qwen3NextConfig) -> Dict[str, Any]:
    D, E, Eh = c.hidden_size, c.num_experts, c.experts_held
    F, Fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    Hk, Hv, dk, dv = c.linear_num_key_heads, c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    Hq, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    out: Dict[str, Any] = {"embed": (c.vocab_size, D), "final_norm": (D,), "head": (D, c.vocab_size)}
    for l in range(c.num_hidden_layers):
        layer: Dict[str, Any] = {"input_norm": (D,), "post_norm": (D,)}
        if c.is_attention(l):
            layer["attn"] = {
                "q": (D, Hq * hd * 2), "k": (D, Hkv * hd), "v": (D, Hkv * hd),
                "q_norm": (hd,), "k_norm": (hd,), "o": (Hq * hd, D),
            }
        else:
            layer["gdn"] = {
                "qkvz": (D, 2 * Hk * dk + 2 * Hv * dv), "ba": (D, 2 * Hv),
                "conv": (c.linear_conv_kernel_dim, c.conv_dim), "dt_bias": (Hv,), "A_log": (Hv,),
                "norm": (dv,), "out": (Hv * dv, D),
            }
        layer["moe"] = {
            "router": (D, E), "gate": (Eh, D, F), "up": (Eh, D, F), "down": (Eh, F, D),
            "shared_gate": (D, Fs), "shared_up": (D, Fs), "shared_down": (Fs, D), "shared_router": (D, 1),
        }
        out[f"layers_{l}"] = layer
    return out


def init_params(key, c: Qwen3NextConfig) -> Dict[str, Any]:
    """The family's initialisation: normal(0.02) products, zero ``1 + w`` norm
    weights, a plain one for the gated norm, ``A`` uniform in (0, 16)."""
    shapes = param_shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for k, (path, shape) in zip(keys, flat):
        name = str(getattr(path[-1], "key", path[-1]))
        owner = str(getattr(path[-2], "key", "")) if len(path) > 1 else ""
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, f32) if owner == "gdn" else jnp.zeros(shape, f32))
        elif name == "dt_bias":
            leaves.append(jnp.ones(shape, f32))
        elif name == "A_log":
            leaves.append(jnp.log(jax.random.uniform(k, shape, f32, 1e-3, 16.0)))
        elif name == "conv":
            leaves.append(jax.random.normal(k, shape, f32) * (1.0 / math.sqrt(shape[0])))
        else:
            leaves.append(jax.random.normal(k, shape, f32) * 0.02)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps, plain: bool = False):
    x = x.astype(f32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * (w if plain else 1.0 + w)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _rope(x, pos, c: Qwen3NextConfig):
    """Rotate the first ``rotary_dim`` of each head; ``x`` ``[..., H, hd]``,
    ``pos`` the shape of ``x`` without its last two axes."""
    r = c.rotary_dim
    inv = 1.0 / (c.rope_theta ** (jnp.arange(0, r, 2, dtype=f32) / r))
    ang = pos.astype(f32)[..., None, None] * inv  # [..., 1, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., : r // 2], x[..., r // 2 : r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def segment_positions(reset):
    """``[B, L]`` resets -> (segment id, position inside the segment)."""
    L = reset.shape[1]
    seg = jnp.cumsum(reset.astype(jnp.int32), 1)
    idx = jnp.arange(L, dtype=jnp.int32)[None]
    start = jax.lax.cummax(jnp.where(reset > 0, idx, 0), axis=1)
    return seg, idx - start


# -- the expert layer (``models/moe.py``, shared with the other cores) ----------


def moe(p, x, c: Qwen3NextConfig, dtype, rows: int = 1) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The shared expert layer as this model has it: the ten largest
    renormalised, a shared expert behind its own ``sigmoid`` gate."""
    return _moe.moe(p, x, c.moe_spec, dtype, rows)


# -- Gated DeltaNet ------------------------------------------------------------


def _gdn_project(p, x, c: Qwen3NextConfig, dtype):
    Hk, Hv, dk, dv = c.linear_num_key_heads, c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    qkvz = _mm(x, p["qkvz"], dtype)
    ba = _mm(x, p["ba"], dtype)
    qkv, z = qkvz[..., : c.conv_dim], qkvz[..., c.conv_dim:]
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    return qkv, z.reshape(z.shape[:-1] + (Hv, dv)), beta, g


def _gdn_heads(qkv, c: Qwen3NextConfig):
    """Convolved, activated channels -> q, k (normalised, one key head to
    ``Hv / Hk`` value heads) and v, each ``[..., Hv, d]``."""
    Hk, Hv, dk, dv = c.linear_num_key_heads, c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    lead = qkv.shape[:-1]
    q = _l2norm(qkv[..., : Hk * dk].reshape(lead + (Hk, dk))) * dk**-0.5
    k = _l2norm(qkv[..., Hk * dk : 2 * Hk * dk].reshape(lead + (Hk, dk)))
    v = qkv[..., 2 * Hk * dk:].reshape(lead + (Hv, dv))
    rep = Hv // Hk
    return jnp.repeat(q, rep, -2), jnp.repeat(k, rep, -2), v


def _gdn_out(p, o, z, c: Qwen3NextConfig, dtype):
    y = rms_norm(o, p["norm"], c.rms_norm_eps, plain=True) * jax.nn.silu(z)
    return _mm(y.reshape(y.shape[:-2] + (-1,)), p["out"], dtype)


def gdn_window(p, x, reset, c: Qwen3NextConfig, dtype):
    """``x`` ``[B, L, D]`` -> ``(y, state)``; ``state`` holds, for every chunk
    boundary, the delta-rule state and the convolution's tail before it."""
    B, L, _ = x.shape
    K = c.linear_conv_kernel_dim
    qkv, z, beta, g = _gdn_project(p, x, c, dtype)
    # a causal depthwise convolution that does not reach over a reset
    seg, _ = segment_positions(reset)
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    seg_p = jnp.pad(seg, ((0, 0), (K - 1, 0)), constant_values=-1)
    conv = jnp.zeros_like(qkv)
    for j in range(K):
        same = (seg_p[:, j : j + L] == seg)[..., None]
        conv = conv + jnp.where(same, padded[:, j : j + L], 0.0) * p["conv"][j].astype(f32)
    q, k, v = _gdn_heads(jax.nn.silu(conv), c)
    with jax.named_scope("delta_rule"):
        o, _, S_before = delta_rule.chunked(q, k, v, g, beta, reset, chunk=c.chunk, dtype=dtype)
    N = L // c.chunk
    # the K-1 pre-convolution rows before each boundary token, zero where they
    # belong to another segment than the token (a reset at or before it)
    at = jnp.arange(N) * c.chunk
    tail_idx = at[:, None] + jnp.arange(K - 1)[None]  # into ``padded``
    tail = padded[:, tail_idx]  # [B, N, K-1, C]
    tail = jnp.where((seg_p[:, tail_idx] == seg[:, at][..., None])[..., None], tail, 0.0)
    return _gdn_out(p, o, z, c, dtype), {"S": S_before, "conv": tail}


def gdn_decode(p, x, state, c: Qwen3NextConfig, dtype):
    """One token per stream: ``x`` ``[R, S, D]``, ``state`` ``{"S", "conv"}``."""
    qkv, z, beta, g = _gdn_project(p, x, c, dtype)
    taps = jnp.concatenate([state["conv"], qkv[..., None, :]], -2)  # [R, S, K, C]
    conv = jnp.sum(taps * p["conv"].astype(f32), -2)
    q, k, v = _gdn_heads(jax.nn.silu(conv), c)
    S, o = delta_rule.step(state["S"], q, k, v, g, beta)
    return _gdn_out(p, o, z, c, dtype), {"S": S, "conv": taps[..., 1:, :]}


# -- gated attention -----------------------------------------------------------


def _attn_project(p, x, pos, c: Qwen3NextConfig, dtype):
    Hq, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lead = x.shape[:-1]
    qg = _mm(x, p["q"], dtype).reshape(lead + (Hq, 2 * hd))
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(x, p["k"], dtype).reshape(lead + (Hkv, hd))
    v = _mm(x, p["v"], dtype).reshape(lead + (Hkv, hd))
    q = _rope(rms_norm(q, p["q_norm"], c.rms_norm_eps), pos, c)
    k = _rope(rms_norm(k, p["k_norm"], c.rms_norm_eps), pos, c)
    return q, gate, k, v


def attn_window(p, x, reset, c: Qwen3NextConfig, dtype):
    B, L, _ = x.shape
    Hq, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    seg, pos = segment_positions(reset)
    q, gate, k, v = _attn_project(p, x, pos, c, dtype)
    qh = q.reshape(B, L, Hkv, Hq // Hkv, hd).astype(dtype)
    kd, vd = k.astype(dtype), v.astype(dtype)
    idx = jnp.arange(L)
    Q = min(L, 256)  # queries a block: the logits of a whole window would not fit

    @jax.checkpoint
    def block(i):
        at = i * Q + jnp.arange(Q)
        q_b = jax.lax.dynamic_slice_in_dim(qh, i * Q, Q, 1)
        seg_b = jax.lax.dynamic_slice_in_dim(seg, i * Q, Q, 1)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", q_b, kd, preferred_element_type=f32) * hd**-0.5
        mask = (at[:, None] >= idx[None, :])[None] & (seg_b[:, :, None] == seg[:, None, :])
        w = jax.nn.softmax(jnp.where(mask[:, None, None], logits, -1e30), -1).astype(dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", w, vd, preferred_element_type=f32)

    o = jax.lax.map(block, jnp.arange(L // Q))  # [L/Q, B, Q, G, r, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(B, L, Hq, hd)
    y = _mm((o * jax.nn.sigmoid(gate)).reshape(B, L, Hq * hd), p["o"], dtype)
    return y, {"k": k.astype(dtype), "v": v.astype(dtype)}


def attn_decode(p, x, state, pos, rope_pos, context, c: Qwen3NextConfig, dtype):
    """``x`` ``[R, S, D]``; ``state`` the streams' own ring ``{"k", "v"}``
    ``[R, S, Lo, Hkv, hd]``; ``pos`` ``[R, S]`` tokens written to it so far;
    ``context`` ``None`` or ``(k, v [R, Lc, Hkv, hd], mask [R, S, Lc])``."""
    R, S, _ = x.shape
    Hq, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q, gate, k, v = _attn_project(p, x, rope_pos, c, dtype)
    Lo = state["k"].shape[2]
    slot = jax.nn.one_hot(pos % Lo, Lo, dtype=jnp.bool_)[..., None, None]  # [R, S, Lo, 1, 1]
    own_k = jnp.where(slot, k.astype(dtype)[:, :, None], state["k"])
    own_v = jnp.where(slot, v.astype(dtype)[:, :, None], state["v"])
    own_mask = jnp.arange(Lo)[None, None] < jnp.minimum(pos + 1, Lo)[..., None]
    qh = q.reshape(R, S, Hkv, Hq // Hkv, hd).astype(dtype)
    logits = jnp.einsum("rsgqd,rskgd->rsgqk", qh, own_k, preferred_element_type=f32)
    logits = jnp.where(own_mask[:, :, None, None], logits * hd**-0.5, -1e30)
    values = [("rsgqk,rskgd->rsgqd", own_v)]
    if context is not None:
        ck, cv, cmask = context
        cl = jnp.einsum("rsgqd,rkgd->rsgqk", qh, ck, preferred_element_type=f32)
        logits = jnp.concatenate([jnp.where(cmask[:, :, None, None], cl * hd**-0.5, -1e30), logits], -1)
        values.insert(0, ("rsgqk,rkgd->rsgqd", cv))
    w = jax.nn.softmax(logits, -1).astype(dtype)
    o, at = 0.0, 0
    for spec, val in values:
        n = val.shape[-3]
        o = o + jnp.einsum(spec, w[..., at : at + n], val, preferred_element_type=f32)
        at += n
    o = o.reshape(R, S, Hq, hd)
    y = _mm((o * jax.nn.sigmoid(gate)).reshape(R, S, Hq * hd), p["o"], dtype)
    return y, {"k": own_k, "v": own_v}


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def embed(params, tokens):
    return params["embed"][tokens].astype(f32)


def head_logits(params, h, dtype, scope: str = SCOPE):
    """The untied head over final-norm outputs, in f32."""
    with jax.named_scope(f"{scope}/head"):
        return _mm(h, params["head"], dtype)


def window(params, tokens, reset, c: Qwen3NextConfig, dtype=f32, scope: str = SCOPE):
    """A whole window. ``tokens``/``reset`` ``[B, L]``, ``L`` a multiple of the
    chunk. Returns ``(h [B, L, D] after the final norm, states, stats)``. Every
    block is rematerialised in the backward pass."""
    B, L = tokens.shape
    with jax.named_scope(f"{scope}/head"):
        x = embed(params, tokens)
    states, total = {}, None
    for l in range(c.num_hidden_layers):
        p = params[f"layers_{l}"]

        def block(p, x, l=l):
            h = rms_norm(x, p["input_norm"], c.rms_norm_eps)
            if c.is_attention(l):
                with jax.named_scope(f"{scope}/attn"):
                    y, st = attn_window(p["attn"], h, reset, c, dtype)
            else:
                with jax.named_scope(f"{scope}/gdn"):
                    y, st = gdn_window(p["gdn"], h, reset, c, dtype)
            x = x + y
            with jax.named_scope(f"{scope}/moe"):
                h = rms_norm(x, p["post_norm"], c.rms_norm_eps)
                y, stats = moe(p["moe"], h.reshape(B * L, -1), c, dtype, rows=B)
            return x + y.reshape(B, L, -1), st, stats

        x, st, stats = jax.checkpoint(block)(p, x)
        states[f"layers_{l}"] = st
        total = _moe.add_stats(total, stats)
    with jax.named_scope(f"{scope}/head"):
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    # tiles (a head's chunk) of the delta rule that its kernels take, the WY build's and the
    # inter-chunk pass's: every delta-rule layer's forward pass, its rematerialisation and
    # its transpose (0 in the XLA form)
    key_shape = (B, L, c.linear_num_value_heads, c.linear_key_head_dim)
    passes = 3.0 * sum(not c.is_attention(l) for l in range(c.num_hidden_layers))
    return h, states, {
        **total,
        "delta_rule_fused_tiles": passes * delta_rule.fused_tiles(key_shape, c.linear_value_head_dim, c.chunk),
        "delta_rule_scan_fused_tiles": passes * delta_rule.scan_fused_tiles(key_shape, c.linear_value_head_dim, c.chunk),
    }


def decode(params, state, tokens, c: Qwen3NextConfig, dtype=f32, context=None, scope: str = SCOPE):
    """One token per stream. ``tokens`` ``[R, S]``; ``state`` from
    :func:`init_state` or :func:`boundary_state`; ``context`` maps an attention
    layer's name to ``(k, v, mask)``. Returns ``(h [R, S, D], state, stats)``."""
    R, S = tokens.shape
    with jax.named_scope(f"{scope}/head"):
        x = embed(params, tokens)
    new_state: Dict[str, Any] = {"pos": state["pos"] + 1, "rope_pos": state["rope_pos"] + 1}
    total = None
    for l in range(c.num_hidden_layers):
        name = f"layers_{l}"
        p = params[name]
        h = rms_norm(x, p["input_norm"], c.rms_norm_eps)
        if c.is_attention(l):
            with jax.named_scope(f"{scope}/attn"):
                y, st = attn_decode(p["attn"], h, state[name], state["pos"], state["rope_pos"],
                                    None if context is None else context.get(name), c, dtype)
        else:
            with jax.named_scope(f"{scope}/gdn"):
                y, st = gdn_decode(p["gdn"], h, state[name], c, dtype)
        x = x + y
        with jax.named_scope(f"{scope}/moe"):
            h = rms_norm(x, p["post_norm"], c.rms_norm_eps)
            y, stats = moe(p["moe"], h.reshape(R * S, -1), c, dtype)
        x = x + y.reshape(R, S, -1)
        new_state[name] = st
        total = _moe.add_stats(total, stats)
    with jax.named_scope(f"{scope}/head"):
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return h, new_state, total


def balance_step(params, load, c: Qwen3NextConfig):
    """Nothing moves outside the gradient: this model balances its experts by
    the loss's load-balancing term (:meth:`Qwen3NextConfig.balance_loss`).
    Returns ``(params, what the step reports)``."""
    return params, {}


def init_state(c: Qwen3NextConfig, R: int, S: int, cache_len: Optional[int] = None, dtype=f32):
    """Per-stream state at an episode's start: all zero."""
    Lo = c.cache_len if cache_len is None else int(cache_len)
    state: Dict[str, Any] = {"pos": jnp.zeros((R, S), jnp.int32), "rope_pos": jnp.zeros((R, S), jnp.int32)}
    for l in range(c.num_hidden_layers):
        if c.is_attention(l):
            kv = (R, S, Lo, c.num_key_value_heads, c.head_dim)
            state[f"layers_{l}"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
        else:
            state[f"layers_{l}"] = {
                "S": jnp.zeros((R, S, c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim), f32),
                "conv": jnp.zeros((R, S, c.linear_conv_kernel_dim - 1, c.conv_dim), f32),
            }
    return state


def reset_state(state, mask):
    """Zero the streams where ``mask`` ``[R, S]`` is set (an episode ended)."""
    def zero(x):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
        return jnp.where(m, jnp.zeros((), x.dtype), x)

    return jax.tree_util.tree_map(zero, state)


def boundary_state(states, reset, c: Qwen3NextConfig, own_len: int, dtype=f32):
    """Decode state and attention context at every chunk boundary of a window
    pass: streams ``[B, N]`` (``N`` boundaries a row), each as the pass had it
    before the boundary's token. ``own_len`` sizes the streams' own cache."""
    B, L = reset.shape
    N = L // c.chunk
    at = jnp.arange(N) * c.chunk
    seg, pos = segment_positions(reset)
    # a boundary token that is itself an episode's first starts from nothing:
    # no state, no context, position 0 (``seg`` and ``pos`` count its reset in)
    fresh = reset[:, at] > 0
    state = init_state(c, B, N, own_len, dtype)
    state["rope_pos"] = pos[:, at].astype(jnp.int32)
    context = {}
    for l in range(c.num_hidden_layers):
        name = f"layers_{l}"
        st = states[name]
        if c.is_attention(l):
            mask = (jnp.arange(L)[None, None] < at[None, :, None]) & (seg[:, None, :] == seg[:, at][..., None])
            context[name] = (st["k"], st["v"], mask)
        else:
            S = jnp.moveaxis(st["S"], 0, 1)
            state[name] = {"S": jnp.where(fresh[..., None, None, None], 0.0, S), "conv": st["conv"]}
    return state, context
