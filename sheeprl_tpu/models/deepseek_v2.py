"""The DeepSeek-V2 decoder as a sequence core: multi-head latent attention
(MLA) in every layer, a dense SwiGLU MLP in the first ``first_k_dense_replace``
layers and a sparse expert layer (``models/moe.py``) in the others.

Plain functions over a parameter dict (``init_params`` names every leaf), in
two entry points that must agree:

- :func:`window` — a whole packed window ``[B, L]`` at once. The latent
  ``c = RMSNorm(x W_DKV[:, :r])`` is up-projected to per-head keys and values
  (``W_UKV``), the one rotary key all heads share is broadcast beside them,
  causal attention is masked to each token's own episode segment and rotary
  positions restart at a segment's first token.
- :func:`decode` — one token per stream against a **latent cache**: ``[c |
  rotated k_rope]``, ``kv_lora_rank + qk_rope_head_dim`` numbers a token and
  layer, in a ring. No per-head key or value is stored or rebuilt over the
  context: ``W_UK`` is absorbed into the query (``q_abs = q_nope W_UK``, a head
  ``kv_lora_rank`` wide), the scores are ``(q_abs . c + q_rope . k_rope) s``,
  the values are the latents themselves, and ``W_UV`` is applied to the
  attended latent. Streams are ``[R, S]``: ``S`` streams share row ``r``'s
  *context* (the window pass's own latent cache, masked to the stream's
  episode up to its boundary); acting has ``S = 1`` and no context.

Equations follow the family's published implementation (``model_type:
deepseek_v2``, ``q_lora_rank`` null): RMSNorm with a plain weight; queries and
keys ``[nope | rope]``; YaRN scaling of the rotary frequencies with its
softmax-scale factor (:func:`yarn_inv_freq`, :func:`softmax_scale`), part of
the model at every position; softmax routing, the ``k`` largest as they are
(``norm_topk_prob`` false) times ``routed_scaling_factor``, ``n_shared_experts``
always-on experts as one MLP of their joint width, the sequence-wise balance
term. The rotary pairs are ``(i, i + d/2)`` (the published code reads the
projection's columns interleaved and permutes them to this: a permutation of
``W_Q``'s and ``W_DKV``'s rotary columns, the same model on seeded weights).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models import moe as _moe
from sheeprl_tpu.models.moe import mm as _mm
from sheeprl_tpu.models.qwen3_next import segment_positions  # packing is the same for every core

f32 = jnp.float32
#: the ``jax.named_scope`` the stack's parts (``mla``, ``mlp``, ``moe``, ``head``)
#: are named under, unless the caller gives its own
SCOPE = "core"
#: one-token statistics ``seq_agent`` sums over imagination's steps -> the run counter each feeds
DECODE_COUNTS = {
    "held_pairs": "imagination_pairs", "experts_hit": "imagination_experts_hit",
    "context_tokens": "decode_context_tokens", "cache_tokens": "decode_cache_tokens",
}
#: window-pass statistics reported as run counters beside the expert layer's
WINDOW_COUNTS = ("attended_pairs",)


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    intermediate_size: int = 10_944
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10_000.0
    # ``rope_scaling`` (type yarn), flattened by :meth:`from_mapping`
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 64  # the router's outputs
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1408
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    aux_loss_alpha: float = 0.001
    vocab_size: int = 102_400
    held_index: int = 0  # this chip's share of each layer's experts: (index, of)
    held_of: int = 1
    chunk: int = 64  # the stride of imagination starts
    cache_len: int = 1024

    @property
    def moe_spec(self) -> _moe.MoESpec:
        return _moe.MoESpec(
            self.n_routed_experts, self.num_experts_per_tok, self.held_index, self.held_of,
            normalize=self.norm_topk_prob, scale=self.routed_scaling_factor, shared_gate=False, aux_per_choice=True,
        )

    @property
    def experts_held(self) -> int:
        return self.moe_spec.experts_held

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    def balance_loss(self, aux_sum):
        """Every expert layer adds ``alpha`` times its own term (the published form)."""
        return self.aux_loss_alpha * aux_sum

    @property
    def latent_dim(self) -> int:
        """What the cache keeps of a token in a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def from_mapping(cls, m) -> "DeepseekV2Config":
        names = {f.name for f in dataclasses.fields(cls)}
        given = {k: m[k] for k in m if k in names}
        scaling = m.get("rope_scaling")
        if scaling:
            if scaling.get("type", "yarn") != "yarn":
                raise ValueError(f"rope_scaling.type {scaling['type']!r}: this core knows yarn")
            given.update({f"rope_{k}": scaling[k] for k in scaling if f"rope_{k}" in names})
        return cls(**given)


#: the name ``seq_agent`` asks every core module for
Config = DeepseekV2Config


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def param_shapes(c: DeepseekV2Config) -> Dict[str, Any]:
    D, H, r = c.hidden_size, c.num_attention_heads, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    E, Eh, F = c.n_routed_experts, c.experts_held, c.moe_intermediate_size
    Fs = c.n_shared_experts * F
    out: Dict[str, Any] = {"embed": (c.vocab_size, D), "final_norm": (D,), "head": (D, c.vocab_size)}
    for l in range(c.num_hidden_layers):
        layer: Dict[str, Any] = {
            "input_norm": (D,), "post_norm": (D,),
            "mla": {"q": (D, H * (dn + dr)), "dkv": (D, r + dr), "kv_norm": (r,), "ukv": (r, H * (dn + dv)),
                    "o": (H * dv, D)},
        }
        if c.is_dense(l):
            layer["mlp"] = {"gate": (D, c.intermediate_size), "up": (D, c.intermediate_size),
                            "down": (c.intermediate_size, D)}
        else:
            layer["moe"] = {
                "router": (D, E), "gate": (Eh, D, F), "up": (Eh, D, F), "down": (Eh, F, D),
                "shared_gate": (D, Fs), "shared_up": (D, Fs), "shared_down": (Fs, D),
            }
        out[f"layers_{l}"] = layer
    return out


def init_params(key, c: DeepseekV2Config) -> Dict[str, Any]:
    """The family's initialisation: normal(0.02) products, norm weights one."""
    shapes = param_shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = [
        jnp.ones(shape, f32) if str(getattr(path[-1], "key", path[-1])).endswith("norm")
        else jax.random.normal(k, shape, f32) * 0.02
        for k, (path, shape) in zip(keys, flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(c: DeepseekV2Config) -> np.ndarray:
    """The rotary frequencies under YaRN: per pair ``i`` the blend of
    ``theta^(-2i/d)`` and that over ``factor``, by a linear ramp between the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations over the
    original context (frequencies below the first keep their own, above the
    second are interpolated)."""
    d, base, factor = c.qk_rope_head_dim, float(c.rope_theta), float(c.rope_factor)
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(rotations):
        return d * math.log(c.rope_original_max_position_embeddings / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(c.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(c.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(c: DeepseekV2Config) -> float:
    """``(d_nope + d_rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    m = yarn_mscale(c.rope_factor, c.rope_mscale_all_dim) if c.rope_mscale_all_dim else 1.0
    return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * m * m


def _rope(x, pos, c: DeepseekV2Config):
    """Rotate ``x`` ``[..., d_rope]`` by ``pos`` (broadcast against ``x``'s
    leading axes); pairs ``(i, i + d/2)``."""
    ang = pos.astype(f32)[..., None] * jnp.asarray(yarn_inv_freq(c))
    factor = yarn_mscale(c.rope_factor, c.rope_mscale) / yarn_mscale(c.rope_factor, c.rope_mscale_all_dim)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dense_mlp(p, x, dtype):
    return _mm(jax.nn.silu(_mm(x, p["gate"], dtype)) * _mm(x, p["up"], dtype), p["down"], dtype)


# -- multi-head latent attention -------------------------------------------------


def _mla_project(p, x, pos, c: DeepseekV2Config, dtype):
    """``x`` ``[..., D]`` at rotary positions ``pos`` ``[...]`` -> ``q_nope
    [..., H, d_nope]``, rotated ``q_rope [..., H, d_rope]`` and what the cache
    keeps of the token: ``[RMSNorm(c) | rotated k_rope]`` in the compute type."""
    H, r, dn, dr = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim
    q = _mm(x, p["q"], dtype).reshape(x.shape[:-1] + (H, dn + dr))
    ckr = _mm(x, p["dkv"], dtype)
    latent = rms_norm(ckr[..., :r], p["kv_norm"], c.rms_norm_eps)
    cached = jnp.concatenate([latent, _rope(ckr[..., r:], pos, c)], -1).astype(dtype)
    return q[..., :dn], _rope(q[..., dn:], pos[..., None], c), cached


def mla_window(p, x, reset, c: DeepseekV2Config, dtype, scope: str = SCOPE):
    """``x`` ``[B, L, D]`` -> ``(y, {"latent": the pass's own latent cache [B, L,
    r + d_rope]})``. Scores and values are taken a block of queries at a time
    (the logits of a whole window would not fit), each against the keys up to
    its own end: a block after the causal mask's edge is never computed."""
    B, L, _ = x.shape
    H, r, dn, dv = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.v_head_dim
    with jax.named_scope(f"{scope}/mla"):
        seg, pos = segment_positions(reset)
        q_nope, q_rope, cached = _mla_project(p, x, pos, c, dtype)
        kv = _mm(cached[..., :r], p["ukv"], dtype).reshape(B, L, H, dn + dv)
        k_rope = jnp.broadcast_to(cached[:, :, None, r:], (B, L, H, c.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :dn].astype(dtype), k_rope], -1)
        v = kv[..., dn:].astype(dtype)
        q = jnp.concatenate([q_nope, q_rope], -1).astype(dtype)
    Q = min(L, 256)
    scale = softmax_scale(c)

    @jax.checkpoint
    def block(q_b, k_b, v_b, seg_q, seg_k, first):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_b, k_b, preferred_element_type=f32) * scale
        causal = (first + jnp.arange(q_b.shape[1]))[:, None] >= jnp.arange(k_b.shape[1])[None, :]
        mask = causal[None] & (seg_q[:, :, None] == seg_k[:, None, :])
        w = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), -1).astype(dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v_b, preferred_element_type=f32)

    with jax.named_scope(f"{scope}/mla/scores"):
        o = jnp.concatenate([
            block(q[:, i : i + Q], k[:, : i + Q], v[:, : i + Q], seg[:, i : i + Q], seg[:, : i + Q], i)
            for i in range(0, L, Q)
        ], 1)
    with jax.named_scope(f"{scope}/mla"):
        y = _mm(o.reshape(B, L, H * dv), p["o"], dtype)
    return y, {"latent": cached}


def mla_decode(p, x, state, pos, rope_pos, context, c: DeepseekV2Config, dtype, scope: str = SCOPE):
    """The absorbed one-token path. ``x`` ``[R, S, D]``; ``state`` the streams'
    own ring ``{"latent": [R, S, Lo, r + d_rope]}``; ``pos`` ``[R, S]`` tokens
    written to it so far; ``context`` ``None`` or ``(latent [R, Lc, r + d_rope],
    mask [R, S, Lc])``. Returns ``(y, state, counts)``: ``context_tokens`` the
    latent positions the streams attended to, ``cache_tokens`` the positions
    that had to be read (a row's shared context once, up to the stream that
    sees most of it)."""
    R, S, _ = x.shape
    H, r, dn, dv = c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.v_head_dim
    with jax.named_scope(f"{scope}/mla"):
        q_nope, q_rope, cached = _mla_project(p, x, rope_pos, c, dtype)
        Lo = state["latent"].shape[2]
        slot = jax.nn.one_hot(pos % Lo, Lo, dtype=jnp.bool_)[..., None]  # [R, S, Lo, 1]
        own = jnp.where(slot, cached[:, :, None], state["latent"])
        own_mask = jnp.arange(Lo)[None, None] < jnp.minimum(pos + 1, Lo)[..., None]
        w_ukv = p["ukv"].reshape(r, H, dn + dv).astype(dtype)
        q_abs = jnp.einsum("rshd,chd->rshc", q_nope.astype(dtype), w_ukv[..., :dn], preferred_element_type=f32)
        q_lat = jnp.concatenate([q_abs, q_rope], -1).astype(dtype)  # [R, S, H, r + d_rope]
    scale = softmax_scale(c)
    with jax.named_scope(f"{scope}/mla/latent_decode"):
        logits = jnp.einsum("rshc,rskc->rshk", q_lat, own, preferred_element_type=f32)
        logits = jnp.where(own_mask[:, :, None], logits * scale, -1e30)
        if context is not None:
            c_lat, c_mask = context
            cl = jnp.einsum("rshc,rkc->rshk", q_lat, c_lat, preferred_element_type=f32)
            logits = jnp.concatenate([jnp.where(c_mask[:, :, None], cl * scale, -1e30), logits], -1)
        w = jax.nn.softmax(logits, -1).astype(dtype)
        # the values are the latents: the rotary columns ride along and are dropped from the result
        o_lat = jnp.einsum("rshk,rskc->rshc", w[..., -Lo:], own, preferred_element_type=f32)
        if context is not None:
            o_lat = o_lat + jnp.einsum("rshk,rkc->rshc", w[..., :-Lo], c_lat, preferred_element_type=f32)
        o_lat = o_lat[..., :r].astype(dtype)
    with jax.named_scope(f"{scope}/mla"):
        o = jnp.einsum("rshc,chd->rshd", o_lat, w_ukv[..., dn:], preferred_element_type=f32)
        y = _mm(o.reshape(R, S, H * dv), p["o"], dtype)
    seen = jnp.sum(own_mask, -1).astype(f32)  # [R, S]
    counts = {"context_tokens": jnp.sum(seen), "cache_tokens": jnp.sum(seen)}
    if context is not None:
        ahead = jnp.sum(c_mask, -1).astype(f32)
        counts = {"context_tokens": counts["context_tokens"] + jnp.sum(ahead),
                  "cache_tokens": counts["cache_tokens"] + jnp.sum(jnp.max(ahead, -1))}
    return y, {"latent": own}, counts


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def embed(params, tokens):
    return params["embed"][tokens].astype(f32)


def head_logits(params, h, dtype, scope: str = SCOPE):
    """The untied head over final-norm outputs, in f32."""
    with jax.named_scope(f"{scope}/head"):
        return _mm(h, params["head"], dtype)


def _feed_forward(p, h, c: DeepseekV2Config, l: int, dtype, scope: str, rows: int):
    """Layer ``l``'s MLP over ``h`` ``[N, D]``: dense, or the expert layer and its statistics."""
    if c.is_dense(l):
        with jax.named_scope(f"{scope}/mlp"):
            return dense_mlp(p["mlp"], h, dtype), None
    with jax.named_scope(f"{scope}/moe"):
        return _moe.moe(p["moe"], h, c.moe_spec, dtype, rows=rows)


def window(params, tokens, reset, c: DeepseekV2Config, dtype=f32, scope: str = SCOPE):
    """A whole window. ``tokens``/``reset`` ``[B, L]``. Returns ``(h [B, L, D]
    after the final norm, states, stats)``; ``stats`` are the expert layers'
    and ``attended_pairs``, the query-key pairs inside an episode's segment
    over all layers. Every block is rematerialised in the backward pass."""
    B, L = tokens.shape
    with jax.named_scope(f"{scope}/head"):
        x = embed(params, tokens)
    states, total = {}, None
    for l in range(c.num_hidden_layers):

        def block(p, x, l=l):
            y, st = mla_window(p["mla"], rms_norm(x, p["input_norm"], c.rms_norm_eps), reset, c, dtype, scope)
            x = x + y
            y, stats = _feed_forward(p, rms_norm(x, p["post_norm"], c.rms_norm_eps).reshape(B * L, -1), c, l, dtype,
                                     scope, B)
            return x + y.reshape(B, L, -1), st, stats

        x, st, stats = jax.checkpoint(block)(params[f"layers_{l}"], x)
        states[f"layers_{l}"] = st
        total = total if stats is None else _moe.add_stats(total, stats)
    with jax.named_scope(f"{scope}/head"):
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    _, pos = segment_positions(reset)
    total = dict(total or {}, attended_pairs=c.num_hidden_layers * jnp.sum(pos + 1).astype(f32))
    return h, states, total


def decode(params, state, tokens, c: DeepseekV2Config, dtype=f32, context=None, scope: str = SCOPE):
    """One token per stream. ``tokens`` ``[R, S]``; ``state`` from
    :func:`init_state` or :func:`boundary_state`; ``context`` maps a layer's
    name to ``(latent, mask)``. Returns ``(h [R, S, D], state, stats)``."""
    R, S = tokens.shape
    with jax.named_scope(f"{scope}/head"):
        x = embed(params, tokens)
    new_state: Dict[str, Any] = {"pos": state["pos"] + 1, "rope_pos": state["rope_pos"] + 1}
    total, counts = None, None
    for l in range(c.num_hidden_layers):
        name = f"layers_{l}"
        p = params[name]
        y, new_state[name], read = mla_decode(
            p["mla"], rms_norm(x, p["input_norm"], c.rms_norm_eps), state[name], state["pos"], state["rope_pos"],
            None if context is None else context[name], c, dtype, scope,
        )
        x = x + y
        y, stats = _feed_forward(p, rms_norm(x, p["post_norm"], c.rms_norm_eps).reshape(R * S, -1), c, l, dtype,
                                 scope, 1)
        x = x + y.reshape(R, S, -1)
        total = total if stats is None else _moe.add_stats(total, stats)
        counts = read if counts is None else {k: counts[k] + read[k] for k in read}
    with jax.named_scope(f"{scope}/head"):
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return h, new_state, {**(total or {}), **counts}


def balance_step(params, load, c: DeepseekV2Config):
    """Nothing moves outside the gradient: this model balances its experts by
    the loss's load-balancing term (:meth:`DeepseekV2Config.balance_loss`).
    Returns ``(params, what the step reports)``."""
    return params, {}


def init_state(c: DeepseekV2Config, R: int, S: int, cache_len: Optional[int] = None, dtype=f32):
    """Per-stream state at an episode's start: an empty latent ring a layer."""
    Lo = c.cache_len if cache_len is None else int(cache_len)
    state: Dict[str, Any] = {"pos": jnp.zeros((R, S), jnp.int32), "rope_pos": jnp.zeros((R, S), jnp.int32)}
    for l in range(c.num_hidden_layers):
        state[f"layers_{l}"] = {"latent": jnp.zeros((R, S, Lo, c.latent_dim), dtype)}
    return state


def reset_state(state, mask):
    """Drop the rings of the streams where ``mask`` ``[R, S]`` is set (an
    episode ended): their counters go to zero, which hides every slot until it
    is written again; the slots themselves are not rewritten."""
    zero = lambda x: jnp.where(mask, jnp.zeros((), x.dtype), x)
    return {**state, "pos": zero(state["pos"]), "rope_pos": zero(state["rope_pos"])}


def boundary_state(states, reset, c: DeepseekV2Config, own_len: int, dtype=f32):
    """Decode state and context at every ``chunk``-th token of a window pass:
    streams ``[B, N]``, each with an empty ring of ``own_len`` tokens of its
    own and, as context, the pass's latent cache of its row masked to the
    stream's own episode before the boundary's token (a boundary token that is
    itself an episode's first sees nothing and starts at position 0)."""
    B, L = reset.shape
    at = jnp.arange(L // c.chunk) * c.chunk
    seg, pos = segment_positions(reset)
    state = init_state(c, B, at.shape[0], own_len, dtype)
    state["rope_pos"] = pos[:, at].astype(jnp.int32)
    mask = (jnp.arange(L)[None, None] < at[None, :, None]) & (seg[:, None, :] == seg[:, at][..., None])
    context = {name: (st["latent"], mask) for name, st in states.items()}
    return state, context
