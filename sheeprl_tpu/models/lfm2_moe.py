"""The LFM2-MoE decoder as a sequence core: a gated short convolution or
grouped-query attention in every layer (``layer_types``), a dense SwiGLU MLP in
the first ``num_dense_layers`` layers and a sparse expert layer
(``models/moe.py``) in the others, the head tied to the embedding.

Plain functions over a parameter dict (``init_params`` names every leaf), in
two entry points that must agree:

- :func:`window` — a whole packed window ``[B, L]`` at once. The convolution is
  ``conv_L_cache`` shifted, masked products, so that it never reaches over an
  episode's first token; causal attention is masked to each token's own episode
  segment, a block of queries at a time, and rotary positions restart at a
  segment's first token.
- :func:`decode` — one token per stream against per-stream state of two kinds
  and very unequal size: a convolution layer keeps the ``conv_L_cache - 1``
  gated rows ``B * u`` before the token (its whole recurrent state), an
  attention layer a key-value ring, so a stream may run longer than the ring.
  Streams are ``[R, S]``: ``S`` streams share row ``r``'s *context* keys and
  values (imagination starts of one replay row); acting has ``S = 1`` and no
  context.

Equations follow the family's published implementation (``model_type:
lfm2_moe``): RMSNorm with a plain weight; the operator ``[B, C, u] = W_in x``,
``y = W_out (C * conv(B * u))`` with a causal depthwise convolution and no
activation anywhere; queries and keys RMS-normalised per head and then rotated
over the whole head (pairs ``(i, i + d/2)``); the router a ``sigmoid`` of every
expert, the ``k`` chosen by ``score + expert_bias``, their weights the scores
themselves over their sum ``+ 1e-6``; no shared expert. The bias gets no
gradient: :func:`balance_step` moves it after the optimiser's step, against
each expert's load (arXiv:2408.15664), and nothing balances through the loss.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models import moe as _moe
from sheeprl_tpu.models.moe import mm as _mm
from sheeprl_tpu.models.qwen3_next import segment_positions  # packing is the same for every core

f32 = jnp.float32
#: the ``jax.named_scope`` the stack's parts (``conv``, ``attn``, ``mlp``, ``moe``,
#: ``head``) are named under, unless the caller gives its own
SCOPE = "core"
#: one-token statistics ``seq_agent`` sums over imagination's steps -> the run counter each feeds
DECODE_COUNTS = {"held_pairs": "imagination_pairs", "experts_hit": "imagination_experts_hit"}
#: window-pass statistics reported as run counters beside the expert layer's
WINDOW_COUNTS = ("attended_pairs", "router_max_load")
#: the published model's 24 layers
LAYER_TYPES = tuple("full_attention" if l in (2, 6, 10, 14, 18, 21) else "conv" for l in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = LAYER_TYPES  # the first ``num_hidden_layers`` are built
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    num_experts: int = 32  # the router's outputs
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    bias_update_rate: float = 0.001  # the balance step's (the config gives the bias, not how it is trained); goes with the run's length
    vocab_size: int = 65_536
    held_index: int = 0  # this chip's share of each layer's experts: (index, of)
    held_of: int = 1
    chunk: int = 64  # the stride of imagination starts
    cache_len: int = 1024

    def __post_init__(self):
        kinds = tuple(self.layer_types)[: self.num_hidden_layers]
        if len(kinds) < self.num_hidden_layers or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {kinds} do not name {self.num_hidden_layers} conv or full_attention layers")
        object.__setattr__(self, "layer_types", kinds)

    @property
    def moe_spec(self) -> _moe.MoESpec:
        return _moe.MoESpec(
            self.num_experts, self.num_experts_per_tok, self.held_index, self.held_of,
            normalize=self.norm_topk_prob, scale=self.routed_scaling_factor, score="sigmoid",
            select_bias=self.use_expert_bias, normalize_eps=1e-6, shared=False,
        )

    @property
    def experts_held(self) -> int:
        return self.moe_spec.experts_held

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == "full_attention"

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def balance_loss(self, aux_sum):
        """Nothing: this model balances outside the gradient (:func:`balance_step`)."""
        return 0.0

    @classmethod
    def from_mapping(cls, m) -> "Lfm2MoeConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: tuple(m[k]) if k == "layer_types" else m[k] for k in m if k in names})


#: the name ``seq_agent`` asks every core module for
Config = Lfm2MoeConfig


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def param_shapes(c: Lfm2MoeConfig) -> Dict[str, Any]:
    D, H, Hkv, hd = c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim
    E, Eh, F = c.num_experts, c.experts_held, c.moe_intermediate_size
    out: Dict[str, Any] = {"embed": (c.vocab_size, D), "embedding_norm": (D,)}
    for l in range(c.num_hidden_layers):
        layer: Dict[str, Any] = {"operator_norm": (D,), "ffn_norm": (D,)}
        if c.is_attention(l):
            layer["attn"] = {"q": (D, H * hd), "k": (D, Hkv * hd), "v": (D, Hkv * hd), "q_layernorm": (hd,),
                             "k_layernorm": (hd,), "o": (H * hd, D)}
        else:
            layer["conv"] = {"in": (D, 3 * D), "conv": (c.conv_L_cache, D), "out": (D, D)}
        if c.is_dense(l):
            layer["mlp"] = {"gate": (D, c.intermediate_size), "up": (D, c.intermediate_size),
                            "down": (c.intermediate_size, D)}
        else:
            layer["moe"] = {"router": (D, E), "expert_bias": (E,), "gate": (Eh, D, F), "up": (Eh, D, F),
                            "down": (Eh, F, D)}
        out[f"layers_{l}"] = layer
    return out


def init_params(key, c: Lfm2MoeConfig) -> Dict[str, Any]:
    """The family's initialisation: normal(0.02) products, norm weights one,
    the convolution by its taps, the selection bias zero."""
    shapes = param_shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for k, (path, shape) in zip(keys, flat):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, f32))
        elif name == "expert_bias":
            leaves.append(jnp.zeros(shape, f32))
        elif name == "conv":
            leaves.append(jax.random.normal(k, shape, f32) * shape[0] ** -0.5)
        else:
            leaves.append(jax.random.normal(k, shape, f32) * 0.02)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, pos, c: Lfm2MoeConfig):
    """Rotate the whole head; ``x`` ``[..., H, hd]``, ``pos`` the shape of
    ``x`` without its last two axes; pairs ``(i, i + hd/2)``."""
    hd = x.shape[-1]
    inv = 1.0 / (c.rope_theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = pos.astype(f32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dense_mlp(p, x, dtype):
    return _mm(jax.nn.silu(_mm(x, p["gate"], dtype)) * _mm(x, p["up"], dtype), p["down"], dtype)


# -- the gated short convolution --------------------------------------------------


def conv_window(p, x, reset, c: Lfm2MoeConfig, dtype, scope: str = SCOPE):
    """``x`` ``[B, L, D]`` -> ``(y, {"conv": the ``K - 1`` gated rows before
    every ``chunk``-th token [B, N, K - 1, D]})``, rows of another episode than
    the token's zero: a one-token stream goes on from there."""
    B, L, D = x.shape
    K = c.conv_L_cache
    with jax.named_scope(f"{scope}/conv"):
        bcu = _mm(x, p["in"], dtype)
        seg, _ = segment_positions(reset)
        seg_p = jnp.pad(seg, ((0, 0), (K - 1, 0)), constant_values=-1)
        with jax.named_scope("gate_conv"):
            gated = bcu[..., :D] * bcu[..., 2 * D:]
            padded = jnp.pad(gated, ((0, 0), (K - 1, 0), (0, 0)))
            mixed = jnp.zeros_like(gated)
            for j in range(K):  # tap j reads the row K - 1 - j before the token, where that row is of its episode
                same = (seg_p[:, j : j + L] == seg)[..., None]
                mixed = mixed + jnp.where(same, padded[:, j : j + L], 0.0) * p["conv"][j].astype(f32)
            y = bcu[..., D : 2 * D] * mixed
        at = jnp.arange(L // c.chunk) * c.chunk
        tail_idx = at[:, None] + jnp.arange(K - 1)[None]  # into ``padded``
        tail = jnp.where((seg_p[:, tail_idx] == seg[:, at][..., None])[..., None], padded[:, tail_idx], 0.0)
        return _mm(y, p["out"], dtype), {"conv": tail}


def conv_decode(p, x, state, c: Lfm2MoeConfig, dtype, scope: str = SCOPE):
    """One token per stream: ``x`` ``[R, S, D]``, ``state`` ``{"conv": [R, S, K - 1, D]}``."""
    D = x.shape[-1]
    with jax.named_scope(f"{scope}/conv"):
        bcu = _mm(x, p["in"], dtype)
        with jax.named_scope("gate_conv"):
            taps = jnp.concatenate([state["conv"], (bcu[..., :D] * bcu[..., 2 * D:])[..., None, :]], -2)
            y = bcu[..., D : 2 * D] * jnp.sum(taps * p["conv"].astype(f32), -2)
        return _mm(y, p["out"], dtype), {"conv": taps[..., 1:, :]}


# -- grouped-query attention --------------------------------------------------------


def _attn_project(p, x, pos, c: Lfm2MoeConfig, dtype):
    H, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lead = x.shape[:-1]
    q = _mm(x, p["q"], dtype).reshape(lead + (H, hd))
    k = _mm(x, p["k"], dtype).reshape(lead + (Hkv, hd))
    v = _mm(x, p["v"], dtype).reshape(lead + (Hkv, hd))
    q = _rope(rms_norm(q, p["q_layernorm"], c.norm_eps), pos, c)
    k = _rope(rms_norm(k, p["k_layernorm"], c.norm_eps), pos, c)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def attn_window(p, x, reset, c: Lfm2MoeConfig, dtype, scope: str = SCOPE):
    """``x`` ``[B, L, D]`` -> ``(y, {"k", "v"}: the pass's own keys and values
    [B, L, Hkv, hd])``. Scores and values are taken a block of queries at a time
    (the logits of a whole window would not fit), each against the keys up to
    its own end: a block after the causal mask's edge is never computed."""
    B, L, _ = x.shape
    H, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope(f"{scope}/attn"):
        seg, pos = segment_positions(reset)
        q, k, v = _attn_project(p, x, pos, c, dtype)
        q = q.reshape(B, L, Hkv, H // Hkv, hd)
    Q = min(L, 256)

    @jax.checkpoint
    def block(q_b, k_b, v_b, seg_q, seg_k, first):
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", q_b, k_b, preferred_element_type=f32) * hd**-0.5
        causal = (first + jnp.arange(q_b.shape[1]))[:, None] >= jnp.arange(k_b.shape[1])[None, :]
        mask = causal[None] & (seg_q[:, :, None] == seg_k[:, None, :])
        w = jax.nn.softmax(jnp.where(mask[:, None, None], logits, -1e30), -1).astype(dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", w, v_b, preferred_element_type=f32)

    with jax.named_scope(f"{scope}/attn/scores"):
        o = jnp.concatenate([
            block(q[:, i : i + Q], k[:, : i + Q], v[:, : i + Q], seg[:, i : i + Q], seg[:, : i + Q], i)
            for i in range(0, L, Q)
        ], 1)
    with jax.named_scope(f"{scope}/attn"):
        return _mm(o.reshape(B, L, H * hd), p["o"], dtype), {"k": k, "v": v}


def attn_decode(p, x, state, pos, rope_pos, context, c: Lfm2MoeConfig, dtype, scope: str = SCOPE):
    """``x`` ``[R, S, D]``; ``state`` the streams' own ring ``{"k", "v"}``
    ``[R, S, Lo, Hkv, hd]``; ``pos`` ``[R, S]`` tokens written to it so far;
    ``context`` ``None`` or ``(k, v [R, Lc, Hkv, hd], mask [R, S, Lc])``."""
    R, S, _ = x.shape
    H, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope(f"{scope}/attn"):
        q, k, v = _attn_project(p, x, rope_pos, c, dtype)
        Lo = state["k"].shape[2]
        slot = jax.nn.one_hot(pos % Lo, Lo, dtype=jnp.bool_)[..., None, None]  # [R, S, Lo, 1, 1]
        own_k = jnp.where(slot, k[:, :, None], state["k"])
        own_v = jnp.where(slot, v[:, :, None], state["v"])
        own_mask = jnp.arange(Lo)[None, None] < jnp.minimum(pos + 1, Lo)[..., None]
        q = q.reshape(R, S, Hkv, H // Hkv, hd)
        logits = jnp.einsum("rsgqd,rskgd->rsgqk", q, own_k, preferred_element_type=f32)
        logits = jnp.where(own_mask[:, :, None, None], logits * hd**-0.5, -1e30)
        if context is not None:
            ck, cv, cmask = context
            ahead = jnp.einsum("rsgqd,rkgd->rsgqk", q, ck, preferred_element_type=f32)
            logits = jnp.concatenate([jnp.where(cmask[:, :, None, None], ahead * hd**-0.5, -1e30), logits], -1)
        w = jax.nn.softmax(logits, -1).astype(dtype)
        o = jnp.einsum("rsgqk,rskgd->rsgqd", w[..., -Lo:], own_v, preferred_element_type=f32)
        if context is not None:
            o = o + jnp.einsum("rsgqk,rkgd->rsgqd", w[..., :-Lo], cv, preferred_element_type=f32)
        return _mm(o.reshape(R, S, H * hd), p["o"], dtype), {"k": own_k, "v": own_v}


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def embed(params, tokens):
    return params["embed"][tokens].astype(f32)


def head_logits(params, h, dtype, scope: str = SCOPE):
    """The head over final-norm outputs, in f32: the embedding, tied."""
    with jax.named_scope(f"{scope}/head"):
        return jnp.einsum("...d,vd->...v", h.astype(dtype), params["embed"].astype(dtype), preferred_element_type=f32)


def _feed_forward(p, h, c: Lfm2MoeConfig, l: int, dtype, scope: str, rows: int):
    """Layer ``l``'s MLP over ``h`` ``[N, D]``: dense, or the expert layer and its statistics."""
    if c.is_dense(l):
        with jax.named_scope(f"{scope}/mlp"):
            return dense_mlp(p["mlp"], h, dtype), None
    with jax.named_scope(f"{scope}/moe"):
        return _moe.moe(p["moe"], h, c.moe_spec, dtype, rows=rows)


def window(params, tokens, reset, c: Lfm2MoeConfig, dtype=f32, scope: str = SCOPE):
    """A whole window. ``tokens``/``reset`` ``[B, L]``, ``L`` a multiple of the
    chunk. Returns ``(h [B, L, D] after the final norm, states, stats)``;
    ``stats`` are the expert layers' (``load`` a row a routing layer),
    ``router_max_load``, the largest load among all the router's outputs in any
    layer, and ``attended_pairs``, the query-key pairs inside an episode's
    segment over the attention layers. Every block is rematerialised in the
    backward pass."""
    B, L = tokens.shape
    with jax.named_scope(f"{scope}/head"):
        x = embed(params, tokens)
    states, total = {}, None
    for l in range(c.num_hidden_layers):

        def block(p, x, l=l):
            h = rms_norm(x, p["operator_norm"], c.norm_eps)
            if c.is_attention(l):
                y, st = attn_window(p["attn"], h, reset, c, dtype, scope)
            else:
                y, st = conv_window(p["conv"], h, reset, c, dtype, scope)
            x = x + y
            y, stats = _feed_forward(p, rms_norm(x, p["ffn_norm"], c.norm_eps).reshape(B * L, -1), c, l, dtype,
                                     scope, B)
            return x + y.reshape(B, L, -1), st, stats

        x, st, stats = jax.checkpoint(block)(params[f"layers_{l}"], x)
        states[f"layers_{l}"] = st
        total = total if stats is None else _moe.add_stats(total, stats)
    with jax.named_scope(f"{scope}/head"):
        h = rms_norm(x, params["embedding_norm"], c.norm_eps)
    _, pos = segment_positions(reset)
    layers = sum(c.is_attention(l) for l in range(c.num_hidden_layers))
    return h, states, dict(total, attended_pairs=layers * jnp.sum(pos + 1).astype(f32),
                           router_max_load=jnp.max(total["load"]))


def decode(params, state, tokens, c: Lfm2MoeConfig, dtype=f32, context=None, scope: str = SCOPE):
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
        h = rms_norm(x, p["operator_norm"], c.norm_eps)
        if c.is_attention(l):
            y, new_state[name] = attn_decode(p["attn"], h, state[name], state["pos"], state["rope_pos"],
                                             None if context is None else context.get(name), c, dtype, scope)
        else:
            y, new_state[name] = conv_decode(p["conv"], h, state[name], c, dtype, scope)
        x = x + y
        y, stats = _feed_forward(p, rms_norm(x, p["ffn_norm"], c.norm_eps).reshape(R * S, -1), c, l, dtype, scope, 1)
        x = x + y.reshape(R, S, -1)
        total = total if stats is None else _moe.add_stats(total, stats)
    with jax.named_scope(f"{scope}/head"):
        h = rms_norm(x, params["embedding_norm"], c.norm_eps)
    return h, new_state, total


def balance_step(params, load, c: Lfm2MoeConfig):
    """The step training takes outside the gradient, after the optimiser's:
    every routing layer's selection bias moved against that step's load of its
    experts (``load`` ``[routing layers, E]``, summed over the data axis).
    Returns ``(params, what the step reports)``."""
    if not c.use_expert_bias:
        return params, {}
    params, layer, largest = dict(params), 0, 0.0
    for l in range(c.num_hidden_layers):
        if c.is_dense(l):
            continue
        name = f"layers_{l}"
        bias = _moe.balance_step(params[name]["moe"]["expert_bias"], load[layer], c.bias_update_rate)
        params[name] = {**params[name], "moe": {**params[name]["moe"], "expert_bias": bias}}
        largest, layer = jnp.maximum(largest, jnp.max(jnp.abs(bias))), layer + 1
    return params, {"expert_bias_abs_max": largest, "router_load": load}


def init_state(c: Lfm2MoeConfig, R: int, S: int, cache_len: Optional[int] = None, dtype=f32):
    """Per-stream state at an episode's start: zero rows before the first
    token in a convolution layer, an empty key-value ring in an attention layer."""
    Lo = c.cache_len if cache_len is None else int(cache_len)
    state: Dict[str, Any] = {"pos": jnp.zeros((R, S), jnp.int32), "rope_pos": jnp.zeros((R, S), jnp.int32)}
    for l in range(c.num_hidden_layers):
        if c.is_attention(l):
            kv = (R, S, Lo, c.num_key_value_heads, c.head_dim)
            state[f"layers_{l}"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
        else:
            state[f"layers_{l}"] = {"conv": jnp.zeros((R, S, c.conv_L_cache - 1, c.hidden_size), f32)}
    return state


def reset_state(state, mask):
    """Start the streams where ``mask`` ``[R, S]`` is set anew (an episode
    ended): the convolution's rows go to zero, and so do the counters, which
    hides every slot of a ring until it is written again."""
    out = {}
    for name, value in state.items():
        if name in ("pos", "rope_pos"):
            out[name] = jnp.where(mask, 0, value)
        elif "conv" in value:
            out[name] = {"conv": jnp.where(mask[..., None, None], 0.0, value["conv"])}
        else:
            out[name] = value
    return out


def boundary_state(states, reset, c: Lfm2MoeConfig, own_len: int, dtype=f32):
    """Decode state and attention context at every ``chunk``-th token of a
    window pass: streams ``[B, N]``, each as the pass had it before the
    boundary's token — the convolution's rows of the token's own episode, an
    empty ring of ``own_len`` tokens and, as context, the pass's keys and values
    of its row masked to the stream's episode before the token (a boundary
    token that is itself an episode's first sees nothing and starts at 0)."""
    B, L = reset.shape
    at = jnp.arange(L // c.chunk) * c.chunk
    seg, pos = segment_positions(reset)
    state = init_state(c, B, at.shape[0], own_len, dtype)
    state["rope_pos"] = pos[:, at].astype(jnp.int32)
    mask = (jnp.arange(L)[None, None] < at[None, :, None]) & (seg[:, None, :] == seg[:, at][..., None])
    context = {}
    for l in range(c.num_hidden_layers):
        name = f"layers_{l}"
        if c.is_attention(l):
            context[name] = (states[name]["k"], states[name]["v"], mask)
        else:
            state[name] = {"conv": states[name]["conv"]}
    return state, context
