"""A sparse expert layer that is told which experts it holds, for every
sequence core (``qwen3_next.py``, ``deepseek_v2.py``, ``lfm2_moe.py``).

``held = (index, of)``: ``of`` chips share a layer's experts and this one is
``index`` of them. The router keeps all its outputs and its experts per token;
the layer computes its own experts' part for the token-expert pairs routed to
them, as grouped products over pairs sorted by expert (``jax.lax.ragged_dot``:
a Mosaic kernel on the TPU, XLA elsewhere), plus the shared expert, which every
chip computes alike. What absent experts would add is left out; no pair is
dropped (:func:`held_experts` walks the sorted pairs in windows).

What differs between the models is in :class:`MoESpec`: how the router scores
(a softmax over all experts, or a ``sigmoid`` of each alone), whether the ``k``
experts are chosen by the score plus a per-expert bias the weights never see
(an ``expert_bias`` leaf no gradient reaches: :func:`balance_step` moves it),
whether the chosen experts' weights are renormalised, the factor on the routed
sum, whether there is a shared expert and whether it sits behind a ``sigmoid``
gate of its own (a ``shared_router`` leaf) or is always on. The load-balancing
term is the same for all (Switch's ``E * sum_e f_e P_e`` taken row by row, over
the scores as shares of their sum); a model that normalises it otherwise does
so itself (:attr:`MoESpec.aux_per_choice`), and one that balances without a
loss leaves it out of its loss.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class MoESpec:
    num_experts: int  # the router's outputs
    num_experts_per_tok: int
    held_index: int = 0  # this chip's share of the experts: (index, of)
    held_of: int = 1
    normalize: bool = True  # the chosen experts' weights divided by their sum
    scale: float = 1.0  # the factor on the routed sum
    shared_gate: bool = True  # ``sigmoid(x @ shared_router)`` on the shared expert
    aux_per_choice: bool = False  # f_e counted per choice (divided by k), as DeepSeek's
    score: str = "softmax"  # or "sigmoid": every expert scored alone
    select_bias: bool = False  # the k are chosen by score + ``expert_bias``; the weights are the scores'
    normalize_eps: float = 0.0  # added to the chosen weights' sum before the division
    shared: bool = True  # a shared expert beside the routed ones

    @property
    def experts_held(self) -> int:
        if self.num_experts % self.held_of:
            raise ValueError(f"{self.num_experts} experts do not divide over {self.held_of} shares")
        return self.num_experts // self.held_of


def mm(x, w, dtype):
    """``x @ w`` with both operands in the compute type, accumulated in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=f32)


def _expert_mlp(xs, gate, up, down, sizes, valid, dtype):
    """The held experts over one window of sorted pairs: grouped products
    (``jax.lax.ragged_dot``; rows outside every group are not computed, so
    they are masked on both sides of each product)."""
    xs = jnp.where(valid, xs, 0)
    g = jax.lax.ragged_dot(xs, gate.astype(dtype), sizes, preferred_element_type=dtype)
    u = jax.lax.ragged_dot(xs, up.astype(dtype), sizes, preferred_element_type=dtype)
    hidden = jnp.where(valid, jax.nn.silu(g.astype(f32)) * u.astype(f32), 0).astype(dtype)
    y = jax.lax.ragged_dot(hidden, down.astype(dtype), sizes, preferred_element_type=dtype)
    return jnp.where(valid, y, 0)


def _window_of(i, tok, weight, cum, n_held, W):
    lo = i * W
    tok_w = jax.lax.dynamic_slice_in_dim(tok, lo, W)
    w_w = jax.lax.dynamic_slice_in_dim(weight, lo, W)
    valid = ((lo + jnp.arange(W)) < n_held)[:, None]
    sizes = jnp.clip(cum[1:], lo, lo + W) - jnp.clip(cum[:-1], lo, lo + W)
    return lo, tok_w, w_w, valid, sizes


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def held_experts(x, gate, up, down, weight, tok, sizes, W, dtype):
    """``sum_pairs weight * E_expert(x[token])`` over the pairs routed to held
    experts, sorted by expert: ``tok``/``weight`` ``[M]`` (``M`` a multiple of
    ``W``), ``sizes`` ``[E_held]`` pairs an expert. The pairs are taken ``W`` at
    a time, as many windows as hold them (one, unless routing is far from
    even), so no pair is dropped and no buffer has the worst case's size; the
    backward pass walks the same windows and recomputes each."""
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    n_held = cum[-1]

    def body(i, out):
        _, tok_w, w_w, valid, sizes_w = _window_of(i, tok, weight, cum, n_held, W)
        y = _expert_mlp(x[tok_w], gate, up, down, sizes_w, valid, dtype)
        return out.at[tok_w].add(y.astype(f32) * w_w[:, None])

    return jax.lax.fori_loop(0, (n_held + W - 1) // W, body, jnp.zeros(x.shape, f32))


def _held_experts_fwd(x, gate, up, down, weight, tok, sizes, W, dtype):
    return held_experts(x, gate, up, down, weight, tok, sizes, W, dtype), (x, gate, up, down, weight, tok, sizes)


def _held_experts_bwd(W, dtype, res, g):
    x, gate, up, down, weight, tok, sizes = res
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    n_held = cum[-1]

    def body(i, acc):
        dx, dgate, dup, ddown, dweight = acc
        lo, tok_w, w_w, valid, sizes_w = _window_of(i, tok, weight, cum, n_held, W)
        y, vjp = jax.vjp(lambda xs, a, b, d: _expert_mlp(xs, a, b, d, sizes_w, valid, dtype), x[tok_w], gate, up, down)
        g_w = jnp.where(valid, g[tok_w], 0)
        dxs, dg, du, dd = vjp((g_w * w_w[:, None]).astype(dtype))
        dw = jnp.sum(y.astype(f32) * g_w, -1)
        return (
            dx.at[tok_w].add(jnp.where(valid, dxs, 0).astype(f32)), dgate + dg, dup + du, ddown + dd,
            jax.lax.dynamic_update_slice_in_dim(dweight, dw, lo, 0),
        )

    zeros = lambda a: jnp.zeros(a.shape, f32)
    dx, dgate, dup, ddown, dweight = jax.lax.fori_loop(
        0, (n_held + W - 1) // W, body, (zeros(x), zeros(gate), zeros(up), zeros(down), zeros(weight))
    )
    return dx.astype(x.dtype), dgate, dup, ddown, dweight, None, None


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def route(p, x, spec: MoESpec, dtype):
    """Every expert's score (a softmax over all, or each one's ``sigmoid``) and
    the ``k`` chosen: the largest scores, or with ``spec.select_bias`` the
    largest of ``score + expert_bias`` — the bias decides who is chosen and is
    no part of a weight. Weights renormalised and scaled as ``spec`` says.
    Returns ``(scores [N, E], top_p [N, k], top_i [N, k])``."""
    logits = mm(x, p["router"], dtype)
    scores = jax.nn.sigmoid(logits) if spec.score == "sigmoid" else jax.nn.softmax(logits, -1)
    if spec.select_bias:
        _, top_i = jax.lax.top_k(scores + p["expert_bias"], spec.num_experts_per_tok)
        top_p = jnp.take_along_axis(scores, top_i, -1)
    else:
        top_p, top_i = jax.lax.top_k(scores, spec.num_experts_per_tok)
    if spec.normalize:
        total = jnp.sum(top_p, -1, keepdims=True)
        if spec.normalize_eps:
            total = total + spec.normalize_eps
        top_p = top_p / total
    if spec.scale != 1.0:
        top_p = top_p * spec.scale
    return scores, top_p, top_i


def moe(p, x, spec: MoESpec, dtype, rows: int = 1) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``x`` ``[N, D]`` (``rows`` window rows of ``N / rows`` tokens) -> the
    held experts' part plus the shared expert (where the model has one), and
    the layer's routing statistics. The load-balancing term is taken row by
    row and averaged; ``load`` ``[1, E]`` is how many tokens chose each of
    *all* the router's outputs, held here or not."""
    N, D = x.shape
    k, Eh = spec.num_experts_per_tok, spec.experts_held
    scores, top_p, top_i = route(p, x, spec, dtype)
    local = top_i - spec.held_index * Eh
    held = (local >= 0) & (local < Eh)
    group = jnp.where(held, local, Eh).reshape(-1)  # pairs of absent experts sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[group].add(1)[:Eh]
    n_held = jnp.sum(sizes)
    # a window is twice the pairs an even routing sends here, at least a tile
    W = min(-(-max(2 * N * k // spec.held_of, 1) // 512) * 512, -(-N * k // 512) * 512)
    pad = -(-N * k // W) * W - N * k
    tok = jnp.pad(order // k, (0, pad)).astype(jnp.int32)
    weight = jnp.pad(jnp.where(held, top_p, 0.0).reshape(-1)[order], (0, pad))
    out = held_experts(x.astype(dtype), p["gate"], p["up"], p["down"], weight, tok, sizes, W, dtype)
    if spec.shared:
        shared = mm(jax.nn.silu(mm(x, p["shared_gate"], dtype)) * mm(x, p["shared_up"], dtype), p["shared_down"], dtype)
        if spec.shared_gate:
            shared = jax.nn.sigmoid(mm(x, p["shared_router"], dtype)) * shared
        out = out + shared
    # load balancing over all of the router's outputs (Switch): E * sum_e f_e P_e,
    # f_e the share of a row's tokens that chose e, P_e their mean probability
    row = jnp.repeat(jnp.arange(rows), N // rows)
    counts = jnp.zeros((rows, spec.num_experts), f32).at[row[:, None], top_i].add(1.0)
    chosen = counts * (rows / N)
    probs = scores / jnp.sum(scores, -1, keepdims=True) if spec.score == "sigmoid" else scores
    mean_p = jnp.mean(probs.reshape(rows, N // rows, -1), 1)
    aux = jnp.mean(spec.num_experts * jnp.sum(jax.lax.stop_gradient(chosen) * mean_p, -1))
    if spec.aux_per_choice:
        aux = aux / k
    stats = {
        "aux": aux,
        "held_pairs": n_held.astype(f32),
        "max_load": jnp.max(sizes).astype(f32),
        "experts_hit": jnp.sum(sizes > 0).astype(f32),
        "dropped_pairs": (jnp.sum(held) - n_held).astype(f32),
        "load": jnp.sum(counts, 0)[None],
    }
    return out, stats


def add_stats(total, stats):
    """Routing statistics summed over layers (the largest load: its maximum;
    ``load``: a row a layer)."""
    if total is None:
        return stats
    join = {"max_load": jnp.maximum, "load": lambda a, b: jnp.concatenate([a, b])}
    return {name: join.get(name, jnp.add)(total[name], value) for name, value in stats.items()}


def balance_step(bias, load, rate: float):
    """Loss-free balancing (arXiv:2408.15664): an expert under the mean load
    is made likelier to be chosen, one over it less, by ``rate`` a step
    whatever the distance: ``b_e <- b_e + rate * sign(mean(load) - load_e)``.
    ``load`` is over every token of the step (summed over the data axis by the
    caller) and over all the router's outputs."""
    return bias + rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
