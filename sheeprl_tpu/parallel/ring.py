"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention and therefore no sequence parallelism
(SURVEY §5.7) — its longest "sequence" is a replay window scanned by a GRU.
For the TPU framework long-context support is first-class: these primitives
shard the *sequence* dimension of attention over a named mesh axis so
contexts far beyond one chip's HBM can be trained.

Two standard schemes, both built on XLA collectives (no NCCL):

- :func:`ring_attention` — blockwise flash-style attention where K/V blocks
  rotate around the mesh axis with ``lax.ppermute`` (one ICI hop per step)
  while each device keeps a running (max, denominator, numerator) softmax
  accumulator. Memory per device is O(T/P); communication is P−1 neighbor
  exchanges fully overlappable with the block matmuls. (Liu et al., "Ring
  Attention with Blockwise Transformers".)
- :func:`ulysses_attention` — all-to-all resharding: sequence-sharded
  Q/K/V are transposed to *head*-sharded with one ``lax.all_to_all``, plain
  local attention runs over the full sequence, and a second all-to-all
  restores sequence sharding. Cheaper collectives for moderate T, requires
  num_heads divisible by the axis size. (DeepSpeed-Ulysses.)

Both are pure jax functions meant to run *inside* ``jax.shard_map`` (the
caller owns the mesh); :func:`ring_self_attention` is the convenience
wrapper that does the shard_map plumbing from a global ``[B, T, H, D]``.
All paths are differentiable (ppermute/all_to_all have transposes), so they
drop into training steps, not just inference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sheeprl_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

_NEG_INF = -1e30


def _block_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    # q: [B, Tq, H, D], k: [B, Tk, H, D] -> [B, H, Tq, Tk]
    return jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale


def _causal_mask(q_start: jnp.ndarray, k_start: jnp.ndarray, tq: int, tk: int) -> jnp.ndarray:
    qpos = q_start + jnp.arange(tq)
    kpos = k_start + jnp.arange(tk)
    return qpos[:, None] >= kpos[None, :]  # [Tq, Tk]


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Plain single-device softmax attention over ``[B, T, H, D]`` — the
    numerical reference for the parallel schemes and the local kernel of
    :func:`ulysses_attention`."""
    scale = float(q.shape[-1]) ** -0.5 if scale is None else scale
    scores = _block_scores(q, k, scale)
    if causal:
        mask = _causal_mask(jnp.int32(0), jnp.int32(0), q.shape[1], k.shape[1])
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = SEQ_AXIS,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    Call inside ``shard_map`` with the sequence dim sharded: ``q``/``k``/``v``
    are the *local* blocks ``[B, T_local, H, D]`` of a global ``[B, T, H, D]``.
    Returns the local output block. K/V travel the ring; Q stays put.

    Known trade-off: with ``causal=True`` and contiguous block assignment,
    devices holding early blocks compute fully-masked score/PV matmuls on
    ~half the ring steps (SPMD runs the same program everywhere, so the work
    cannot be branched away). A zigzag/striped block assignment would balance
    this; at the ring sizes the framework targets (≤ one pod slice) the
    imbalance is bounded by 2× on the attention FLOPs only.
    """
    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = float(q.shape[-1]) ** -0.5 if scale is None else scale
    b, tq, h, d = q.shape
    tk = k.shape[1]

    # Running flash accumulators: numerator [B,Tq,H,D], max & denom [B,H,Tq].
    # Derive them from q (×0) so they inherit q's device-varying type over
    # every mesh axis (shard_map vma typing).
    zero_q = (q * 0).astype(jnp.float32)
    acc = zero_q
    m = jnp.einsum("bqhd->bhq", zero_q) + _NEG_INF
    l = jnp.einsum("bqhd->bhq", zero_q)
    # Send to the right neighbor; after s steps we hold block (my − s) mod P.
    perm = [(i, (i + 1) % p) for i in range(p)]

    # Unrolled over the (static, small) ring size so the last iteration can
    # skip the K/V exchange — P−1 ICI hops, not P. (Inside a scan the
    # ppermute is a collective and XLA cannot dead-code the wasted one.)
    kb, vb = k, v
    for s in range(p):
        scores = _block_scores(q, kb.astype(q.dtype), scale)  # [B,H,Tq,Tk]
        if causal:
            kv_block = (my - s) % p
            mask = _causal_mask(my * tq, kv_block * tk, tq, tk)
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
        scores = scores.astype(jnp.float32)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # Guard exp(-inf - -inf): rows with no unmasked key yet keep m=-inf.
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        probs = jnp.exp(scores - m_new[..., None])
        if causal:
            probs = jnp.where(mask[None, None], probs, 0.0)
        l = l * alpha + probs.sum(axis=-1)
        acc = acc * jnp.einsum("bhq->bqh", alpha)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", probs, vb.astype(jnp.float32)
        )
        m = m_new
        if s + 1 < p:
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
    denom = jnp.einsum("bhq->bqh", l)[..., None]
    return (acc / jnp.maximum(denom, 1e-30)).astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = SEQ_AXIS,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """All-to-all (Ulysses) sequence parallelism over ``axis_name``.

    Inside ``shard_map``: local blocks ``[B, T_local, H, D]`` with ``H``
    divisible by the axis size. One all-to-all turns sequence sharding into
    head sharding (full T on every device), local attention runs, a second
    all-to-all restores sequence sharding.
    """
    p = jax.lax.axis_size(axis_name)
    if q.shape[2] % p != 0:
        raise ValueError(f"ulysses needs heads ({q.shape[2]}) divisible by axis size ({p})")

    def seq_to_heads(x):  # [B, T/P, H, D] -> [B, T, H/P, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):  # [B, T, H/P, D] -> [B, T/P, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    out = attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v), causal=causal, scale=scale)
    return heads_to_seq(out)


def ring_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    seq_axis: str = SEQ_AXIS,
    batch_axis: str = DATA_AXIS,
    causal: bool = False,
    impl: str = "ring",
) -> jnp.ndarray:
    """Global-view wrapper: ``[B, T, H, D]`` in, same out, T sharded over
    ``seq_axis`` (and B over ``batch_axis`` when the mesh has one)."""
    if q.shape[1] % mesh.shape[seq_axis] != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} must divide over seq axis {mesh.shape[seq_axis]}. "
            "With causal=True you can right-pad q/k/v (parallel.mesh.pad_to_multiple) and "
            "slice the output — padded positions sit in the future and cannot affect real "
            "ones. With causal=False there is no key-padding mask, so padding would let "
            "every query attend to the pad keys; pad the batch layout upstream instead."
        )
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    ba = batch_axis if batch_axis in mesh.shape else None
    spec = P(ba, seq_axis)
    local = functools.partial(fn, axis_name=seq_axis, causal=causal)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
