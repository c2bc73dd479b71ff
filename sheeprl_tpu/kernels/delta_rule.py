"""The gated delta rule — the third kernel family (howto/kernels.md).

One recurrence per value head, state ``S`` of shape ``[d_k, d_v]``::

    S_t = a_t * S_{t-1} + b_t * k_t (v_t - a_t * S_{t-1}^T k_t)^T,    o_t = S_t^T q_t

with ``a_t = exp(g_t)`` (``g_t <= 0``) and ``b_t`` in ``(0, 1)``. A *reset* at
``t`` (an episode's first token) takes ``S_{t-1}`` for zero.

Two tiers, one math:

- :func:`recurrent` — the reference tier: a ``lax.scan`` of :func:`step`, one
  token at a time. It is what the tests hold the chunked tier to, and
  :func:`step` is what one-token decoding (imagination, acting) runs.
- :func:`chunked` — chunks of ``chunk`` tokens in the WY form. Inside a chunk
  the ``C`` rank-one updates are written as ``U = T (V_b - (K_b * decay) S_0)``
  with ``T = (I + L)^{-1}``, ``L`` the strictly lower part of
  ``(K_b K^T) * D`` and ``D_ij`` the decay from ``j`` to ``i``; ``L`` is
  nilpotent, so the inverse is the finite product
  ``(I - L)(I + L^2)(I + L^4)...``: matrix products only, which the MXU runs
  and autodiff differentiates (the backward pass is the transpose of this
  program; nothing is hand-written). Between chunks a ``lax.scan`` carries
  ``S``. A reset inside a chunk masks ``D`` to the token's own segment and
  cuts the carried state off from the tokens after it.

Both return the state *before* each chunk boundary as well (``[n_chunks, ...]``
for :func:`chunked`), which is where imagination starts from.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["step", "recurrent", "chunked"]

_HI = jax.lax.Precision.HIGHEST


def step(S, q, k, v, g, beta):
    """One token. ``S`` ``[..., d_k, d_v]`` (f32); ``q``/``k`` ``[..., d_k]``;
    ``v`` ``[..., d_v]``; ``g``/``beta`` ``[...]``. Returns ``(S, o)``."""
    S = S * jnp.exp(g)[..., None, None]
    kv = jnp.einsum("...kv,...k->...v", S, k)
    u = (v - kv) * beta[..., None]
    S = S + k[..., :, None] * u[..., None, :]
    return S, jnp.einsum("...kv,...k->...v", S, q)


def recurrent(q, k, v, g, beta, reset=None, initial_state=None):
    """The reference tier. ``q``/``k`` ``[B, T, H, d_k]``, ``v`` ``[B, T, H, d_v]``,
    ``g``/``beta`` ``[B, T, H]``, ``reset`` ``[B, T]`` (1 where the state is
    dropped before the token), all f32. Returns ``(o [B, T, H, d_v], S_final)``."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    S0 = jnp.zeros((B, H, dk, dv), jnp.float32) if initial_state is None else initial_state
    reset = jnp.zeros((B, T), jnp.float32) if reset is None else reset.astype(jnp.float32)

    def body(S, inp):
        q_t, k_t, v_t, g_t, b_t, r_t = inp
        S = S * (1.0 - r_t)[:, None, None, None]
        S, o = step(S, q_t, k_t, v_t, g_t, b_t)
        return S, o

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0) for x in (q, k, v, g, beta)) + (reset.T,)
    S, o = jax.lax.scan(body, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


@jax.custom_vjp
def _nilpotent_inverse(L):
    """``(I + L)^{-1}`` for strictly lower triangular ``L`` ``[..., C, C]``: the
    finite product ``(I - L)(I + L^2)(I + L^4)...``. Its transpose needs the
    inverse alone (``dL = -T^T dT T^T``), so the powers are not kept."""
    chunk = L.shape[-1]
    eye = jnp.eye(chunk, dtype=L.dtype)
    out = eye - L
    power = L
    span = 2
    while span < chunk:
        power = jnp.matmul(power, power, precision=_HI)
        out = jnp.matmul(out, eye + power, precision=_HI)
        span *= 2
    return out


def _nilpotent_inverse_fwd(L):
    T = _nilpotent_inverse(L)
    return T, T


def _nilpotent_inverse_bwd(T, g):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.matmul(jnp.matmul(Tt, g, precision=_HI), Tt, precision=_HI),)


_nilpotent_inverse.defvjp(_nilpotent_inverse_fwd, _nilpotent_inverse_bwd)


def chunked(q, k, v, g, beta, reset=None, initial_state=None, chunk: int = 64,
            dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The chunked tier; arguments as :func:`recurrent`, ``T`` a multiple of
    ``chunk``. ``dtype`` is what the large products take their operands in
    (bf16 under mixed precision; sums, decays and the state stay f32).
    Returns ``(o [B, T, H, d_v], S_final, S_before [n_chunks, B, H, d_k, d_v])``."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = int(chunk)
    if T % C:
        raise ValueError(f"sequence length {T} is not a multiple of the chunk {C}")
    N = T // C
    f32 = jnp.float32
    reset = jnp.zeros((B, T), f32) if reset is None else reset.astype(f32)

    def split(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    qc, kc, vc = split(q.astype(f32)), split(k.astype(f32)), split(v.astype(f32))
    bc = split(beta.astype(f32))  # [N, B, H, C]
    r = jnp.moveaxis(reset.reshape(B, N, C), 1, 0)[:, :, None, :]  # [N, B, 1, C]
    gc = split(g.astype(f32)) * (1.0 - r)  # the decay into a reset token is the mask's
    G = jnp.cumsum(gc, -1)
    seg = jnp.cumsum(r, -1)  # resets at or before each token, within the chunk
    same = (seg[..., :, None] == seg[..., None, :]).astype(f32)  # [N, B, 1, C, C]
    from_state = (seg == 0).astype(f32)  # tokens the carried state still reaches
    tril = jnp.tril(jnp.ones((C, C), f32))
    # exp of a masked difference: the upper triangle would overflow otherwise
    diff = (G[..., :, None] - G[..., None, :]) * tril
    D = jnp.exp(diff) * tril * same  # decay from j to i, i >= j, same segment

    kb = kc * bc[..., None]
    L = jnp.einsum("...ik,...jk->...ij", kb, kc, precision=_HI) * D * jnp.tril(jnp.ones((C, C), f32), -1)
    Tm = _nilpotent_inverse(L)
    decay_in = (jnp.exp(G) * from_state)[..., None]  # [N, B, H, C, 1]
    U0 = jnp.matmul(Tm, vc * bc[..., None], precision=_HI)  # the updates, state left out
    W = jnp.matmul(Tm, kb * decay_in, precision=_HI)  # what the carried state takes off them
    QK = jnp.einsum("...ik,...jk->...ij", qc.astype(dtype), kc.astype(dtype),
                    preferred_element_type=f32) * D
    G_last = G[..., -1:]
    decay_out = jnp.exp(G_last - G) * same[..., -1, :]  # from each token to the chunk's end
    k_out = (kc * decay_out[..., None]).astype(dtype)
    keep = jnp.exp(G_last) * from_state[..., -1:]  # the carried state's own decay; 0 after a reset
    q_in = (qc * decay_in).astype(dtype)

    def body(S, inp):
        U0_c, W_c, QK_c, q_c, k_c, keep_c = inp
        Sd = S.astype(dtype)
        U = U0_c - jnp.matmul(W_c.astype(dtype), Sd, preferred_element_type=f32)
        o = jnp.matmul(q_c, Sd, preferred_element_type=f32) + jnp.matmul(
            QK_c.astype(dtype), U.astype(dtype), preferred_element_type=f32
        )
        S_next = S * keep_c[..., None] + jnp.einsum(
            "...ck,...cv->...kv", k_c, U.astype(dtype), preferred_element_type=f32
        )
        return S_next, (o, S)

    S0 = jnp.zeros((B, H, dk, dv), f32) if initial_state is None else initial_state.astype(f32)
    keep = jnp.broadcast_to(keep, (N, B, H, 1))
    S, (o, S_before) = jax.lax.scan(body, S0, (U0, W, QK, q_in, k_out, keep))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, T, H, dv)
    return o, S, S_before
