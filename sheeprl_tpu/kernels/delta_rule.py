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

The chunk-local build of the WY form (``L``, ``T``, ``U0 = T V_b``,
``W = T K_d``) has a fused schedule (howto/kernels.md): in a program lowered
for a TPU, with a chunk that is a multiple of 8 and head widths that are
multiples of 128, a Pallas kernel builds a block of tiles at a time with ``L``,
its powers and the partial products in VMEM, and a second one is its transpose;
``T`` alone is handed from the one to the other. Platform and shapes choose it
(``jax.lax.platform_dependent``), no option does; the products and their
precision are the XLA form's, which is what runs everywhere else and what the
tests hold the kernels to (``interpret=True``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["step", "recurrent", "chunked", "fused_tiles"]

_HI = jax.lax.Precision.HIGHEST
#: tiles (one head's chunk) a grid step of the fused kernels builds, at most
_BLOCK_TILES = 16
#: the transpose kernel needs 20.04 MiB of VMEM at 16 tiles a step; the default limit is 16
_VMEM_LIMIT_BYTES = 64 << 20


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


# -- the chunk-local WY build ---------------------------------------------------
#
# ``wy(K, V, D, beta, decay_in) -> (U0, W)`` over tiles ``K [..., C, d_k]``,
# ``V [..., C, d_v]``, ``D [..., C, C]`` and per-token ``beta``, ``decay_in``
# ``[..., C]``: with ``K_b = K * beta``, ``L`` is the strictly lower part of
# ``(K_b K^T) * D``, ``T = (I + L)^{-1}``, ``U0 = T (V * beta)`` and
# ``W = T (K_b * decay_in)``.


def _abT(a, b):
    return jnp.einsum("...ik,...jk->...ij", a, b, precision=_HI)


def _strictly_lower(D):
    C = D.shape[-1]
    below = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) > jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return D * below.astype(D.dtype)


def _wy_xla(k, v, D, beta, decay_in):
    """The XLA form: every intermediate an array of its own. ``(U0, W, T)``."""
    kb, vb = k * beta[..., None], v * beta[..., None]
    Tm = _nilpotent_inverse(_abT(kb, k) * _strictly_lower(D))
    return jnp.matmul(Tm, vb, precision=_HI), jnp.matmul(Tm, kb * decay_in[..., None], precision=_HI), Tm


# The kernels take two tiles at a time, side by side: at 64 tokens a tile's ``[C, C]``
# matrices fill half of the 128 lanes of a vector register and a quarter of the matrix
# unit. ``D`` and ``T`` cross HBM *lane-packed*, ``[pairs, C, 2C]`` with tile ``2p`` in
# lanes ``[0, C)`` and tile ``2p + 1`` in ``[C, 2C)`` (full lanes, half the bytes). In a
# kernel a pair is either that (``cat``: a product ``cat @ blockdiag`` multiplies both
# tiles from the right at the cost of one) or block-diagonal ``[2C, 2C]`` (``bd``:
# ``bd @ stacked rows`` and ``bd^T @ ...`` multiply from the left). Everything is a
# whole-register slice, mask or reshape; the products are the XLA form's, pair by pair.


def _pack(x):
    """``[tiles, C, C]`` -> ``[tiles / 2, C, 2C]``."""
    tiles, C, _ = x.shape
    return jnp.swapaxes(x.reshape(tiles // 2, 2, C, C), 1, 2).reshape(tiles // 2, C, 2 * C)


def _unpack(x):
    pairs, C, _ = x.shape
    return jnp.swapaxes(x.reshape(pairs, C, 2, C), 1, 2).reshape(2 * pairs, C, C)


def _pair_forms(tiles: int, C: int):
    """``(below, bd, stack, unstack)`` for a block of ``tiles`` tiles: the strictly
    lower mask of both halves of a ``cat``, and the conversions."""
    f32 = jnp.float32
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    left = lane < C
    below = (row > jnp.where(left, lane, lane - C)).astype(f32)

    def bd(cat):  # [..., C, 2C] -> [..., 2C, 2C]
        return jnp.concatenate([cat * left.astype(f32), cat * (~left).astype(f32)], axis=-2)

    def stack(x):  # [tiles, C, d] -> [pairs, 2C, d]
        return x.reshape(tiles // 2, 2 * C, x.shape[-1])

    def unstack(x):
        return x.reshape(tiles, C, x.shape[-1])

    return below, bd, stack, unstack


def _wy_kernel(k, v, Dc, beta, decay_in, U0, W, Tc):
    tiles, C, _ = k.shape
    below, bd, stack, unstack = _pair_forms(tiles, C)
    k, beta = k[...], beta[...][:, :, None]
    kb = k * beta
    # the cross products of a pair's two tiles, off the diagonal blocks, meet the mask's zeros
    L = _abT(stack(kb), stack(k)) * bd(Dc[...] * below)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (2 * C, 2 * C), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (2 * C, 2 * C), 1)).astype(jnp.float32)
    power = L[:, :C] + L[:, C:]  # cat
    out = eye[:C] + eye[C:] - power
    power_bd, span = L, 2
    while span < C:  # the product of _nilpotent_inverse, both tiles at once
        power = jnp.matmul(power, power_bd, precision=_HI)
        power_bd = bd(power)
        out = jnp.matmul(out, eye + power_bd, precision=_HI)
        span *= 2
    Tm = bd(out)
    U0[...] = unstack(jnp.matmul(Tm, stack(v[...] * beta), precision=_HI))
    W[...] = unstack(jnp.matmul(Tm, stack(kb * decay_in[...][:, :, None]), precision=_HI))
    Tc[...] = out


def _wy_transpose_kernel(k, v, Dc, beta, decay_in, Tc, dU0, dW, dk, dv, dDc, dbeta, ddecay_in):
    """The transpose of :func:`_wy_kernel`, given the ``T`` it built. The pair's
    cross blocks of ``dT`` drop out between the block-diagonal ``T^T``s."""
    tiles, C, _ = k.shape
    below, bd, stack, unstack = _pair_forms(tiles, C)
    k, v, beta, decay_in = k[...], v[...], beta[...][:, :, None], decay_in[...][:, :, None]
    kb, vb = k * beta, v * beta
    kd = kb * decay_in
    P, Dm = _abT(stack(kb), stack(k)), bd(Dc[...] * below)
    Tt = jnp.swapaxes(bd(Tc[...]), 1, 2)  # T^T, turned once for its four products
    dU0, dW = stack(dU0[...]), stack(dW[...])
    dvb, dkd = (unstack(jnp.matmul(Tt, x, precision=_HI)) for x in (dU0, dW))
    dT = _abT(dU0, stack(vb)) + _abT(dW, stack(kd))
    dL = -jnp.matmul(jnp.matmul(Tt, dT, precision=_HI), Tt, precision=_HI)  # -T^T dT T^T
    dD = dL * P * bd(below)
    dDc[...] = dD[:, :C] + dD[:, C:]
    A = dL * Dm
    dkb = unstack(jnp.matmul(A, stack(k), precision=_HI)) + dkd * decay_in
    dk[...] = unstack(jnp.matmul(jnp.swapaxes(A, 1, 2), stack(kb), precision=_HI)) + dkb * beta
    dv[...] = dvb * beta
    dbeta[...] = jnp.sum(dkb * k, -1) + jnp.sum(dvb * v, -1)
    ddecay_in[...] = jnp.sum(dkd * kb, -1)


def _tiles_call(kernel, out_like, *operands, aliases=None, interpret=False):
    """``kernel`` over operands ``[tiles, ...]`` (``[tiles / 2, ...]`` the
    lane-packed ones), a block of tiles a grid step; its outputs are shaped as
    the operands ``out_like`` indexes. ``aliases`` maps an operand that nothing
    reads afterwards to the output that may take its memory."""
    tiles = operands[0].shape[0]
    block = next(b for b in range(min(_BLOCK_TILES, tiles), 0, -2) if tiles % b == 0 and (b % 8 == 0 or b == tiles))

    def spec(x):
        rows = block * x.shape[0] // tiles
        return pl.BlockSpec((rows,) + x.shape[1:], lambda i, n=x.ndim - 1: (i,) + (0,) * n)

    return pl.pallas_call(
        kernel,
        grid=(tiles // block,),
        in_specs=[spec(x) for x in operands],
        out_specs=[spec(operands[i]) for i in out_like],
        out_shape=[jax.ShapeDtypeStruct(operands[i].shape, operands[i].dtype) for i in out_like],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        input_output_aliases=aliases or {},
        interpret=interpret,
    )(*operands)


def _wy_pallas(k, v, D, beta, decay_in, interpret=False):
    """``(U0, W, T)`` over ``[tiles, ...]`` operands, ``T`` lane-packed ``[tiles / 2, C, 2C]``."""
    return _tiles_call(_wy_kernel, (1, 0, 2), k, v, _pack(D), beta, decay_in, interpret=interpret)


def _wy_transpose_pallas(k, v, D, beta, decay_in, Tc, dU0, dW, interpret=False):
    # dU0 -> dV and dW -> dK: the cotangents land where the ones that came in lay
    dk, dv, dDc, dbeta, ddecay_in = _tiles_call(
        _wy_transpose_kernel, range(5), k, v, _pack(D), beta, decay_in, Tc, dU0, dW, aliases={6: 1, 7: 0},
        interpret=interpret)
    return dk, dv, _unpack(dDc), dbeta, ddecay_in


def _on_platform(kernel, xla_form, *operands):
    """``kernel`` where the program is lowered for a TPU, ``xla_form`` elsewhere."""
    return jax.lax.platform_dependent(*operands, tpu=kernel, default=xla_form)


def _fusable(tiles: int, chunk: int, dk: int, dv: int) -> bool:
    """Whole ``(8, 128)`` vector tiles in every block: a chunk that is a multiple
    of 8, head widths that are multiples of 128, and pairs of tiles in blocks of
    8 tiles or in one block."""
    return chunk % 8 == 0 and dk % 128 == 0 and dv % 128 == 0 and (
        tiles % 8 == 0 or (tiles % 2 == 0 and tiles <= _BLOCK_TILES))


def _wy_xla_packed(*operands):
    U0, W, Tm = _wy_xla(*operands)
    return U0, W, _pack(Tm)


def _wy_xla_transpose(k, v, D, beta, decay_in, Tc, dU0, dW):
    """Autodiff's transpose of the XLA form, which builds its own ``T`` again."""
    return jax.vjp(lambda *operands: _wy_xla(*operands)[:2], k, v, D, beta, decay_in)[1]((dU0, dW))


@jax.custom_vjp
def _wy_fused(k, v, D, beta, decay_in):
    return _wy_fused_fwd(k, v, D, beta, decay_in)[0]


def _wy_fused_fwd(*operands):
    # T is kept, lane-packed, from a block's rematerialisation to its transpose
    U0, W, Tc = _on_platform(_wy_pallas, _wy_xla_packed, *operands)
    return (U0, W), operands + (Tc,)


def _wy_fused_bwd(kept, cotangents):
    return _on_platform(_wy_transpose_pallas, _wy_xla_transpose, *kept, *cotangents)


_wy_fused.defvjp(_wy_fused_fwd, _wy_fused_bwd)


def fused_tiles(shape, d_v: int, chunk: int = 64):
    """Tiles one pass of :func:`chunked` over keys of ``shape`` ``[B, T, H, d_k]``
    builds in the fused kernel: a scalar that reads ``B * T / chunk * H`` in a
    program lowered for a TPU where the shapes allow the kernel, 0 elsewhere."""
    B, T, H, dk = shape
    tiles = B * (T // chunk) * H
    if not _fusable(tiles, chunk, dk, d_v):
        return jnp.float32(0.0)
    return jax.lax.platform_dependent(tpu=lambda: jnp.float32(tiles), default=lambda: jnp.float32(0.0))


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

    decay_in = jnp.exp(G) * from_state  # [N, B, H, C]
    # U0: the updates, state left out; W: what the carried state takes off them
    if _fusable(N * B * H, C, dk, dv):  # the kernels take tiles, one a head's chunk
        U0, W = (x.reshape(vc.shape[:3] + x.shape[1:]) for x in _wy_fused(
            *(x.reshape((N * B * H,) + x.shape[3:]) for x in (kc, vc, D, bc, decay_in))))
    else:
        U0, W, _ = _wy_xla(kc, vc, D, bc, decay_in)
    QK = jnp.einsum("...ik,...jk->...ij", qc.astype(dtype), kc.astype(dtype),
                    preferred_element_type=f32) * D
    G_last = G[..., -1:]
    decay_out = jnp.exp(G_last - G) * same[..., -1, :]  # from each token to the chunk's end
    k_out = (kc * decay_out[..., None]).astype(dtype)
    keep = jnp.exp(G_last) * from_state[..., -1:]  # the carried state's own decay; 0 after a reset
    q_in = (qc * decay_in[..., None]).astype(dtype)

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
