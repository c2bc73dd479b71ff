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
  and autodiff differentiates. Between chunks the state is carried:
  ``U = U0 - W S``, ``o = (q * decay_in) S + ((q k^T) * D) U`` and
  ``S' = keep S + (k * decay_out)^T U``. A reset inside a chunk masks ``D`` to
  the token's own segment and cuts the carried state off from the tokens after
  it.

Both return the state *before* each chunk boundary as well (``[n_chunks, ...]``
for :func:`chunked`), which is where imagination starts from.

Two fused schedules (howto/kernels.md), each a pair of Pallas kernels behind a
``jax.custom_vjp``, run in a program lowered for a TPU where the chunk is a
multiple of 8 and the head widths are multiples of 128:

- the chunk-local build of the WY form (``L``, ``T``, ``U0 = T V_b``,
  ``W = T K_d``): a kernel builds the tiles of a block of a row's heads (an
  even number of them) with ``L``, its powers and the partial products in
  VMEM, and its transpose is handed ``T`` alone;
- the inter-chunk pass: a kernel walks the chunks of 8 heads of a row in order,
  their float32 states in VMEM from the first chunk to the last, building
  ``(q k^T) * D``, ``q * decay_in`` and ``k * decay_out`` in VMEM and writing
  ``o``, ``S_before`` and ``S_final``; its transpose walks them in reverse with
  ``dS`` in VMEM and rebuilds ``U`` and ``q k^T`` from the kept ``S_before``.

They read ``q``, ``k``, ``v`` and write ``o`` and the cotangents of ``q``, ``k``,
``v`` in the model's own ``[B, T, H, d]`` layout. Platform and shapes choose them
(``jax.lax.platform_dependent``), no option does; elsewhere the XLA form runs,
``lax.scan`` between chunks. The products and their precision are the XLA
form's as it runs on the chip, which is what the tests hold the kernels to
(``interpret=True``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["step", "recurrent", "chunked", "fused_tiles", "scan_fused_tiles"]

_HI = jax.lax.Precision.HIGHEST
#: tiles (one head's chunk) a grid step of the fused kernels builds, at most
_BLOCK_TILES = 16
#: heads a grid step of the inter-chunk kernels carries across every chunk
_SCAN_HEADS = 8
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


# A kernel's block holds some heads of one row: of ``[B, T, H, d]`` operands a row's
# ``C`` tokens of those heads, ``[C, heads, d]``, a head a strided slice of sublanes;
# of per-chunk operands the same heads' tiles.


def _heads(ref):
    """A ``[1, C, heads, d]`` block -> ``[heads, C, d]``."""
    return jnp.stack([ref[0, :, h, :] for h in range(ref.shape[2])])


def _put_heads(ref, x):
    """``ref`` ``[1, C, heads, d]`` <- ``x`` ``[heads, C, d]``."""
    for h in range(x.shape[0]):
        ref[0, :, h, :] = x[h]


def _tile_rows(x, chunk: int):
    """``[B, T, H, d]`` -> ``[N * B * H, C, d]``, a tile a head's chunk."""
    B, T, H, d = x.shape
    return jnp.moveaxis(x.reshape(B, T // chunk, chunk, H, d), (1, 2), (0, 3)).reshape(-1, chunk, d)


def _wy_kernel(k, v, Dc, beta, decay_in, U0, W, Tc):
    k, beta = _heads(k), beta[0][:, :, None]
    tiles, C, _ = k.shape
    below, bd, stack, unstack = _pair_forms(tiles, C)
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
    U0[...] = unstack(jnp.matmul(Tm, stack(_heads(v) * beta), precision=_HI))
    W[...] = unstack(jnp.matmul(Tm, stack(kb * decay_in[0][:, :, None]), precision=_HI))
    Tc[...] = out


def _wy_transpose_kernel(k, v, Dc, beta, decay_in, Tc, dU0, dW, dk, dv, dDc, dbeta, ddecay_in):
    """The transpose of :func:`_wy_kernel`, given the ``T`` it built. The pair's
    cross blocks of ``dT`` drop out between the block-diagonal ``T^T``s."""
    k, v, beta, decay_in = _heads(k), _heads(v), beta[0][:, :, None], decay_in[0][:, :, None]
    tiles, C, _ = k.shape
    below, bd, stack, unstack = _pair_forms(tiles, C)
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
    _put_heads(dk, unstack(jnp.matmul(jnp.swapaxes(A, 1, 2), stack(kb), precision=_HI)) + dkb * beta)
    _put_heads(dv, dvb * beta)
    dbeta[0] = jnp.sum(dkb * k, -1) + jnp.sum(dvb * v, -1)
    ddecay_in[0] = jnp.sum(dkd * kb, -1)


def _tile_block(heads: int):
    """Heads a grid step of the WY kernels takes: an even number, at most
    :data:`_BLOCK_TILES`, dividing ``heads``, a multiple of 8 or all of them
    (whole ``(8, 128)`` vector tiles); ``None`` where there is none."""
    return next((h for h in range(min(_BLOCK_TILES, heads) // 2 * 2, 0, -2)
                 if heads % h == 0 and (h % 8 == 0 or h == heads)), None)


def _tiles_call(kernel, operands, outs, interpret=False):
    """``kernel`` over a grid of a row's chunks x blocks of :func:`_tile_block`
    heads. ``operands`` and ``outs`` (``ShapeDtypeStruct``s) are ``(kind,
    array)``: ``"tiles"`` ``[N * B * H, ...]`` (``[N * B * H / 2, ...]`` the
    lane-packed ones), ``"rows"`` ``[N * B, H, C]`` per-token vectors,
    ``"tokens"`` ``[B, T, H, d]``."""
    B, T, H, _ = next(x.shape for kind, x in operands if kind == "tokens")
    rows = next(x.shape[0] for kind, x in operands if kind == "rows")
    hb = _tile_block(H)
    blocks = H // hb

    def spec(kind, x):
        if kind == "tiles":
            return pl.BlockSpec((hb * x.shape[0] // (rows * H),) + x.shape[1:],
                                lambda r, j, n=x.ndim - 1: (r * blocks + j,) + (0,) * n)
        if kind == "rows":
            return pl.BlockSpec((1, hb, x.shape[-1]), lambda r, j: (r, j, 0))
        return pl.BlockSpec((1, T // (rows // B), hb, x.shape[-1]), lambda r, j: (r % B, r // B, j, 0))

    return tuple(pl.pallas_call(
        kernel,
        grid=(rows, blocks),
        in_specs=[spec(*x) for x in operands],
        out_specs=[spec(*x) for x in outs],
        out_shape=[x for _, x in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(*(x for _, x in operands)))


def _wy_pallas(k, v, D, beta, decay_in, interpret=False):
    """``(U0, W, T)``: ``k``, ``v`` ``[B, T, H, d]``, ``D`` ``[tiles, C, C]``, ``beta``,
    ``decay_in`` ``[N * B, H, C]``; ``U0``, ``W`` ``[tiles, C, d]``, ``T``
    lane-packed ``[tiles / 2, C, 2C]``."""
    tiles, C, _ = D.shape
    out = lambda *shape: ("tiles", jax.ShapeDtypeStruct(shape, jnp.float32))
    return _tiles_call(_wy_kernel, (("tokens", k), ("tokens", v), ("tiles", _pack(D)), ("rows", beta),
                                    ("rows", decay_in)),
                       (out(tiles, C, v.shape[-1]), out(tiles, C, k.shape[-1]), out(tiles // 2, C, 2 * C)),
                       interpret=interpret)


def _wy_transpose_pallas(k, v, D, beta, decay_in, Tc, dU0, dW, interpret=False):
    """The cotangents of ``(k, v, D, beta, decay_in)``, laid out as they are."""
    like = lambda kind, x: (kind, jax.ShapeDtypeStruct(x.shape, jnp.float32))
    Dc = _pack(D)
    operands = (("tokens", k), ("tokens", v), ("tiles", Dc), ("rows", beta), ("rows", decay_in), ("tiles", Tc),
                ("tiles", dU0), ("tiles", dW))
    dk, dv, dDc, dbeta, ddecay_in = _tiles_call(_wy_transpose_kernel, operands, tuple(like(*x) for x in operands[:5]),
                                                interpret=interpret)
    return dk, dv, _unpack(dDc), dbeta, ddecay_in


def _on_platform(kernel, xla_form, *operands):
    """``kernel`` where the program is lowered for a TPU, ``xla_form`` elsewhere."""
    return jax.lax.platform_dependent(*operands, tpu=kernel, default=xla_form)


def _fusable(heads: int, chunk: int, dk: int, dv: int) -> bool:
    """Whole ``(8, 128)`` vector tiles in every block: a chunk that is a multiple
    of 8, head widths that are multiples of 128, and a row's ``heads`` in blocks
    of an even number (:func:`_tile_block`)."""
    return chunk % 8 == 0 and dk % 128 == 0 and dv % 128 == 0 and _tile_block(heads) is not None


def _wy_tiles(k, v, D, beta, decay_in):
    """The XLA form's operands, tile by tile, from the kernels'."""
    C = D.shape[-1]
    return _tile_rows(k, C), _tile_rows(v, C), D, beta.reshape(-1, C), decay_in.reshape(-1, C)


def _wy_xla_packed(*operands):
    U0, W, Tm = _wy_xla(*_wy_tiles(*operands))
    return U0, W, _pack(Tm)


def _wy_xla_transpose(k, v, D, beta, decay_in, Tc, dU0, dW):
    """Autodiff's transpose of the XLA form, which builds its own ``T`` again."""
    return jax.vjp(lambda *operands: _wy_xla(*_wy_tiles(*operands))[:2], k, v, D, beta, decay_in)[1]((dU0, dW))


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


# -- the inter-chunk pass -------------------------------------------------------
#
# Over the chunks in order, a head's state ``S`` [d_k, d_v] turns each chunk's
# ``U0``, ``W``, ``D``, ``q``, ``k`` and per-token ``decay_in``, ``decay_out`` [C]
# into the chunk's ``o`` and the next state, with ``QK = (q k^T) * D``; ``keep``,
# the carried state's own decay over the chunk, is ``decay_in`` at the chunk's
# last token. ``U0``, ``W``, ``D`` and the decays come chunk by chunk and head by
# head (``[N, heads, ...]``), ``q``, ``k`` and ``o`` as the model has them
# (``[B, T, H, d]``).


def _inter_chunk(S, U0, W, QK, q, k, decay_in, decay_out, dtype):
    """One chunk, any leading dimensions: ``(S_next, o)``. The products take
    their operands in ``dtype`` and sum in float32; ``S`` stays float32."""
    f32 = jnp.float32
    Sd = S.astype(dtype)
    Ud = (U0 - jnp.matmul(W.astype(dtype), Sd, preferred_element_type=f32)).astype(dtype)
    q_in = (q * decay_in[..., None]).astype(dtype)
    k_out = (k * decay_out[..., None]).astype(dtype)
    o = jnp.matmul(q_in, Sd, preferred_element_type=f32) + jnp.matmul(QK.astype(dtype), Ud, preferred_element_type=f32)
    S_next = S * _last(decay_in)[..., None] + jnp.einsum("...ck,...cv->...kv", k_out, Ud, preferred_element_type=f32)
    return S_next, o


def _last(x):
    """``x[..., -1:]`` as a static slice (an index with ``None`` would gather)."""
    return jax.lax.slice_in_dim(x, x.shape[-1] - 1, x.shape[-1], axis=x.ndim - 1)


def _qk(q, k, D, dtype):
    """``(q k^T) * D`` over the leading dimensions, the product's operands in ``dtype``."""
    return jnp.einsum("...ik,...jk->...ij", q.astype(dtype), k.astype(dtype), preferred_element_type=jnp.float32) * D


def _scan_xla(U0, W, D, q, k, decay_in, decay_out, S0, dtype):
    """The XLA form: a ``lax.scan`` over the chunks. ``(o, S_final, S_before)``;
    no ``S0`` is a zero state."""
    B, T, H, dk = q.shape
    N, C, dv = U0.shape[0], U0.shape[-2], U0.shape[-1]
    lead = U0.shape[:-2]  # [N, B, H] or [N, B * H]
    qc, kc = (_tile_rows(x, C).reshape(lead + (C, dk)) for x in (q, k))
    if S0 is None:
        S0 = jnp.zeros(lead[1:] + (dk, dv), jnp.float32)

    def body(S, chunk):
        S_next, o = _inter_chunk(S, *chunk, dtype)
        return S_next, (o, S)

    S, (o, S_before) = jax.lax.scan(body, S0, (U0, W, _qk(qc, kc, D, dtype), qc, kc, decay_in, decay_out))
    o = jnp.moveaxis(o.reshape(N, B, H, C, dv), (0, 3), (1, 2)).reshape(B, T, H, dv)
    return o, S, S_before


def _scan_kernel(dtype, U0, W, D, q, k, decay_in, decay_out, *refs):
    """A block of heads at one chunk; the grid walks the chunks in order and the
    block's states stay in the VMEM scratch ``S`` from the first to the last."""
    *S0, o, S_final, S_before, S = refs
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        S[...] = S0[0][...] if S0 else jnp.zeros(S.shape, S.dtype)

    qh, kh = _heads(q), _heads(k)
    S_before[0] = S[...]
    S_next, o_c = _inter_chunk(S[...], U0[0], W[0], _qk(qh, kh, D[0], dtype), qh, kh, decay_in[0], decay_out[0],
                               dtype)
    S[...] = S_next
    _put_heads(o, o_c)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        S_final[...] = S_next


def _scan_transpose_kernel(dtype, U0, W, D, q, k, decay_in, decay_out, S_before, do, dS_before, dS_final,
                           dU0, dW, dD, dq, dk, ddecay_in, ddecay_out, dS0, dS):
    """The transpose of :func:`_scan_kernel`, the chunks in reverse, ``dS`` carried
    in the VMEM scratch; ``U`` and ``q k^T`` are rebuilt from the kept ``S_before``
    and the operands. Cotangents stay float32; a product takes its operands in
    ``dtype`` and sums in float32, as the XLA form's transpose does on the chip."""
    f32 = jnp.float32
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        dS[...] = dS_final[...]

    def product(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=f32)

    def mm(a, b):
        return product("hik,hkj->hij", a, b)

    def mm_ta(a, b):  # a^T b
        return product("hki,hkj->hij", a, b)

    def mm_tb(a, b):  # a b^T
        return product("hik,hjk->hij", a, b)

    S, g = S_before[0], dS[...]
    din, dout, Dm = decay_in[0], decay_out[0], D[0]
    qh, kh, do_h = _heads(q), _heads(k), _heads(do)
    raw = mm_tb(qh, kh)
    QK = raw * Dm
    U = U0[0] - mm(W[0], S)
    q_in, k_out = qh * din[..., None], kh * dout[..., None]
    dU = mm(k_out, g) + mm_ta(QK, do_h)
    dU0[0] = dU
    dW[0] = -mm_tb(dU, S)
    dq_in, dk_out = mm_tb(do_h, S), mm_tb(U, g)
    # QK = (q k^T) * D
    dQK = mm_tb(do_h, U)
    dD[0] = dQK * raw
    draw = dQK * Dm
    _put_heads(dq, dq_in * din[..., None] + mm(draw, kh))
    _put_heads(dk, dk_out * dout[..., None] + mm_ta(draw, qh))
    # keep = decay_in at the last token: its cotangent joins that token's
    dkeep = jnp.sum(jnp.sum(S * g, -1), -1, keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, din.shape, 1) == din.shape[-1] - 1
    ddecay_in[0] = jnp.sum(dq_in * qh, -1) + jnp.where(last, dkeep, 0.0)
    ddecay_out[0] = jnp.sum(dk_out * kh, -1)
    dS_next = g * _last(din)[..., None] + mm_ta(q_in, do_h) - mm_ta(W[0], dU) + dS_before[0]
    dS[...] = dS_next

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        dS0[...] = dS_next


def _chunks_call(kernel, operands, outs, reverse=False, aliases=None, interpret=False):
    """``kernel`` over a grid of rows x blocks of :data:`_SCAN_HEADS` heads x the
    chunks, in order or in ``reverse``, a float32 ``[d_k, d_v]`` a head in VMEM
    scratch. ``operands`` and ``outs`` (``ShapeDtypeStruct``s) are ``(kind,
    array)``: ``"chunks"`` ``[N, B * H, ...]``, ``"tokens"`` ``[B, T, H, d]``,
    ``"state"`` ``[B * H, d_k, d_v]`` (a block fetched once, written once)."""
    B, T, H, _ = next(x.shape for kind, x in operands if kind == "tokens")
    N = next(x.shape[0] for kind, x in operands if kind == "chunks")
    state = next(x.shape[1:] for kind, x in outs if kind == "state")
    blocks = H // _SCAN_HEADS

    def spec(kind, x):
        at = (lambda n: N - 1 - n) if reverse else (lambda n: n)
        if kind == "chunks":
            return pl.BlockSpec((1, _SCAN_HEADS) + x.shape[2:],
                                lambda b, j, n, r=x.ndim - 2: (at(n), b * blocks + j) + (0,) * r)
        if kind == "tokens":
            return pl.BlockSpec((1, T // N, _SCAN_HEADS, x.shape[-1]), lambda b, j, n: (b, at(n), j, 0))
        return pl.BlockSpec((_SCAN_HEADS,) + x.shape[1:], lambda b, j, n: (b * blocks + j, 0, 0))

    return tuple(pl.pallas_call(
        kernel,
        grid=(B, blocks, N),
        in_specs=[spec(*x) for x in operands],
        out_specs=[spec(*x) for x in outs],
        out_shape=[x for _, x in outs],
        scratch_shapes=[pltpu.VMEM((_SCAN_HEADS,) + state, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        input_output_aliases=aliases or {},
        interpret=interpret,
    )(*(x for _, x in operands)))


def _scan_pallas(U0, W, D, q, k, decay_in, decay_out, S0, dtype, interpret=False):
    N, BH, C, dk = W.shape
    B, T, H, _ = q.shape
    dv = U0.shape[-1]
    f32 = jnp.float32
    outs = (("tokens", jax.ShapeDtypeStruct((B, T, H, dv), f32)), ("state", jax.ShapeDtypeStruct((BH, dk, dv), f32)),
            ("chunks", jax.ShapeDtypeStruct((N, BH, dk, dv), f32)))
    operands = (("chunks", U0), ("chunks", W), ("chunks", D), ("tokens", q), ("tokens", k), ("chunks", decay_in),
                ("chunks", decay_out)) + (() if S0 is None else (("state", S0),))
    return _chunks_call(functools.partial(_scan_kernel, dtype), operands, outs, interpret=interpret)


def _scan_transpose_pallas(U0, W, D, q, k, decay_in, decay_out, S_before, do, dS_final, dS_before, dtype,
                           interpret=False):
    """The cotangents of ``(U0, W, D, q, k, decay_in, decay_out, S0)``. ``dU0`` and
    ``dW`` take the memory of ``U0`` and ``W``, which nothing reads afterwards."""
    chunks = lambda *x: tuple(("chunks", y) for y in x)
    operands = chunks(U0, W, D) + (("tokens", q), ("tokens", k)) + chunks(decay_in, decay_out, S_before) + (
        ("tokens", do), ("chunks", dS_before), ("state", dS_final))
    like = lambda kind, x: (kind, jax.ShapeDtypeStruct(x.shape, jnp.float32))
    outs = tuple(like(*x) for x in operands[:7]) + (like("state", dS_final),)
    return _chunks_call(functools.partial(_scan_transpose_kernel, dtype), operands, outs, reverse=True,
                        aliases={0: 0, 1: 1}, interpret=interpret)


def _scan_xla_transpose(U0, W, D, q, k, decay_in, decay_out, S_before, do, dS_final, dS_before, dtype):
    """Autodiff's transpose of the XLA form, which runs the scan again from the
    first kept state."""
    _, back = jax.vjp(functools.partial(_scan_xla, dtype=dtype), U0, W, D, q, k, decay_in, decay_out, S_before[0])
    return back((do, dS_final, dS_before))


def _scan_fusable(heads: int, chunk: int, dk: int, dv: int) -> bool:
    """Whole ``(8, 128)`` vector tiles in every block: a chunk that is a multiple
    of 8, head widths that are multiples of 128, and a row's ``heads`` in blocks
    of :data:`_SCAN_HEADS`."""
    return chunk % 8 == 0 and dk % 128 == 0 and dv % 128 == 0 and heads % _SCAN_HEADS == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _scan_fused(U0, W, D, q, k, decay_in, decay_out, S0, dtype):
    return _scan_fused_fwd(U0, W, D, q, k, decay_in, decay_out, S0, dtype)[0]


def _scan_fused_fwd(U0, W, D, q, k, decay_in, decay_out, S0, dtype):
    # S_before is an output and the transpose's residual; S0 is kept for its structure
    operands = (U0, W, D, q, k, decay_in, decay_out)
    o, S, S_before = _on_platform(functools.partial(_scan_pallas, dtype=dtype),
                                  functools.partial(_scan_xla, dtype=dtype), *operands, S0)
    return (o, S, S_before), (operands, S_before, S0)


def _scan_fused_bwd(dtype, kept, cotangents):
    operands, S_before, S0 = kept
    *grads, dS0 = _on_platform(functools.partial(_scan_transpose_pallas, dtype=dtype),
                               functools.partial(_scan_xla_transpose, dtype=dtype),
                               *operands, S_before, *cotangents)
    return (*grads, None if S0 is None else dS0)


_scan_fused.defvjp(_scan_fused_fwd, _scan_fused_bwd)


def fused_tiles(shape, d_v: int, chunk: int = 64):
    """Tiles one pass of :func:`chunked` over keys of ``shape`` ``[B, T, H, d_k]``
    builds in the fused kernel: a scalar that reads ``B * T / chunk * H`` in a
    program lowered for a TPU where the shapes allow the kernel, 0 elsewhere."""
    B, T, H, dk = shape
    return _on_tpu(B * (T // chunk) * H if _fusable(H, chunk, dk, d_v) else 0)


def scan_fused_tiles(shape, d_v: int, chunk: int = 64):
    """A head's chunks that one pass of :func:`chunked` over keys of ``shape``
    carries through the inter-chunk kernels: ``B * T / chunk * H`` in a program
    lowered for a TPU where the shapes allow them, 0 elsewhere."""
    B, T, H, dk = shape
    return _on_tpu(B * (T // chunk) * H if _scan_fusable(H, chunk, dk, d_v) else 0)


def _on_tpu(count: int):
    """``count`` as a float32 scalar in a program lowered for a TPU, 0 elsewhere."""
    if not count:
        return jnp.float32(0.0)
    return jax.lax.platform_dependent(tpu=lambda: jnp.float32(count), default=lambda: jnp.float32(0.0))


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

    # from the chunk's start to each token, 0 after a reset; at the chunk's last
    # token it is the carried state's own decay over the chunk
    decay_in = jnp.exp(G) * from_state  # [N, B, H, C]
    # U0: the updates, state left out; W: what the carried state takes off them
    with jax.named_scope("wy"):
        if _fusable(H, C, dk, dv):  # the kernels take tiles, one a head's chunk, in blocks of a row's heads
            U0, W = (x.reshape((N, B, H) + x.shape[1:]) for x in _wy_fused(
                k.astype(f32), v.astype(f32), D.reshape(N * B * H, C, C), bc.reshape(N * B, H, C),
                decay_in.reshape(N * B, H, C)))
        else:
            U0, W, _ = _wy_xla(split(k.astype(f32)), split(v.astype(f32)), D, bc, decay_in)
    decay_out = jnp.exp(G[..., -1:] - G) * same[..., -1, :]  # from each token to the chunk's end
    S0 = None if initial_state is None else initial_state.astype(f32)
    with jax.named_scope("scan"):
        if _scan_fusable(H, C, dk, dv):  # the kernels take blocks of a row's heads, every chunk of each
            heads = lambda x: x.reshape((N, B * H) + x.shape[3:])
            o, S, S_before = _scan_fused(heads(U0), heads(W), heads(D), q.astype(f32), k.astype(f32),
                                         heads(decay_in), heads(decay_out),
                                         None if S0 is None else S0.reshape(B * H, dk, dv), dtype)
            S, S_before = S.reshape(B, H, dk, dv), S_before.reshape(N, B, H, dk, dv)
        else:
            o, S, S_before = _scan_xla(U0, W, D, q.astype(f32), k.astype(f32), decay_in, decay_out, S0, dtype)
    return o, S, S_before
