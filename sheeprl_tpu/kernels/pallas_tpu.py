"""Tier ``pallas`` — TPU Pallas kernels for the Hafner LayerNorm-GRU.

Two kernels (see /opt/skills guide + howto/kernels.md):

- **cell**: one fused step — joint matmul (two MXU dots, ``h`` and ``x``
  parts separately so no lane-concat is needed), masked LayerNorm over the
  real lanes, gate block — all in one ``pallas_call`` on the padded
  ``Hp = ceil(H/128)·128`` layout (DV2: 600 → 640, so the 3·H projection
  runs 1920 full lanes instead of 1800 straddled ones). The batch rows are
  tiled over a ``parallel`` grid so VMEM use does not grow with the row
  count (imagination calls the cell with ``T·B`` rows).
- **sequence**: the whole ``lax.scan`` time loop fused into ONE kernel:
  ``grid=(T,)`` with the hidden state resident in a VMEM scratch across
  grid steps (``pl.when(t == 0)`` seeds it from ``h0``), one timestep of
  ``xs`` streamed in per step and one row of the trajectory written out.

Both are wrapped in ``jax.custom_vjp`` whose backward is ``jax.vjp`` of
the *padded XLA program* (``kernels.xla``) over the same padded operands:
the fused forward changes the schedule, not the math, so the XLA gradient
is the gradient. Forward parity vs the reference cell and gradient parity
vs reference autodiff are asserted on the CPU through ``interpret=True``
(``tests/test_models/test_kernels.py``) and, compiled by Mosaic at the DV2
shape, by ``chip_smoke.py`` on the TPU.

Padding: ``H`` and the input width ``X`` go up to the 128-lane multiple
(zero parameter rows/columns contribute nothing) and the row count up to
the f32 sublane multiple, so every operand lands on full ``(8, 128)``
tiles. The whole ``[Hp+Xp, 3·Hp]`` weight is one constant-index block,
which the Pallas pipeline double-buffers (2 × 8.8 MB at the DV2 shape —
above the 16 MiB default scoped-VMEM limit), so each call states its VMEM
need through ``vmem_limit_bytes``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sheeprl_tpu.kernels import xla

__all__ = ["LANE", "hafner_cell", "hafner_sequence"]

#: TPU vector-lane width — the tile the hidden state is padded to
LANE = 128
#: f32 sublane count — the tile the row (batch) dimension is padded to
SUBLANE = 8
#: rows per grid step of the cell kernel
CELL_BLOCK_ROWS = 256
#: headroom on top of the counted blocks for Mosaic's own scratch
_VMEM_SLACK_BYTES = 4 << 20


def _gate_block(z, h, *, H, Hp, eps, layer_norm, scale, bias):
    """Shared in-kernel epilogue: masked LayerNorm + Hafner gates."""
    if layer_norm:
        n_real = 3.0 * H
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 3 * Hp), 1)
        # real lanes of gate g are [g·Hp, g·Hp + H) — comparisons only, no
        # vector integer remainder
        real = lane < H
        for g in (1, 2):
            real = real | ((lane >= g * Hp) & (lane < g * Hp + H))
        mask = real.astype(jnp.float32)
        mu = jnp.sum(z, axis=-1, keepdims=True) / n_real
        var = jnp.sum(jnp.square(z - mu) * mask, axis=-1, keepdims=True) / n_real
        z = (z - mu) * jax.lax.rsqrt(var + eps)
        z = z * scale + bias
    reset = jax.nn.sigmoid(z[:, :Hp])
    cand = jnp.tanh(reset * z[:, Hp : 2 * Hp])
    update = jax.nn.sigmoid(z[:, 2 * Hp :] - 1.0)
    return update * cand + (1.0 - update) * h


def _step(h, x, w_ref, b_ref, s_ref, lb_ref, *, H, Hp, eps, layer_norm):
    """One LayerNorm-GRU step on loaded ``h``/``x`` tiles. The weight is
    sliced as a *ref* (row ``Hp`` is sublane-aligned), so the two dots read
    their halves straight from VMEM — no lane-dim concat of ``[h, x]`` and no
    value-slice of the loaded 8.8 MB weight."""
    z = jnp.dot(h, w_ref[:Hp, :], preferred_element_type=jnp.float32)
    z += jnp.dot(x, w_ref[Hp:, :], preferred_element_type=jnp.float32)
    z += b_ref[...]
    return _gate_block(
        z, h, H=H, Hp=Hp, eps=eps, layer_norm=layer_norm, scale=s_ref[...], bias=lb_ref[...]
    )


def _cell_kernel(h_ref, x_ref, w_ref, b_ref, s_ref, lb_ref, o_ref, **static):
    o_ref[...] = _step(h_ref[...], x_ref[...], w_ref, b_ref, s_ref, lb_ref, **static)


def _seq_kernel(h0_ref, xs_ref, w_ref, b_ref, s_ref, lb_ref, o_ref, h_scr, **static):
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        h_scr[...] = h0_ref[...]

    new_h = _step(h_scr[...], xs_ref[0], w_ref, b_ref, s_ref, lb_ref, **static)
    h_scr[...] = new_h
    o_ref[0] = new_h


def _compiler_params(semantics: str, rows: int, Hp: int, Xp: int):
    """Mosaic parameters for one grid axis of ``semantics`` whose step holds
    ``rows`` batch rows: every in/out block double-buffered by the pipeline,
    plus the ``[rows, 3·Hp]`` pre-activation and its elementwise temporaries
    (counted as six copies), plus slack."""
    blocks = rows * (2 * Hp + Xp) + (Hp + Xp) * 3 * Hp + 3 * 3 * Hp
    temporaries = 6 * rows * 3 * Hp + rows * Hp
    return pltpu.CompilerParams(
        dimension_semantics=(semantics,),
        vmem_limit_bytes=4 * (2 * blocks + temporaries) + _VMEM_SLACK_BYTES,
    )


def _pad_operands(h, x, kernel, bias, ln_scale, ln_bias, *, hidden_size, layer_norm):
    """Real-width operands → full-tile padded layout (H and X both padded;
    dummy ones/zeros LN affine when the cell runs without LayerNorm, so the
    kernel signature is static)."""
    H = int(hidden_size)
    kernel, bias, ln_scale, ln_bias, Hp = xla.pad_hafner_params(
        kernel, bias, ln_scale, ln_bias, hidden_size=H, pad_to=LANE
    )
    X = kernel.shape[0] - Hp
    Xp = xla.round_up(max(X, 1), LANE)
    if Xp != X:
        kernel = jnp.concatenate([kernel[:Hp], xla.pad_axis(kernel[Hp:], 0, Xp)], axis=0)
    x = xla.pad_axis(x, -1, Xp)
    h = xla.pad_axis(h, -1, Hp)
    if bias is None:
        bias = jnp.zeros((3 * Hp,), kernel.dtype)
    if not layer_norm or ln_scale is None:
        ln_scale = jnp.ones((3 * Hp,), kernel.dtype)
        ln_bias = jnp.zeros((3 * Hp,), kernel.dtype)
    return h, x, kernel, bias.reshape(1, -1), ln_scale.reshape(1, -1), ln_bias.reshape(1, -1), Hp


@functools.lru_cache(maxsize=None)
def _make_cell(H: int, Hp: int, eps: float, layer_norm: bool, interpret: bool):
    body = functools.partial(_cell_kernel, H=H, Hp=Hp, eps=eps, layer_norm=layer_norm)

    def impl(h, x, w, b, s, lb):
        rows, Xp = x.shape
        block = min(rows, CELL_BLOCK_ROWS)
        tile, fixed = (lambda i: (i, 0)), (lambda i: (0, 0))
        call = pl.pallas_call(
            body,
            grid=(rows // block,),
            in_specs=[
                pl.BlockSpec((block, Hp), tile),
                pl.BlockSpec((block, Xp), tile),
                pl.BlockSpec(w.shape, fixed),
                pl.BlockSpec(b.shape, fixed),
                pl.BlockSpec(s.shape, fixed),
                pl.BlockSpec(lb.shape, fixed),
            ],
            out_specs=pl.BlockSpec((block, Hp), tile),
            out_shape=jax.ShapeDtypeStruct(h.shape, jnp.float32),
            compiler_params=_compiler_params("parallel", block, Hp, Xp),
            interpret=interpret,
        )
        return call(h, x, w, b, s, lb)

    @jax.custom_vjp
    def cell(h, x, w, b, s, lb):
        return impl(h, x, w, b, s, lb)

    def fwd(h, x, w, b, s, lb):
        return impl(h, x, w, b, s, lb), (h, x, w, b, s, lb)

    def bwd(res, g):
        # gradient of the padded XLA program — same math, XLA's autodiff
        def ref(h, x, w, b, s, lb):
            return xla.hafner_cell_padded(
                h, x, w, b.reshape(-1),
                s.reshape(-1) if layer_norm else None,
                lb.reshape(-1) if layer_norm else None,
                hidden_size=H, padded_size=Hp, eps=eps,
            )

        _, vjp = jax.vjp(ref, *res)
        return vjp(g)

    cell.defvjp(fwd, bwd)
    return cell


@functools.lru_cache(maxsize=None)
def _make_sequence(H: int, Hp: int, eps: float, layer_norm: bool, interpret: bool):
    body = functools.partial(_seq_kernel, H=H, Hp=Hp, eps=eps, layer_norm=layer_norm)

    def impl(h0, xs, w, b, s, lb):
        T, B, Xp = xs.shape
        fixed, step = (lambda t: (0, 0)), (lambda t: (t, 0, 0))
        call = pl.pallas_call(
            body,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((B, Hp), fixed),
                pl.BlockSpec((1, B, Xp), step),
                pl.BlockSpec(w.shape, fixed),
                pl.BlockSpec(b.shape, fixed),
                pl.BlockSpec(s.shape, fixed),
                pl.BlockSpec(lb.shape, fixed),
            ],
            out_specs=pl.BlockSpec((1, B, Hp), step),
            out_shape=jax.ShapeDtypeStruct((T, B, Hp), jnp.float32),
            scratch_shapes=[pltpu.VMEM((B, Hp), jnp.float32)],
            # the (T,) grid is a serial recurrence through the VMEM scratch
            compiler_params=_compiler_params("arbitrary", B, Hp, Xp),
            interpret=interpret,
        )
        return call(h0, xs, w, b, s, lb)

    @jax.custom_vjp
    def seq(h0, xs, w, b, s, lb):
        return impl(h0, xs, w, b, s, lb)

    def fwd(h0, xs, w, b, s, lb):
        return impl(h0, xs, w, b, s, lb), (h0, xs, w, b, s, lb)

    def bwd(res, g):
        def ref(h0, xs, w, b, s, lb):
            return _xla_sequence_padded(
                h0, xs, w, b, s, lb, H=H, Hp=Hp, eps=eps, layer_norm=layer_norm
            )

        _, vjp = jax.vjp(ref, *res)
        return vjp(g)

    seq.defvjp(fwd, bwd)
    return seq


def _xla_sequence_padded(h0, xs, w, b, s, lb, *, H, Hp, eps, layer_norm):
    """Padded-layout XLA twin of the sequence kernel (hoisted input GEMM +
    scan) — the custom-VJP backward program."""
    kh, kx = w[:Hp], w[Hp:]
    zx = jnp.einsum("tbx,xh->tbh", xs, kx) + b

    def bodyfn(h, zx_t):
        z = h @ kh + zx_t
        if layer_norm:
            z = xla.masked_layer_norm(
                z, s.reshape(-1), lb.reshape(-1), eps=eps, hidden_size=H, padded_size=Hp
            )
        reset = jax.nn.sigmoid(z[:, :Hp])
        cand = jnp.tanh(reset * z[:, Hp : 2 * Hp])
        update = jax.nn.sigmoid(z[:, 2 * Hp :] - 1.0)
        new_h = update * cand + (1.0 - update) * h
        return new_h, new_h

    _, hs = jax.lax.scan(bodyfn, h0, zx)
    return hs


def _padded_rows(rows: int, block: int) -> int:
    """Row count after padding: the sublane multiple, and a whole number of
    ``block``-row grid steps once there is more than one."""
    rows = xla.round_up(rows, SUBLANE)
    return rows if rows <= block else xla.round_up(rows, block)


def hafner_cell(
    h: jnp.ndarray,
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    ln_scale: Optional[jnp.ndarray],
    ln_bias: Optional[jnp.ndarray],
    *,
    hidden_size: int,
    eps: float = 1e-3,
    layer_norm: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """One fused LayerNorm-GRU step on real-width operands (any leading
    batch dims): flattens to rows, pads to tiles, runs the Pallas cell,
    slices the real rows and lanes back out. ``interpret`` is for the CPU
    parity tests only."""
    H = int(hidden_size)
    lead = h.shape[:-1]
    h2, x2 = h.reshape(-1, H), x.reshape(-1, x.shape[-1])
    rows = h2.shape[0]
    h_p, x_p, w, b, s, lb, Hp = _pad_operands(
        h2, x2, kernel, bias, ln_scale, ln_bias, hidden_size=H, layer_norm=layer_norm
    )
    rows_p = _padded_rows(rows, CELL_BLOCK_ROWS)
    h_p, x_p = xla.pad_axis(h_p, 0, rows_p), xla.pad_axis(x_p, 0, rows_p)
    cell = _make_cell(H, Hp, float(eps), bool(layer_norm and ln_scale is not None), interpret)
    out = cell(h_p, x_p, w, b, s, lb)
    return out[:rows, :H].reshape(lead + (H,))


def hafner_sequence(
    h0: jnp.ndarray,
    xs: jnp.ndarray,
    kernel: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    ln_scale: Optional[jnp.ndarray],
    ln_bias: Optional[jnp.ndarray],
    *,
    hidden_size: int,
    eps: float = 1e-3,
    layer_norm: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Whole-sequence fused scan: ``xs`` is ``[T, B, X]`` → trajectory
    ``[T, B, H]``, hidden state VMEM-resident across the ``grid=(T,)``.
    ``interpret`` is for the CPU parity tests only."""
    H = int(hidden_size)
    B = h0.shape[0]
    h_p, xs_p, w, b, s, lb, Hp = _pad_operands(
        h0, xs, kernel, bias, ln_scale, ln_bias, hidden_size=H, layer_norm=layer_norm
    )
    Bp = xla.round_up(B, SUBLANE)
    h_p, xs_p = xla.pad_axis(h_p, 0, Bp), xla.pad_axis(xs_p, 1, Bp)
    seq = _make_sequence(H, Hp, float(eps), bool(layer_norm and ln_scale is not None), interpret)
    out = seq(h_p, xs_p, w, b, s, lb)
    return out[:, :B, :H]
