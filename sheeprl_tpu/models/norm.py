"""LayerNorm with a hand-derived one-pass backward (``jax.custom_vjp``).

Why this exists (round 4): the DV3 S-preset profile puts
~2.3 ms of the 14.03 ms device step in LayerNorm *backward* lane reductions
across the conv stacks — XLA autodiffs flax's ``nn.LayerNorm`` into a chain
that re-derives the variance path and schedules several cross-lane
reductions per instance. The canonical LN backward needs exactly two row
reductions:

    dx = rstd * (g*γ - mean(g*γ) - x̂ * mean(g*γ * x̂))

computed here from residuals ``(x̂, rstd)`` saved by the forward. Everything
is plain ``jnp`` — no Pallas, deliberately: the round-2/3 fused-kernel
experiments showed XLA cannot overlap async weight prefetches across a
custom-call region, so per-layer custom calls lose their standalone wins to
scheduling barriers. A ``custom_vjp`` keeps the math inside XLA's fusion
domain.

``FastLayerNorm`` is parameter-compatible with ``nn.LayerNorm`` (same
``scale``/``bias`` names and shapes): swapping it in changes no checkpoint.

Memory trade (deliberate, account for it in HBM capacity planning): the
forward saves ``x̂`` as a **float32** residual per LN instance, so under
bf16 training each LayerNorm retains ~4 bytes/element of activation memory
that flax's autodiff backward could rematerialize instead. At the S preset
this is noise; at L/XL presets alongside the device replay ring it is part
of the activation footprint the ``DeviceRingReplay`` HBM guard must leave
headroom for (wrap training in ``jax.checkpoint`` over the scan if it ever
binds — the residual then lives only inside one scan segment).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

__all__ = ["fast_layer_norm", "FastLayerNorm"]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def fast_layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float):
    """LayerNorm over the last axis. Statistics always computed in float32
    from the ORIGINAL-precision input (like flax's ``_compute_stats``);
    returns float32 — the caller casts to its compute dtype."""
    return _ln_fwd(x, scale, bias, eps)[0]


def _ln_fwd(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (xf - mu) * rstd
    y = xhat * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    # zero-size dtype token: the bwd must emit dx in x's exact dtype
    return y, (xhat, rstd, scale, jnp.zeros((0,), x.dtype))


def _ln_bwd(eps, res, g):
    xhat, rstd, scale, x_dtype_token = res
    gf = g.astype(jnp.float32)
    # parameter grads reduce over every leading (row) axis
    row_axes = tuple(range(g.ndim - 1))
    dbias = jnp.sum(gf, axis=row_axes)
    dscale = jnp.sum(gf * xhat, axis=row_axes)
    gg = gf * scale.astype(jnp.float32)
    m1 = jnp.mean(gg, axis=-1, keepdims=True)
    m2 = jnp.mean(gg * xhat, axis=-1, keepdims=True)
    dx = rstd * (gg - m1 - xhat * m2)
    return (
        dx.astype(x_dtype_token.dtype),
        dscale.astype(scale.dtype),
        dbias.astype(scale.dtype),
    )


fast_layer_norm.defvjp(_ln_fwd, _ln_bwd)


class FastLayerNorm(nn.Module):
    """Drop-in for ``nn.LayerNorm`` (last-axis, affine) with the one-pass
    custom-VJP backward. Parameter names/shapes match ``nn.LayerNorm``, and
    the dtype contract mirrors flax: stats from the original-precision
    input, output in ``dtype`` (or the promotion of input and param dtypes
    when ``dtype`` is None)."""

    epsilon: float = 1e-6
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        features = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (features,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (features,), self.param_dtype)
        out_dtype = self.dtype or jnp.promote_types(x.dtype, self.param_dtype)
        y = fast_layer_norm(x, scale, bias, float(self.epsilon))
        return y.astype(out_dtype)
