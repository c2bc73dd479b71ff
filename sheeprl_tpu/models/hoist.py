"""A time scan whose backward loop does not add up its ``Dense`` kernels' gradients.

``jax.lax.scan``'s transpose carries the cotangent of every array its body
closes over and adds to it once an iteration: a weight the body multiplies by
costs a read and a write of a weight-sized float32 buffer every step of the
backward loop, whatever the batch. For ``y_t = x_t @ W`` the sum is one
product, ``dW = Σ_t x_tᵀ dy_t = [T·B, I]ᵀ × [T·B, O]``, and needs nothing of the
loop but ``x_t`` and ``dy_t`` stacked over time.

:func:`scan_hoisting_dense_grads` is ``jax.lax.scan`` over a flax ``apply``
with that product made after the loop, for every ``nn.Dense`` the body calls:
each such ``Dense`` adds a zero ``probe_t`` to its output and hands out its
input, the scan is differentiated with the kernels closed over (so its
transpose carries no cotangent for them) and the probes' cotangents, which a
scan stacks and does not add up, are the ``dy_t``. ``dx_t = dy_t @ Wᵀ`` stays
in the loop: it is the recurrence.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.traverse_util import flatten_dict, unflatten_dict

Path = Tuple[str, ...]


class _Site(NamedTuple):
    """One ``nn.Dense`` call of the scan body, as the hoisted product needs it."""

    rows: Tuple[int, ...]  # the input's leading shape, an iteration
    features: int
    dtype: Any  # what the Dense computes in: the probe's and the stacked input's
    precision: Any


def _dense_calls(record: Callable[[nn.Dense, jnp.ndarray, Callable], jnp.ndarray]):
    """Interceptor that routes every ``nn.Dense.__call__`` through ``record``."""

    def interceptor(next_fun, args, kwargs, context):
        if type(context.module) is nn.Dense and context.method_name == "__call__":
            return record(context.module, args[0], next_fun)
        return next_fun(*args, **kwargs)

    return nn.intercept_methods(interceptor)


def scan_hoisting_dense_grads(
    step: Callable[[Any, Any, Any, Any], Tuple[Any, Any]],
    params: Dict[str, Any],
    consts: Any,
    init: Any,
    xs: Any,
):
    """``jax.lax.scan(lambda c, x: step(params, consts, c, x), init, xs)``, same
    values and same gradients, with the gradient of every ``nn.Dense`` kernel
    of ``params`` that ``step`` applies once an iteration computed as one
    product after the backward loop.

    ``params`` is the ``"params"`` tree ``step`` hands to ``Module.apply``
    (module paths index it); ``consts`` holds whatever else ``step`` needs
    and gradients flow to — ``step`` closes over nothing that is
    differentiated. The product has the per-step product's operands (the
    ``Dense``'s compute dtype and ``precision``) and accumulates in float32.
    A ``Dense`` called more than once an iteration, or anything that is not a
    ``nn.Dense``, keeps its gradient in the loop.
    """
    flat = flatten_dict(params)

    # which Dense modules the body calls, on what rows, computing in what dtype
    calls: Dict[Path, list] = {}

    def note(module, x, next_fun):
        y = next_fun(x)
        calls.setdefault(module.path + ("kernel",), []).append(
            _Site(x.shape[:-1], y.shape[-1], y.dtype, module.precision)
        )
        return y

    def discover():
        with _dense_calls(note):
            return step(params, consts, init, jax.tree_util.tree_map(lambda x: x[0], xs))

    jax.eval_shape(discover)
    sites = {path: seen[0] for path, seen in calls.items() if len(seen) == 1 and path in flat}
    kernels = {path: flat.pop(path) for path in sites}
    kernel_dtypes = {path: kernel.dtype for path, kernel in kernels.items()}

    def merged(kernels, rest):
        return unflatten_dict({**rest, **kernels})

    def plain(kernels, rest, consts, init, xs):
        return jax.lax.scan(lambda c, x: step(merged(kernels, rest), consts, c, x), init, xs)

    def forward(kernels, rest, consts, init, xs):
        length = jax.tree_util.tree_leaves(xs)[0].shape[0]
        probes = {
            path: jnp.zeros((length, *site.rows, site.features), site.dtype) for path, site in sites.items()
        }

        def tapped(rest, consts, init, xs, probes):
            def body(carry, inp):
                x, probe = inp
                taps = {}

                def tap(module, x, next_fun):
                    path = module.path + ("kernel",)
                    y = next_fun(x)
                    if path in probe:
                        taps[path] = x.astype(y.dtype)
                        y = y + probe[path]
                    return y

                with _dense_calls(tap):
                    carry, y = step(merged(kernels, rest), consts, carry, x)
                return carry, (y, taps)

            carry, (ys, taps) = jax.lax.scan(body, init, (xs, probes))
            return (carry, ys), taps

        # the kernels are closed over: the transposed scan has no cotangent for them
        out, vjp, taps = jax.vjp(tapped, rest, consts, init, xs, probes, has_aux=True)
        return out, (vjp, taps)

    def backward(res, cotangent):
        vjp, taps = res
        d_rest, d_consts, d_init, d_xs, d_probes = vjp(cotangent)
        d_kernels = {
            path: jnp.einsum(
                "ni,no->io",
                taps[path].reshape(-1, taps[path].shape[-1]),
                d_probes[path].reshape(-1, site.features),
                precision=site.precision,
                preferred_element_type=jnp.float32,
            ).astype(kernel_dtypes[path])
            for path, site in sites.items()
        }
        return d_kernels, d_rest, d_consts, d_init, d_xs

    run = jax.custom_vjp(plain)
    run.defvjp(forward, backward)
    return run(kernels, flat, consts, init, xs)
