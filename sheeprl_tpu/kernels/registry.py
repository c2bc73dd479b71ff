"""Tiered dispatch registry for the fused-kernel subsystem.

One knob — ``algo.fused_kernels`` — resolved ONCE at agent-build time by
``resolve_tier`` into a tier string baked into the flax modules:

- ``off``    — the reference flax path (``kernels/reference.py``), bitwise
  today's runtime. Also what ``auto`` means on hosts with no fused win.
- ``xla``    — padded + fused pure-XLA cells (``kernels/xla.py``); runs
  everywhere, ``pad_to`` defaults to the 128-lane tile on TPU and 1 (no
  padding, bitwise reference) elsewhere.
- ``pallas`` — the Pallas TPU kernels (``kernels/pallas_tpu.py``). An
  explicit request on a non-TPU backend raises: nothing reachable from
  config runs the kernels through the interpreter (``interpret=True`` is an
  argument of the dispatchers for the CPU parity tests only). A family
  with no Pallas kernel yet degrades to ``xla`` with a logged notice and a
  ``kernel_tier_degraded`` telemetry count. Programs of a TPU run that are
  lowered for the host CPU (the ``algo.player_on_host`` acting mirror)
  take the kernel's padded-XLA twin — chosen per lowering platform by
  ``jax.lax.platform_dependent``, never by a Python branch.
- ``auto``   — the best tier available: ``pallas`` on TPU where the family
  has one, ``xla`` elsewhere.

The registry also owns two cross-cutting facilities:

- ``reference_cost_mode()`` — a contextvar the dispatchers check at TRACE
  time: inside it every fused cell lowers as the reference program. PR-8's
  ``register_train_cost`` retraces the train step under this mode, so
  roofline/MFU accounting always prices the *reference* FLOPs/bytes — a
  fused (padded) program cannot inflate its own MFU denominator.
- ``fused_active()`` — whether any non-``off`` tier was resolved in this
  process, so cost accounting knows a retrace is needed at all.

Adding a kernel (howto/kernels.md): put the reference math in
``reference.py``, the fused tiers in ``xla.py``/``pallas_tpu.py``, add a
``KERNELS`` row + an analytic ``kernel_cost`` entry here, dispatch from
the owning flax module through this registry, and extend the parity suite.
``tools/lint_kernels.py`` enforces that gate math lives nowhere else.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from sheeprl_tpu.kernels import reference, xla

_LOGGER = logging.getLogger(__name__)

TIERS = ("off", "xla", "pallas")

#: kernel family -> implemented tiers (beyond the always-available ``off``)
KERNELS: Dict[str, Dict[str, Any]] = {
    # the RSSM recurrent core (models.LayerNormGRUCell): DV2/P2E-DV2 at
    # H=600, DV3 shares the module but keeps fused_kernels=off for now
    "hafner_ln_gru": {"tiers": ("off", "xla", "pallas")},
    # DreamerV1's flax nn.GRUCell math: no Pallas kernel yet — a ``pallas``
    # request degrades to ``xla`` with a notice
    "flax_gru": {"tiers": ("off", "xla")},
}

_REFERENCE_COST = contextvars.ContextVar("sheeprl_kernels_reference_cost", default=False)
_ACTIVE_FUSED = set()


@contextlib.contextmanager
def reference_cost_mode():
    """While active (including at trace time inside a fresh ``jax.jit``),
    every registry dispatch takes the reference path regardless of tier."""
    token = _REFERENCE_COST.set(True)
    try:
        yield
    finally:
        _REFERENCE_COST.reset(token)


def cost_mode_active() -> bool:
    return bool(_REFERENCE_COST.get())


def fused_active() -> bool:
    """True when any agent in this process was built with a fused tier."""
    return bool(_ACTIVE_FUSED)


def normalize_tier(value: Any) -> str:
    """Config values arrive as strings or YAML booleans (bare ``off`` in
    YAML 1.1 parses as ``False``; ``on``/``True`` means ``auto``)."""
    if value is None or value is False:
        return "off"
    if value is True:
        return "auto"
    tier = str(value).strip().lower()
    if tier in ("", "0", "false", "none", "no"):
        return "off"
    if tier in ("1", "true", "yes", "on"):
        return "auto"
    return tier


def resolve_tier(requested: Any, *, family: str = "hafner_ln_gru") -> str:
    """Resolve the ``algo.fused_kernels`` knob to a concrete tier for one
    kernel family on the current backend (called at agent-build time)."""
    tier = normalize_tier(requested)
    has_pallas = "pallas" in KERNELS[family]["tiers"]
    if tier == "auto":
        tier = "pallas" if has_pallas and jax.default_backend() == "tpu" else "xla"
    if tier not in TIERS:
        raise ValueError(
            f"algo.fused_kernels={requested!r}: expected one of {TIERS + ('auto',)}"
        )
    if tier == "pallas" and not has_pallas:
        _LOGGER.warning(
            "fused_kernels=pallas: kernel family %r has no Pallas tier yet — "
            "degrading to xla",
            family,
        )
        _count_degrade()
        tier = "xla"
    if tier == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"algo.fused_kernels=pallas on backend={jax.default_backend()}: the "
            "Pallas kernels compile for TPU only. Use algo.fused_kernels=auto for "
            "the best tier this backend has (xla here), or xla/off explicitly."
        )
    if tier != "off":
        _ACTIVE_FUSED.add(tier)
    return tier


def _count_degrade() -> None:
    # late import: obs.counters is optional at import time and obs imports us
    try:
        from sheeprl_tpu.obs.counters import add_kernel_tier_degraded

        add_kernel_tier_degraded()
    except Exception:  # pragma: no cover - counters not initialised
        pass


def default_pad_to(tier: str) -> int:
    """The xla tier pads to the MXU tile only where tiling exists: on CPU
    ``pad_to=1`` keeps the fused cell bitwise the reference op sequence."""
    if tier == "xla" and jax.default_backend() != "tpu":
        return 1
    return 128


# ---------------------------------------------------------------------------
# dispatchers — the only entrypoints the flax modules call
# ---------------------------------------------------------------------------


def _pallas_or_twin(name, twin, interpret, *operands, hidden_size, eps):
    """Tier ``pallas``: the Mosaic kernel where the program is lowered for
    TPU, the kernel's padded-XLA twin (the program its custom-VJP already
    differentiates) where a TPU run lowers it for the host CPU — the
    ``algo.player_on_host`` acting mirror cannot run Mosaic code.
    ``interpret=True`` (CPU parity tests) runs the kernel body itself."""
    from sheeprl_tpu.kernels import pallas_tpu

    kernel_fn = getattr(pallas_tpu, name)
    layer_norm = operands[4] is not None

    def run_kernel(*ops):
        return kernel_fn(
            *ops, hidden_size=hidden_size, eps=eps, layer_norm=layer_norm, interpret=interpret
        )

    if interpret:
        return run_kernel(*operands)
    return jax.lax.platform_dependent(
        *operands,
        tpu=run_kernel,
        default=lambda *ops: twin(
            *ops, hidden_size=hidden_size, eps=eps, pad_to=pallas_tpu.LANE
        ),
    )


def hafner_gru_cell(
    h: jnp.ndarray,
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    ln_scale: Optional[jnp.ndarray],
    ln_bias: Optional[jnp.ndarray],
    *,
    hidden_size: int,
    eps: float,
    tier: str,
    pad_to: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One LayerNorm-GRU step through the resolved tier."""
    if tier == "off" or cost_mode_active():
        return reference.hafner_cell(h, x, kernel, bias, ln_scale, ln_bias, eps=eps)
    if tier == "xla":
        return xla.hafner_cell_fused(
            h, x, kernel, bias, ln_scale, ln_bias,
            hidden_size=hidden_size, eps=eps,
            pad_to=default_pad_to(tier) if pad_to is None else pad_to,
        )
    if tier == "pallas":
        return _pallas_or_twin(
            "hafner_cell", xla.hafner_cell_fused, interpret,
            h, x, kernel, bias, ln_scale, ln_bias, hidden_size=hidden_size, eps=eps,
        )
    raise ValueError(f"unknown kernel tier {tier!r}")


def hafner_gru_sequence(
    h0: jnp.ndarray,
    xs: jnp.ndarray,
    kernel: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    ln_scale: Optional[jnp.ndarray],
    ln_bias: Optional[jnp.ndarray],
    *,
    hidden_size: int,
    eps: float,
    tier: str,
    pad_to: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Whole-sequence LayerNorm-GRU (``xs`` known up front): the fused
    scan with the hoisted input GEMM (xla) or the VMEM-resident Pallas
    scan. ``off`` runs the reference cell under ``lax.scan``."""
    if tier == "off" or cost_mode_active():
        def body(h, x_t):
            new_h = reference.hafner_cell(h, x_t, kernel, bias, ln_scale, ln_bias, eps=eps)
            return new_h, new_h

        _, hs = jax.lax.scan(body, h0, xs)
        return hs
    if tier == "xla":
        return xla.hafner_sequence_fused(
            h0, xs, kernel, bias, ln_scale, ln_bias,
            hidden_size=hidden_size, eps=eps,
            pad_to=default_pad_to(tier) if pad_to is None else pad_to,
        )
    if tier == "pallas":
        return _pallas_or_twin(
            "hafner_sequence", xla.hafner_sequence_fused, interpret,
            h0, xs, kernel, bias, ln_scale, ln_bias, hidden_size=hidden_size, eps=eps,
        )
    raise ValueError(f"unknown kernel tier {tier!r}")


def flax_gru_cell(
    h: jnp.ndarray,
    x: jnp.ndarray,
    params,
    *,
    hidden_size: int,
    tier: str,
    pad_to: Optional[int] = None,
) -> jnp.ndarray:
    """One flax-convention GRU step through the resolved tier (``pallas``
    resolves to ``xla`` for this family at build time)."""
    if tier == "off" or cost_mode_active():
        return reference.flax_gru_cell(h, x, params)
    return xla.flax_gru_cell_fused(
        h, x, params,
        hidden_size=hidden_size,
        pad_to=default_pad_to("xla") if pad_to is None else pad_to,
    )


# ---------------------------------------------------------------------------
# analytic per-kernel cost specs (reference widths — never the padded ones)
# ---------------------------------------------------------------------------


def kernel_cost(
    family: str,
    *,
    batch: int,
    hidden_size: int,
    input_size: int,
    seq_len: int = 1,
    layer_norm: bool = True,
) -> Dict[str, float]:
    """Reference FLOPs/bytes for one forward of a kernel family at REAL
    (unpadded) widths — the denominator bench_kernels.py and the roofline
    use, so padding can never inflate a utilization number."""
    B, H, X, T = int(batch), int(hidden_size), int(input_size), int(seq_len)
    if family not in KERNELS:
        raise KeyError(f"unknown kernel family {family!r}")
    steps = B * T
    matmul = 2.0 * steps * (H + X) * (3 * H)
    ln = (8.0 * steps * 3 * H) if (layer_norm and family == "hafner_ln_gru") else 0.0
    gates = 10.0 * steps * H
    flops = matmul + ln + gates
    # params once + activations per step, f32
    param_bytes = 4.0 * ((H + X) * 3 * H + 3 * H * (3 if layer_norm else 1))
    act_bytes = 4.0 * steps * (H + X + H)
    return {"flops": flops, "bytes": param_bytes + act_bytes}
