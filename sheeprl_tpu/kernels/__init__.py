"""Fused-kernel subsystem (howto/kernels.md, ROADMAP item 4).

Tiered recurrent-core kernels behind the ``algo.fused_kernels`` knob:
``kernels/reference.py`` is the bitwise flax math (tier ``off``),
``kernels/xla.py`` the padded+fused pure-XLA tier, ``kernels/pallas_tpu.py``
the Pallas TPU kernels, and ``kernels/delta_rule.py`` the gated delta rule (recurrent reference and
chunked tier), and ``kernels/registry.py`` the build-time tier
resolution + trace-time dispatch + reference-cost accounting hooks.
"""

from sheeprl_tpu.kernels import delta_rule, reference, registry, xla
from sheeprl_tpu.kernels.registry import (
    KERNELS,
    TIERS,
    cost_mode_active,
    default_pad_to,
    flax_gru_cell,
    fused_active,
    hafner_gru_cell,
    hafner_gru_sequence,
    kernel_cost,
    normalize_tier,
    reference_cost_mode,
    resolve_tier,
)

__all__ = [
    "delta_rule",
    "reference",
    "registry",
    "xla",
    "KERNELS",
    "TIERS",
    "cost_mode_active",
    "default_pad_to",
    "flax_gru_cell",
    "fused_active",
    "hafner_gru_cell",
    "hafner_gru_sequence",
    "kernel_cost",
    "normalize_tier",
    "reference_cost_mode",
    "resolve_tier",
]
