"""Parity suite for the fused-kernel subsystem (sheeprl_tpu/kernels).

Tier contract (ISSUE 13 / howto/kernels.md):

- ``off``  — IS the reference math, bitwise (also asserted e2e on DV2
  checkpoints in tests/test_envs/test_rollout.py).
- ``xla``  — with ``pad_to=1`` (the CPU default) the cell is bitwise the
  reference op sequence; with ``pad_to=128`` (the TPU tile) it is
  numerically equivalent, and padding must never leak into real lanes.
- ``pallas`` — exercised on CPU via ``interpret=True``: forward parity
  within float tolerance, and the ``custom_vjp`` backward must match
  reference autodiff (it IS the padded-XLA autodiff by construction —
  these tests pin that the padded program's gradient matches the
  real-width reference gradient). Whether Mosaic compiles the kernels is
  ``chip_smoke.py``'s check, on the TPU.

Width sweep includes the DV2 production shape (600, straddling the
128-lane tile), a prime just under it (599), an exact tile (128), and the
degenerate width 1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.kernels import (
    normalize_tier,
    reference,
    registry,
    resolve_tier,
    xla,
)
from sheeprl_tpu.kernels import pallas_tpu

WIDTHS = [(600, 400), (599, 37), (128, 64), (1, 3)]
B = 4


def _hafner_operands(H, X, *, layer_norm=True, seed=0):
    k = jax.random.PRNGKey(seed)
    kh, kx, kk, kb = jax.random.split(k, 4)
    h = jax.random.normal(kh, (B, H), jnp.float32)
    x = jax.random.normal(kx, (B, X), jnp.float32)
    kernel = jax.random.normal(kk, (H + X, 3 * H), jnp.float32) * 0.1
    bias = jax.random.normal(kb, (3 * H,), jnp.float32) * 0.1
    if layer_norm:
        ln_scale = jnp.ones((3 * H,), jnp.float32) + 0.1 * jax.random.normal(kb, (3 * H,))
        ln_bias = 0.1 * jax.random.normal(kk, (3 * H,), jnp.float32)
    else:
        ln_scale = ln_bias = None
    return h, x, kernel, bias, ln_scale, ln_bias


# ---------------------------------------------------------------------------
# tier b (xla): pad_to=1 bitwise, padded tolerance, no padding leak
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("H,X", WIDTHS)
@pytest.mark.parametrize("layer_norm", [True, False])
def test_xla_cell_pad1_bitwise_reference(H, X, layer_norm):
    ops = _hafner_operands(H, X, layer_norm=layer_norm)
    ref = jax.jit(lambda *a: reference.hafner_cell(*a, eps=1e-3))(*ops)
    fused = jax.jit(
        lambda *a: xla.hafner_cell_fused(*a, hidden_size=H, eps=1e-3, pad_to=1)
    )(*ops)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


@pytest.mark.parametrize("H,X", WIDTHS)
def test_xla_cell_padded_tolerance(H, X):
    ops = _hafner_operands(H, X)
    ref = jax.jit(lambda *a: reference.hafner_cell(*a, eps=1e-3))(*ops)
    fused = jax.jit(
        lambda *a: xla.hafner_cell_fused(*a, hidden_size=H, eps=1e-3, pad_to=128)
    )(*ops)
    assert fused.shape == (B, H)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(fused), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,X", [(600, 400), (599, 37)])
def test_xla_padded_hidden_lanes_stay_zero(H, X):
    """The padding invariant the docstring promises: a zero padded lane can
    never contaminate a real lane, because it stays exactly 0 through the
    gate block. Checked on the padded program's full-width output."""
    h, x, kernel, bias, ln_scale, ln_bias = _hafner_operands(H, X)
    kernel_p, bias_p, scale_p, lnb_p, Hp = xla.pad_hafner_params(
        kernel, bias, ln_scale, ln_bias, hidden_size=H, pad_to=128
    )
    hp = xla.pad_axis(h, -1, Hp)
    out = jax.jit(
        lambda *a: xla.hafner_cell_padded(*a, hidden_size=H, padded_size=Hp, eps=1e-3)
    )(hp, x, kernel_p, bias_p, scale_p, lnb_p)
    np.testing.assert_array_equal(np.asarray(out[..., H:]), 0.0)


@pytest.mark.parametrize("pad_to", [1, 128])
def test_xla_sequence_matches_reference_scan(pad_to):
    H, X, T = 64, 48, 7
    _, _, kernel, bias, ln_scale, ln_bias = _hafner_operands(H, X)
    k = jax.random.PRNGKey(3)
    h0 = jax.random.normal(k, (B, H), jnp.float32)
    xs = jax.random.normal(k, (T, B, X), jnp.float32)

    def ref_scan(h0, xs):
        def body(h, x_t):
            nh = reference.hafner_cell(h, x_t, kernel, bias, ln_scale, ln_bias, eps=1e-3)
            return nh, nh

        _, hs = jax.lax.scan(body, h0, xs)
        return hs

    ref = jax.jit(ref_scan)(h0, xs)
    fused = jax.jit(
        lambda h0, xs: xla.hafner_sequence_fused(
            h0, xs, kernel, bias, ln_scale, ln_bias, hidden_size=H, eps=1e-3, pad_to=pad_to
        )
    )(h0, xs)
    assert fused.shape == (T, B, H)
    # the hoisted input GEMM changes the reduction grouping — numerically
    # equivalent, not bitwise; errors compound over the T serial steps
    np.testing.assert_allclose(np.asarray(ref), np.asarray(fused), rtol=1e-4, atol=1e-5)


def test_xla_padded_cell_grad_matches_reference():
    """Gradients flow back through the padding ops and slice themselves to
    the real blocks — the padded program's parameter gradients must equal
    the reference program's at real widths."""
    H, X = 599, 37
    ops = _hafner_operands(H, X)

    def loss_ref(*a):
        return jnp.sum(jnp.tanh(reference.hafner_cell(*a, eps=1e-3)))

    def loss_fused(*a):
        return jnp.sum(jnp.tanh(xla.hafner_cell_fused(*a, hidden_size=H, eps=1e-3, pad_to=128)))

    g_ref = jax.jit(jax.grad(loss_ref, argnums=tuple(range(6))))(*ops)
    g_fused = jax.jit(jax.grad(loss_fused, argnums=tuple(range(6))))(*ops)
    for a, b in zip(g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# tier a (pallas, interpret=True on CPU): forward + custom_vjp parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("H,X", WIDTHS)
@pytest.mark.parametrize("layer_norm", [True, False])
def test_pallas_cell_interpret_forward_parity(H, X, layer_norm):
    ops = _hafner_operands(H, X, layer_norm=layer_norm)
    ref = jax.jit(lambda *a: reference.hafner_cell(*a, eps=1e-3))(*ops)
    out = jax.jit(
        lambda *a: pallas_tpu.hafner_cell(
            *a, hidden_size=H, eps=1e-3, layer_norm=layer_norm, interpret=True
        )
    )(*ops)
    assert out.shape == (B, H)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-5, atol=2e-5)


def test_pallas_sequence_interpret_forward_parity():
    H, X, T = 600, 400, 5
    _, _, kernel, bias, ln_scale, ln_bias = _hafner_operands(H, X)
    k = jax.random.PRNGKey(5)
    h0 = jax.random.normal(k, (B, H), jnp.float32)
    xs = jax.random.normal(k, (T, B, X), jnp.float32)

    def ref_scan(h0, xs):
        def body(h, x_t):
            nh = reference.hafner_cell(h, x_t, kernel, bias, ln_scale, ln_bias, eps=1e-3)
            return nh, nh

        _, hs = jax.lax.scan(body, h0, xs)
        return hs

    ref = jax.jit(ref_scan)(h0, xs)
    out = jax.jit(
        lambda h0, xs: pallas_tpu.hafner_sequence(
            h0, xs, kernel, bias, ln_scale, ln_bias,
            hidden_size=H, eps=1e-3, interpret=True,
        )
    )(h0, xs)
    assert out.shape == (T, B, H)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("H,X", [(600, 400), (128, 64)])
def test_pallas_cell_custom_vjp_grad_parity(H, X):
    """The Pallas cell's backward is declared as the padded-XLA autodiff;
    it must match the real-width reference autodiff for every operand."""
    ops = _hafner_operands(H, X)

    def loss_ref(*a):
        return jnp.sum(jnp.tanh(reference.hafner_cell(*a, eps=1e-3)))

    def loss_pallas(*a):
        return jnp.sum(
            jnp.tanh(
                pallas_tpu.hafner_cell(*a, hidden_size=H, eps=1e-3, interpret=True)
            )
        )

    g_ref = jax.jit(jax.grad(loss_ref, argnums=tuple(range(6))))(*ops)
    g_pal = jax.jit(jax.grad(loss_pallas, argnums=tuple(range(6))))(*ops)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_pallas_sequence_custom_vjp_grad_parity():
    H, X, T = 128, 64, 4
    _, _, kernel, bias, ln_scale, ln_bias = _hafner_operands(H, X)
    k = jax.random.PRNGKey(7)
    h0 = jax.random.normal(k, (B, H), jnp.float32)
    xs = jax.random.normal(k, (T, B, X), jnp.float32)

    def loss_ref(h0, xs, kernel, bias, ln_scale, ln_bias):
        def body(h, x_t):
            nh = reference.hafner_cell(h, x_t, kernel, bias, ln_scale, ln_bias, eps=1e-3)
            return nh, nh

        _, hs = jax.lax.scan(body, h0, xs)
        return jnp.sum(jnp.tanh(hs))

    def loss_pallas(h0, xs, kernel, bias, ln_scale, ln_bias):
        hs = pallas_tpu.hafner_sequence(
            h0, xs, kernel, bias, ln_scale, ln_bias,
            hidden_size=H, eps=1e-3, interpret=True,
        )
        return jnp.sum(jnp.tanh(hs))

    args = (h0, xs, kernel, bias, ln_scale, ln_bias)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=tuple(range(6))))(*args)
    g_pal = jax.jit(jax.grad(loss_pallas, argnums=tuple(range(6))))(*args)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# DV1 family (flax_gru): off bitwise the flax module, fused tolerance
# ---------------------------------------------------------------------------


def _flax_gru_params(H, X, seed=0):
    import flax.linen as nn

    from sheeprl_tpu.models import FusedGRUCell

    cell = FusedGRUCell(H)
    k = jax.random.PRNGKey(seed)
    h = jax.random.normal(k, (B, H), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(k, 1), (B, X), jnp.float32)
    variables = cell.init(jax.random.fold_in(k, 2), h, x)
    ref_cell = nn.GRUCell(features=H, kernel_init=nn.initializers.orthogonal())
    return cell, ref_cell, variables, h, x


def test_fused_gru_cell_off_bitwise_flax_gru():
    """FusedGRUCell (the module DV1's RecurrentModel now uses) keeps the
    exact flax nn.GRUCell parameter tree and, at fused='off', the exact
    flax math — swapping it in changed no checkpoint and no trajectory."""
    cell, ref_cell, variables, h, x = _flax_gru_params(32, 16)
    ours = jax.jit(lambda v, h, x: cell.apply(v, h, x)[1])(variables, h, x)
    theirs = jax.jit(lambda v, h, x: ref_cell.apply(v, h, x)[1])(variables, h, x)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


@pytest.mark.parametrize("pad_to", [1, 128])
def test_flax_gru_fused_tolerance(pad_to):
    H, X = 200, 230  # DV1 Atari shape class: H=200, X straddles nothing
    cell, _, variables, h, x = _flax_gru_params(H, X)
    ref = jax.jit(lambda v, h, x: cell.apply(v, h, x)[1])(variables, h, x)
    fused = jax.jit(
        lambda p, h, x: xla.flax_gru_cell_fused(h, x, p, hidden_size=H, pad_to=pad_to)
    )(variables["params"], h, x)
    # the six Denses collapse into two joint GEMMs — numerically equivalent,
    # not bitwise (different reduction grouping)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(fused), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# module dispatch: the tier changes the schedule, never the params/results
# ---------------------------------------------------------------------------


def test_layer_norm_gru_module_tier_param_tree_invariant():
    from sheeprl_tpu.models import LayerNormGRUCell

    H, X = 600, 400
    k = jax.random.PRNGKey(11)
    h = jax.random.normal(k, (B, H), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(k, 1), (B, X), jnp.float32)
    v_off = LayerNormGRUCell(H, layer_norm=True, fused="off").init(k, x, h)
    v_xla = LayerNormGRUCell(H, layer_norm=True, fused="xla").init(k, x, h)
    assert jax.tree_util.tree_structure(v_off) == jax.tree_util.tree_structure(v_xla)
    for a, b in zip(jax.tree_util.tree_leaves(v_off), jax.tree_util.tree_leaves(v_xla)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layer_norm_gru_module_xla_tier_bitwise_on_cpu():
    """On a non-TPU backend default_pad_to is 1, so the module's xla tier
    must be bitwise its off tier — the e2e guarantee the DV2 checkpoint
    test in tests/test_envs/test_rollout.py rests on."""
    from sheeprl_tpu.models import LayerNormGRUCell

    if jax.default_backend() == "tpu":
        pytest.skip("CPU/GPU-only property: pad_to defaults to the 128 tile on TPU")
    H, X = 600, 400
    k = jax.random.PRNGKey(13)
    h = jax.random.normal(k, (B, H), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(k, 1), (B, X), jnp.float32)
    off = LayerNormGRUCell(H, layer_norm=True, fused="off")
    fused = LayerNormGRUCell(H, layer_norm=True, fused="xla")
    v = off.init(k, x, h)
    a = jax.jit(lambda v, x, h: off.apply(v, x, h))(v, x, h)
    b = jax.jit(lambda v, x, h: fused.apply(v, x, h))(v, x, h)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# knob plumbing: normalize/resolve + degrade counter
# ---------------------------------------------------------------------------


def test_normalize_tier_yaml_spellings():
    assert normalize_tier("off") == "off"
    assert normalize_tier(False) == "off"  # YAML 1.1 bare `off`
    assert normalize_tier(None) == "off"
    assert normalize_tier("") == "off"
    assert normalize_tier(True) == "auto"  # YAML 1.1 bare `on`
    assert normalize_tier("XLA") == "xla"
    assert normalize_tier(" pallas ") == "pallas"


def test_resolve_tier_explicit_pallas_off_tpu_raises_auto_resolves_xla():
    """An explicit pallas request on a backend that cannot compile it is an
    error, never a quiet xla; `auto` is the way to ask for the best tier."""
    from sheeprl_tpu.obs import counters as obs_counters

    c = obs_counters.Counters()
    obs_counters.install(c)
    try:
        with pytest.raises(ValueError, match="fused_kernels=pallas on backend=cpu"):
            resolve_tier("pallas", family="hafner_ln_gru")
        assert resolve_tier("auto", family="hafner_ln_gru") == "xla"
        assert resolve_tier("auto", family="flax_gru") == "xla"
        assert c.kernel_tier_degraded == 0
        # DV1's family has no pallas kernel at all: the one remaining degrade
        assert resolve_tier("pallas", family="flax_gru") == "xla"
        assert c.kernel_tier_degraded == 1
    finally:
        obs_counters.install(None)


def test_pallas_compiler_params_construct_on_installed_jax():
    """Interpret mode never builds the Mosaic parameters, so a renamed
    pltpu class (TPUCompilerParams -> CompilerParams) would only surface on
    the chip: build them here."""
    from sheeprl_tpu.kernels import pallas_tpu

    params = pallas_tpu._compiler_params("arbitrary", 16, 640, 512)
    assert tuple(params.dimension_semantics) == ("arbitrary",)
    # the double-buffered [Hp+Xp, 3*Hp] f32 weight alone is 17.7 MB
    assert 2 * 4 * 1152 * 1920 < params.vmem_limit_bytes < (100 << 20)


def test_pallas_tier_lowers_mosaic_for_tpu_and_xla_twin_for_cpu():
    """Tier `pallas` picks per LOWERING platform: the Mosaic kernel in a TPU
    program, the padded-XLA twin in a host-CPU program of the same run (the
    algo.player_on_host acting mirror) — and no path takes the interpreter."""
    H, X = 600, 400
    h, x, kernel, bias, scale, ln_bias = _hafner_operands(H, X)

    def cell(*operands):
        return registry.hafner_gru_cell(*operands, hidden_size=H, eps=1e-5, tier="pallas")

    traced = jax.jit(cell).trace(h, x, kernel, bias, scale, ln_bias)
    assert "tpu_custom_call" in traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in traced.lower(lowering_platforms=("cpu",)).as_text()
    # on this CPU backend the call itself runs the twin
    got = cell(h, x, kernel, bias, scale, ln_bias)
    want = reference.hafner_cell(h, x, kernel, bias, scale, ln_bias, eps=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_resolve_tier_rejects_unknown():
    with pytest.raises(ValueError):
        resolve_tier("mystery")


# ---------------------------------------------------------------------------
# cost accounting: registered train cost is tier-invariant (PaLM-MFU rule)
# ---------------------------------------------------------------------------


class _FakeTelemetry:
    def __init__(self):
        self.flops = self.bytes = None

    def needs_train_flops(self):
        return True

    def set_train_cost(self, flops, bytes_accessed, dispatches_per_step=1):
        self.flops, self.bytes = flops, bytes_accessed


def test_register_train_cost_is_tier_invariant():
    """A fused (padded) train program must register the REFERENCE model
    FLOPs/bytes: register_train_cost retraces through reference_cost_mode,
    so MFU and the roofline numerators cannot depend on the kernel tier."""
    from sheeprl_tpu.obs.perf import register_train_cost
    from sheeprl_tpu.obs.prof.roofline import cost_of

    H, X = 600, 400
    ops = _hafner_operands(H, X)

    def make(tier):
        def step(h, x, kernel, bias, ln_scale, ln_bias):
            out = registry.hafner_gru_cell(
                h, x, kernel, bias, ln_scale, ln_bias,
                hidden_size=H, eps=1e-3, tier=tier, pad_to=128,
            )
            return jnp.sum(out * out)

        return jax.jit(step)

    ref_fn, fused_fn = make("off"), make("xla")
    raw_ref = cost_of(ref_fn, *ops)
    raw_fused = cost_of(fused_fn, *ops)
    if raw_ref is None:
        pytest.skip("backend has no XLA cost model")
    # non-vacuity: the padded program really does cost more as-lowered
    assert raw_fused["flops"] > raw_ref["flops"]

    # mark a fused tier active (what resolve_tier does at agent build)
    registry._ACTIVE_FUSED.add("xla")
    tel_ref, tel_fused = _FakeTelemetry(), _FakeTelemetry()
    register_train_cost(tel_ref, ref_fn, *ops)
    register_train_cost(tel_fused, fused_fn, *ops)
    assert tel_fused.flops == pytest.approx(tel_ref.flops)
    if tel_ref.bytes and tel_fused.bytes:
        assert tel_fused.bytes == pytest.approx(tel_ref.bytes)


def test_kernel_cost_uses_real_widths():
    c600 = registry.kernel_cost("hafner_ln_gru", batch=8, hidden_size=600, input_size=400)
    c640 = registry.kernel_cost("hafner_ln_gru", batch=8, hidden_size=640, input_size=400)
    # the analytic spec prices real widths — 600 never bills as 640
    assert c600["flops"] < c640["flops"]
    seq = registry.kernel_cost(
        "hafner_ln_gru", batch=8, hidden_size=600, input_size=400, seq_len=10
    )
    assert seq["flops"] == pytest.approx(10 * c600["flops"], rel=1e-6)
    with pytest.raises(KeyError):
        registry.kernel_cost("nope", batch=1, hidden_size=1, input_size=1)
