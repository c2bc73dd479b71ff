"""The third kernel family: the chunked gated delta rule against its reference
tier (the recurrence, one token at a time), values and gradients, with resets
inside chunks; and the states it hands over at chunk boundaries. Each case runs
the XLA form and, where its shapes allow, the fused kernels of the chunk-local
WY build and of the inter-chunk pass through the Pallas interpreter (what a TPU
program would run)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.kernels import delta_rule


def inputs(B, T, H, dk, dv, seed, resets):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    reset = jnp.zeros((B, T))
    for b, t in resets:
        reset = reset.at[b, t].set(1.0)
    return (q, k, v, g, beta), reset


CASES = {
    "no_reset": dict(B=2, T=64, H=2, dk=8, dv=8, chunk=16, resets=()),
    "reset_inside_a_chunk": dict(B=2, T=64, H=3, dk=16, dv=16, chunk=32, resets=((0, 37), (1, 5), (1, 50))),
    "reset_on_a_boundary_and_at_zero": dict(B=2, T=96, H=2, dk=8, dv=16, chunk=32, resets=((0, 0), (0, 32), (1, 64))),
    "two_resets_in_one_chunk": dict(B=1, T=128, H=2, dk=16, dv=8, chunk=64, resets=((0, 70), (0, 100))),
    # head widths the fused kernels take (multiples of 128)
    "wide_no_reset": dict(B=1, T=32, H=2, dk=128, dv=128, chunk=16, resets=()),
    "wide_resets_inside_chunks": dict(B=2, T=48, H=4, dk=128, dv=256, chunk=16, resets=((0, 21), (1, 5), (1, 40))),
    "wide_one_chunk_of_64": dict(B=1, T=64, H=2, dk=128, dv=128, chunk=64, resets=((0, 0), (0, 30))),
    # a row's heads in blocks of 8: the inter-chunk kernels take the pass too
    "wide_eight_heads": dict(B=2, T=48, H=8, dk=128, dv=256, chunk=16, resets=((0, 21), (1, 5), (1, 40))),
    "wide_keys_of_256_reset_on_a_chunks_first_token": dict(B=1, T=64, H=8, dk=256, dv=128, chunk=16,
                                                          resets=((0, 16), (0, 37), (0, 48))),
}
#: (case, tier): every case in the XLA form, the wide ones through the kernels too
TIERS = [(case, "xla") for case in sorted(CASES)] + [(case, "kernel") for case in sorted(CASES) if case.startswith("wide")]


@pytest.fixture
def tier(request, monkeypatch):
    """``kernel``: what a program lowered for a TPU runs, through the Pallas
    interpreter (the CPU has no Mosaic); the calls are counted."""
    calls = []
    if request.param == "kernel":
        def interpreted(kernel, xla_form, *operands):
            calls.append(kernel)
            return kernel(*operands, interpret=True)

        monkeypatch.setattr(delta_rule, "_on_platform", interpreted)
    yield request.param, calls
    if request.param == "kernel":
        assert calls, "the case never reached the kernels"


def with_tier(test):
    return pytest.mark.parametrize("case,tier", TIERS, indirect=["tier"])(test)


@with_tier
def test_chunked_tier_gives_the_recurrences_values(case, tier):
    c = CASES[case]
    args, reset = inputs(c["B"], c["T"], c["H"], c["dk"], c["dv"], 3, c["resets"])
    o_ref, S_ref = delta_rule.recurrent(*args, reset)
    o, S, S_before = delta_rule.chunked(*args, reset, chunk=c["chunk"])
    np.testing.assert_allclose(o, o_ref, atol=2e-6)
    np.testing.assert_allclose(S, S_ref, atol=5e-6)
    # the state before chunk n is the recurrence's after n * chunk tokens
    n = c["T"] // c["chunk"] - 1
    upto = n * c["chunk"]
    _, S_n = delta_rule.recurrent(*(a[:, :upto] for a in args), reset[:, :upto])
    np.testing.assert_allclose(S_before[n], S_n, atol=5e-6)


@with_tier
def test_chunked_tier_gives_the_recurrences_gradients(case, tier):
    """Every cotangent: ``q, k, v, g, beta`` and the state handed in; the loss
    reads the outputs and the final state."""
    c = CASES[case]
    args, reset = inputs(c["B"], c["T"], c["H"], c["dk"], c["dv"], 4, c["resets"])
    S0 = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (c["B"], c["H"], c["dk"], c["dv"]))

    def loss(run):
        def f(S0, *a):
            o, S, *_ = run(*a, reset, S0)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(S))
        return f

    ref = jax.grad(loss(delta_rule.recurrent), argnums=tuple(range(6)))(S0, *args)
    got = jax.grad(loss(lambda *a: delta_rule.chunked(*a, chunk=c["chunk"])), argnums=tuple(range(6)))(S0, *args)
    for a, b in zip(got, ref):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * max(float(jnp.abs(b).max()), 1.0)


@pytest.mark.parametrize("tier", ["xla", "kernel"], indirect=True)
def test_chunked_tier_in_bf16_stays_near_the_recurrence(tier):
    """``dtype=bf16`` rounds the scan's operands; the chunk-local build stays
    float32 in both tiers, which therefore agree far closer than either does
    with the recurrence."""
    args, reset = inputs(2, 64, 2, 128, 128, 6, ((0, 20), (1, 33)))
    o_ref, _ = delta_rule.recurrent(*args, reset)
    run = lambda *a: delta_rule.chunked(*a, reset, chunk=16, dtype=jnp.bfloat16)[0]
    o = run(*args)
    assert float(jnp.abs(o - o_ref).max()) <= 2e-2 * float(jnp.abs(o_ref).max())
    with pytest.MonkeyPatch.context() as m:  # the other tier, whichever this one is
        m.setattr(delta_rule, "_fusable", lambda *a: False)
        other = run(*args)
    np.testing.assert_allclose(o, other, atol=1e-6 * float(jnp.abs(o_ref).max()))
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(run(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)


@pytest.mark.parametrize("shape", ["narrow_keys", "narrow_values", "odd_chunk", "eighteen_tiles", "three_tiles"])
def test_a_shape_the_kernels_refuse_keeps_the_xla_form(shape, monkeypatch):
    c = dict(narrow_keys=dict(H=2, dk=64, dv=128, chunk=16), narrow_values=dict(H=2, dk=128, dv=64, chunk=16),
             odd_chunk=dict(H=2, dk=128, dv=128, chunk=12),
             eighteen_tiles=dict(H=18, dk=128, dv=128, chunk=48),  # no block of 8 tiles, too many for one
             three_tiles=dict(H=1, dk=128, dv=128, chunk=16))[shape]  # the kernels take tiles in pairs
    monkeypatch.setattr(delta_rule, "_on_platform",
                        lambda *a: pytest.fail("a refused shape reached the choice of platform"))
    args, reset = inputs(1, 48, c["H"], c["dk"], c["dv"], 8, ((0, 17),))
    o_ref, S_ref = delta_rule.recurrent(*args, reset)
    o, S, _ = delta_rule.chunked(*args, reset, chunk=c["chunk"])
    np.testing.assert_allclose(o, o_ref, atol=2e-6)
    np.testing.assert_allclose(S, S_ref, atol=5e-6)
    assert float(delta_rule.fused_tiles(args[1].shape, c["dv"], c["chunk"])) == 0.0
    assert float(delta_rule.scan_fused_tiles(args[1].shape, c["dv"], c["chunk"])) == 0.0


def lower_triangles(n, C, seed):
    return jnp.tril(jax.random.normal(jax.random.PRNGKey(seed), (n, C, C)) * 0.4 * C**-0.5, -1)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_kernels_inverse_times_its_matrix_is_the_identity(chunk):
    """``T (I + L) = I`` to 1e-6, ``T`` read off the forward kernel: every key
    the first unit vector and ``beta`` one (``K_b K^T`` all ones), ``D = L``,
    ``V = I`` padded to the head width: ``U0 = T``; ``decay_in`` two: ``W = 2 T K``.
    Four tiles: one row of four heads, one chunk."""
    L = lower_triangles(4, chunk, 9)
    eye = jnp.broadcast_to(jnp.eye(chunk, 128)[None, :, None], (1, chunk, 4, 128))
    first = jnp.zeros((1, chunk, 4, 128)).at[..., 0].set(1.0)
    ones = jnp.ones((1, 4, chunk))
    U0, W, T = delta_rule._wy_pallas(first, eye, L, ones, 2.0 * ones, interpret=True)
    first = delta_rule._tile_rows(first, chunk)
    T = delta_rule._unpack(T)  # the kernel hands it on two tiles side by side
    np.testing.assert_allclose(U0[..., :chunk], T, atol=0)
    np.testing.assert_allclose(T @ (jnp.eye(chunk) + L), jnp.broadcast_to(jnp.eye(chunk), T.shape), atol=1e-6)
    np.testing.assert_allclose(W, 2.0 * T @ first, atol=1e-6)
    np.testing.assert_allclose(U0[..., chunk:], 0.0, atol=0)


def test_packing_lays_two_tiles_side_by_side_and_back():
    x = jnp.arange(4 * 3 * 3, dtype=jnp.float32).reshape(4, 3, 3)
    packed = delta_rule._pack(x)
    assert packed.shape == (2, 3, 6)
    np.testing.assert_array_equal(packed[1, :, :3], x[2])
    np.testing.assert_array_equal(packed[1, :, 3:], x[3])
    np.testing.assert_array_equal(delta_rule._unpack(packed), x)


def test_the_transpose_kernel_is_the_xla_forms_vjp():
    """All five cotangents of the seam, and nothing on or above the diagonal of
    ``dD``. Six tiles: one row of six heads, one chunk; the kernel takes ``K``,
    ``V`` and hands back their cotangents as the model lays them out."""
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    n, C = 6, 16
    k, v = 0.3 * jax.random.normal(ks[0], (1, C, n, 128)), 0.3 * jax.random.normal(ks[2], (1, C, n, 256))
    dW, dU0 = 0.3 * jax.random.normal(ks[1], (n, C, 128)), 0.3 * jax.random.normal(ks[3], (n, C, 256))
    D = jnp.tril(jax.random.uniform(ks[4], (n, C, C)))
    beta, decay_in = jax.random.uniform(ks[5], (1, n, C)), jax.random.uniform(ks[6], (1, n, C))
    tiles = (delta_rule._tile_rows(k, C), delta_rule._tile_rows(v, C), D, beta[0], decay_in[0])
    (_, _, T), back = jax.vjp(delta_rule._wy_xla, *tiles)
    want = back((dU0, dW, jnp.zeros_like(T)))
    dk, dv, dD, dbeta, ddecay_in = delta_rule._wy_transpose_pallas(
        k, v, D, beta, decay_in, delta_rule._pack(T), dU0, dW, interpret=True)
    got = (delta_rule._tile_rows(dk, C), delta_rule._tile_rows(dv, C), dD, dbeta[0], ddecay_in[0])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-6 * max(float(jnp.abs(b).max()), 1.0))
    assert float(jnp.abs(jnp.triu(got[2])).max()) == 0.0


def test_a_tpu_program_takes_the_kernels_and_a_cpu_program_the_xla_form():
    """Platform and shape choose, no option: the same traced function lowers to
    three Mosaic calls (forward, its rematerialisation, the transpose) for a
    TPU and to none for the CPU, and counts its tiles accordingly."""
    args, reset = inputs(1, 32, 2, 128, 128, 0, ())

    def f(*a):
        run = jax.checkpoint(lambda *a: delta_rule.chunked(*a, reset, chunk=16)[0])
        return jax.value_and_grad(lambda *a: jnp.sum(run(*a)), argnums=(0, 1, 2, 3, 4))(*a), \
            delta_rule.fused_tiles(a[1].shape, 128, 16)

    traced = jax.jit(f).trace(*args)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 3
    assert "4.000000e+00" in tpu  # 1 row x 2 chunks x 2 heads
    assert "tpu_custom_call" not in traced.lower(lowering_platforms=("cpu",)).as_text()
    assert float(f(*args)[1]) == 0.0  # on this CPU backend the call itself runs the XLA form


def chunked_vjp(args, reset, S0, chunk, dtype, seed):
    """``chunked``'s three outputs and the cotangents of ``(S0, q, k, v, g, beta)``
    for seeded cotangents on all three (``S_before``'s too: imagination reads it)."""
    out, back = jax.vjp(lambda S0, *a: delta_rule.chunked(*a, reset, S0, chunk=chunk, dtype=dtype), S0, *args)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return out, back(tuple(jax.random.normal(key, x.shape) for key, x in zip(keys, out)))


def interpreted_kernels(monkeypatch):
    """What a TPU program runs, through the Pallas interpreter; returns the names
    of the kernels that ran."""
    calls = []

    def interpreted(kernel, xla_form, *operands):
        calls.append(getattr(kernel, "func", kernel).__name__)
        return kernel(*operands, interpret=True)

    monkeypatch.setattr(delta_rule, "_on_platform", interpreted)
    return calls


@pytest.mark.parametrize("state", ["handed_in", "zero"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_inter_chunk_kernels_are_the_xla_forms_pass_and_its_vjp(dtype, state, monkeypatch):
    """``o``, ``S_final``, ``S_before`` and every cotangent (``q, k, v, g, beta`` and
    the state handed in), cotangents arriving on all three outputs, resets inside a
    chunk and on a chunk's first token, 8 heads of 128 and 256: the kernels against
    the XLA form. In float32 they agree to rounding, and in bf16 the outputs are
    the XLA form's products of the same bf16 operands. The bf16 transpose takes a
    float32 cotangent into a product rounded to bf16, as the XLA form does on the
    chip (where the two agreed to 1e-4 of the mean and closer); XLA:CPU takes such
    a product in float32, so here the cotangents are held to float32 instead: no
    further from the float32 pass than the XLA form's own are, with a quarter of
    room."""
    dtype = getattr(jnp, dtype)
    args, reset = inputs(2, 48, 8, 128, 256, 12, ((0, 16), (0, 21), (1, 5), (1, 40)))
    S0 = 0.1 * jax.random.normal(jax.random.PRNGKey(13), (2, 8, 128, 256)) if state == "handed_in" else None
    want = chunked_vjp(args, reset, S0, 16, dtype, 14)
    exact = chunked_vjp(args, reset, S0, 16, jnp.float32, 14)
    calls = interpreted_kernels(monkeypatch)
    got = chunked_vjp(args, reset, S0, 16, dtype, 14)
    assert {"_scan_pallas", "_scan_transpose_pallas", "_wy_pallas", "_wy_transpose_pallas"} <= set(calls)
    assert (got[1][0] is None) == (S0 is None)
    for a, b in zip(got[0], want[0]):
        np.testing.assert_allclose(a, b, atol=2e-6 * max(float(jnp.abs(b).max()), 1.0))
    for a, b, f in zip(got[1], want[1], exact[1]):
        if b is None:
            continue
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=2e-6 * max(float(jnp.abs(b).max()), 1.0))
        else:
            assert float(jnp.abs(a - f).mean()) <= 1.25 * float(jnp.abs(b - f).mean())


def test_a_tpu_program_carries_the_state_across_chunks_in_the_kernels_and_a_cpu_program_in_xla():
    """At 8 heads a row the inter-chunk pass lowers to its kernel pair as well: six
    Mosaic calls for a TPU (the WY build and the pass forward, both rematerialised,
    and the two transposes) and none for the CPU; its count reads a head's chunks
    in the TPU program, 0 here."""
    args, reset = inputs(1, 32, 8, 128, 128, 0, ((0, 16),))

    def f(*a):
        run = jax.checkpoint(lambda *a: delta_rule.chunked(*a, reset, chunk=16)[0])
        return jax.value_and_grad(lambda *a: jnp.sum(run(*a)), argnums=(0, 1, 2, 3, 4))(*a), \
            delta_rule.scan_fused_tiles(a[1].shape, 128, 16)

    traced = jax.jit(f).trace(*args)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 6
    assert "1.600000e+01" in tpu  # 1 row x 2 chunks x 8 heads
    assert "tpu_custom_call" not in traced.lower(lowering_platforms=("cpu",)).as_text()
    assert float(f(*args)[1]) == 0.0


@pytest.mark.parametrize("heads", [4, 12])
def test_a_row_of_heads_the_inter_chunk_kernels_refuse_keeps_the_xla_scan(heads, monkeypatch):
    """Heads that do not fill blocks of 8 keep the ``lax.scan`` between chunks (the
    WY build still takes its kernels) and count no chunks: values as the recurrence's."""
    calls = interpreted_kernels(monkeypatch)
    args, reset = inputs(1, 32, heads, 128, 128, 15, ((0, 9),))
    o_ref, S_ref = delta_rule.recurrent(*args, reset)
    o, S, _ = delta_rule.chunked(*args, reset, chunk=16)
    np.testing.assert_allclose(o, o_ref, atol=2e-6)
    np.testing.assert_allclose(S, S_ref, atol=5e-6)
    assert calls == ["_wy_pallas"]
    assert float(delta_rule.scan_fused_tiles(args[1].shape, 128, 16)) == 0.0


def test_a_window_that_is_no_multiple_of_the_chunk_is_refused():
    args, reset = inputs(1, 40, 1, 8, 8, 0, ())
    with pytest.raises(ValueError, match="multiple of the chunk"):
        delta_rule.chunked(*args, reset, chunk=16)


# -- compiled for a described v5e, no chip (tools/aot_hlo.py) ---------------------


def load_aot_hlo():
    """``tools/aot_hlo.py`` as a module (``tools`` is no package)."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "aot_hlo", pathlib.Path(__file__).parents[2] / "tools" / "aot_hlo.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def aot_hlo():
    """``tools/aot_hlo.py`` where a ``v5e`` topology can be described; the
    persistent compile cache is off around these compiles (an entry written for
    a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says that no TPU compiler is here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    module = load_aot_hlo()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield module
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_mosaic_compiles_both_kernels_at_the_cells_shapes(aot_hlo):
    """4,096 tiles of 64 tokens (8 rows of 16 chunks of 32 heads), heads of 128:
    what interpret mode cannot show (alignment, VMEM) the chip's compiler refuses
    here, at no chip time."""
    f32 = jnp.float32
    tokens = jax.ShapeDtypeStruct((8, 1024, 32, 128), f32)
    wide, square = (jax.ShapeDtypeStruct((4096, 64) + tail, f32) for tail in ((128,), (64,)))
    row, packed = jax.ShapeDtypeStruct((128, 32, 64), f32), jax.ShapeDtypeStruct((2048, 64, 128), f32)
    forward = aot_hlo.compile_for(delta_rule._wy_pallas, tokens, tokens, square, row, row)
    assert forward.as_text().count("tpu_custom_call") == 1
    transpose = aot_hlo.compile_for(delta_rule._wy_transpose_pallas, tokens, tokens, square, row, row, packed, wide,
                                    wide)
    assert transpose.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("state", ["handed_in", "zero"])
def test_mosaic_compiles_the_inter_chunk_kernels_at_the_cells_shapes(aot_hlo, state):
    """8 rows of 1,024 tokens, 32 heads of 128, chunks of 64, bf16 operands: the
    forward kernel (with a state handed in, and from zero) and the transpose."""
    f32 = jnp.float32
    B, T, H, N, C, d = 8, 1024, 32, 16, 64, 128
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, f32)
    chunks, square, row, tokens = spec(N, B * H, C, d), spec(N, B * H, C, C), spec(N, B * H, C), spec(B, T, H, d)
    states, chunk_states = spec(B * H, d, d), spec(N, B * H, d, d)
    start = (states,) if state == "handed_in" else ()
    forward = aot_hlo.compile_for(
        lambda *a: delta_rule._scan_pallas(*a[:7], a[7] if start else None, dtype=jnp.bfloat16),
        chunks, chunks, square, tokens, tokens, row, row, *start)
    assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1
    transpose = aot_hlo.compile_for(
        functools.partial(delta_rule._scan_transpose_pallas, dtype=jnp.bfloat16),
        chunks, chunks, square, tokens, tokens, row, row, chunk_states, tokens, states, chunk_states)
    assert transpose.as_text().count('custom_call_target="tpu_custom_call"') == 1


def test_the_counters_read_every_tile_in_a_v5e_program_at_the_cells_shapes(aot_hlo):
    """``qwen3_next.window`` at ``dv3-qwen3next.ep16.learn512``'s shapes (8 rows of
    1,024 tokens, 32 value heads of 128, chunks of 64, three delta-rule layers of
    four): ``delta_rule_scan_fused_tiles`` and ``delta_rule_fused_tiles`` each read
    B * (T / C) * H * 3 passes * 3 layers = 36,864 in the program compiled for the
    chip and 0 in the one compiled for this CPU. The compiler folds the counts to
    constants and drops the model, which nothing returned reads."""
    import json
    import pathlib

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sheeprl_tpu.models import qwen3_next

    config = json.loads((pathlib.Path(__file__).parents[2] / "benchmarks" / "configs"
                         / "dv3-qwen3next.ep16.json").read_text())
    c = qwen3_next.Qwen3NextConfig.from_mapping(config)
    rows, tokens = config["sizes"]["batch_size"], 2 * config["sizes"]["sequence_length"]
    assert (rows, tokens, c.linear_num_value_heads, c.chunk, c.num_hidden_layers) == (8, 1024, 32, 64, 4)
    params = jax.eval_shape(lambda: qwen3_next.init_params(jax.random.PRNGKey(0), c))
    row = jax.ShapeDtypeStruct((rows, tokens), jnp.int32)

    def counts(p, tokens, reset):
        stats = qwen3_next.window(p, tokens, reset, c, jnp.bfloat16)[2]
        return stats["delta_rule_scan_fused_tiles"], stats["delta_rule_fused_tiles"]

    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), (params, row, row))
    # the arguments are kept, nothing returned reads them: they say where the program runs
    tpu = jax.jit(counts, keep_unused=True).lower(*on_chip).compile().as_text()
    cpu = jax.jit(counts, keep_unused=True).lower(params, row, row).compile().as_text()
    assert "f32[]{:T(128)} constant(36864)" in tpu and "constant(0)" not in tpu
    assert "constant(36864)" not in cpu and "f32[] constant(0)" in cpu


def test_a_v5e_compile_of_the_chunked_tier_holds_the_kernels_and_reckons_the_rest(aot_hlo):
    """The whole tier lowered for the described chip takes the kernels; the rows of
    ``aot_hlo`` weigh the scan's body by its 4 trips and name the program's scopes.
    A row of 4 heads keeps the XLA scan between chunks (the inter-chunk kernels
    take blocks of 8: ``test_a_v5e_compile_at_the_cells_shapes_...``)."""
    B, T, H, d = 1, 256, 4, 128
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((B, T, H, d),) * 3 + ((B, T, H),) * 2]

    def tier(q, k, v, g, beta):
        with jax.named_scope("delta_rule"):
            return delta_rule.chunked(q, k, v, g, beta, chunk=64, dtype=jnp.bfloat16)[0]

    text = aot_hlo.compile_for(tier, *shapes).as_text()
    assert text.count("tpu_custom_call") == 1
    rows = aot_hlo.rows(text)
    assert rows == sorted(rows, reverse=True) and rows[0][0] > 0
    in_loop = [r for r in rows if "delta_rule/scan/while/body" in r[2]]
    assert in_loop and all(operations % 4 == 0 for _, operations, _ in in_loop)


def test_aot_hlo_weighs_a_loop_body_by_its_trip_count():
    """The reader alone, on a module written by hand: no compiler needed."""
    hlo = """
%cond (p: (s32[])) -> pred[] {
  %c = s32[]{:T(128)} constant(16)
  ROOT %lt = pred[] compare(%g, %c), direction=LT
}

%body (p: (s32[])) -> (s32[]) {
  %f = f32[8] fusion(%x), kind=kLoop, metadata={op_name="jit(f)/scan/mul"}, backend_config={"estimated_cycles":"100"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = (s32[]) while(%t), condition=%cond, body=%body
  %g = f32[8] fusion(%a), kind=kLoop, metadata={op_name="jit(f)/add"}, backend_config={"estimated_cycles":"700"}
}
"""
    assert load_aot_hlo().rows(hlo) == [(1600, 16, "jit(f)/scan/mul"), (700, 1, "jit(f)/add")]
