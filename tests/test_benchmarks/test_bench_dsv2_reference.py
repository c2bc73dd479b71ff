"""The latent-attention sequence core against its plain reference at a size a
test can hold (hidden 64, a dense layer and two expert layers, latents of 32 +
8, 16 routed experts of which 4 are held, vocabulary 256), seeded weights: each
part alone, a whole window (logits, a loss, gradients leaf by leaf), the
absorbed one-token path after a window prefix against the reference's full,
un-absorbed forward pass across a reset, and the sum of all expert shares
against the uncut layer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import bench_tiny_dsv2
from benchmarks.manifest import load_module
from sheeprl_tpu.models import deepseek_v2 as ds

REFERENCE = load_module(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_dsv2.REFERENCE))
SIZES = bench_tiny_dsv2.tiny_config()["sizes"]
CORE = "world_model/core"


def core_config(sizes=SIZES, **changes) -> ds.DeepseekV2Config:
    s = dict(sizes, **changes)
    return ds.Config.from_mapping(dict(
        s, n_routed_experts=s["router_outputs"], held_index=s["expert_share_index"],
        held_of=s["router_outputs"] // s["num_experts"], cache_len=changes.get("cache_len", 64),
    ))


def weights(sizes=SIZES, seed=11, scale=4.0):
    """The benchmark's weights, the core's products scaled up so that every
    layer's part of the output is well above rounding."""
    shapes = {k: v for k, v in REFERENCE.param_shapes(sizes).items() if k.startswith(CORE)}
    flat = jax.jit(lambda s: REFERENCE.make_weights(shapes, s))(np.int32(seed))
    flat = {k: v * scale if v.ndim >= 2 else v for k, v in flat.items()}
    tree = {}
    for name, value in flat.items():
        node = tree
        parts = name[len(CORE) + 1:].split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return flat, tree


def close(got, want, rel=1e-4):
    np.testing.assert_allclose(got, want, atol=rel * float(jnp.abs(want).max()) + 1e-7)


def window_inputs(B=2, L=64, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0, SIZES["vocab_size"])
    reset = jnp.zeros((B, L), jnp.int32).at[0, 0].set(1).at[0, 21].set(1).at[1, 32].set(1).at[1, 50].set(1)
    return tokens, reset


def test_the_published_configuration_has_the_reckoned_size():
    with open(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_dsv2.CONFIG)) as f:
        sizes = json.load(f)["sizes"]
    shapes = REFERENCE.param_shapes(sizes)
    count = lambda prefix: sum(int(np.prod(v)) for k, v in shapes.items() if k.startswith(prefix))
    # ISSUE 32's table: layer 0 81.0 M, an expert layer 100.4 M (13.76 attention, 69.2 experts, 17.30 shared, 0.13 router)
    assert count(f"{CORE}/layers_0/") == 81_007_104 and count(f"{CORE}/layers_3/") == 100_405_760
    assert count(f"{CORE}/layers_3/mla/") == 13_763_072 and count(f"{CORE}/layers_0/mlp/") == 3 * 2048 * 10944
    assert count(f"{CORE}/") == 635_466_752
    assert shapes[f"{CORE}/layers_2/mla/dkv"] == (2048, 576) and shapes[f"{CORE}/layers_2/mla/ukv"] == (512, 4096)
    assert shapes[f"{CORE}/layers_2/moe/gate"] == (8, 2048, 1408) and shapes[f"{CORE}/layers_2/moe/router"] == (2048, 64)
    assert shapes[f"{CORE}/layers_2/moe/shared_up"] == (2048, 2816) and f"{CORE}/layers_0/moe/router" not in shapes
    program = ds.param_shapes(core_config(sizes, cache_len=sizes["cache_len"]))
    flat = {"/".join(str(p.key) for p in path): shape for path, shape in
            jax.tree_util.tree_flatten_with_path(program, is_leaf=lambda x: isinstance(x, tuple))[0]}
    assert {f"{CORE}/{k}": v for k, v in flat.items()} == {k: tuple(v) for k, v in shapes.items() if k.startswith(CORE)}
    # the acting state of one env: a ring of 1,024 latents of 576 numbers in each of six layers, in bf16
    state = jax.eval_shape(lambda: ds.init_state(core_config(sizes, cache_len=sizes["cache_len"]), 1, 1, None, jnp.bfloat16))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)) == 7_077_888 + 8


@pytest.mark.parametrize("kind", ["mla", "mlp", "moe"])
def test_a_part_alone_gives_the_references_output(kind):
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()
    x = jax.random.normal(jax.random.PRNGKey(2), tokens.shape + (SIZES["hidden_size"],))
    if kind == "mla":
        got, state = ds.mla_window(tree["layers_1"]["mla"], x, reset, c, jnp.float32)
        want, ref_state = REFERENCE.mla_window(flat, f"{CORE}/layers_1/mla", x, reset, SIZES, "f32")
        # the cache holds the latent and the one rotary key every head shares: the reference's per-head keys' last columns
        close(state["latent"][..., c.kv_lora_rank:], ref_state["k"][:, :, 0, c.qk_nope_head_dim:])
    elif kind == "mlp":
        got = ds.dense_mlp(tree["layers_0"]["mlp"], x, jnp.float32)
        want = REFERENCE.dense_mlp(flat, f"{CORE}/layers_0/mlp", x, "f32")
    else:
        got, stats = ds._feed_forward(tree["layers_1"], x.reshape(-1, x.shape[-1]), c, 1, jnp.float32, "core", 2)
        want, _, _ = REFERENCE.experts(flat, f"{CORE}/layers_1/moe", x.reshape(-1, x.shape[-1]), SIZES, "f32")
        assert float(stats["dropped_pairs"]) == 0 and float(stats["held_pairs"]) > 0
        got, want = got.reshape(x.shape), want.reshape(x.shape)
    assert float(jnp.abs(want).max()) > 0.1
    close(got, want)


def _next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def test_a_whole_window_gives_the_references_logits_loss_and_gradients_leaf_by_leaf():
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()

    def mine(p):
        h, _, stats = ds.window(p, tokens, reset, c)
        logits = ds.head_logits(p, h, jnp.float32)
        return _next_token_loss(logits, tokens) + c.balance_loss(stats["aux"]), logits

    def theirs(f):
        h, _, aux = REFERENCE.core_window(f, SIZES, tokens, reset)
        logits = REFERENCE.matmul(h, f[f"{CORE}/head"], "f32")
        return _next_token_loss(logits, tokens) + SIZES["aux_loss_alpha"] * jnp.mean(aux), logits

    (loss, logits), grads = jax.jit(jax.value_and_grad(mine, has_aux=True))(tree)
    (want_loss, want_logits), want_grads = jax.jit(jax.value_and_grad(theirs, has_aux=True))(flat)
    close(logits, want_logits, 2e-4)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = {f"{CORE}/" + "/".join(str(k.key) for k in path): g for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(got[name], want, atol=2e-4 * float(jnp.abs(want).max()), err_msg=name)


def test_the_absorbed_path_after_a_window_prefix_agrees_with_the_references_full_forward_pass():
    """Logits to logits: prefill by the window pass, then one token at a time
    through the latent cache (``W_UK`` in the query, ``W_UV`` on the attended
    latent) against the reference, which builds every head's keys and values
    for every position; a reset falls into the decoded stretch."""
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()
    reset = reset.at[0, 40].set(1)
    want = REFERENCE.matmul(REFERENCE.core_window(flat, SIZES, tokens, reset)[0], flat[f"{CORE}/head"], "f32")
    scale = float(jnp.abs(want).max())
    prefix = 32  # a chunk boundary: the window pass's own latent cache up to it is the context
    _, states, _ = ds.window(tree, tokens[:, :prefix], reset[:, :prefix], c)
    state, context = ds.boundary_state(states, reset[:, :prefix], c, own_len=32)
    seg, pos = ds.segment_positions(reset)
    # the stream that goes on from the prefix's end: its context is the whole prefix, masked to its episode
    state = jax.tree_util.tree_map(lambda x: x[:, :1], state)
    state["rope_pos"] = pos[:, prefix : prefix + 1]
    mask = (seg[:, :prefix] == seg[:, prefix : prefix + 1]) & (reset[:, prefix : prefix + 1] == 0)
    context = {name: (latent, mask[:, None]) for name, (latent, _) in context.items()}
    decode = jax.jit(lambda p, s, t, ctx: ds.decode(p, s, t, c, context=ctx))
    for t in range(prefix, tokens.shape[1]):
        hit = reset[:, t : t + 1] > 0
        if t > prefix and bool(hit.any()):  # an episode ends: the stream's ring and the row's context are dropped
            state = ds.reset_state(state, hit)
            context = {name: (latent, m & ~hit[:, :, None]) for name, (latent, m) in context.items()}
        out, state, _ = decode(tree, state, tokens[:, t : t + 1], context)
        np.testing.assert_allclose(ds.head_logits(tree, out[:, 0], jnp.float32), want[:, t], atol=2e-4 * scale, err_msg=str(t))


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four shares of four experts each: their parts, the shared experts
    counted once, are the layer with all sixteen experts held."""
    uncut_sizes = dict(SIZES, num_experts=16, expert_share_index=0)
    flat, tree = weights(uncut_sizes)
    x = jax.random.normal(jax.random.PRNGKey(3), (96, SIZES["hidden_size"]))
    pre = f"{CORE}/layers_2/moe"
    want, _, _ = REFERENCE.experts(flat, pre, x, uncut_sizes, "f32")
    shared_only, _, _ = REFERENCE.experts(flat, pre, x, uncut_sizes, "f32", held=False)
    total = shared_only
    for index in range(4):
        p = dict(tree["layers_2"]["moe"])
        for name in ("gate", "up", "down"):
            p[name] = p[name][4 * index : 4 * index + 4]
        part, stats = ds._feed_forward({"moe": p}, x, core_config(expert_share_index=index), 2, jnp.float32, "core", 1)
        assert float(stats["dropped_pairs"]) == 0
        total = total + (part - shared_only)
    np.testing.assert_allclose(total, want, atol=3e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(want - shared_only).max()) > 0.1 * float(jnp.abs(want).max())


def test_the_references_rotary_frequencies_are_the_programs():
    with open(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_dsv2.CONFIG)) as f:
        sizes = json.load(f)["sizes"]
    c = core_config(sizes)
    np.testing.assert_allclose(REFERENCE.yarn_frequencies(sizes), ds.yarn_inv_freq(c), rtol=1e-6)
    assert REFERENCE.score_scale(sizes) == pytest.approx(ds.softmax_scale(c), rel=1e-9)
    assert REFERENCE.score_scale(sizes) == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
