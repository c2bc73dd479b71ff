"""The convolution-attention sequence core against its plain reference at a
size a test can hold (hidden 64, the cell's six layers, 16 router outputs of
which 4 are held, vocabulary 256), seeded weights: each part alone, a whole
window (logits, a loss, gradients leaf by leaf), the one-token path after a
window prefix against the reference's full forward pass across a reset, the
balance step, and the sum of all expert shares against the uncut layer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import bench_tiny_lfm2
from benchmarks.manifest import load_module
from sheeprl_tpu.models import lfm2_moe as lf

REFERENCE = load_module(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_lfm2.REFERENCE))
SIZES = bench_tiny_lfm2.tiny_config()["sizes"]
CORE = "world_model/core"


def core_config(sizes=SIZES, **changes) -> lf.Lfm2MoeConfig:
    s = dict(sizes, **changes)
    return lf.Config.from_mapping(dict(
        s, num_experts=s["router_outputs"], held_index=s["expert_share_index"],
        held_of=s["router_outputs"] // s["num_experts"], cache_len=changes.get("cache_len", 64),
    ))


def weights(sizes=SIZES, seed=11, scale=4.0):
    """The benchmark's weights, the core's products scaled up so that every
    layer's part of the output is well above rounding."""
    shapes = {k: v for k, v in REFERENCE.param_shapes(sizes).items() if k.startswith(CORE)}
    flat = jax.jit(lambda s: REFERENCE.make_weights(shapes, s))(np.int32(seed))
    flat = {k: v * scale if v.ndim >= 2 and not k.endswith("/conv/conv") else v for k, v in flat.items()}
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
    with open(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_lfm2.CONFIG)) as f:
        sizes = json.load(f)["sizes"]
    shapes = REFERENCE.param_shapes(sizes)
    count = lambda prefix: sum(int(np.prod(v)) for k, v in shapes.items() if k.startswith(prefix))
    # ISSUE 34's table: a dense conv layer 60.83 M, the attention layer 98.64 M held, an expert conv layer 104.93 M held
    assert count(f"{CORE}/layers_0/") == count(f"{CORE}/layers_1/") == 60_827_648
    assert count(f"{CORE}/layers_2/") == 98_635_936 and count(f"{CORE}/layers_3/") == 104_933_408
    assert count(f"{CORE}/layers_2/attn/") == 10_485_888 and count(f"{CORE}/layers_0/mlp/") == 3 * 2048 * 7168
    assert count(f"{CORE}/layers_3/conv/") == 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert sum(count(f"{CORE}/layers_{l}/") for l in range(6)) == 535_091_456 and count(f"{CORE}/") == 568_647_936
    assert shapes[f"{CORE}/layers_4/moe/gate"] == (8, 2048, 1792) and shapes[f"{CORE}/layers_4/moe/router"] == (2048, 32)
    assert shapes[f"{CORE}/layers_4/moe/expert_bias"] == (32,) and f"{CORE}/layers_1/moe/router" not in shapes
    assert f"{CORE}/head" not in shapes and not any("shared" in k for k in shapes)  # a tied head, no shared expert
    program = lf.param_shapes(core_config(sizes, cache_len=sizes["cache_len"]))
    flat = {"/".join(str(p.key) for p in path): shape for path, shape in
            jax.tree_util.tree_flatten_with_path(program, is_leaf=lambda x: isinstance(x, tuple))[0]}
    assert {f"{CORE}/{k}": v for k, v in flat.items()} == {k: tuple(v) for k, v in shapes.items() if k.startswith(CORE)}
    # the acting state of one env: two float32 rows of 2,048 in five layers, a ring of 1,024 keys and values of 8 x 64 in bf16
    state = jax.eval_shape(lambda: lf.init_state(core_config(sizes, cache_len=sizes["cache_len"]), 1, 1, None, jnp.bfloat16))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)) == 5 * 16_384 + 2_097_152 + 8


@pytest.mark.parametrize("kind", ["conv", "attn", "mlp", "moe"])
def test_a_part_alone_gives_the_references_output(kind):
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()
    x = jax.random.normal(jax.random.PRNGKey(2), tokens.shape + (SIZES["hidden_size"],))
    if kind == "conv":
        got, state = lf.conv_window(tree["layers_3"]["conv"], x, reset, c, jnp.float32)
        want, ref_state = REFERENCE.conv_window(flat, f"{CORE}/layers_3/conv", x, reset, SIZES, "f32")
        # the state at a chunk boundary: the reference's two gated rows before it, where they are of its episode
        close(state["conv"][0, 1], ref_state["gated"][0, 14:16])  # row 0, token 16: rows 14 and 15, same episode
        close(state["conv"][1, 2], jnp.zeros((2, 64)))  # row 1, token 32 is an episode's first: nothing before it
    elif kind == "attn":
        got, state = lf.attn_window(tree["layers_2"]["attn"], x, reset, c, jnp.float32)
        want, ref_state = REFERENCE.attn_window(flat, f"{CORE}/layers_2/attn", x, reset, SIZES, "f32")
        close(state["k"], ref_state["k"])
    elif kind == "mlp":
        got = lf.dense_mlp(tree["layers_0"]["mlp"], x, jnp.float32)
        want = REFERENCE.dense_mlp(flat, f"{CORE}/layers_0/mlp", x, "f32")
    else:
        got, stats = lf._feed_forward(tree["layers_3"], x.reshape(-1, x.shape[-1]), c, 3, jnp.float32, "core", 2)
        want, chosen = REFERENCE.experts(flat, f"{CORE}/layers_3/moe", x.reshape(-1, x.shape[-1]), SIZES, "f32")
        assert float(stats["dropped_pairs"]) == 0 and float(stats["held_pairs"]) > 0
        np.testing.assert_array_equal(stats["load"][0], chosen.sum(0))  # over all 16 outputs, 4 of them held
        assert float(stats["load"].sum()) == 128 * 3 and float(stats["held_pairs"]) == float(chosen[:, 4:8].sum())
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
        h, _, stats = lf.window(p, tokens, reset, c)
        logits = lf.head_logits(p, h, jnp.float32)
        return _next_token_loss(logits, tokens) + c.balance_loss(stats["aux"]), (logits, stats["load"])

    def theirs(f):
        h, _, load = REFERENCE.core_window(f, SIZES, tokens, reset)
        logits = REFERENCE.tied_head(f, h, "f32")
        return _next_token_loss(logits, tokens), (logits, load)

    (loss, (logits, load)), grads = jax.jit(jax.value_and_grad(mine, has_aux=True))(tree)
    (want_loss, (want_logits, want_load)), want_grads = jax.jit(jax.value_and_grad(theirs, has_aux=True))(flat)
    close(logits, want_logits, 2e-4)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_array_equal(load, want_load)
    got = {f"{CORE}/" + "/".join(str(k.key) for k in path): g for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        if name.endswith("expert_bias"):  # no gradient reaches the selection bias, in either
            assert float(jnp.abs(want).max()) == 0 == float(jnp.abs(got[name]).max()), name
            continue
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(got[name], want, atol=2e-4 * float(jnp.abs(want).max()), err_msg=name)


def test_the_one_token_path_after_a_window_prefix_agrees_with_the_references_full_forward_pass():
    """Logits to logits: prefill by the window pass, then one token at a time
    from the two gated rows and the key-value ring against the reference,
    which convolves and attends over whole rows; a reset falls into the
    decoded stretch."""
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()
    reset = reset.at[0, 40].set(1)
    want = REFERENCE.tied_head(flat, REFERENCE.core_window(flat, SIZES, tokens, reset)[0], "f32")
    scale = float(jnp.abs(want).max())
    prefix = 32  # a chunk boundary: the window pass's own keys and values up to it are the context
    _, states, _ = lf.window(tree, tokens[:, : prefix + 16], reset[:, : prefix + 16], c)
    state, context = lf.boundary_state(states, reset[:, : prefix + 16], c, own_len=32)
    # the stream that starts at the prefix's end: the third boundary of its row
    state = jax.tree_util.tree_map(lambda x: x[:, 2:3], state)
    context = {name: (k[:, :prefix], v[:, :prefix], mask[:, 2:3, :prefix]) for name, (k, v, mask) in context.items()}
    decode = jax.jit(lambda p, s, t, ctx: lf.decode(p, s, t, c, context=ctx))
    for t in range(prefix, tokens.shape[1]):
        hit = reset[:, t : t + 1] > 0
        if t > prefix and bool(hit.any()):  # an episode ends: the stream's state and the row's context are dropped
            state = lf.reset_state(state, hit)
            context = {name: (k, v, m & ~hit[:, :, None]) for name, (k, v, m) in context.items()}
        out, state, _ = decode(tree, state, tokens[:, t : t + 1], context)
        np.testing.assert_allclose(lf.head_logits(tree, out[:, 0], jnp.float32), want[:, t], atol=2e-4 * scale, err_msg=str(t))


def test_the_balance_step_is_the_references():
    """The same loads move the same biases: every routing layer's, by the
    rate, against the load's distance from the layer's mean, whatever its size."""
    flat, tree = weights()
    c = core_config()
    load = jnp.asarray(np.random.default_rng(0).integers(0, 25, (4, 16)), jnp.float32).at[1].set(12.0)
    got, report = lf.balance_step(tree, load, c)
    want = REFERENCE.balance_step(flat, load, SIZES)
    moved = 0
    for i, l in enumerate((2, 3, 4, 5)):
        name = f"{CORE}/layers_{l}/moe/expert_bias"
        np.testing.assert_array_equal(got[f"layers_{l}"]["moe"]["expert_bias"], want[name])
        step = np.asarray(want[name] - flat[name])
        np.testing.assert_allclose(step, SIZES["bias_update_rate"] * np.sign(float(load[i].mean()) - np.asarray(load[i])), atol=1e-7)
        moved += int((step != 0).sum())
    assert moved > 32 and np.all(np.asarray(want[f"{CORE}/layers_3/moe/expert_bias"]) == np.asarray(flat[f"{CORE}/layers_3/moe/expert_bias"]))
    assert float(report["expert_bias_abs_max"]) == max(float(jnp.abs(want[f"{CORE}/layers_{l}/moe/expert_bias"]).max()) for l in (2, 3, 4, 5))
    # every other leaf is the optimiser's alone
    same = jax.tree_util.tree_map(lambda a, b: a is b, {k: v for k, v in got.items() if k in ("embed", "layers_0")},
                                  {k: v for k, v in tree.items() if k in ("embed", "layers_0")})
    assert all(jax.tree_util.tree_leaves(same))


def test_the_benchmarks_bias_changes_the_choice_from_the_first_step():
    """``make_weights`` seeds the bias away from zero, so that a program that
    left it out of the selection would route otherwise: at the cell's own
    widths of router, most tokens choose differently with it than without."""
    flat, _ = weights(scale=1.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (256, SIZES["hidden_size"]))
    pre = f"{CORE}/layers_4/moe"
    assert 0.05 < float(jnp.abs(flat[f"{pre}/expert_bias"]).max()) <= REFERENCE.BIAS_SPAN
    _, with_bias = REFERENCE.experts(flat, pre, x, SIZES, "f32")
    _, without = REFERENCE.experts(flat, pre, x, SIZES, "f32", bias=False)
    assert float(jnp.mean(jnp.any(with_bias != without, -1))) > 0.5


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four shares of four experts each: their parts (no expert is shared, so
    nothing is counted twice) are the layer with all sixteen experts held."""
    uncut_sizes = dict(SIZES, num_experts=16, expert_share_index=0)
    flat, tree = weights(uncut_sizes)
    x = jax.random.normal(jax.random.PRNGKey(3), (96, SIZES["hidden_size"]))
    pre = f"{CORE}/layers_4/moe"
    want, chosen = REFERENCE.experts(flat, pre, x, uncut_sizes, "f32")
    total = 0.0
    for index in range(4):
        p = dict(tree["layers_4"]["moe"])
        for name in ("gate", "up", "down"):
            p[name] = p[name][4 * index : 4 * index + 4]
        part, stats = lf._feed_forward({"moe": p}, x, core_config(expert_share_index=index), 4, jnp.float32, "core", 1)
        assert float(stats["dropped_pairs"]) == 0
        np.testing.assert_array_equal(stats["load"][0], chosen.sum(0))  # every share sees the whole router's load
        total = total + part
    np.testing.assert_allclose(total, want, atol=3e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(want).max()) > 0.1
