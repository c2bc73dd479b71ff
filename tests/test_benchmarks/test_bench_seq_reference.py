"""The sequence core against its plain reference at a size a test can hold
(hidden 64, four layers, 16 experts of which 4 are held, vocabulary 256),
seeded weights: every mixer and the expert layer alone, a whole window against
prefill-then-decode through the cache, and the sum of all expert shares
against the uncut layer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import bench_tiny_seq
from benchmarks.manifest import load_module
from sheeprl_tpu.models import qwen3_next as qn

REFERENCE = load_module(os.path.join(bench_tiny.BENCH, "configs", "dv3-qwen3next.ep16.reference.py"))
SIZES = bench_tiny_seq.tiny_config()["sizes"]
CORE = "world_model/core"


def core_config(**changes) -> qn.Qwen3NextConfig:
    s = dict(SIZES, **changes)
    return qn.Qwen3NextConfig.from_mapping(dict(
        s, num_experts=s["router_outputs"], held_index=s["expert_share_index"],
        held_of=s["router_outputs"] // s["num_experts"], cache_len=64,
    ))


def weights(sizes=SIZES, seed=11, scale=4.0):
    """The benchmark's weights, the core's products scaled up so that every
    layer's part of the output is well above rounding."""
    shapes = {k: v for k, v in REFERENCE.param_shapes(sizes).items() if k.startswith(CORE)}
    flat = jax.jit(lambda s: REFERENCE.make_weights(shapes, s))(np.int32(seed))
    flat = {k: v * scale if v.ndim >= 2 and not k.endswith("conv") else v for k, v in flat.items()}
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
    with open(os.path.join(bench_tiny.BENCH, "configs", "dv3-qwen3next.ep16.json")) as f:
        sizes = json.load(f)["sizes"]
    shapes = REFERENCE.param_shapes(sizes)
    core = sum(int(np.prod(v)) for k, v in shapes.items() if k.startswith(CORE))
    # ISSUE 28's 625.7 M: three Gated DeltaNet layers of 138.6 M, a gated-attention layer of 132.1 M, 77.8 M of vocabulary
    assert core == 625_667_136
    assert shapes[f"{CORE}/layers_0/gdn/qkvz"] == (2048, 12288) and shapes[f"{CORE}/layers_3/attn/q"] == (2048, 8192)
    assert shapes[f"{CORE}/layers_2/moe/gate"] == (32, 2048, 512) and shapes[f"{CORE}/layers_2/moe/router"] == (2048, 512)
    program = qn.param_shapes(core_config(**{k: sizes[k] for k in SIZES if k in sizes}))
    flat = {"/".join(str(p.key) for p in path): shape for path, shape in
            jax.tree_util.tree_flatten_with_path(program, is_leaf=lambda x: isinstance(x, tuple))[0]}
    assert {f"{CORE}/{k}": v for k, v in flat.items()} == {k: tuple(v) for k, v in shapes.items() if k.startswith(CORE)}


@pytest.mark.parametrize("kind", ["gdn", "attn", "moe"])
def test_a_layer_alone_gives_the_references_output(kind):
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()
    x = jax.random.normal(jax.random.PRNGKey(2), tokens.shape + (SIZES["hidden_size"],))
    if kind == "gdn":
        got, state = qn.gdn_window(tree["layers_0"]["gdn"], x, reset, c, jnp.float32)
        want, ref_state = REFERENCE.gdn_window(flat, f"{CORE}/layers_0/gdn", x, reset, SIZES, "f32")
        close(jnp.moveaxis(state["S"], 0, 1), ref_state["S"])
        close(state["conv"], ref_state["conv"])
    elif kind == "attn":
        got, state = qn.attn_window(tree["layers_3"]["attn"], x, reset, c, jnp.float32)
        want, ref_state = REFERENCE.attn_window(flat, f"{CORE}/layers_3/attn", x, reset, SIZES, "f32")
        close(state["k"], ref_state["k"])
    else:
        got, stats = qn.moe(tree["layers_1"]["moe"], x.reshape(-1, x.shape[-1]), c, jnp.float32)
        want, _, _ = REFERENCE.experts(flat, f"{CORE}/layers_1/moe", x.reshape(-1, x.shape[-1]), SIZES, "f32")
        assert float(stats["dropped_pairs"]) == 0 and float(stats["held_pairs"]) > 0
        got, want = got.reshape(x.shape), want.reshape(x.shape)
    assert float(jnp.abs(want).max()) > 0.1
    close(got, want)


def test_pairs_beyond_one_window_of_the_sorted_list_are_not_dropped():
    """A router that sends nearly everything to the held experts: the pairs
    fill several windows of :func:`held_experts`, and none is lost."""
    flat, tree = weights()
    c = core_config()
    p = dict(tree["layers_1"]["moe"])
    held = slice(c.held_index * c.experts_held, (c.held_index + 1) * c.experts_held)
    p["router"] = p["router"].at[:, held].add(0.0) * 0.0 + jnp.zeros_like(p["router"]).at[:, held].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (3000, SIZES["hidden_size"])))
    got, stats = qn.moe(p, x, c, jnp.float32)
    flat = dict(flat, **{f"{CORE}/layers_1/moe/router": p["router"]})
    want, _, _ = REFERENCE.experts(flat, f"{CORE}/layers_1/moe", x, SIZES, "f32")
    assert float(stats["held_pairs"]) == 3000 * c.num_experts_per_tok  # every pair is held: 9,000 of a 4,608-pair window
    assert float(stats["dropped_pairs"]) == 0
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(qn.moe(p, x, c, jnp.float32)[0])), argnums=(0, 1))(p, x)
    ref = jax.grad(lambda f, x: jnp.sum(jnp.sin(REFERENCE.experts(f, f"{CORE}/layers_1/moe", x, SIZES, "f32")[0])),
                   argnums=(0, 1))(flat, x)
    for name in ("gate", "up", "down"):
        want_g = ref[0][f"{CORE}/layers_1/moe/{name}"]
        np.testing.assert_allclose(grads[0][name], want_g, atol=5e-5 * float(jnp.abs(want_g).max()))
    np.testing.assert_allclose(grads[1], ref[1], atol=5e-5 * float(jnp.abs(ref[1]).max()))


def test_a_whole_window_agrees_with_the_reference_and_with_prefill_then_decode():
    """Logits, not samples: the window pass, one-token decoding through the
    cache from an empty state, and decoding on from a chunk boundary."""
    flat, tree = weights()
    c = core_config()
    tokens, reset = window_inputs()
    h, states, _ = jax.jit(lambda p, t, r: qn.window(p, t, r, c))(tree, tokens, reset)
    logits = qn.head_logits(tree, h, jnp.float32)
    want, _, _ = REFERENCE.core_window(flat, SIZES, tokens, reset)
    want_logits = REFERENCE.matmul(want, flat[f"{CORE}/head"], "f32")
    scale = float(jnp.abs(want_logits).max())
    np.testing.assert_allclose(logits, want_logits, atol=2e-4 * scale)

    decode = jax.jit(lambda p, s, t, ctx=None: qn.decode(p, s, t, c, context=ctx))
    state, outs = qn.init_state(c, tokens.shape[0], 1), []
    for t in range(tokens.shape[1]):
        state = qn.reset_state(state, reset[:, t : t + 1] > 0)
        out, state, _ = decode(tree, state, tokens[:, t : t + 1])
        outs.append(out[:, 0])
    np.testing.assert_allclose(qn.head_logits(tree, jnp.stack(outs, 1), jnp.float32), want_logits, atol=2e-4 * scale)

    # from every chunk boundary, for as long as no reset falls into the stretch
    state, context = qn.boundary_state(states, reset, c, own_len=4)
    at = np.arange(tokens.shape[1] // c.chunk) * c.chunk
    for i in range(4):
        out, state, _ = decode(tree, state, tokens[:, at + i], context)
        clean = np.asarray([[not reset[b, a + 1 : a + i + 1].any() for a in at] for b in range(tokens.shape[0])])
        got = qn.head_logits(tree, out, jnp.float32)
        np.testing.assert_allclose(got[clean], want_logits[:, at + i][clean], atol=2e-4 * scale)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four shares of four experts each: their parts, the shared expert
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
        part, stats = qn.moe(p, x, core_config(expert_share_index=index), jnp.float32)
        assert float(stats["dropped_pairs"]) == 0
        total = total + (part - shared_only)
    np.testing.assert_allclose(total, want, atol=3e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(want - shared_only).max()) > 0.1 * float(jnp.abs(want).max())
