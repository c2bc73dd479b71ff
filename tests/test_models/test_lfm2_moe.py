"""The LFM2-MoE sequence core by itself (``models/lfm2_moe.py``): the one-token
pass against the window pass over resets inside a window and past a wrapped
ring, the convolution that never reads over an episode's first step, the
parameter count at the cell's sizes, what the state holds and the counters the
two passes report."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import lfm2_moe as lf

TINY = lf.Lfm2MoeConfig(
    hidden_size=64, num_hidden_layers=6, intermediate_size=96, num_attention_heads=4, num_key_value_heads=2,
    num_experts=16, num_experts_per_tok=3, moe_intermediate_size=32, vocab_size=256, held_index=1, held_of=4, chunk=16,
    cache_len=64,
)
CELL = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks", "configs", "dv3-lfm2.ep4.json")


def weights(c=TINY, scale=5.0):
    p = lf.init_params(jax.random.PRNGKey(0), c)
    p = jax.tree_util.tree_map(lambda w: w * scale if w.ndim >= 2 else w, p)
    for l in range(c.num_dense_layers, c.num_hidden_layers):  # a bias that changes who is chosen
        p[f"layers_{l}"]["moe"]["expert_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(l), (c.num_experts,))
    return p


def window_inputs(B=2, L=64):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, TINY.vocab_size)
    reset = jnp.zeros((B, L), jnp.int32).at[0, 0].set(1).at[0, 21].set(1).at[1, 32].set(1).at[1, 50].set(1)
    return tokens, reset


def _decode_all(p, c, tokens, reset):
    """Every token through the one-token pass, the state reset where an episode begins."""
    state = lf.init_state(c, tokens.shape[0], 1)
    step = jax.jit(lambda p, s, t: lf.decode(p, s, t, c))
    outs = []
    for t in range(tokens.shape[1]):
        state = lf.reset_state(state, reset[:, t : t + 1] > 0)
        h, state, stats = step(p, state, tokens[:, t : t + 1])
        outs.append(h[:, 0])
    return jnp.stack(outs, 1), state, stats


def test_the_configuration_reads_the_published_keys_and_the_stack_they_give():
    with open(CELL) as f:
        published = json.load(f)
    c = lf.Config.from_mapping({**published["sizes"], "num_experts": 32, "held_index": 0, "held_of": 4, "no_such_key": 1})
    assert [c.is_attention(l) for l in range(6)] == [False, False, True, False, False, False]
    assert [c.is_dense(l) for l in range(6)] == [True, True, False, False, False, False]
    assert (c.experts_held, c.moe_layers, c.head_dim, c.conv_L_cache) == (8, 4, 64, 3) and c.layer_types == lf.LAYER_TYPES[:6]
    spec = c.moe_spec
    assert (spec.score, spec.select_bias, spec.shared, spec.normalize, spec.normalize_eps, spec.scale) == \
        ("sigmoid", True, False, True, 1e-6, 1.0)
    assert c.balance_loss(jnp.float32(3.0)) == 0.0  # nothing balances through the loss
    # the published 24 layers: 18 convolutions and 6 attention layers, two dense ones first
    whole = lf.Config()
    assert sum(whole.is_attention(l) for l in range(24)) == 6 and whole.moe_layers == 22
    assert lf.LAYER_TYPES == tuple(published["layer_types"])
    with pytest.raises(ValueError, match="layer_types"):
        lf.Config(num_hidden_layers=3, layer_types=("conv", "conv"))


def test_the_cells_sizes_give_the_reckoned_parameter_count():
    with open(CELL) as f:
        sizes = json.load(f)["sizes"]
    c = lf.Config.from_mapping({**sizes, "num_experts": sizes["router_outputs"], "held_index": 0, "held_of": 4})
    shapes = lf.param_shapes(c)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert count(shapes["layers_0"]) == 60_827_648 and count(shapes["layers_2"]) == 98_635_936
    assert count(shapes["layers_5"]) == 104_933_408 and count(shapes) == 568_647_936
    assert "head" not in shapes and "shared_gate" not in shapes["layers_3"]["moe"]


def test_the_one_token_pass_agrees_with_the_window_pass_across_resets():
    p = weights()
    tokens, reset = window_inputs()
    h, _, stats = lf.window(p, tokens, reset, TINY)
    got, state, _ = _decode_all(p, TINY, tokens, reset)
    np.testing.assert_allclose(got, h, atol=2e-5 * float(jnp.abs(h).max()))
    # each attention layer counts a token's pairs inside its own episode; every token chose 3 of 16 in 4 layers
    seg_len = [21, 43, 32, 18, 14]
    assert float(stats["attended_pairs"]) == sum(n * (n + 1) // 2 for n in seg_len)
    assert stats["load"].shape == (4, 16) and float(stats["load"].sum()) == 4 * 128 * 3
    assert float(stats["router_max_load"]) == float(stats["load"].max()) >= 128 * 3 / 16
    assert float(stats["dropped_pairs"]) == 0 and 0 < float(stats["held_pairs"]) < float(stats["load"].sum())
    # the state: counters since the last reset, two rows a convolution layer, a ring in the attention layer
    assert state["pos"].tolist() == [[43], [14]] and set(state["layers_0"]) == {"conv"} and set(state["layers_2"]) == {"k", "v"}
    assert state["layers_3"]["conv"].shape == (2, 1, 2, 64) and state["layers_2"]["k"].shape == (2, 1, 64, 2, 16)


def test_a_wrapped_ring_still_agrees_with_a_window_pass_over_what_it_holds():
    """A ring of 16 tokens under a stream of 40: the one-token pass attends to
    the last 16, the convolution rows carry on. A core whose attention layer
    is given those last 16 tokens as its whole window (positions kept) agrees."""
    c = dataclasses.replace(TINY, cache_len=16)
    p = weights(c)["layers_2"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    ring = lf.init_state(c, 1, 1)["layers_2"]
    for t in range(40):
        at = jnp.full((1, 1), t, jnp.int32)
        y, ring = lf.attn_decode(p, x[:, t : t + 1], ring, at, at, None, c, jnp.float32)
    # the window pass over the last 16 tokens, rotary positions shifted alike on queries and keys: scores unchanged
    want, _ = lf.attn_window(p, x[:, 24:], jnp.zeros((1, 16), jnp.int32).at[0, 0].set(1), c, jnp.float32)
    np.testing.assert_allclose(y[:, 0], want[:, -1], atol=2e-5 * float(jnp.abs(want).max()))


def test_the_convolution_never_reads_over_an_episodes_first_step():
    """Rows before a reset changed at will: nothing at or after the reset
    moves, in the window pass or in the state a one-token stream starts from."""
    p = weights()["layers_3"]["conv"]
    _, reset = window_inputs()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    other = x.at[0, :21].add(7.0).at[1, 30:32].add(-5.0)
    y, state = lf.conv_window(p, x, reset, TINY, jnp.float32)
    y2, state2 = lf.conv_window(p, other, reset, TINY, jnp.float32)
    np.testing.assert_array_equal(y[0, 21:], y2[0, 21:])
    np.testing.assert_array_equal(y[1, 32:], y2[1, 32:])
    assert float(jnp.abs(y[0, :21] - y2[0, :21]).max()) > 0.1
    # boundaries at 0, 16, 32, 48: row 0's from 32 on and row 1's at 32 (an episode's first: zero rows) and 48 stand
    np.testing.assert_array_equal(state["conv"][0, 2:], state2["conv"][0, 2:])
    np.testing.assert_array_equal(state["conv"][1, 2:], state2["conv"][1, 2:])
    assert float(jnp.abs(state["conv"][1, 2]).max()) == 0 and float(jnp.abs(state["conv"][0, 0]).max()) == 0
    # the second tap of a token right after a reset reads nothing either: token 22 sees 21 and itself
    lone = lf.conv_window(p, x[:, 21:23], jnp.ones((2, 2), jnp.int32).at[:, 1].set(0), dataclasses.replace(TINY, chunk=2),
                          jnp.float32)[0]
    np.testing.assert_allclose(y[0, 21:23], lone[0], atol=1e-5)


def test_imagination_starts_go_on_from_the_window_pass():
    """A stream from a chunk boundary (two gated rows, the row's keys and
    values as context) gives the window pass's own outputs for the tokens
    that follow it, whether the boundary lies inside an episode or begins one."""
    p = weights()
    tokens, reset = window_inputs()
    h, states, _ = lf.window(p, tokens, reset, TINY)
    state, context = lf.boundary_state(states, reset, TINY, own_len=8)
    assert state["layers_0"]["conv"].shape == (2, 4, 2, 64) and state["layers_2"]["k"].shape == (2, 4, 8, 2, 16)
    step = jax.jit(lambda s, t: lf.decode(p, s, t, TINY, context=context))
    at = jnp.arange(4) * 16
    for k in range(5):  # no reset falls into (16, 21), (32, 37) or (48, 50) of row 1... compare where none does
        out, state, _ = step(state, tokens[:, at + k])
        for row, start in ((0, 0), (0, 32), (0, 48), (1, 0), (1, 16), (1, 32)):
            n = start // 16
            np.testing.assert_allclose(out[row, n], h[row, start + k], atol=3e-5 * float(jnp.abs(h).max()), err_msg=f"{row} {start} {k}")


@pytest.mark.parametrize("rate", [0.001, 0.02])
def test_the_balance_step_moves_the_bias_alone_at_the_configured_rate(rate):
    """``core.bias_update_rate`` is the recipe's (the paper's 0.001) or the
    benchmark's (a run of 160 steps): every routing layer's bias moves by that
    much against its load, the layers' order the stack's, no other leaf."""
    c = dataclasses.replace(TINY, bias_update_rate=rate)
    p = weights(c)
    load = jnp.asarray(np.random.default_rng(0).integers(0, 40, (c.moe_layers, c.num_experts)), jnp.float32)
    new, report = lf.balance_step(p, load, c)
    layers = [l for l in range(c.num_hidden_layers) if not c.is_dense(l)]
    largest = 0.0
    for row, l in enumerate(layers):
        want = p[f"layers_{l}"]["moe"]["expert_bias"] + rate * np.sign(float(load[row].mean()) - np.asarray(load[row]))
        np.testing.assert_allclose(new[f"layers_{l}"]["moe"]["expert_bias"], want, rtol=0, atol=1e-7)
        largest = max(largest, float(np.abs(want).max()))
        assert all(new[f"layers_{l}"]["moe"][k] is p[f"layers_{l}"]["moe"][k] for k in ("router", "gate", "up", "down"))
    assert new["embed"] is p["embed"] and new["layers_0"] is p["layers_0"]
    assert float(report["expert_bias_abs_max"]) == pytest.approx(largest, abs=1e-6) and report["router_load"] is load


def test_without_the_expert_bias_nothing_moves_outside_the_gradient_and_the_score_alone_chooses():
    c = dataclasses.replace(TINY, use_expert_bias=False)
    p = weights(c)
    tokens, reset = window_inputs()
    load = jnp.ones((c.moe_layers, c.num_experts), jnp.float32)
    same, report = lf.balance_step(p, load, c)
    assert same is p and report == {}
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, w: jnp.zeros_like(w) if path[-1].key == "expert_bias" else w, p)
    h, _, stats = lf.window(p, tokens, reset, c)
    h0, _, stats0 = lf.window(zeroed, tokens, reset, TINY)
    np.testing.assert_array_equal(h, h0)  # the bias leaf is there and is not read
    np.testing.assert_array_equal(stats["load"], stats0["load"])
    h_biased, _, _ = lf.window(p, tokens, reset, TINY)
    assert float(jnp.abs(h_biased - h).max()) > 1e-3
