"""The DeepSeek-V2 sequence core by itself (``models/deepseek_v2.py``): YaRN's
frequencies and softmax scale against their closed forms, the absorbed
one-token path against the window pass across resets, what the latent cache
holds, and the counters the two passes report."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import deepseek_v2 as ds

TINY = ds.DeepseekV2Config(
    hidden_size=64, num_hidden_layers=3, intermediate_size=96, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=32, vocab_size=256, held_index=1, held_of=4, chunk=16, cache_len=64,
)


def weights(c=TINY, scale=5.0):
    p = ds.init_params(jax.random.PRNGKey(0), c)
    return jax.tree_util.tree_map(lambda w: w * scale if w.ndim >= 2 else w, p)


def window_inputs(B=2, L=64):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, TINY.vocab_size)
    reset = jnp.zeros((B, L), jnp.int32).at[0, 0].set(1).at[0, 21].set(1).at[1, 32].set(1).at[1, 50].set(1)
    return tokens, reset


def test_yarns_frequencies_and_softmax_scale_are_the_closed_forms():
    c = ds.DeepseekV2Config()  # the published values: factor 40 over 4,096, beta 32 and 1, theta 10,000, 64 rotary dims
    inv = ds.yarn_inv_freq(c)
    own = 10_000.0 ** (-np.arange(32) * 2 / 64)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10, 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0.0, 1.0)
    np.testing.assert_allclose(inv, own / 40 * ramp + own * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv[:11], own[:11], rtol=1e-6)  # fast dims keep their own frequency
    np.testing.assert_allclose(inv[23:], own[23:] / 40, rtol=1e-6)  # slow dims are interpolated
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert ds.softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m, rel=1e-9)
    # cos and sin carry m(40, mscale) / m(40, mscale_all_dim) = 1: a rotation keeps the norm
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    np.testing.assert_allclose(jnp.linalg.norm(ds._rope(x, jnp.arange(5) * 1000, c), axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # without scaling the frequencies are the plain ones and the scale is 192^-1/2
    plain = ds.DeepseekV2Config(rope_factor=1.0)
    np.testing.assert_allclose(ds.yarn_inv_freq(plain), own, rtol=1e-6)
    assert ds.softmax_scale(plain) == pytest.approx(192 ** -0.5)


def test_the_configuration_reads_the_published_rope_scaling_block():
    published = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
                 "original_max_position_embeddings": 4096, "type": "yarn"}
    c = ds.Config.from_mapping({"hidden_size": 2048, "rope_scaling": dict(published, factor=8), "held_index": 1, "held_of": 8,
                                "no_such_key": 1})
    assert (c.rope_factor, c.rope_beta_fast, c.rope_original_max_position_embeddings) == (8, 32, 4096)
    assert c.experts_held == 8 and c.moe_layers == 26 and c.latent_dim == 576 and c.is_dense(0) and not c.is_dense(1)
    with pytest.raises(ValueError, match="yarn"):
        ds.Config.from_mapping({"rope_scaling": dict(published, type="linear")})


def test_the_absorbed_one_token_path_agrees_with_the_window_pass_across_resets():
    """Logits, not samples: one token at a time through the latent ring from an
    empty state (resets dropping the ring inside the row), and on from every
    ``chunk``-th token against the window pass's own latent cache."""
    c, p = TINY, weights()
    tokens, reset = window_inputs()
    h, states, stats = jax.jit(lambda p, t, r: ds.window(p, t, r, c))(p, tokens, reset)
    logits = ds.head_logits(p, h, jnp.float32)
    scale = float(jnp.abs(logits).max())
    assert scale > 1.0
    decode = jax.jit(lambda p, s, t, ctx=None: ds.decode(p, s, t, c, context=ctx))
    state, outs = ds.init_state(c, tokens.shape[0], 1), []
    for t in range(tokens.shape[1]):
        state = ds.reset_state(state, reset[:, t : t + 1] > 0)
        out, state, _ = decode(p, state, tokens[:, t : t + 1])
        outs.append(out[:, 0])
    np.testing.assert_allclose(ds.head_logits(p, jnp.stack(outs, 1), jnp.float32), logits, atol=2e-5 * scale)
    # the state is the latent ring and nothing per head: 32 + 8 numbers a token and layer
    assert {k: v.shape for k, v in state["layers_0"].items()} == {"latent": (2, 1, 64, 40)}

    state, context = ds.boundary_state(states, reset, c, own_len=4)
    at = np.arange(tokens.shape[1] // c.chunk) * c.chunk
    for i in range(4):
        out, state, counts = decode(p, state, tokens[:, at + i], context)
        clean = np.asarray([[not reset[b, a + 1 : a + i + 1].any() for a in at] for b in range(tokens.shape[0])])
        got = ds.head_logits(p, out, jnp.float32)
        np.testing.assert_allclose(got[clean], logits[:, at + i][clean], atol=2e-5 * scale)
        # row 0 (resets at 0, 21) sees 0, 16, 11, 27 positions of its episode before its boundaries, row 1
        # (32, 50) 0, 16, 0, 16, plus the i + 1 of its own; a row's cache is read up to the stream that sees most
        ahead = np.array([[0, 16, 11, 27], [0, 16, 0, 16]])
        assert float(counts["context_tokens"]) == c.num_hidden_layers * (ahead.sum() + 8 * (i + 1))
        assert float(counts["cache_tokens"]) == c.num_hidden_layers * (ahead.max(1).sum() + 8 * (i + 1))


def test_the_window_pass_counts_the_pairs_inside_an_episodes_segment():
    c, p = TINY, weights()
    tokens, reset = window_inputs()
    _, states, stats = ds.window(p, tokens, reset, c)
    segments = [[21, 43], [32, 18, 14]]  # token counts of each row's episodes (row 1 starts mid-episode)
    pairs = sum(n * (n + 1) // 2 for row in segments for n in row)
    assert float(stats["attended_pairs"]) == c.num_hidden_layers * pairs
    assert float(stats["dropped_pairs"]) == 0 and float(stats["held_pairs"]) > 0
    assert states["layers_2"]["latent"].shape == (2, 64, c.latent_dim)


def test_scores_are_not_computed_past_the_causal_edge_and_blocks_agree():
    """A window longer than one block of queries: the blocks' outputs are the whole pass's."""
    c, p = TINY, weights()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 512, c.hidden_size))
    reset = jnp.zeros((1, 512), jnp.int32).at[0, 0].set(1).at[0, 300].set(1)
    y, _ = ds.mla_window(p["layers_1"]["mla"], x, reset, c, jnp.float32)
    # the same tokens one episode at a time, each in a window of its own
    first, _ = ds.mla_window(p["layers_1"]["mla"], x[:, :300], reset[:, :300], c, jnp.float32)
    second, _ = ds.mla_window(p["layers_1"]["mla"], x[:, 300:], reset[:, 300:], c, jnp.float32)
    np.testing.assert_allclose(y, jnp.concatenate([first, second], 1), atol=2e-5 * float(jnp.abs(y).max()))
