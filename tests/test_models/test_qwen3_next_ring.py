"""The one-token attention's ring past its length, for the three sequence cores
(two key-value rings, a latent ring). In the benchmark's cells an episode (at most 400
steps, 800 tokens) ends before the 1,024-token ring wraps, so no run there
shows a wrap; this holds it to what a wrapped ring is: a stream that sees its
last ``cache_len`` tokens, each under its own rotary position."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import deepseek_v2 as ds
from sheeprl_tpu.models import lfm2_moe as lf
from sheeprl_tpu.models import qwen3_next as qn

CONFIG = qn.Qwen3NextConfig(
    hidden_size=32, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16, shared_expert_intermediate_size=16,
    vocab_size=32, chunk=4, cache_len=4,
)


LATENT_CONFIG = ds.DeepseekV2Config(
    hidden_size=32, num_hidden_layers=2, intermediate_size=48, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=16, vocab_size=32, chunk=4, cache_len=4,
)


GQA_CONFIG = lf.Lfm2MoeConfig(
    hidden_size=32, num_hidden_layers=3, intermediate_size=48, num_attention_heads=4, num_key_value_heads=2,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16, vocab_size=32, chunk=4, cache_len=4,
)


def _qwen3_next():
    p = qn.init_params(jax.random.PRNGKey(0), CONFIG)["layers_3"]["attn"]

    def ring(length):
        kv = (1, 1, length, CONFIG.num_key_value_heads, CONFIG.head_dim)
        return {"k": jnp.zeros(kv), "v": jnp.zeros(kv)}

    return p, ring, lambda p, x, r, pos, rope: qn.attn_decode(p, x, r, pos, rope, None, CONFIG, jnp.float32)[:2]


def _deepseek_v2():
    p = ds.init_params(jax.random.PRNGKey(0), LATENT_CONFIG)["layers_1"]["mla"]
    ring = lambda length: {"latent": jnp.zeros((1, 1, length, LATENT_CONFIG.latent_dim))}
    return p, ring, lambda p, x, r, pos, rope: ds.mla_decode(p, x, r, pos, rope, None, LATENT_CONFIG, jnp.float32)[:2]


def _lfm2_moe():
    p = lf.init_params(jax.random.PRNGKey(0), GQA_CONFIG)["layers_2"]["attn"]

    def ring(length):
        kv = (1, 1, length, GQA_CONFIG.num_key_value_heads, GQA_CONFIG.head_dim)
        return {"k": jnp.zeros(kv), "v": jnp.zeros(kv)}

    return p, ring, lambda p, x, r, pos, rope: lf.attn_decode(p, x, r, pos, rope, None, GQA_CONFIG, jnp.float32)


@pytest.mark.parametrize("core", [_qwen3_next, _deepseek_v2, _lfm2_moe])
@pytest.mark.parametrize("tokens", [4, 7, 11])
def test_a_wrapped_ring_attends_to_the_last_tokens_under_their_own_positions(tokens, core):
    p, ring_of, decode = core()
    p = jax.tree_util.tree_map(lambda w: w + 0.3 * jax.random.normal(jax.random.PRNGKey(1), w.shape), p)
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, 1, 1, CONFIG.hidden_size))
    at = lambda t: jnp.full((1, 1), t, jnp.int32)
    ring, length = ring_of(CONFIG.cache_len), CONFIG.cache_len
    for t in range(tokens):
        y, ring = decode(p, x[t], ring, at(t), at(t))
    # a ring that never wraps, given only the last ``length`` tokens, each at its true rotary position
    fresh, first = ring_of(2 * length), tokens - length
    for t in range(first, tokens):
        want, fresh = decode(p, x[t], fresh, at(t - first), at(t))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-6)
