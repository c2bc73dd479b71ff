"""The shared expert layer (``models/moe.py``) against a dense computation of
the same layer, written expert by expert: outputs, gradients and the balance
term, as each of the two sequence cores configures it (Qwen3-Next: the chosen
weights renormalised, a gated shared expert; DeepSeek-V2: the weights as they
are times a factor, always-on shared experts, the term per choice)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import moe
from sheeprl_tpu.models import deepseek_v2 as ds
from sheeprl_tpu.models import qwen3_next as qn

D, F, E, K = 24, 16, 8, 3
SPECS = {
    "qwen3_next": qn.Qwen3NextConfig(num_experts=E, num_experts_per_tok=K, held_index=1, held_of=2).moe_spec,
    "deepseek_v2": ds.DeepseekV2Config(n_routed_experts=E, num_experts_per_tok=K, held_index=1, held_of=2,
                                       routed_scaling_factor=1.5).moe_spec,
}


def weights(spec, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    Eh = spec.experts_held
    p = {"router": jax.random.normal(keys[0], (D, E)), "gate": jax.random.normal(keys[1], (Eh, D, F)) * 0.3,
         "up": jax.random.normal(keys[2], (Eh, D, F)) * 0.3, "down": jax.random.normal(keys[3], (Eh, F, D)) * 0.3,
         "shared_gate": jax.random.normal(keys[4], (D, F)) * 0.3, "shared_up": jax.random.normal(keys[5], (D, F)) * 0.3,
         "shared_down": jax.random.normal(keys[6], (F, D)) * 0.3}
    if spec.shared_gate:
        p["shared_router"] = jax.random.normal(keys[7], (D, 1))
    return p


def dense_layer(p, x, spec):
    """Every token through every held expert, one expert at a time."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top_p, top_i = jax.lax.top_k(probs, spec.num_experts_per_tok)
    if spec.normalize:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * spec.scale
    out = 0.0
    for local in range(spec.experts_held):
        weight = jnp.sum(jnp.where(top_i == spec.held_index * spec.experts_held + local, top_p, 0.0), -1)
        y = (jax.nn.silu(x @ p["gate"][local]) * (x @ p["up"][local])) @ p["down"][local]
        out = out + weight[:, None] * y
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) @ p["shared_down"]
    if spec.shared_gate:
        shared = jax.nn.sigmoid(x @ p["shared_router"]) * shared
    return out + shared, probs, top_i


@pytest.mark.parametrize("model", sorted(SPECS))
def test_the_layer_gives_the_dense_computations_output_and_counts_its_pairs(model):
    spec = SPECS[model]
    p, x = weights(spec), jax.random.normal(jax.random.PRNGKey(9), (40, D))
    got, stats = moe.moe(p, x, spec, jnp.float32, rows=2)
    want, probs, top_i = dense_layer(p, x, spec)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    held = (top_i >= spec.held_index * spec.experts_held) & (top_i < (spec.held_index + 1) * spec.experts_held)
    assert float(stats["held_pairs"]) == float(held.sum()) > 0 and float(stats["dropped_pairs"]) == 0
    # the balance term, row by row: E * sum_e f_e P_e, f_e per token (Qwen3-Next) or per choice (DeepSeek-V2)
    per_row = []
    for rows in (slice(0, 20), slice(20, 40)):
        f = jnp.zeros((E,)).at[top_i[rows].reshape(-1)].add(1.0) / 20 / (K if spec.aux_per_choice else 1)
        per_row.append(E * jnp.sum(f * probs[rows].mean(0)))
    np.testing.assert_allclose(stats["aux"], np.mean(per_row), rtol=1e-5)


@pytest.mark.parametrize("model", sorted(SPECS))
def test_the_layers_gradients_are_the_dense_computations(model):
    spec = SPECS[model]
    p, x = weights(spec, seed=3), jax.random.normal(jax.random.PRNGKey(4), (40, D))
    got = jax.grad(lambda p, x: jnp.sum(jnp.sin(moe.moe(p, x, spec, jnp.float32)[0])), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(dense_layer(p, x, spec)[0])), argnums=(0, 1))(p, x)
    for name in p:
        np.testing.assert_allclose(got[0][name], want[0][name], atol=5e-5 * float(jnp.abs(want[0][name]).max()), err_msg=name)
    np.testing.assert_allclose(got[1], want[1], atol=5e-5 * float(jnp.abs(want[1]).max()))


def test_each_model_reaches_the_layer_through_its_own_configuration():
    """Qwen3-Next's wrapper is the shared layer under its spec; the two specs differ where the models do."""
    spec = SPECS["qwen3_next"]
    c = qn.Qwen3NextConfig(num_experts=E, num_experts_per_tok=K, held_index=1, held_of=2)
    p, x = weights(spec), jax.random.normal(jax.random.PRNGKey(5), (16, D))
    np.testing.assert_array_equal(qn.moe(p, x, c, jnp.float32)[0], moe.moe(p, x, spec, jnp.float32)[0])
    assert (spec.normalize, spec.shared_gate, spec.aux_per_choice, spec.scale) == (True, True, False, 1.0)
    other = SPECS["deepseek_v2"]
    assert (other.normalize, other.shared_gate, other.aux_per_choice, other.scale) == (False, False, True, 1.5)
    with pytest.raises(ValueError, match="do not divide"):
        moe.MoESpec(10, 2, 0, 4).experts_held
