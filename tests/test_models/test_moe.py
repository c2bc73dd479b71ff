"""The shared expert layer (``models/moe.py``) against a dense computation of
the same layer, written expert by expert: outputs, gradients and the balance
term, as each of the three sequence cores configures it (Qwen3-Next: the chosen
weights renormalised, a gated shared expert; DeepSeek-V2: the weights as they
are times a factor, always-on shared experts, the term per choice; LFM2-MoE: a
sigmoid of every expert, the choice by score plus a bias the weights never see,
no shared expert, the bias moved by the balance step)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import moe
from sheeprl_tpu.models import deepseek_v2 as ds
from sheeprl_tpu.models import lfm2_moe as lf
from sheeprl_tpu.models import qwen3_next as qn

D, F, E, K = 24, 16, 8, 3
SPECS = {
    "qwen3_next": qn.Qwen3NextConfig(num_experts=E, num_experts_per_tok=K, held_index=1, held_of=2).moe_spec,
    "deepseek_v2": ds.DeepseekV2Config(n_routed_experts=E, num_experts_per_tok=K, held_index=1, held_of=2,
                                       routed_scaling_factor=1.5).moe_spec,
    "lfm2_moe": lf.Lfm2MoeConfig(num_experts=E, num_experts_per_tok=K, held_index=1, held_of=2).moe_spec,
}


def weights(spec, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    Eh = spec.experts_held
    p = {"router": jax.random.normal(keys[0], (D, E)), "gate": jax.random.normal(keys[1], (Eh, D, F)) * 0.3,
         "up": jax.random.normal(keys[2], (Eh, D, F)) * 0.3, "down": jax.random.normal(keys[3], (Eh, F, D)) * 0.3,
         "shared_gate": jax.random.normal(keys[4], (D, F)) * 0.3, "shared_up": jax.random.normal(keys[5], (D, F)) * 0.3,
         "shared_down": jax.random.normal(keys[6], (F, D)) * 0.3}
    if not spec.shared:
        p = {k: v for k, v in p.items() if not k.startswith("shared")}
    elif spec.shared_gate:
        p["shared_router"] = jax.random.normal(keys[7], (D, 1))
    if spec.select_bias:
        p["expert_bias"] = 0.3 * jax.random.normal(keys[7], (E,))
    return p


def dense_layer(p, x, spec):
    """Every token through every held expert, one expert at a time."""
    if spec.score == "sigmoid":
        probs = jax.nn.sigmoid(x @ p["router"])
        _, top_i = jax.lax.top_k(probs + p["expert_bias"], spec.num_experts_per_tok)
        top_p = jnp.take_along_axis(probs, top_i, -1)
    else:
        probs = jax.nn.softmax(x @ p["router"], -1)
        top_p, top_i = jax.lax.top_k(probs, spec.num_experts_per_tok)
    if spec.normalize:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + spec.normalize_eps)
    top_p = top_p * spec.scale
    out = 0.0
    for local in range(spec.experts_held):
        weight = jnp.sum(jnp.where(top_i == spec.held_index * spec.experts_held + local, top_p, 0.0), -1)
        y = (jax.nn.silu(x @ p["gate"][local]) * (x @ p["up"][local])) @ p["down"][local]
        out = out + weight[:, None] * y
    if not spec.shared:
        return out, probs / probs.sum(-1, keepdims=True), top_i
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
    # the load of all the router's outputs, held here or not: the balance step's input
    np.testing.assert_array_equal(stats["load"], jnp.zeros((E,)).at[top_i.reshape(-1)].add(1.0)[None])


@pytest.mark.parametrize("model", sorted(SPECS))
def test_the_layers_gradients_are_the_dense_computations(model):
    spec = SPECS[model]
    p, x = weights(spec, seed=3), jax.random.normal(jax.random.PRNGKey(4), (40, D))
    got = jax.grad(lambda p, x: jnp.sum(jnp.sin(moe.moe(p, x, spec, jnp.float32)[0])), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(dense_layer(p, x, spec)[0])), argnums=(0, 1))(p, x)
    for name in p:
        if name == "expert_bias":  # it decides who is chosen and nothing else: no gradient reaches it
            assert float(jnp.abs(got[0][name]).max()) == 0 == float(jnp.abs(want[0][name]).max())
            continue
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


def test_the_sigmoid_router_chooses_by_score_plus_bias_and_weighs_by_score_alone():
    spec = SPECS["lfm2_moe"]
    assert (spec.score, spec.select_bias, spec.shared, spec.normalize, spec.normalize_eps) == ("sigmoid", True, False, True, 1e-6)
    p, x = weights(spec), jax.random.normal(jax.random.PRNGKey(6), (64, D))
    scores, top_p, top_i = moe.route(p, x, spec, jnp.float32)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(x @ p["router"]), rtol=1e-6)
    np.testing.assert_array_equal(top_i, jax.lax.top_k(scores + p["expert_bias"], K)[1])
    chosen = jnp.take_along_axis(scores, top_i, -1)
    np.testing.assert_allclose(top_p, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # a bias that changes the choice and not the weights' formula: another bias, other experts, weights still the scores'
    other = {**p, "expert_bias": -p["expert_bias"]}
    _, other_p, other_i = moe.route(other, x, spec, jnp.float32)
    assert float(jnp.mean(jnp.any(jnp.sort(other_i, -1) != jnp.sort(top_i, -1), -1))) > 0.3
    picked = jnp.take_along_axis(scores, other_i, -1)
    np.testing.assert_allclose(other_p, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # with the bias at zero the choice is the scores' own
    plain = {**p, "expert_bias": jnp.zeros((E,))}
    np.testing.assert_array_equal(moe.route(plain, x, spec, jnp.float32)[2], jax.lax.top_k(scores, K)[1])
    # no shared expert: a token none of whose experts is held gets nothing
    out, _ = moe.moe(p, x, spec, jnp.float32)
    held = (top_i >= spec.held_index * spec.experts_held) & (top_i < (spec.held_index + 1) * spec.experts_held)
    assert bool(jnp.any(~held.any(-1))) and float(jnp.abs(out[~held.any(-1)]).max()) == 0


def test_the_sigmoid_routers_shares_add_up_to_the_uncut_layer():
    """The guide's share test: four shares of two experts, each told its own,
    give parts that add up to the layer with all eight held; nothing is shared,
    so nothing is counted twice, and every share reports the whole router's load."""
    whole = lf.Lfm2MoeConfig(num_experts=E, num_experts_per_tok=K).moe_spec
    p, x = weights(whole, seed=5), jax.random.normal(jax.random.PRNGKey(7), (48, D))
    want, stats = moe.moe(p, x, whole, jnp.float32)
    total = 0.0
    for index in range(4):
        spec = lf.Lfm2MoeConfig(num_experts=E, num_experts_per_tok=K, held_index=index, held_of=4).moe_spec
        share = {**p, **{name: p[name][2 * index : 2 * index + 2] for name in ("gate", "up", "down")}}
        part, part_stats = moe.moe(share, x, spec, jnp.float32)
        np.testing.assert_array_equal(part_stats["load"], stats["load"])
        assert float(part_stats["dropped_pairs"]) == 0
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(want, dense_layer(p, x, whole)[0], atol=2e-5 * float(jnp.abs(want).max()))


def test_the_balance_step_against_a_hand_count():
    """Eight experts, 24 choices: mean load 3. Under it the bias rises by the
    rate, over it it falls, at it it stands; the distance does not matter."""
    load = jnp.asarray([[0.0, 3.0, 9.0, 3.0, 1.0, 2.0, 4.0, 2.0]])
    bias = jnp.asarray([[0.5, -0.25, 0.0, 0.125, 0.0, 0.0, -1.0, 0.25]])
    got = moe.balance_step(bias, load, 0.001)
    np.testing.assert_allclose(got, bias + jnp.asarray([[1, 0, -1, 0, 1, 1, -1, 1]]) * 0.001, rtol=0, atol=1e-9)
    # layer by layer: each row against its own mean
    two = moe.balance_step(jnp.zeros((2, 4)), jnp.asarray([[4.0, 0.0, 2.0, 2.0], [1.0, 1.0, 1.0, 5.0]]), 0.5)
    np.testing.assert_array_equal(two, jnp.asarray([[-0.5, 0.5, 0.0, 0.0], [0.5, 0.5, 0.5, -0.5]]))
    # a routed layer under repeated steps: the largest load falls towards the mean
    spec = SPECS["lfm2_moe"]
    p, x = weights(spec, seed=8), jax.random.normal(jax.random.PRNGKey(9), (512, D)) + 1.0
    load_of = jax.jit(lambda p: moe.moe(p, x, spec, jnp.float32)[1]["load"][0])
    largest = []
    for _ in range(400):
        load = load_of(p)
        largest.append(float(load.max()))
        p = {**p, "expert_bias": moe.balance_step(p["expert_bias"], load, 0.005)}
    # whatever the seeded router: well over the mean of 192 at first, a good deal nearer it at the end (the sign
    # rule hovers about an even load, and where the tokens are much alike it swings whole groups of them)
    assert largest[0] > 1.4 * 512 * K / E and np.mean(largest[-50:]) < 0.75 * largest[0]
