"""The third kernel family: the chunked gated delta rule against its reference
tier (the recurrence, one token at a time), values and gradients, with resets
inside chunks; and the states it hands over at chunk boundaries."""

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
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_tier_gives_the_recurrences_values(case):
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


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_tier_gives_the_recurrences_gradients(case):
    c = CASES[case]
    args, reset = inputs(c["B"], c["T"], c["H"], c["dk"], c["dv"], 4, c["resets"])
    ref = jax.grad(lambda *a: jnp.sum(jnp.sin(delta_rule.recurrent(*a, reset)[0])), argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(delta_rule.chunked(*a, reset, chunk=c["chunk"])[0])),
                   argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, ref):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * max(float(jnp.abs(b).max()), 1.0)


def test_a_window_that_is_no_multiple_of_the_chunk_is_refused():
    args, reset = inputs(1, 40, 1, 8, 8, 0, ())
    with pytest.raises(ValueError, match="multiple of the chunk"):
        delta_rule.chunked(*args, reset, chunk=16)
