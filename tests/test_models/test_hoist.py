"""``scan_hoisting_dense_grads`` is ``jax.lax.scan`` to the caller: the same
values and the same gradients — of the hoisted kernels, of every other weight
the body closes over, of the scanned inputs and of the initial carry — with
the kernels' gradients made by one product after the backward loop."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.models.hoist import scan_hoisting_dense_grads
from sheeprl_tpu.models.models import MLP, LayerNormGRUCell

T, B, X, H, O = 6, 4, 7, 12, 5


class Cell(nn.Module):
    """A recurrent step shaped like the RSSM's: resets, a pre-layer, the
    LayerNorm GRU and a head, the compute dtype that of ``bf16-mixed``."""

    dtype: object = None

    @nn.compact
    def __call__(self, x, h, first):
        h = (1.0 - first) * h
        feat = MLP(hidden_sizes=[8], activation="silu", layer_norm=True, bias=False, dtype=self.dtype)(x)
        h = LayerNormGRUCell(H, bias=False, layer_norm=True, dtype=self.dtype, name="gru")(feat, h)
        h = h.astype(jnp.float32)
        return h, nn.Dense(O, dtype=self.dtype, name="head")(h).astype(jnp.float32)


def plain_scan(step, params, consts, init, xs):
    return jax.lax.scan(lambda carry, x: step(params, consts, carry, x), init, xs)


def gradients(scan, dtype):
    cell = Cell(dtype=dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    xs = jax.random.normal(keys[0], (T, B, X))
    # resets inside the window, none of them in the first row only
    first = (jax.random.uniform(keys[1], (T, B, 1)) < 0.3).astype(jnp.float32).at[3, 1].set(1.0)
    h0 = jax.random.normal(keys[2], (B, H))
    params = cell.init(keys[3], xs[0], h0, first[0])["params"]
    shift = 0.1 * jax.random.normal(keys[4], (X,))

    def step(params, shift, h, inp):
        x, f = inp
        return cell.apply({"params": params}, x + shift, h, f)

    def loss(params, shift, h0, xs):
        h, outs = scan(step, params, shift, h0, (xs, first))
        return jnp.sum(outs**2) + jnp.sum(h * jnp.arange(H))

    value, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(params, shift, h0, xs)
    p = grads[0]
    return value, {
        "hoisted_kernels": (p["gru"]["Dense_0"]["kernel"], p["MLP_0"]["Dense_0"]["kernel"], p["head"]["kernel"]),
        "other_closed_over_weights": (p["gru"]["LayerNorm_0"], p["MLP_0"]["LayerNorm_0"], p["head"]["bias"], grads[1]),
        "initial_carry": grads[2],
        "scanned_inputs": grads[3],
    }


@pytest.fixture(scope="module")
def float32_pair():
    return gradients(plain_scan, None), gradients(scan_hoisting_dense_grads, None)


@pytest.fixture(scope="module")
def bfloat16_pair():
    return gradients(plain_scan, jnp.bfloat16), gradients(scan_hoisting_dense_grads, jnp.bfloat16)


def worst_gap(got, want):
    """Largest ``|got - want|`` of a leaf over the largest ``|want|`` of that leaf."""
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), got, want
    )
    return max(jax.tree_util.tree_leaves(gaps))


WHAT = ("hoisted_kernels", "other_closed_over_weights", "scanned_inputs", "initial_carry")


@pytest.mark.parametrize("what", WHAT)
def test_gradients_equal_the_plain_scans_in_float32(float32_pair, what):
    (plain_value, plain), (value, hoisted) = float32_pair
    assert abs(float(value) - float(plain_value)) <= 1e-6 * abs(float(plain_value))
    assert worst_gap(hoisted[what], plain[what]) < 1e-5


@pytest.mark.parametrize("what", WHAT)
def test_bf16_gradients_are_as_close_to_float32_as_the_per_step_products(float32_pair, bfloat16_pair, what):
    (_, reference), _ = float32_pair
    (_, plain), (_, hoisted) = bfloat16_pair
    # the per-step form rounds each step's product to bf16 before adding it up
    # in float32; one product accumulated in float32 may not be further off
    assert worst_gap(hoisted[what], reference[what]) <= 1.5 * worst_gap(plain[what], reference[what])


def test_a_dense_applied_twice_a_step_keeps_its_gradient_in_the_loop():
    class Twice(nn.Module):
        @nn.compact
        def __call__(self, h, x):
            shared = nn.Dense(H, name="shared")
            h = jnp.tanh(shared(h) + nn.Dense(H, name="once")(x))
            return shared(h)

    cell = Twice()
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    xs, h0 = jax.random.normal(keys[0], (T, B, X)), jax.random.normal(keys[1], (B, H))
    params = cell.init(keys[2], h0, xs[0])["params"]

    def step(params, _, h, x):
        h = cell.apply({"params": params}, h, x)
        return h, h

    def loss(scan, params, h0, xs):
        return jnp.sum(scan(step, params, None, h0, xs)[1] ** 2)

    def grads_with(scan):
        return jax.grad(lambda *args: loss(scan, *args), argnums=(0, 1, 2))

    want = grads_with(plain_scan)(params, h0, xs)
    assert worst_gap(grads_with(scan_hoisting_dense_grads)(params, h0, xs), want) < 1e-5
    # "once" left the loop, "shared" did not: the backward scan still carries
    # a cotangent of shared's kernel shape and none of once's
    carried = backward_scan_carries(jax.make_jaxpr(grads_with(scan_hoisting_dense_grads))(params, h0, xs).jaxpr)
    assert (H, H) in carried and (X, H) not in carried
    assert (X, H) in backward_scan_carries(jax.make_jaxpr(grads_with(plain_scan))(params, h0, xs).jaxpr)


def backward_scan_carries(jaxpr):
    """Shapes the carries of the transposed scans in ``jaxpr`` have."""
    shapes = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and "transpose" in str(eqn.source_info.name_stack):
            first = eqn.params["num_consts"]
            shapes.update(v.aval.shape for v in eqn.invars[first : first + eqn.params["num_carry"]])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes |= backward_scan_carries(sub)
    return shapes
