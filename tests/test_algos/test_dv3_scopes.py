"""The DreamerV3 train program names its parts: every ``dv3/<part>`` scope of
``build_train_fn`` is in the lowered burst, forward and backward, so a device
profile can be split by part (``benchmarks/scopes.py`` reads them)."""

import re

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

PARTS = ("encoder", "rssm", "heads", "imagination", "behavior", "optimizer")


N, T, B = 2, 4, 2


def tiny_train_fn():
    """DreamerV3's train program at tiny widths, with its agent state."""
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers_and_state, build_train_fn
    from sheeprl_tpu.config.engine import compose
    from sheeprl_tpu.fabric import Fabric

    cfg = compose(
        "config",
        overrides=[
            "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "per_rank_batch_size=2",
            "per_rank_sequence_length=4", "algo.horizon=3", "algo.dense_units=8", "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4",
            "cnn_keys.encoder=[rgb]", "metric.log_level=0",
        ],
    )
    fabric = Fabric(devices=1, accelerator="cpu")
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    world_model, actor, critic, params = build_agent(cfg, (4,), False, obs_space, jax.random.PRNGKey(0))
    world_tx, actor_tx, critic_tx, agent_state = build_optimizers_and_state(cfg, params)
    train_fn = build_train_fn(world_model, actor, critic, world_tx, actor_tx, critic_tx, cfg, fabric, (4,), False)
    return train_fn, agent_state


def burst_args(agent_state):
    stack = {
        "rgb": jnp.zeros((N, T, B, 3, 64, 64), jnp.uint8),
        "actions": jnp.zeros((N, T, B, 4), jnp.float32),
        "rewards": jnp.zeros((N, T, B, 1), jnp.float32),
        "dones": jnp.zeros((N, T, B, 1), jnp.float32),
        "is_first": jnp.zeros((N, T, B, 1), jnp.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(1), N)
    return agent_state, stack, np.int32(0), np.int32(N), keys, jnp.zeros((N,), jnp.float32)


@pytest.fixture(scope="module")
def lowered_burst():
    train_fn, agent_state = tiny_train_fn()
    return train_fn.burst.lower(*burst_args(agent_state)).as_text(debug_info=True)


@pytest.mark.parametrize("part", PARTS)
def test_the_lowered_burst_names_the_part_forward_and_backward(lowered_burst, part):
    stacks = set(re.findall(r'loc\("(jit\(local_burst\)[^"]*)"', lowered_burst))
    mine = {s for s in stacks if re.search(rf"[(/]dv3/{part}[)/]", s)}
    assert mine, f"no operation under dv3/{part}"
    # every one sits in the burst's own loop, under this one scope
    assert all(s.startswith("jit(local_burst)/while/body/") and s.count("dv3/") == 1 for s in mine)
    if part in ("encoder", "rssm", "heads", "behavior"):
        # backward operations inherit the scope, so both passes of a part land together
        assert any(f"transpose(jvp(dv3/{part}))" in s for s in mine)


# ---------------------------------------------------------------------------
# the dynamic-learning scan makes its Dense kernels' gradients after its
# backward loop (models/hoist.py): same gradients, another program structure
# ---------------------------------------------------------------------------


def plain_scan(step, params, consts, init, xs):
    """What ``wm_loss_fn`` called before the hoist: ``jax.lax.scan`` itself."""
    return jax.lax.scan(lambda carry, x: step(params, consts, carry, x), init, xs)


def world_model_step(monkeypatch, scan):
    """One gradient step on a batch with resets inside the window; returns the
    world model's loss, its gradient norm and Adam's first moment (0.1 x the
    clipped gradient after one step: the whole gradient tree)."""
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3

    if scan is not None:
        monkeypatch.setattr(dreamer_v3, "scan_hoisting_dense_grads", scan)
    train_fn, agent_state = tiny_train_fn()
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    data = {
        "rgb": jax.random.randint(keys[0], (T, B, 3, 64, 64), 0, 256).astype(jnp.uint8),
        "actions": jax.nn.one_hot(jax.random.randint(keys[1], (T, B), 0, 4), 4),
        "rewards": jax.random.normal(keys[2], (T, B, 1)),
        "dones": jnp.zeros((T, B, 1)).at[1, 0].set(1.0),
        "is_first": jnp.zeros((T, B, 1)).at[2, 0].set(1.0),
    }
    state, metrics = train_fn(agent_state, data, keys[3], jnp.float32(0.0))
    moments = [leaf for leaf in jax.tree_util.tree_leaves_with_path(state["opt"]["world_model"]) if ".mu" in jax.tree_util.keystr(leaf[0])]
    return float(metrics["Loss/world_model_loss"]), float(metrics["Grads/world_model"]), moments


def test_the_world_models_gradient_tree_is_the_plain_scans(monkeypatch):
    loss, norm, moments = world_model_step(monkeypatch, None)
    plain_loss, plain_norm, plain_moments = world_model_step(monkeypatch, plain_scan)
    assert abs(loss - plain_loss) <= 1e-6 * abs(plain_loss)
    assert abs(norm - plain_norm) <= 1e-5 * plain_norm
    assert len(moments) == len(plain_moments) > 40
    largest = max(float(jnp.max(jnp.abs(leaf))) for _, leaf in plain_moments)
    for (path, got), (_, want) in zip(moments, plain_moments):
        # to 1e-5 of the leaf, and of the tree for a leaf whose gradient is rounding
        tolerance = 1e-5 * max(float(jnp.max(jnp.abs(want))), 1e-3 * largest)
        assert float(jnp.max(jnp.abs(got - want))) <= tolerance, jax.tree_util.keystr(path)


def walk(jaxpr, loops=0):
    """Every equation of ``jaxpr`` and its sub-programs, with how many loops it sits in."""
    for eqn in jaxpr.eqns:
        yield eqn, loops
        inner = loops + (eqn.primitive.name in ("scan", "while"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk(sub, inner)


def test_the_gru_kernels_gradient_is_one_product_outside_the_backward_loop():
    train_fn, agent_state = tiny_train_fn()
    rssm = agent_state["params"]["world_model"]["rssm"]
    kernel = rssm["recurrent_model"]["gru"]["Dense_0"]["kernel"].shape
    same_shape = [leaf for leaf in jax.tree_util.tree_leaves(agent_state["params"]["world_model"]) if leaf.shape == kernel]
    assert len(same_shape) == 1, "pick widths at which the GRU kernel's shape is its own"
    backward = "transpose(jvp(dv3/rssm))"
    carried, products = [], []
    for eqn, loops in walk(jax.make_jaxpr(train_fn.burst)(*burst_args(agent_state)).jaxpr):
        if backward not in str(eqn.source_info.name_stack):
            continue
        if eqn.primitive.name == "scan":
            first = eqn.params["num_consts"]
            carried += [v.aval.shape for v in eqn.invars[first : first + eqn.params["num_carry"]]]
        if eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape == kernel:
            products.append(loops)
    assert carried, "the dynamic-learning scan has a backward loop"
    # no cotangent of the kernel's shape rides the loop's carry ...
    assert kernel not in carried
    # ... and one product makes it, inside the burst's own loop and no other
    assert products == [1]
