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


@pytest.fixture(scope="module")
def lowered_burst():
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
    n, T, B = 2, 4, 2
    stack = {
        "rgb": jnp.zeros((n, T, B, 3, 64, 64), jnp.uint8),
        "actions": jnp.zeros((n, T, B, 4), jnp.float32),
        "rewards": jnp.zeros((n, T, B, 1), jnp.float32),
        "dones": jnp.zeros((n, T, B, 1), jnp.float32),
        "is_first": jnp.zeros((n, T, B, 1), jnp.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(1), n)
    lowered = train_fn.burst.lower(agent_state, stack, np.int32(0), np.int32(n), keys, jnp.zeros((n,), jnp.float32))
    return lowered.as_text(debug_info=True)


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
