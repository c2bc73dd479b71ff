"""DreamerV3 tests: CLI dry runs over action types (reference
``tests/test_algos/test_algos.py`` dreamer_v3 cases) + numeric units for the
λ-return scan and the Moments percentile EMA."""

import os

import numpy as np
import pytest

from sheeprl_tpu import cli


TINY_WIDTHS = (
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4",
)


def dv3_args(tmp_path, extra=()):
    return [
        "dry_run=True",
        "env=dummy",
        "env.sync_env=True",
        "checkpoint.every=1000000",
        "metric.log_every=1000000",
        "metric.log_level=0",
        "env.capture_video=False",
        "buffer.memmap=False",
        "env.num_envs=2",
        f"root_dir={tmp_path}/logs",
        "run_name=test",
        "exp=dreamer_v3",
        "fabric.accelerator=cpu",
        "per_rank_batch_size=2",
        "per_rank_sequence_length=1",
        "algo.horizon=4",
        *TINY_WIDTHS,
        "algo.learning_starts=0",
        "cnn_keys.encoder=[rgb]",
        *extra,
    ]


@pytest.fixture(params=["1", "2"])
def devices(request):
    return request.param


@pytest.mark.parametrize(
    "env_id", ["discrete_dummy", "multidiscrete_dummy", "continuous_dummy"]
)
def test_dreamer_v3(tmp_path, devices, env_id, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cli.run(dv3_args(tmp_path, [f"fabric.devices={devices}", f"env.id={env_id}"]))


def test_dreamer_v3_bf16_mixed(tmp_path, monkeypatch):
    """fabric.precision=bf16-mixed trains end-to-end: bf16 compute, f32
    params/losses (heads cast back), finite losses."""
    monkeypatch.chdir(tmp_path)
    cli.run(
        dv3_args(
            tmp_path,
            ["fabric.devices=1", "env.id=discrete_dummy", "fabric.precision=bf16-mixed"],
        )
    )


def test_bf16_param_dtype_stays_f32():
    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config.engine import compose

    cfg = compose(
        "config",
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "metric.log_level=0",
            "fabric.precision=bf16-mixed",
            *TINY_WIDTHS,
            "cnn_keys.encoder=[rgb]",
        ],
    )
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    world_model, actor, critic, params = build_agent(
        cfg, (4,), False, obs_space, jax.random.PRNGKey(0)
    )
    # mixed precision: master params stay f32
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == jnp.float32
    # heads still emit f32 logits for the loss math
    out = actor.apply({"params": params["actor"]}, jnp.zeros((1, 4 * 4 + 8)))
    assert all(o.dtype == jnp.float32 for o in out)


def test_dreamer_v3_temporal_train(tmp_path, monkeypatch):
    """Non-dry run so the dynamic-learning scan sees T>1 sequences with real
    action conditioning (the dry run trains on T=1 reset-only steps)."""
    monkeypatch.chdir(tmp_path)
    cli.run(
        dv3_args(
            tmp_path,
            [
                "fabric.devices=1",
                "env.id=discrete_dummy",
                "dry_run=False",
                "total_steps=16",
                "per_rank_sequence_length=4",
                "buffer.size=128",
                "algo.learning_starts=8",
                "algo.train_every=4",
            ],
        )
    )


def test_dreamer_v3_device_ring_train(tmp_path, monkeypatch):
    """buffer.device_ring=True: batches are gathered from the device-resident
    replay mirror instead of staged from host per gradient step."""
    monkeypatch.chdir(tmp_path)
    cli.run(
        dv3_args(
            tmp_path,
            [
                "fabric.devices=1",
                "env.id=discrete_dummy",
                "dry_run=False",
                "total_steps=16",
                "per_rank_sequence_length=4",
                "buffer.size=128",
                "buffer.device_ring=True",
                "algo.learning_starts=8",
                "algo.train_every=4",
                "metric.fetch_train_metrics_every=0",
            ],
        )
    )


def test_dreamer_v3_checkpoint_resume(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cli.run(
        dv3_args(
            tmp_path,
            ["fabric.devices=1", "env.id=discrete_dummy", "checkpoint.every=1", "checkpoint.save_last=True"],
        )
    )
    import glob
    import os

    ckpts = glob.glob(f"{tmp_path}/logs/**/checkpoint/ckpt_*", recursive=True)
    assert ckpts, "no checkpoint written"
    cli.run(
        dv3_args(
            tmp_path,
            ["fabric.devices=1", "env.id=discrete_dummy", f"checkpoint.resume_from={os.path.abspath(ckpts[-1])}"],
        )
    )


def test_dreamer_v3_resume_with_buffer_checkpoint(tmp_path, monkeypatch):
    """buffer.checkpoint=True round-trip: the replay buffer is embedded in the
    checkpoint and restored on resume (reference callback.py:32-64)."""
    monkeypatch.chdir(tmp_path)
    cli.run(
        dv3_args(
            tmp_path,
            [
                "fabric.devices=1",
                "env.id=discrete_dummy",
                "checkpoint.every=1",
                "checkpoint.save_last=True",
                "buffer.checkpoint=True",
            ],
        )
    )
    import glob
    import os

    ckpts = glob.glob(f"{tmp_path}/logs/**/checkpoint/ckpt_*", recursive=True)
    assert ckpts, "no checkpoint written"
    cli.run(
        dv3_args(
            tmp_path,
            [
                "fabric.devices=1",
                "env.id=discrete_dummy",
                "buffer.checkpoint=True",
                f"checkpoint.resume_from={os.path.abspath(ckpts[-1])}",
            ],
        )
    )


def test_compute_lambda_values_matches_reference_recursion():
    from sheeprl_tpu.algos.dreamer_v3.utils import compute_lambda_values

    rng = np.random.default_rng(0)
    H, B = 7, 5
    rewards = rng.normal(size=(H, B, 1)).astype(np.float32)
    values = rng.normal(size=(H, B, 1)).astype(np.float32)
    continues = (rng.random(size=(H, B, 1)) > 0.1).astype(np.float32) * 0.997
    lmbda = 0.95

    # reference recursion (dreamer_v3/utils.py:70-81)
    vals = [values[-1:]]
    interm = rewards + continues * values * (1 - lmbda)
    for t in reversed(range(H)):
        vals.append(interm[t : t + 1] + continues[t : t + 1] * lmbda * vals[-1])
    expected = np.concatenate(list(reversed(vals))[:-1], axis=0)

    got = np.asarray(compute_lambda_values(rewards, values, continues, lmbda))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


def test_moments_percentile_ema():
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments, update_moments

    state = init_moments()
    x = jnp.asarray(np.linspace(-10.0, 10.0, 1001, dtype=np.float32))
    state, offset, invscale = update_moments(state, x, decay=0.0, max_=1.0)
    # decay 0 → pure percentiles of x; invscale = max(1/max, high-low)
    assert np.isclose(float(offset), -9.0, atol=0.1)
    assert np.isclose(float(invscale), 18.0, atol=0.2)
    # EMA accumulates with decay
    state2, offset2, _ = update_moments(state, x, decay=0.5, max_=1.0)
    assert np.isclose(float(offset2), 0.5 * float(offset) + 0.5 * (-9.0), atol=0.2)


def test_hafner_initialization_heads():
    import jax

    from sheeprl_tpu.algos.dreamer_v3.agent import (
        CRITIC_UNIFORM_HEADS,
        hafner_initialization,
    )

    params = {
        "Dense_0": {"kernel": np.ones((8, 16), np.float32), "bias": np.zeros(16, np.float32)},
        "head": {"kernel": np.ones((16, 255), np.float32), "bias": np.zeros(255, np.float32)},
    }
    out = hafner_initialization(params, jax.random.PRNGKey(0), CRITIC_UNIFORM_HEADS)
    # zero-scale head → exactly zero (reference uniform_init_weights(0.0))
    assert np.allclose(np.asarray(out["head"]["kernel"]), 0.0)
    # trunk re-initialized with truncated normal, bounded by 2σ
    k = np.asarray(out["Dense_0"]["kernel"])
    std = np.sqrt(1.0 / 12.0) / 0.87962566103423978
    assert np.abs(k).max() <= 2 * std + 1e-6
    assert k.std() > 0.1 * std


# -- the acting subset of the world model (agent.acting_params) -----------------


@pytest.fixture(scope="module")
def tiny_player():
    """A tiny DreamerV3 agent with a pixel and a vector key, as the benchmark's
    configuration has: its parameters, and every player function that takes
    world-model parameters as a call ``wm_params -> outputs`` (the state the
    stepping ones start from is made from the same ``wm_params``)."""
    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent, build_player_fns
    from sheeprl_tpu.config.engine import compose

    cfg = compose(
        "config",
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "metric.log_level=0",
            *TINY_WIDTHS,
            "cnn_keys.encoder=[rgb]",
            "mlp_keys.encoder=[state]",
        ],
    )
    obs_space = gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8),
            "state": gym.spaces.Box(-1, 1, (5,), np.float32),
        }
    )
    world_model, actor, _, params = build_agent(cfg, (4,), False, obs_space, jax.random.PRNGKey(0))
    fns = build_player_fns(world_model, actor, cfg, (4,), False)
    rng = np.random.default_rng(0)
    n_envs = 2
    obs = {
        "rgb": rng.integers(0, 255, (n_envs, 3, 64, 64)).astype(np.uint8),
        "state": rng.standard_normal((n_envs, 5)).astype(np.float32),
    }
    key, expl, mask = jax.random.PRNGKey(1), jnp.float32(0.3), jnp.asarray([[1.0], [0.0]])

    def init(wm):
        return fns["init_states"](wm, n_envs)

    def explore(wm):
        return fns["exploration_action_raw"](wm, params["actor"], init(wm), obs, key, expl)

    calls = {
        "init_states": init,
        "reset_states": lambda wm: fns["reset_states"](wm, explore(wm)[1], mask),
        "exploration_action_raw": explore,
        "greedy_action_raw": lambda wm: fns["greedy_action_raw"](wm, params["actor"], init(wm), obs, key),
    }
    return params, calls


@pytest.mark.parametrize(
    "fn_name", ["init_states", "reset_states", "exploration_action_raw", "greedy_action_raw"]
)
def test_acting_params_give_the_whole_trees_bits(tiny_player, fn_name):
    import jax

    from sheeprl_tpu.algos.dreamer_v3.agent import acting_params

    params, calls = tiny_player
    whole = jax.tree_util.tree_leaves(calls[fn_name](params["world_model"]))
    subset = jax.tree_util.tree_leaves(calls[fn_name](acting_params(params["world_model"])))
    assert len(whole) == len(subset) > 0
    for a, b in zip(whole, subset):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_acting_params_are_minimal_and_complete(tiny_player):
    """Removing a top-level key of the subset raises (nothing falls back), and
    every leaf of it is an input that acting's jaxpr reads: a ``WorldModel``
    method that acting starts to call outside the subset fails here (or in the
    test above) and does not act on leaves that are never refreshed."""
    import jax
    from flax.errors import ScopeParamNotFoundError
    from jax._src.interpreters import partial_eval as pe

    from sheeprl_tpu.algos.dreamer_v3.agent import acting_params

    params, calls = tiny_player
    subset = acting_params(params["world_model"])
    assert set(subset) < set(params["world_model"])
    for dropped in subset:
        rest = {k: v for k, v in subset.items() if k != dropped}
        with pytest.raises(ScopeParamNotFoundError):
            calls["reset_states"](rest)

    def leaves_read(wm_params):
        # init_states and a step, traced with the parameters as the only inputs
        jaxpr = jax.make_jaxpr(calls["exploration_action_raw"])(wm_params).jaxpr
        return pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))[1]

    assert all(leaves_read(subset))
    # and the selection drops something: of the whole tree, acting reads exactly the subset's leaves
    whole_read = leaves_read(params["world_model"])
    assert sum(whole_read) == len(jax.tree_util.tree_leaves(subset)) < len(whole_read)


def test_acting_params_bytes_at_the_benchmarks_widths():
    """At the ``dv3-XL`` shapes the refresh moves 432,006,468 bytes (the acting
    subset and the actor, 60 leaves) of the 822,933,076 (124 leaves) that the
    whole world model and the actor hold: PERF.md's prediction, held by a number."""
    import json
    import os

    import jax
    from flax.traverse_util import unflatten_dict

    from benchmarks.manifest import ROOT, load_module
    from sheeprl_tpu.algos.dreamer_v3.agent import acting_params

    with open(os.path.join(ROOT, "configs", "dv3-XL.json")) as f:
        config = json.load(f)
    shapes = load_module(os.path.join(ROOT, "configs", config["reference"])).param_shapes(config["sizes"])
    tree = unflatten_dict(
        {name: jax.ShapeDtypeStruct(tuple(shape), np.float32) for name, shape in shapes.items()}, sep="/"
    )

    def count(*trees):
        leaves = jax.tree_util.tree_leaves(trees)
        return len(leaves), sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves)

    assert count(tree["world_model"], tree["actor"]) == (124, 822_933_076)
    assert count(acting_params(tree["world_model"]), tree["actor"]) == (60, 432_006_468)


def _seeded_run(tmp_path, run_name, extra=()):
    """A few seeded updates of ``exp=dreamer_v3`` through ``cli.run``: the
    actions the policy took (``SHEEPRL_ACT_DUMP``) and the run's counters."""
    import json
    import pickle

    summary = tmp_path / f"{run_name}.telemetry.json"
    cli.run(
        dv3_args(
            tmp_path,
            [
                "fabric.devices=1",
                "env.id=discrete_dummy",
                "dry_run=False",
                "total_steps=40",
                "per_rank_sequence_length=4",
                "buffer.size=128",
                "buffer.prefetch=False",  # the synchronous sampling path, for a bitwise comparison
                "algo.learning_starts=8",
                "algo.train_every=8",
                "algo.run_test=False",
                f"run_name={run_name}",
                "metric.telemetry.enabled=true",
                f"metric.telemetry.summary_path={summary}",
                *extra,
            ],
        )
    )
    rows = []
    with open(os.environ["SHEEPRL_ACT_DUMP"], "rb") as f:
        while True:
            try:
                rows.append(pickle.load(f))
            except EOFError:
                break
    with open(summary) as f:
        counters = json.load(f)
    # the first row is the reset observation, every other one a policy step
    return np.stack([row["actions"] for row in rows[1:]]), counters


def test_dreamer_v3_acts_on_the_mirrored_subset(tmp_path, monkeypatch):
    """With the host mirror switched on (on the CPU it is off, so no other
    tier-1 test acts on mirrored parameters) the entrypoint hands it the acting
    subset and the actor, a refresh moves their bytes and no more, and the
    policy's actions are those of the same seeded run whose mirror is handed
    the whole tree."""
    import jax

    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import acting_params
    from sheeprl_tpu.utils.host import HostParamMirror

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SHEEPRL_ACT_DUMP", str(tmp_path / "actions.pkl"))  # a run truncates it first
    monkeypatch.setattr(HostParamMirror, "enabled_for", staticmethod(lambda fabric, cfg: True))
    built, handed = {}, []
    build_agent, mirror_call = dv3.build_agent, HostParamMirror.__call__

    def keeping_build_agent(*args, **kwargs):
        out = build_agent(*args, **kwargs)
        built["params"] = out[3]
        return out

    def recording_call(mirror, tree):
        handed.append(tuple(sorted(tree)))
        return mirror_call(mirror, tree)

    monkeypatch.setattr(dv3, "build_agent", keeping_build_agent)
    monkeypatch.setattr(HostParamMirror, "__call__", recording_call)

    def nbytes(*trees):
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(trees))

    actions, counters = _seeded_run(tmp_path, "subset")
    params = built["params"]
    per_burst = nbytes(acting_params(params["world_model"]), params["actor"])
    assert per_burst < nbytes(params["world_model"])
    assert set(handed) == {("encoder", "rssm"), tuple(sorted(params["actor"]))}
    refreshes = counters["publish_refreshes"]
    # one refresh of each mirror at start-up and after every burst
    assert refreshes == len(handed) >= 6 and refreshes % 2 == 0
    assert counters["publish_bytes"] * 2 == per_burst * refreshes
    # every refresh landed in the mirror's reused memory and was aliased: the
    # loop lets go of a snapshot before its landing set's turn comes again
    assert counters["publish_copied_leaves"] == 0
    assert len(actions) >= 16

    handed.clear()
    monkeypatch.setattr(dv3, "acting_params", lambda wm_params: wm_params)
    whole_actions, whole_counters = _seeded_run(tmp_path, "whole")
    assert tuple(sorted(params["world_model"])) in handed
    assert whole_counters["publish_bytes"] * 2 == nbytes(params["world_model"], params["actor"]) * refreshes
    np.testing.assert_array_equal(actions, whole_actions)


@pytest.mark.parametrize(
    "mesh",
    [
        ["fabric.devices=1"],
        # the leaves split over two model shards: acting gathers them either way
        # (a world of two: the step counts doubled, so that the run has the same updates)
        ["fabric.devices=2", "parallel.model_axis=2", "parallel.shard_min_bytes=0",
         "total_steps=80", "algo.learning_starts=16", "algo.train_every=16"],
    ],
    ids=["one_device", "model_axis_2"],
)
def test_dreamer_v3_acts_alike_on_the_mirror_and_on_the_trained_leaves(tmp_path, monkeypatch, mesh):
    """The two ways acting is handed its parameters, through ``cli.run`` on the
    same seed: the trained leaves themselves (mirror off, what the CPU runs by
    default) and the host mirror's snapshot of them (forced on) give the same
    actions bit for bit."""
    from sheeprl_tpu.utils.host import HostParamMirror

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SHEEPRL_ACT_DUMP", str(tmp_path / "actions.pkl"))  # a run truncates it first
    leaf_actions, leaf_counters = _seeded_run(tmp_path, "leaves", mesh)
    assert leaf_counters["publish_refreshes"] == 0 and len(leaf_actions) >= 16
    monkeypatch.setattr(HostParamMirror, "enabled_for", staticmethod(lambda fabric, cfg: True))
    mirror_actions, mirror_counters = _seeded_run(tmp_path, "mirror", mesh)
    assert mirror_counters["publish_refreshes"] >= 6
    np.testing.assert_array_equal(leaf_actions, mirror_actions)


@pytest.mark.parametrize("sharded", [False, True], ids=["replicated", "model_axis_2"])
def test_the_train_burst_returns_the_state_and_the_metrics_and_nothing_of_the_parameters_size(sharded):
    """Both builds of the DreamerV3 train program (``shard_map`` on the data
    mesh, GSPMD under a sharding plan): ``.burst`` returns ``(state, metrics)``,
    the state in the shapes it came in, and no metric is as large as the
    smallest parameter matrix: acting reads the state's own leaves, so the
    program has no second parameter-sized output."""
    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers_and_state, build_train_fn
    from sheeprl_tpu.config.engine import compose
    from sheeprl_tpu.fabric import Fabric

    cfg = compose(
        "config",
        overrides=[
            "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "per_rank_batch_size=2",
            "per_rank_sequence_length=4", "algo.horizon=3", *TINY_WIDTHS, "cnn_keys.encoder=[rgb]",
            "metric.log_level=0",
        ],
    )
    fabric = (
        Fabric(devices=2, accelerator="cpu", model_axis=2, shard_min_bytes=0)
        if sharded
        else Fabric(devices=1, accelerator="cpu")
    )
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    world_model, actor, critic, params = build_agent(cfg, (4,), False, obs_space, jax.random.PRNGKey(0))
    world_tx, actor_tx, critic_tx, agent_state = build_optimizers_and_state(cfg, params)
    plan = fabric.shard_plan(agent_state)
    assert (plan is not None) == sharded
    train_fn = build_train_fn(
        world_model, actor, critic, world_tx, actor_tx, critic_tx, cfg, fabric, (4,), False, plan=plan
    )
    n, T, B = 2, 4, 2
    stack = {
        "rgb": jnp.zeros((n, T, B, 3, 64, 64), jnp.uint8),
        "actions": jnp.zeros((n, T, B, 4), jnp.float32),
        "rewards": jnp.zeros((n, T, B, 1), jnp.float32),
        "dones": jnp.zeros((n, T, B, 1), jnp.float32),
        "is_first": jnp.zeros((n, T, B, 1), jnp.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(1), n)
    out = jax.eval_shape(train_fn.burst, agent_state, stack, np.int32(0), np.int32(n), keys, jnp.zeros((n,), jnp.float32))
    assert isinstance(out, tuple) and len(out) == 2
    state, metrics = out

    def shapes(tree):
        return jax.tree_util.tree_map(lambda x: (tuple(x.shape), x.dtype), tree)

    assert shapes(state) == shapes(agent_state)
    smallest_matrix = min(x.size for x in jax.tree_util.tree_leaves(params) if x.ndim >= 2)
    assert "Loss/world_model_loss" in metrics
    assert all(x.size < smallest_matrix for x in jax.tree_util.tree_leaves(metrics))


@pytest.mark.parametrize("entrypoint", ["dreamer_v3", "p2e_dv3_exploration", "p2e_dv3_finetuning"])
def test_the_acting_callback_asks_nothing_of_the_device_that_runs_it(entrypoint, tmp_path, monkeypatch):
    """With the mirror off (the DreamerV3 family's default), acting's program runs
    on the device that holds the trained leaves and waits there for its host
    callback: a callback that asked that device for the fresh player state would
    wait for the program that waits for it (on the chip a hang at the first
    episode's end after a burst; the CPU backend lets it through). So that state
    is made before a rollout, never inside one."""
    import glob
    import importlib

    from sheeprl_tpu.envs.rollout import BurstActor

    module = importlib.import_module(
        "sheeprl_tpu.algos.dreamer_v3.dreamer_v3" if entrypoint == "dreamer_v3" else f"sheeprl_tpu.algos.p2e_dv3.{entrypoint}"
    )
    seen = {"in_rollout": False, "rollouts": 0, "made": 0, "made_in_rollout": 0}
    rollout, build_player_fns = BurstActor.rollout, module.build_player_fns

    def flagged_rollout(actor, *args, **kwargs):
        seen["in_rollout"] = True
        seen["rollouts"] += 1
        try:
            return rollout(actor, *args, **kwargs)
        finally:
            seen["in_rollout"] = False

    def watched_player_fns(*args, **kwargs):
        fns = build_player_fns(*args, **kwargs)

        def init_states(wm_params, n_envs):
            seen["made"] += 1
            seen["made_in_rollout"] += seen["in_rollout"]
            return fns["init_states"](wm_params, n_envs)

        return {**fns, "init_states": init_states}

    def args(exp, extra):
        ensembles = ["algo.ensembles.n=3"] if exp.startswith("p2e") else []
        base = [a for a in dv3_args(tmp_path, extra) if a != "exp=dreamer_v3"]
        return base + [f"exp={exp}", "fabric.devices=1", "env.id=discrete_dummy", *ensembles]

    monkeypatch.chdir(tmp_path)
    extra = []
    if entrypoint == "p2e_dv3_finetuning":
        cli.run(args("p2e_dv3_exploration", ["checkpoint.every=1", "checkpoint.save_last=True"]))
        ckpts = glob.glob(f"{tmp_path}/logs/**/checkpoint/ckpt_*", recursive=True)
        extra = [f"checkpoint.exploration_ckpt_path={os.path.abspath(ckpts[-1])}"]
    monkeypatch.setattr(BurstActor, "rollout", flagged_rollout)
    monkeypatch.setattr(module, "build_player_fns", watched_player_fns)
    cli.run(
        args(
            entrypoint,
            [
                "dry_run=False", "total_steps=40", "per_rank_sequence_length=4", "buffer.size=128",
                "algo.learning_starts=8", "algo.train_every=8", "algo.run_test=False", "run_name=fresh", *extra,
            ],
        )
    )
    # the dummy env's episodes last five steps, so episodes end inside these rollouts;
    # one fresh state at start-up and one for each burst's parameters
    assert seen["rollouts"] >= 8 and seen["made"] >= 4
    assert seen["made_in_rollout"] == 0
