"""On-device rollout engine tests (``sheeprl_tpu/envs/rollout``).

- the native pure-JAX env dynamics are **bitwise ports** of the gymnasium
  classic-control envs (stepped side by side from the same physical state);
- jitted-scan collection (tier a) is seeded-bitwise the sync host loop:
  same keys → same actions/obs/rewards, and the device-ring contents match
  a host-side replay of the same burst;
- the in-jit ``scatter_append`` wraps the ring correctly at the capacity
  edge, matching what per-row host adds would have produced;
- burst acting (tier b) with K>1 is bitwise K=1 at the BurstActor level
  (same trajectories into the same replay buffer) and at the SAC
  entrypoint level (identical checkpointed buffer shards);
- one SAC end-to-end CPU run with ``env.backend=jax`` lands the rollout
  telemetry counters (``rollout_bursts``/``act_dispatches``/
  ``env_steps_jax``) in telemetry.json.
"""

import glob
import json
import os
import threading

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_ring import DeviceRingTransitions, scatter_append
from sheeprl_tpu.envs.rollout import (
    BurstActor,
    DeviceActor,
    JaxCartPole,
    JaxPendulum,
    JaxRolloutEngine,
    make_jax_env,
)


# -- native env parity with gymnasium -----------------------------------------


def test_jax_cartpole_matches_gymnasium():
    """Step the pure-JAX CartPole and gymnasium's from the same physical
    state with the same action sequence: identical obs/reward/termination."""
    env = JaxCartPole()
    genv = gym.make("CartPole-v1")
    state, obs = env.reset(jax.random.PRNGKey(3))
    genv.reset(seed=0)
    genv.unwrapped.state = np.asarray(obs, np.float64)
    terminated = False
    for t in range(200):
        a = t % 2
        state, obs, rew, term, trunc = env.step(state, jnp.int32(a), jax.random.PRNGKey(t))
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(obs), gobs, atol=1e-5)
        assert float(rew) == float(grew) == 1.0
        assert bool(term) == bool(gterm)
        if term or trunc:
            terminated = True
            break
    assert terminated, "the alternating-action episode must terminate"


def test_jax_pendulum_matches_gymnasium():
    env = JaxPendulum()
    genv = gym.make("Pendulum-v1")
    # the start state is pinned to jax's own key implementation: a Fabric built
    # earlier in the process (any cli.run) leaves ``jax_default_prng_impl`` at
    # rbg, whose start state drifts past the tolerance below within 50 steps
    with jax.default_prng_impl("threefry2x32"):
        state, _ = env.reset(jax.random.PRNGKey(1))
    genv.reset(seed=0)
    genv.unwrapped.state = np.array([float(state["th"]), float(state["thdot"])])
    for t in range(50):
        a = np.array([0.7 * np.sin(t)], np.float32)
        state, obs, rew, term, trunc = env.step(state, jnp.asarray(a), jax.random.PRNGKey(t))
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(np.asarray(obs), gobs, atol=1e-4)
        np.testing.assert_allclose(float(rew), float(grew), atol=1e-4)
        assert not bool(term)


def test_make_jax_env_unknown_id_points_at_python_backend():
    with pytest.raises(ValueError, match="env.backend=jax"):
        make_jax_env("ALE/MsPacman-v5")


# -- scatter_append ------------------------------------------------------------


def test_scatter_append_wraparound():
    """A burst crossing the capacity edge lands rows at ``(pos + t) % cap``
    — bitwise what per-row host adds at the same positions produce."""
    cap, n_envs, t = 8, 3, 6
    bufs = {"x": jnp.zeros((cap, n_envs, 2), jnp.float32)}
    rows = {"x": jnp.arange(t * n_envs * 2, dtype=jnp.float32).reshape(t, n_envs, 2)}
    pos = 5  # 5,6,7,0,1,2 — wraps
    out = jax.jit(lambda b, p, r: scatter_append(b, p, r, cap))(bufs, jnp.int32(pos), rows)
    expect = np.zeros((cap, n_envs, 2), np.float32)
    for i in range(t):
        expect[(pos + i) % cap] = np.asarray(rows["x"])[i]
    np.testing.assert_array_equal(np.asarray(out["x"]), expect)


def test_scatter_append_rejects_overlong_burst():
    bufs = {"x": jnp.zeros((4, 1), jnp.float32)}
    rows = {"x": jnp.zeros((5, 1), jnp.float32)}
    with pytest.raises(ValueError, match="exceeds the ring capacity"):
        scatter_append(bufs, jnp.int32(0), rows, 4)


def test_ring_adopt_and_sync_host_roundtrip():
    """In-jit writes adopted by the ring advance the host counters without a
    host copy; sync_host (forced by state_dict) downloads the real rows."""
    cap, n_envs = 10, 2
    rb = ReplayBuffer(cap, n_envs, memmap=False, obs_keys=("observations",))
    ring = DeviceRingTransitions(rb)
    eng = JaxRolloutEngine(JaxCartPole(), n_envs, jax.random.PRNGKey(0), ring=ring)
    eng.collect(0, 7, random_actions=True)
    assert rb._pos == 7 and not rb.full
    eng.collect(0, 7, random_actions=True)  # wraps: 14 rows into 10
    assert rb._pos == 4 and rb.full
    # the ring can sample before any host copy exists
    batch = ring.sample_device(4)
    assert batch["observations"].shape == (1, 4, 4)
    # state_dict forces the host download; rows must match the device ring
    state = ring.state_dict()
    assert state["pos"] == 4 and state["full"]
    dev = jax.device_get(ring._buf)
    np.testing.assert_array_equal(
        np.asarray(rb.buffer["observations"]), dev["observations"]
    )
    assert np.abs(np.asarray(rb.buffer["observations"])).sum() > 0


# -- jitted-scan collection vs the sync host loop ------------------------------


def _host_reference_burst(env, n_envs, seed, burst_len):
    """The engine's burst unrolled as a per-step host loop with the exact
    same key discipline — the bitwise reference for the lax.scan path."""
    key, sub = jax.random.split(jax.random.PRNGKey(seed))
    state, obs = jax.vmap(env.reset)(jax.random.split(sub, n_envs))
    obs = np.asarray(obs, np.float32).reshape(n_envs, -1)
    rows = []
    for _ in range(burst_len):
        key, akey = jax.random.split(key)
        actions = jax.vmap(env.sample_action)(jax.random.split(akey, n_envs))
        key, skey, rkey = jax.random.split(key, 3)
        state2, nobs, rew, term, trunc = jax.vmap(env.step)(
            state, actions, jax.random.split(skey, n_envs)
        )
        nobs = np.asarray(nobs, np.float32).reshape(n_envs, -1)
        done = np.asarray(jnp.logical_or(term, trunc))
        rows.append(
            {
                "observations": obs.copy(),
                "actions": np.asarray(actions, np.float32).reshape(n_envs, -1),
                "rewards": np.asarray(rew, np.float32).reshape(n_envs, 1),
                "dones": done.astype(np.float32).reshape(n_envs, 1),
                "next_observations": nobs.copy(),
            }
        )
        reset_state, reset_obs = jax.vmap(env.reset)(jax.random.split(rkey, n_envs))
        state = jax.tree_util.tree_map(
            lambda r, s: jnp.where(
                jnp.asarray(done).reshape((n_envs,) + (1,) * (r.ndim - 1)), r, s
            ),
            reset_state,
            state2,
        )
        obs = np.where(done[:, None], np.asarray(reset_obs).reshape(n_envs, -1), nobs)
        obs = obs.astype(np.float32)
    return rows


def _engine_rows(burst_split, n_envs=4, total=50, cap=64, seed=123):
    env = JaxCartPole()
    rb = ReplayBuffer(cap, n_envs, memmap=False, obs_keys=("observations",))
    ring = DeviceRingTransitions(rb)
    eng = JaxRolloutEngine(env, n_envs, jax.random.PRNGKey(seed), ring=ring)
    left = total
    while left:
        n = min(burst_split, left)
        eng.collect(0, n, random_actions=True)
        left -= n
    ring.sync_host()
    return {k: np.asarray(v) for k, v in rb.buffer.items()}


def test_jitted_scan_collection_bitwise_vs_sync_step_loop():
    """Seeded bitwise parity: ONE jitted 50-step burst leaves exactly the
    ring contents (obs/actions/rewards/dones/next-obs) of 50 per-step
    dispatches — the sync loop the burst replaces. Same key discipline per
    step, so splitting the burst must not change a single bit."""
    whole = _engine_rows(burst_split=50)
    stepwise = _engine_rows(burst_split=1)
    assert whole.keys() == stepwise.keys()
    for k in whole:
        np.testing.assert_array_equal(whole[k], stepwise[k], err_msg=k)


def test_jitted_scan_collection_semantics_vs_host_reference():
    """The burst semantics match a hand-unrolled host loop: same actions and
    terminations bitwise (integer/boolean), dynamics within float tolerance
    (separately compiled programs may fuse float ops differently), and the
    auto-reset path is exercised (CartPole episodes end inside the burst)."""
    n_envs, burst, seed = 4, 50, 123
    got = _engine_rows(burst_split=burst, n_envs=n_envs, total=burst, seed=seed)
    ref_rows = _host_reference_burst(JaxCartPole(), n_envs, seed, burst)
    assert any(r["dones"].any() for r in ref_rows), "burst must cross an episode end"
    for t, ref in enumerate(ref_rows):
        np.testing.assert_array_equal(got["actions"][t], ref["actions"], err_msg=f"step {t}")
        np.testing.assert_array_equal(got["dones"][t], ref["dones"], err_msg=f"step {t}")
        np.testing.assert_array_equal(got["rewards"][t], ref["rewards"], err_msg=f"step {t}")
        for k in ("observations", "next_observations"):
            np.testing.assert_allclose(
                got[k][t], ref[k], atol=1e-6, err_msg=f"step {t} key {k}"
            )


# -- burst acting (tier b) -----------------------------------------------------


def _pendulum_vec(n_envs, seed):
    from gymnasium.vector import AutoresetMode, SyncVectorEnv

    venv = SyncVectorEnv(
        [lambda: gym.make("Pendulum-v1") for _ in range(n_envs)],
        autoreset_mode=AutoresetMode.SAME_STEP,
    )
    obs = venv.reset(seed=seed)[0].astype(np.float32)
    return venv, obs


def _collect_with_burst(k, steps, n_envs=2, seed=11):
    """Drive a fixed stochastic policy through BurstActor with burst size
    ``k``; returns the replay rows + final obs."""
    venv, obs = _pendulum_vec(n_envs, seed)
    rb = ReplayBuffer(steps, n_envs, memmap=False, obs_keys=("observations",))
    box = {"obs": obs}

    def act_fn(params, a_obs, key):
        key, sub = jax.random.split(key)
        noise = jax.random.normal(sub, (n_envs, 1), jnp.float32)
        actions = jnp.tanh(a_obs[:, :1] * params + noise) * 2.0
        return (actions,), key

    def host_step(actions):
        actions = np.asarray(actions)
        next_o, rew, term, trunc, _ = venv.step(actions)
        rb.add(
            {
                "observations": box["obs"][None],
                "actions": actions.astype(np.float32)[None],
                "rewards": np.asarray(rew, np.float32).reshape(1, n_envs, 1),
                "dones": np.logical_or(term, trunc).astype(np.float32).reshape(1, n_envs, 1),
            }
        )
        box["obs"] = next_o.astype(np.float32)
        return box["obs"]

    actor = BurstActor(act_fn, host_step, obs)
    key = jax.random.PRNGKey(seed)
    remaining = steps
    while remaining > 0:
        n = min(k, remaining)
        obs, key = actor.rollout(jnp.float32(0.5), box["obs"], key, n)
        remaining -= n
    venv.close()
    return {kk: np.asarray(v) for kk, v in rb.buffer.items()}, np.asarray(obs)


def test_burst_actor_k4_bitwise_k1():
    """K=4 bursts produce bitwise the K=1 per-step trajectories: same env
    steps, same rng stream, same replay rows."""
    rows1, obs1 = _collect_with_burst(1, 12)
    rows4, obs4 = _collect_with_burst(4, 12)
    assert rows1.keys() == rows4.keys()
    for k in rows1:
        np.testing.assert_array_equal(rows1[k], rows4[k], err_msg=k)
    np.testing.assert_array_equal(obs1, obs4)


def _counting_actor(kind, n_envs=2):
    """An actor of ``kind`` over an env that counts its steps: ``(actor, params, obs)``."""
    obs = np.zeros((n_envs, 1), np.float32)

    def host_step(actions):
        return np.asarray(actions, np.float32) + 1.0

    if kind == "burst":
        actor = BurstActor(lambda params, a_obs, key: ((a_obs * params,), key), host_step, obs)
    else:
        actor = DeviceActor(
            lambda params, state, a_obs, key: (a_obs * params, (), state + 1, key), host_step, jnp.int32(0)
        )
    return actor, jnp.float32(1.0), obs


def _traced_rollout(tmp_path, kind, k):
    """``k`` policy steps of a counting actor under a tracer, inside the
    caller's ``Time/rollout_time``: the final obs and the X events."""
    from sheeprl_tpu.obs.spans import TraceWriter, set_tracer, span

    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    actor, params, obs = _counting_actor(kind)
    if kind == "burst":
        # on a chip the runtime calls the host back on a thread of its own; the
        # CPU backend calls it on the dispatching thread, so dispatch elsewhere
        program = actor._build()

        def on_another_thread(*args):
            out = {}
            worker = threading.Thread(target=lambda: out.setdefault("out", program(*args)))
            worker.start()
            worker.join(timeout=60)
            return out["out"]

        actor._rollout_fn = on_another_thread
    set_tracer(writer)
    try:
        with span("Time/rollout_time", phase="rollout"):
            obs, _ = actor.rollout(params, obs, jax.random.PRNGKey(0), k)
    finally:
        set_tracer(None)
        writer.close()
    with open(writer.path) as f:
        events = [e for e in map(json.loads, f) if e.get("ph") == "X"]
    return np.asarray(obs), events


def _named(events, name):
    return sorted((e for e in events if e["name"] == name), key=lambda e: e["ts"])


@pytest.mark.parametrize("k", [1, 4])
def test_each_host_step_of_a_burst_is_spanned_under_the_callers_rollout(tmp_path, k):
    """The burst program's host callback runs on the runtime's thread; each of
    its K host steps is a ``Time/act_host_step_time`` inside the caller's
    rollout span that names that span as its parent."""
    obs, events = _traced_rollout(tmp_path, "burst", k)
    np.testing.assert_array_equal(obs, np.full((2, 1), float(k), np.float32))
    (rollout,) = _named(events, "Time/rollout_time")
    steps = _named(events, "Time/act_host_step_time")
    assert len(steps) == k
    for step in steps:
        assert step["args"]["parent"] == "Time/rollout_time" and step["tid"] != rollout["tid"]
        # ts and dur are rounded to a tenth of a microsecond
        assert rollout["ts"] - 0.2 <= step["ts"] and step["ts"] + step["dur"] <= rollout["ts"] + rollout["dur"] + 0.2
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 0.2


def test_a_device_actor_step_spans_its_host_step_after_its_decode(tmp_path):
    obs, events = _traced_rollout(tmp_path, "device", 3)
    np.testing.assert_array_equal(obs, np.full((2, 1), 3.0, np.float32))
    decodes, steps = _named(events, "Time/act_decode_time"), _named(events, "Time/act_host_step_time")
    assert len(decodes) == len(steps) == 3
    for decode, step in zip(decodes, steps):
        assert decode["args"]["parent"] == step["args"]["parent"] == "Time/rollout_time"
        assert decode["ts"] + decode["dur"] <= step["ts"] + 0.2
    for step, following in zip(steps, decodes[1:]):
        assert step["ts"] + step["dur"] <= following["ts"] + 0.2


@pytest.mark.parametrize("kind", ["burst", "device"])
def test_with_no_tracer_the_host_step_span_is_a_timer_alone(kind, monkeypatch):
    """Telemetry off: the actors' spans neither read a stack of open spans
    (on either thread) nor call the profiler."""
    from sheeprl_tpu.obs import spans as spans_mod
    from sheeprl_tpu.utils.timer import timer

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"the stack of open spans was read ({name}) with no tracer installed")

        def __setattr__(self, name, value):
            raise AssertionError(f"a stack of open spans was made ({name}) with no tracer installed")

    def refuse(*_a, **_k):
        raise AssertionError("a profiler annotation with no tracer installed")

    assert spans_mod.get_tracer() is None
    monkeypatch.setattr(timer, "disabled", False)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(spans_mod, "_OPEN", Untouchable())
    timer.reset()
    actor, params, obs = _counting_actor(kind)
    obs, _ = actor.rollout(params, obs, jax.random.PRNGKey(0), 2)
    np.testing.assert_array_equal(np.asarray(obs), np.full((2, 1), 2.0, np.float32))
    assert "Time/act_host_step_time" in timer.compute()


@pytest.mark.parametrize("kind", ["burst", "device"])
@pytest.mark.parametrize("platform, counted", [("cpu", 0), ("tpu", 2)])
def test_rollout_device_bursts_counts_the_bursts_acted_off_the_hosts_cpu(kind, platform, counted, monkeypatch):
    """``rollout_device_bursts`` reads 0 where the acting parameters live on the
    host's CPU (every CPU run, and a host mirror on the chip) and counts every
    burst whose parameters are committed to another device."""
    from sheeprl_tpu.obs import counters as counters_mod

    cpu = jax.local_devices(backend="cpu")[0]

    class OtherDevice:
        platform = "tpu"

    there = cpu if platform == "cpu" else OtherDevice()
    put = jax.device_put
    # the stand-in cannot hold an array: what is put "there" lands on the CPU
    monkeypatch.setattr(jax, "device_put", lambda x, device=None, **kw: put(x, cpu if device is there else device, **kw))
    monkeypatch.setattr(BurstActor, "_params_device", staticmethod(lambda params: there))
    before = counters_mod.installed()
    counters = counters_mod.Counters()
    counters_mod.install(counters)
    try:
        actor, params, obs = _counting_actor(kind)
        key = jax.random.PRNGKey(0)
        for _ in range(2):
            obs, key = actor.rollout(params, obs, key, 1)
    finally:
        counters_mod.install(before)
    assert counters.rollout_bursts == 2
    assert counters.rollout_device_bursts == counted
    assert counters.as_dict()["rollout_device_bursts"] == counted


def test_a_mesh_replicated_tree_reaches_the_rollout_program_without_a_copy():
    """Data-parallel training keeps the trained leaves replicated over the mesh,
    and the burst program takes one device: each leaf it is handed is the first
    device's own shard, the same buffer, not a copy of it (412 MiB a rollout at
    DreamerV3-XL)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()[:4]
    assert len(devices) == 4
    replicated = NamedSharding(Mesh(np.array(devices), ("data",)), PartitionSpec())
    params = jax.device_put(
        {"a": jnp.full((64, 64), 2.0), "b": {"c": jnp.arange(1024, dtype=jnp.float32)}}, replicated
    )
    first = min(devices, key=lambda d: d.id)
    in_place = {
        next(shard.data.unsafe_buffer_pointer() for shard in leaf.addressable_shards if shard.device == first)
        for leaf in jax.tree_util.tree_leaves(params)
    }
    obs = np.zeros((2, 1), np.float32)
    actor = BurstActor(
        lambda p, a_obs, key: ((a_obs + p["a"][0, 0],), key), lambda a: np.asarray(a, np.float32), obs
    )
    handed = []
    program = actor._build()

    def watched(p, *rest):
        handed.extend(jax.tree_util.tree_leaves(p))
        return program(p, *rest)

    actor._rollout_fn = watched
    out, _ = actor.rollout(params, obs, jax.random.PRNGKey(0), 3)
    np.testing.assert_array_equal(np.asarray(out), np.full((2, 1), 6.0, np.float32))
    assert len(handed) == 2 and all(leaf.devices() == {first} for leaf in handed)
    assert {leaf.unsafe_buffer_pointer() for leaf in handed} == in_place


# -- entrypoint acceptance -----------------------------------------------------


def _sac_args(tmp_path, run_name, extra):
    return [
        "exp=sac",
        "dry_run=False",
        "total_steps=24",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "per_rank_batch_size=4",
        "algo.learning_starts=4",
        "algo.hidden_size=8",
        "env=gym",
        "env.id=Pendulum-v1",
        "env.sync_env=True",
        "env.capture_video=False",
        "env.num_envs=2",
        "buffer.size=64",
        "buffer.memmap=False",
        "metric.log_level=0",
        "algo.run_test=False",
        f"root_dir={tmp_path}/logs",
        f"run_name={run_name}",
        *extra,
    ]


def _load_ckpt_arrays(tmp_path, run_name, pattern):
    d = sorted(
        glob.glob(f"{tmp_path}/logs/**/{run_name}/**/ckpt_*_0", recursive=True)
    )[-1]
    out = {}
    for f in sorted(glob.glob(os.path.join(d, pattern))):
        z = np.load(f)
        for k in z.files:
            out[(os.path.basename(f), k)] = z[k]
    return out


def test_sac_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """SAC entrypoint equivalence: with training switched off
    (per_rank_gradient_steps=0) the checkpointed replay shards of an
    act_burst=4 run are bitwise the per-step run's."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    common = [
        "algo.per_rank_gradient_steps=0",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "buffer.checkpoint=True",
    ]
    cli.run(_sac_args(tmp_path, "k1", common))
    cli.run(_sac_args(tmp_path, "k4", common + ["env.act_burst=4"]))
    a = _load_ckpt_arrays(tmp_path, "k1", "rb_env*.npz")
    b = _load_ckpt_arrays(tmp_path, "k4", "rb_env*.npz")
    assert a and a.keys() == b.keys()
    written = 24 // 2  # total_steps / n_envs rows actually collected
    for k in a:
        if a[k].ndim == 0 or a[k].shape[0] < written:  # pos/full scalars
            np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))
        else:
            # rows past the write head are np.empty garbage; compare the
            # collected region only
            np.testing.assert_array_equal(
                a[k][:written], b[k][:written], err_msg=str(k)
            )


def test_sac_jax_backend_e2e_counters(tmp_path, monkeypatch):
    """SAC through the pure-JAX rollout engine end-to-end on CPU: trains,
    checkpoints, and telemetry carries the rollout counters (bursts, one
    inference dispatch per burst, in-jit env steps)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    tel = tmp_path / "telemetry.json"
    cli.run(
        _sac_args(
            tmp_path,
            "jaxb",
            [
                "env.backend=jax",
                "env.act_burst=4",
                "checkpoint.every=1000000",
                "metric.telemetry.enabled=true",
                "metric.telemetry.trace=false",
                f"metric.telemetry.summary_path={tel}",
            ],
        )
    )
    summary = json.loads(tel.read_text())
    assert summary["rollout_bursts"] > 0
    assert summary["act_dispatches"] == summary["rollout_bursts"]
    # every env step of the run (24 policy steps / 2 envs = 12 updates) ran
    # inside jit
    assert summary["env_steps_jax"] == 24


def _onpolicy_burst_args(tmp_path, exp, run_name, extra):
    return [
        f"exp={exp}",
        "dry_run=False",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "env=gym",
        "env.id=CartPole-v1",
        "env.sync_env=True",
        "env.capture_video=False",
        "env.num_envs=2",
        "buffer.memmap=False",
        "buffer.checkpoint=True",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "metric.log_level=0",
        "algo.run_test=False",
        "mlp_keys.encoder=[state]",
        f"root_dir={tmp_path}/logs",
        f"run_name={run_name}",
        *extra,
    ]


def _assert_ckpt_bitwise(tmp_path, run_a, run_b, written):
    """Final checkpoint of two runs must be bitwise identical: trained
    params/opt state (state.npz) AND the collected replay rows."""
    a = _load_ckpt_arrays(tmp_path, run_a, "*.npz")
    b = _load_ckpt_arrays(tmp_path, run_b, "*.npz")
    assert a and a.keys() == b.keys()
    for k in a:
        if a[k].ndim == 0 or a[k].shape[0] < written:
            np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))
        else:
            # rows past the write head are np.empty garbage
            np.testing.assert_array_equal(a[k][:written], b[k][:written], err_msg=str(k))


def test_a2c_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """A2C entrypoint equivalence with training ON: the act_burst=4 run's
    final checkpoint (params, opt state, replay rows) is bitwise the
    per-step run's — acting params are frozen per rollout, so burst
    partitioning must not change a single collected bit, and identical data
    implies identical updates."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    common = [
        "total_steps=16",
        "algo.rollout_steps=4",
        "per_rank_batch_size=4",
        "buffer.size=4",
    ]
    cli.run(_onpolicy_burst_args(tmp_path, "a2c", "k1", common))
    cli.run(_onpolicy_burst_args(tmp_path, "a2c", "k4", common + ["env.act_burst=4"]))
    _assert_ckpt_bitwise(tmp_path, "k1", "k4", written=4)


def test_ppo_recurrent_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """Recurrent PPO equivalence: the LSTM carry threads through the burst
    (hidden-state recording, done masking, prev_action resets all host-side)
    and act_burst=4 still reproduces the per-step run bitwise end-to-end."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    common = [
        "total_steps=32",
        "algo.rollout_steps=8",
        "per_rank_sequence_length=4",
        "per_rank_num_batches=2",
        "algo.update_epochs=2",
        "algo.dense_units=8",
        "algo.rnn.lstm.hidden_size=8",
        "buffer.size=8",
    ]
    cli.run(_onpolicy_burst_args(tmp_path, "ppo_recurrent", "rk1", common))
    cli.run(_onpolicy_burst_args(tmp_path, "ppo_recurrent", "rk4", common + ["env.act_burst=4"]))
    _assert_ckpt_bitwise(tmp_path, "rk1", "rk4", written=8)


def _dreamer_burst_args(tmp_path, algo, run_name, extra=()):
    args = [
        f"exp={algo}",
        "dry_run=False",
        "total_steps=32",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.sync_env=True",
        "env.capture_video=False",
        "env.num_envs=2",
        "per_rank_batch_size=2",
        "per_rank_sequence_length=4",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.per_rank_gradient_steps=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.stochastic_size=4",
        "algo.learning_starts=12",
        "algo.train_every=8",
        "cnn_keys.encoder=[rgb]",
        "buffer.size=16",
        "buffer.memmap=False",
        # the prefetch worker samples burst k+1 while collection is still
        # adding rows — scheduling-dependent by design (data/staging.py); a
        # bitwise K-invariance gate needs the synchronous sampling path
        "buffer.prefetch=False",
        "buffer.checkpoint=True",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "metric.log_level=0",
        "algo.run_test=False",
        f"root_dir={tmp_path}/logs",
        f"run_name={run_name}",
    ]
    if algo == "dreamer_v2":
        args += ["algo.world_model.discrete_size=4", "algo.per_rank_pretrain_steps=1"]
    return args + list(extra)


def test_dreamer_v1_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """DreamerV1 equivalence with training ON: the RSSM player state rides
    the burst carry (host-side (1-mask) episode resets), the act key stream
    threads through the scanned burst, and the train_every countdown clamps
    bursts at train boundaries — so act_burst=4 reproduces the per-step run
    bitwise end-to-end (params, opt state, replay rows)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v1", "dk1"))
    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v1", "dk4", ["env.act_burst=4"]))
    _assert_ckpt_bitwise(tmp_path, "dk1", "dk4", written=8)


def test_dreamer_v2_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """DreamerV2 equivalence with training ON, including the is_first row
    bookkeeping and the pretrain-at-learning-starts gate under bursts."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v2", "dk1"))
    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v2", "dk4", ["env.act_burst=4"]))
    _assert_ckpt_bitwise(tmp_path, "dk1", "dk4", written=8)


def test_dreamer_v3_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """DreamerV3 equivalence with training ON: unlike DV1/DV2 (zero reset
    states), DV3's fresh player state depends on the world-model params
    (learned initial posterior), so episode resets inside the burst apply
    ``mask * fresh + (1 - mask) * state`` host-side against a fresh-state
    copy cached per params version — act_burst=4 must still reproduce the
    per-step run bitwise end-to-end (params, opt state, replay rows)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = ["algo.world_model.discrete_size=4"]
    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v3", "vk1", extras))
    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v3", "vk4", extras + ["env.act_burst=4"]))
    _assert_ckpt_bitwise(tmp_path, "vk1", "vk4", written=8)


def test_dreamer_v3_spans_the_host_work_that_holds_its_device(tmp_path, monkeypatch):
    """A traced DreamerV3 run: the fresh player state is spanned where a train
    burst made the cached one stale, once a cycle and before the cycle's first
    rollout, never on the cached path; the train block's preparation once a
    burst, inside ``Time/train_time``; the acting call's preparation before
    and the carried key's fetch after every rollout; each rollout's one policy
    step as a host step inside it."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    trace = tmp_path / "spans.jsonl"
    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v3", "traced", [
        "algo.world_model.discrete_size=4", "metric.telemetry.enabled=true", f"metric.telemetry.trace_file={trace}",
        f"metric.telemetry.summary_path={tmp_path / 'telemetry.json'}", "metric.telemetry.learn.enabled=false",
        "metric.telemetry.flight.enabled=false", "metric.telemetry.live_interval_s=0",
        "metric.telemetry.poll_interval_s=0",
    ]))
    with open(trace) as f:
        events = sorted((e for e in map(json.loads, f) if e.get("ph") == "X"), key=lambda e: e["ts"])

    def named(name):
        return [e for e in events if e["name"] == name]

    def inside(inner, outer):  # ts and dur are rounded to a tenth of a microsecond
        return outer["ts"] - 0.2 <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.2

    trains, rollouts, fresh = named("Time/train_time"), named("Time/rollout_time"), named("Time/act_fresh_state_time")
    assert len(trains) >= 2 and len(rollouts) > len(trains) + 1
    prepares = named("Time/train_prepare_time")
    assert len(prepares) == len(trains)
    assert all(inside(p, t) and p["args"]["parent"] == "Time/train_time" for p, t in zip(prepares, trains))
    fetches, uploads = named("Time/act_key_fetch_time"), named("Time/act_prepare_time")
    assert len(fetches) == len(uploads) == len(rollouts)
    assert all(r["ts"] + r["dur"] <= k["ts"] + 0.2 for r, k in zip(rollouts, fetches))
    assert all(u["ts"] + u["dur"] <= r["ts"] + 0.2 for u, r in zip(uploads, rollouts))
    steps = named("Time/act_host_step_time")
    for rollout in rollouts:
        mine = [s for s in steps if inside(s, rollout)]
        assert len(mine) == 1 and mine[0]["args"]["parent"] == "Time/rollout_time"
    # the cycles after each burst: one fresh state, made before the cycle's first rollout
    ends = [t["ts"] + t["dur"] for t in trains] + [float("inf")]
    for after, before in zip(ends, ends[1:]):
        cycle = [r for r in rollouts if after <= r["ts"] < before]
        made = [f for f in fresh if after <= f["ts"] < before]
        assert len(made) == (1 if cycle else 0)
        assert not cycle or made[0]["ts"] + made[0]["dur"] <= cycle[0]["ts"] + 0.2
    assert len(fresh) < len(rollouts)


@pytest.mark.slow
def test_p2e_dv3_exploration_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """P2E-DV3 exploration equivalence: the exploration actor's player state
    rides the same burst carry as DV3's (params-dependent resets cached per
    params version; ensemble optimizer state riding the train carry), so
    act_burst=4 is bitwise the per-step run. Slow-marked: two full
    six-update-per-step e2e runs."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = ["algo.world_model.discrete_size=4", "algo.ensembles.n=2"]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv3_exploration", "pk1", extras))
    cli.run(
        _dreamer_burst_args(
            tmp_path, "p2e_dv3_exploration", "pk4", extras + ["env.act_burst=4"]
        )
    )
    _assert_ckpt_bitwise(tmp_path, "pk1", "pk4", written=8)


@pytest.mark.slow
def test_p2e_dv1_exploration_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """P2E-DV1 exploration equivalence: same carry layout as DreamerV1
    (zero reset states applied host-side), exploration actor fed per
    rollout — act_burst=4 reproduces the per-step run bitwise end-to-end.
    Slow-marked: two full ensemble-training e2e runs."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = ["algo.ensembles.n=2"]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv1_exploration", "ek1", extras))
    cli.run(
        _dreamer_burst_args(
            tmp_path, "p2e_dv1_exploration", "ek4", extras + ["env.act_burst=4"]
        )
    )
    _assert_ckpt_bitwise(tmp_path, "ek1", "ek4", written=8)


@pytest.mark.slow
def test_p2e_dv1_finetuning_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """P2E-DV1 finetuning equivalence: the converted loop clamps every burst
    to the exploration→task actor switch at ``learning_starts`` (no burst may
    span the swap) and never enters the random phase (resuming plan), so
    act_burst=4 from the same exploration checkpoint reproduces the per-step
    finetuning run bitwise end-to-end. Slow-marked: three e2e runs
    (exploration seed + two finetunings)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = ["algo.ensembles.n=2"]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv1_exploration", "fe", extras))
    expl = sorted(
        glob.glob(f"{tmp_path}/logs/**/fe/**/checkpoint/ckpt_*_0", recursive=True)
    )
    assert expl, "no exploration checkpoint written"
    fine = [f"checkpoint.exploration_ckpt_path={os.path.abspath(expl[-1])}"]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv1_finetuning", "fk1", fine))
    cli.run(
        _dreamer_burst_args(
            tmp_path, "p2e_dv1_finetuning", "fk4", fine + ["env.act_burst=4"]
        )
    )
    _assert_ckpt_bitwise(tmp_path, "fk1", "fk4", written=8)


@pytest.mark.slow
def test_p2e_dv3_finetuning_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """P2E-DV3 finetuning equivalence: combines the DV3 wrinkle
    (params-dependent fresh player state, resets applied host-side against a
    per-params-version cache) with the finetuning wrinkle (every burst is
    clamped to the exploration→task actor switch at ``learning_starts`` and
    the resuming plan skips the random phase) — act_burst=4 from the same
    exploration checkpoint reproduces the per-step finetuning run bitwise
    end-to-end. Slow-marked: three e2e runs (exploration seed + two
    finetunings)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = ["algo.world_model.discrete_size=4", "algo.ensembles.n=2"]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv3_exploration", "f3e", extras))
    expl = sorted(
        glob.glob(f"{tmp_path}/logs/**/f3e/**/checkpoint/ckpt_*_0", recursive=True)
    )
    assert expl, "no exploration checkpoint written"
    fine = extras + [f"checkpoint.exploration_ckpt_path={os.path.abspath(expl[-1])}"]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv3_finetuning", "f3k1", fine))
    cli.run(
        _dreamer_burst_args(
            tmp_path, "p2e_dv3_finetuning", "f3k4", fine + ["env.act_burst=4"]
        )
    )
    _assert_ckpt_bitwise(tmp_path, "f3k1", "f3k4", written=8)


@pytest.mark.slow
def test_p2e_dv2_exploration_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """P2E-DV2 exploration equivalence (the last grandfathered conversion):
    DV2 carry layout (zero reset states host-side, is_first row bookkeeping
    in the burst callback) plus the dual-actor P2E params pytree and the
    pretrain-at-learning-starts gate — act_burst=4 reproduces the per-step
    run bitwise end-to-end. Slow-marked: two full ensemble-training e2e
    runs."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = [
        "algo.world_model.discrete_size=4",
        "algo.per_rank_pretrain_steps=1",
        "algo.ensembles.n=2",
    ]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv2_exploration", "e2k1", extras))
    cli.run(
        _dreamer_burst_args(
            tmp_path, "p2e_dv2_exploration", "e2k4", extras + ["env.act_burst=4"]
        )
    )
    _assert_ckpt_bitwise(tmp_path, "e2k1", "e2k4", written=8)


@pytest.mark.slow
def test_p2e_dv2_finetuning_burst_acting_k4_bitwise_k1_e2e(tmp_path, monkeypatch):
    """P2E-DV2 finetuning equivalence: the converted loop clamps every burst
    to the exploration→task actor switch at ``learning_starts``, never enters
    the random phase (resuming plan), and keeps the DV2 is_first/pretrain
    wrinkles — act_burst=4 from the same exploration checkpoint reproduces
    the per-step finetuning run bitwise end-to-end. Slow-marked: three e2e
    runs (exploration seed + two finetunings)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    extras = [
        "algo.world_model.discrete_size=4",
        "algo.per_rank_pretrain_steps=1",
        "algo.ensembles.n=2",
    ]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv2_exploration", "f2e", extras))
    expl = sorted(
        glob.glob(f"{tmp_path}/logs/**/f2e/**/checkpoint/ckpt_*_0", recursive=True)
    )
    assert expl, "no exploration checkpoint written"
    fine = [
        f"checkpoint.exploration_ckpt_path={os.path.abspath(expl[-1])}",
        "algo.per_rank_pretrain_steps=1",
    ]
    cli.run(_dreamer_burst_args(tmp_path, "p2e_dv2_finetuning", "f2k1", fine))
    cli.run(
        _dreamer_burst_args(
            tmp_path, "p2e_dv2_finetuning", "f2k4", fine + ["env.act_burst=4"]
        )
    )
    _assert_ckpt_bitwise(tmp_path, "f2k1", "f2k4", written=8)


def test_dreamer_v2_fused_xla_bitwise_off_e2e(tmp_path, monkeypatch):
    """The fused-kernel knob (ISSUE 13) must not change a single bit of a
    DV2 run on CPU: ``algo.fused_kernels=xla`` resolves to ``pad_to=1``
    there, whose op sequence is bitwise the reference cell — so the trained
    params, opt state, and replay rows of a fused run must equal the
    default (``off``) run's exactly. This is the e2e teeth behind the
    unit-level ``test_xla_cell_pad1_bitwise_reference``."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v2", "foff"))
    cli.run(_dreamer_burst_args(tmp_path, "dreamer_v2", "fxla", ["algo.fused_kernels=xla"]))
    _assert_ckpt_bitwise(tmp_path, "foff", "fxla", written=8)
