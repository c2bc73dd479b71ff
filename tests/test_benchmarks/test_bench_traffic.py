"""The traffic generator: the same seed gives the same inputs."""

import numpy as np

from benchmarks import check, traffic_env
from benchmarks.window import Recorder


def make(seed=2**31 - 2000, base=None, **kw):
    traffic_env.attach(None)
    return traffic_env.PixelEnv(seed=seed, base_seed=seed if base is None else base, step_ms=0.0,
                                episode_len_min=3, episode_len_max=5, **kw)


def roll(env, n):
    frames, rewards, dones = [env.reset()[0]], [0.0], [False]
    for i in range(n):
        frame, reward, done, _, _ = env.step(i % traffic_env.N_ACTIONS)
        frames.append(frame), rewards.append(reward), dones.append(done)
        if done:
            frames.append(env.reset()[0]), rewards.append(0.0), dones.append(False)
    return frames, rewards, dones


def test_same_seed_same_stream_other_seed_other_stream():
    a, b, c = roll(make(), 30), roll(make(), 30), roll(make(seed=7), 30)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0])) and a[1:] == b[1:]
    assert not all(np.array_equal(x, y) for x, y in zip(a[0], c[0]))


def test_frames_name_their_origin_and_regenerate_from_the_seed():
    env = make(seed=1003, base=1000)
    frames, rewards, dones = roll(env, 30)
    ends = set(traffic_env.episode_ends(1003, 3, 5, 100).tolist())
    for index, frame in enumerate(frames):
        assert traffic_env.frame_origin(frame) == (index, 3)
        assert np.array_equal(frame, traffic_env.frame_pixels(1003, 3, index))
        assert dones[index] == (index in ends)
        is_reset = index == 0 or index - 1 in ends
        assert rewards[index] == (0.0 if is_reset else traffic_env.step_reward(1003, index))
    assert frames[0].shape == (3, 64, 64) and frames[0].dtype == np.uint8
    assert 3 <= min(np.diff(sorted(ends))) - 1 and max(np.diff(sorted(ends))) - 1 <= 5


def test_env_zero_stamps_the_recorder_and_logs_actions():
    recorder = Recorder(1.0, warm_bursts=1)
    traffic_env.attach(recorder)
    envs = [traffic_env.PixelEnv(seed=50 + i, base_seed=50, step_ms=0.5, episode_len_min=3, episode_len_max=3)
            for i in range(2)]
    assert traffic_env.built_envs() == envs
    for env in envs:
        env.reset()
    recorder.on_burst_done(1)
    for env in envs:
        env.step(4)
    assert recorder.opened_at is not None and len(recorder.step_stamps) == 1
    assert envs[0].actions == [4] and envs[1].actions == [4]
    traffic_env.attach(None)


def batch_of(env_seed, first, T):
    """A ``[T, 1]`` replay batch of frames ``first..first+T-1`` of env 0, as the program stores it."""
    env = make(seed=env_seed)
    frames, rewards, dones = roll(env, first + T + 5)
    ends = set(traffic_env.episode_ends(env_seed, 3, 5, 200).tolist())
    idx = range(first, first + T)
    actions = np.zeros((T, 1, traffic_env.N_ACTIONS), np.float32)
    for t, i in enumerate(idx):
        if env.actions[i] >= 0:
            actions[t, 0, env.actions[i]] = 1.0
    col = lambda values: np.asarray(values, np.float32).reshape(T, 1, 1)
    batch = {
        "rgb": np.stack([frames[i] for i in idx])[:, None],
        "rewards": col([rewards[i] for i in idx]),
        "reward": col([rewards[i] for i in idx]),
        "dones": col([float(dones[i]) for i in idx]),
        "is_first": col([float(i == 0 or i - 1 in ends) for i in idx]),
        "actions": actions,
    }
    return batch, env


def test_staging_check_passes_the_emitted_stream_and_counts_altered_rows():
    params = {"episode_len_min": 3, "episode_len_max": 5}
    batch, env = batch_of(900, first=2, T=12)
    assert check.staging_mismatches(batch, params, 900, [env]) == 0
    for key, where in (("rewards", (3, 0, 0)), ("dones", (4, 0, 0)), ("is_first", (5, 0, 0)), ("actions", (6, 0, 1))):
        broken = {k: v.copy() for k, v in batch.items()}
        broken[key][where] += 1.0
        assert check.staging_mismatches(broken, params, 900, [env]) == 1, key
    broken = {k: v.copy() for k, v in batch.items()}
    broken["rgb"][7, 0, 2, 5, 5] ^= 1
    assert check.staging_mismatches(broken, params, 900, [env]) == 1
    swapped = {k: v.copy() for k, v in batch.items()}
    for k in swapped:
        swapped[k][[8, 9]] = swapped[k][[9, 8]]  # rows out of emission order
    assert check.staging_mismatches(swapped, params, 900, [env]) >= 2
