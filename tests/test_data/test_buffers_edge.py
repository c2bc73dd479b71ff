"""Edge-path buffer tests: wrap-around × memmap
interplay, trailing-window overwrites, `prioritize_ends` edges, episode
chunking across `add` calls, eviction file cleanup, and state-dict round
trips — the hairy paths the reference pins with ~75 property-style tests
(reference tests/test_data/test_buffers.py, test_episode_buffer.py)."""

import os

import numpy as np
import pytest

from sheeprl_tpu.data.buffers import (
    EnvIndependentReplayBuffer,
    EpisodeBuffer,
    ReplayBuffer,
    SequentialReplayBuffer,
)
from sheeprl_tpu.utils.memmap import MemmapArray


def _steps(t0, t1, n_envs=1, extra=()):
    """[t0, t1) counter steps: observations[t, e] == t (broadcast over envs)."""
    t = np.arange(t0, t1, dtype=np.float32)[:, None].repeat(n_envs, 1)
    data = {"observations": t.copy()}
    for k in extra:
        data[k] = t.copy()
    return data


# ---------------------------------------------------------------------------
# ReplayBuffer: wrap-around content, memmap interplay
# ---------------------------------------------------------------------------


def test_wraparound_contents_exact():
    rb = ReplayBuffer(buffer_size=5, n_envs=1)
    rb.add(_steps(0, 4))   # pos=4
    rb.add(_steps(4, 8))   # wraps: positions 4,0,1,2 get 4,5,6,7
    assert rb.full
    got = rb["observations"][:, 0]
    np.testing.assert_array_equal(got, [5, 6, 7, 3, 4])


def test_add_longer_than_capacity_keeps_trailing_window():
    rb = ReplayBuffer(buffer_size=4, n_envs=1)
    rb.add(_steps(0, 11))  # 11 > 4: only steps 7..10 survive
    assert rb.full
    got = sorted(rb["observations"][:, 0].tolist())
    assert got == [7, 8, 9, 10]
    # and they sit at the positions single-step inserts would have used
    # (pos after 11 inserts into size 4 = 11 % 4 = 3)
    np.testing.assert_array_equal(rb["observations"][:, 0], [8, 9, 10, 7])


def test_wraparound_with_memmap_persists(tmp_path):
    rb = ReplayBuffer(buffer_size=5, n_envs=2, memmap=True, memmap_dir=tmp_path / "rb")
    rb.add(_steps(0, 8, n_envs=2))
    assert rb.is_memmap and rb.full
    np.testing.assert_array_equal(rb["observations"][:, 0], [5, 6, 7, 3, 4])
    # the ring writes really landed in the backing file
    on_disk = np.memmap(
        tmp_path / "rb" / "observations.memmap", dtype=np.float32, mode="r", shape=(5, 2)
    )
    np.testing.assert_array_equal(np.asarray(on_disk)[:, 1], [5, 6, 7, 3, 4])


def test_sample_next_obs_wraps_across_ring_boundary():
    rb = ReplayBuffer(buffer_size=4, n_envs=1)
    rb.add(_steps(0, 6))  # full ring: [4, 5, 2, 3], pos=2, newest at idx 1
    rb.seed(0)
    batch = rb.sample(256, sample_next_obs=True)
    obs = batch["observations"].reshape(-1)
    nxt = batch["next_observations"].reshape(-1)
    # successor of every sampled step is its +1 step; the newest step (5)
    # has no successor and must never be sampled
    assert 5 not in obs
    np.testing.assert_array_equal(nxt, obs + 1)


def test_sample_next_obs_with_single_step_errors():
    rb = ReplayBuffer(buffer_size=4, n_envs=1)
    rb.add(_steps(0, 1))
    with pytest.raises(RuntimeError, match="at least two samples"):
        rb.sample(1, sample_next_obs=True)


def test_setitem_memmap_dtype_change_recreates_backing_file(tmp_path):
    rb = ReplayBuffer(buffer_size=3, n_envs=1, memmap=True, memmap_dir=tmp_path / "rb")
    rb.add(_steps(0, 3))
    rb["observations"] = np.ones((3, 1), dtype=np.float64)  # dtype changed
    assert isinstance(rb.buffer["observations"], MemmapArray)
    assert rb["observations"].dtype == np.float64
    np.testing.assert_array_equal(np.asarray(rb["observations"]), np.ones((3, 1)))


def test_state_dict_round_trip_preserves_ring_position():
    rb = ReplayBuffer(buffer_size=5, n_envs=1)
    rb.add(_steps(0, 7))
    state = rb.state_dict()
    rb2 = ReplayBuffer(buffer_size=5, n_envs=1)
    rb2.load_state_dict(state)
    assert rb2.full and rb2._pos == rb._pos
    rb2.add(_steps(7, 8))  # continues writing where the original would
    rb.add(_steps(7, 8))
    np.testing.assert_array_equal(rb["observations"], rb2["observations"])


# ---------------------------------------------------------------------------
# SequentialReplayBuffer: wrap + content properties, memmap
# ---------------------------------------------------------------------------


def test_sequential_sequences_are_consecutive_even_wrapped():
    srb = SequentialReplayBuffer(buffer_size=8, n_envs=1)
    srb.add(_steps(0, 13))  # full, pos=5
    srb.seed(1)
    batch = srb.sample(512, sequence_length=3)["observations"]  # [1, 3, 512]
    seqs = batch[0].T  # [512, 3]
    diffs = np.diff(seqs, axis=1)
    np.testing.assert_array_equal(diffs, np.ones_like(diffs))  # consecutive steps
    assert seqs.min() >= 5 and seqs.max() <= 12  # only live steps


def test_sequential_memmap_wrap_sample(tmp_path):
    srb = SequentialReplayBuffer(
        buffer_size=6, n_envs=2, memmap=True, memmap_dir=tmp_path / "srb"
    )
    srb.add(_steps(0, 10, n_envs=2))
    srb.seed(0)
    batch = srb.sample(64, sequence_length=4, n_samples=2)["observations"]
    assert batch.shape == (2, 4, 64)
    diffs = np.diff(batch, axis=1)
    np.testing.assert_array_equal(diffs, np.ones_like(diffs))


def test_sequential_next_obs_is_shifted_window():
    srb = SequentialReplayBuffer(buffer_size=16, n_envs=1)
    srb.add(_steps(0, 10))
    srb.seed(0)
    batch = srb.sample(32, sequence_length=3, sample_next_obs=True)
    np.testing.assert_array_equal(
        batch["next_observations"], batch["observations"] + 1
    )


def test_sequential_rejects_sequence_longer_than_stored():
    srb = SequentialReplayBuffer(buffer_size=16, n_envs=1)
    srb.add(_steps(0, 4))
    with pytest.raises(ValueError, match="only contains 4 steps"):
        srb.sample(1, sequence_length=5)
    # when full, the cap is the buffer size itself
    srb.add(_steps(4, 20))
    with pytest.raises(ValueError, match="Cannot sample a sequence"):
        srb.sample(1, sequence_length=17)


# ---------------------------------------------------------------------------
# EpisodeBuffer: chunked episodes, prioritize_ends edges, eviction cleanup
# ---------------------------------------------------------------------------


def _episode(t0, length, n_envs=1):
    d = _steps(t0, t0 + length, n_envs)
    d["dones"] = np.zeros((length, n_envs), np.float32)
    d["dones"][-1] = 1.0
    return d


def test_episode_assembled_across_multiple_adds():
    eb = EpisodeBuffer(buffer_size=32, sequence_length=2, n_envs=1)
    first = _steps(0, 3)
    first["dones"] = np.zeros((3, 1), np.float32)
    eb.add(first)                  # open episode, nothing stored yet
    assert len(eb) == 0
    second = _steps(3, 5)
    second["dones"] = np.array([[0.0], [1.0]], np.float32)
    eb.add(second)                 # closes a 5-step episode
    assert len(eb) == 1
    np.testing.assert_array_equal(
        np.asarray(eb.buffer[0]["observations"]), [0, 1, 2, 3, 4]
    )


def test_prioritize_ends_reaches_final_window_and_clamps():
    # episode length == sequence_length: the only valid start is 0 even
    # though prioritize_ends draws raw starts up to ep_len-1 (clamp path)
    eb = EpisodeBuffer(buffer_size=64, sequence_length=4, n_envs=1, prioritize_ends=True)
    eb.add(_episode(0, 4))
    eb.seed(0)
    batch = eb.sample(64)["observations"]  # [1, sl, batch]
    np.testing.assert_array_equal(batch[0, :, 0], [0, 1, 2, 3])

    # longer episode: end-biased sampling must hit the final window far more
    # often than uniform would (uniform: 1/13 ≈ 7.7%; prioritized: ~4/16)
    eb2 = EpisodeBuffer(buffer_size=64, sequence_length=4, n_envs=1, prioritize_ends=True)
    eb2.add(_episode(0, 16))
    eb2.seed(0)
    starts = eb2.sample(512)["observations"][0, 0, :]  # first step of each window
    frac_last = float(np.mean(starts == 12))
    assert frac_last > 0.15, frac_last


def test_prioritize_ends_override_at_sample_time():
    eb = EpisodeBuffer(buffer_size=64, sequence_length=4, n_envs=1, prioritize_ends=False)
    eb.add(_episode(0, 16))
    eb.seed(0)
    starts = eb.sample(512, prioritize_ends=True)["observations"][0, 0, :]
    assert float(np.mean(starts == 12)) > 0.15


def test_episode_next_obs_stays_within_episode():
    eb = EpisodeBuffer(buffer_size=64, sequence_length=4, n_envs=1)
    eb.add(_episode(0, 10))
    eb.seed(0)
    batch = eb.sample(128, sample_next_obs=True)
    obs = batch["observations"][0]
    nxt = batch["next_observations"][0]
    np.testing.assert_array_equal(nxt, obs + 1)
    assert nxt.max() <= 9  # never reads past the episode end


def test_eviction_removes_memmap_files(tmp_path):
    eb = EpisodeBuffer(
        buffer_size=8, sequence_length=2, n_envs=1, memmap=True, memmap_dir=tmp_path / "eb"
    )
    eb.add(_episode(0, 5))
    eb.add(_episode(5, 5))  # 5+5 > 8: evicts the first episode
    assert len(eb) == 1
    ep_dirs = [d for d in os.listdir(tmp_path / "eb") if d.startswith("episode_")]
    assert len(ep_dirs) == 1  # the evicted episode's dir is gone
    np.testing.assert_array_equal(
        np.asarray(eb.buffer[0]["observations"]), [5, 6, 7, 8, 9]
    )


def test_episode_too_long_raises():
    eb = EpisodeBuffer(buffer_size=4, sequence_length=2, n_envs=1)
    with pytest.raises(RuntimeError, match="Invalid episode length"):
        eb.save_episode(_episode(0, 6))


def test_episode_state_dict_round_trip_with_open_episode():
    eb = EpisodeBuffer(buffer_size=32, sequence_length=2, n_envs=1)
    eb.add(_episode(0, 4))
    open_chunk = _steps(4, 7)
    open_chunk["dones"] = np.zeros((3, 1), np.float32)
    eb.add(open_chunk)  # leaves an open episode
    state = eb.state_dict()

    eb2 = EpisodeBuffer(buffer_size=32, sequence_length=2, n_envs=1)
    eb2.load_state_dict(state)
    assert len(eb2) == 1 and eb2._cum_length == 4
    closing = _steps(7, 8)
    closing["dones"] = np.ones((1, 1), np.float32)
    eb2.add(closing)  # the restored open chunk [4..6] closes as episode 4..7
    assert len(eb2) == 2
    np.testing.assert_array_equal(
        np.asarray(eb2.buffer[1]["observations"]), [4, 5, 6, 7]
    )


# ---------------------------------------------------------------------------
# EnvIndependentReplayBuffer: routing + coherence
# ---------------------------------------------------------------------------


def test_env_independent_routing_keeps_streams_coherent():
    rb = EnvIndependentReplayBuffer(
        buffer_size=16, n_envs=3, buffer_cls=SequentialReplayBuffer
    )
    # env 1 receives a different stream than envs 0/2, via explicit routing
    rb.add(_steps(0, 6, n_envs=2), env_idxes=[0, 2])
    rb.add(_steps(100, 106, n_envs=1), env_idxes=[1])
    rb.add(_steps(6, 10, n_envs=2), env_idxes=[0, 2])
    rb.add(_steps(106, 110, n_envs=1), env_idxes=[1])
    for b in rb.sample(64, sequence_length=3, n_samples=2).values():
        diffs = np.diff(b, axis=1)  # [n_samples, sl, batch], consecutive along sl
        # consecutive within each stream — env-1 steps never interleave
        np.testing.assert_array_equal(diffs, np.ones_like(diffs))
