"""The benchmark's traffic: a seeded pixel environment with a stated step cost.

The program builds it through ``env.wrapper._target_`` (Hydra data in the
cell's traffic file; no program edit). Every frame, reward and episode end is
a pure function of ``(seed, frame index)``, so the check that decides
``correct`` can regenerate what the environment emitted from the seed alone
and hold the replay batch the program trained on to it, byte for byte.

One frame is emitted per ``reset`` and per ``step``; ``frame index`` counts
them for the life of the env, across episodes. The first eight bytes of a
frame carry ``(frame index, env id)`` so a replay row names its origin.

Env 0 stamps ``time.perf_counter`` at the start of every ``step``, in the
process that holds the chip (the vector env is the in-process sync one); the
stamps go to the :class:`~benchmarks.window.Recorder` the harness attached.
"""

from __future__ import annotations

import time
from typing import Optional

import gymnasium as gym
import numpy as np

#: the harness's recorder; set by :func:`attach`. The program instantiates the
#: env from config data, so the recorder cannot be passed as an argument.
_RECORDER = None
#: every env the program built, in order, so the check can read action logs
_ENVS: list = []

HEADER_BYTES = 8
N_ACTIONS = 17  # Crafter's discrete action count
FRAME_SHAPE = (3, 64, 64)  # CHW uint8, Crafter's 64x64 RGB


def attach(recorder) -> None:
    """Install the harness's recorder and forget envs of an earlier run."""
    global _RECORDER
    _RECORDER = recorder
    _ENVS.clear()


def built_envs() -> list:
    return list(_ENVS)


def episode_ends(seed: int, len_min: int, len_max: int, upto_frame: int) -> np.ndarray:
    """Frame indices of every episode's last frame, up to ``upto_frame``.

    An episode is one reset frame followed by ``L`` step frames, ``L`` drawn
    from the seed; the reset frame of the next episode follows its last."""
    rng = np.random.default_rng([int(seed), 0xE9])
    ends, last = [], -1
    while last < upto_frame:
        last += 1 + int(rng.integers(len_min, len_max + 1))
        ends.append(last)
    return np.asarray(ends, np.int64)


#: brightness levels in 256ths, a factor of 1.5 apart. A stretch of
#: ``LEVEL_FRAMES`` frames shares one, so the sequences of a replay batch
#: differ from each other in more than noise: a batch with rows left out or
#: repeated has another loss and other gradients, which ``correct`` can see.
LEVELS = (15, 22, 34, 51, 76, 114, 171, 256)
LEVEL_FRAMES = 64


def frame_pixels(seed: int, env_id: int, index: int) -> np.ndarray:
    """The frame env ``env_id`` emits as its ``index``-th, as uint8 CHW."""
    level = LEVELS[int(np.random.default_rng([int(seed), int(index) // LEVEL_FRAMES, 0x1B]).integers(len(LEVELS)))]
    rng = np.random.default_rng([int(seed), int(index)])
    noise = rng.integers(0, 256, FRAME_SHAPE, dtype=np.uint16)
    frame = ((noise * level) >> 8).astype(np.uint8)
    head = np.array([index, env_id], dtype="<u4").view(np.uint8)
    frame.reshape(-1)[:HEADER_BYTES] = head
    return frame


def frame_origin(frame: np.ndarray) -> tuple:
    """``(frame index, env id)`` read back from a frame's header."""
    head = np.ascontiguousarray(frame).reshape(-1)[:HEADER_BYTES].view("<u4")
    return int(head[0]), int(head[1])


def step_reward(seed: int, index: int) -> float:
    """Reward that arrives with step frame ``index`` (reset frames carry 0)."""
    return float(np.random.default_rng([int(seed), int(index), 0x7E]).random() < 0.1)


class PixelEnv(gym.Env):
    """64x64x3 uint8 pixels, 17 discrete actions, a scalar reward, episodes of
    a seeded length, and a busy-wait to ``step_ms`` in every ``step``."""

    def __init__(
        self,
        seed: int,
        step_ms: float = 4.0,
        episode_len_min: int = 200,
        episode_len_max: int = 400,
        base_seed: Optional[int] = None,
    ):
        self.seed = int(seed)
        # make_vector_env seeds env i with run seed + i
        self.env_id = self.seed - int(base_seed if base_seed is not None else seed)
        self.step_s = float(step_ms) / 1e3
        self.len_min, self.len_max = int(episode_len_min), int(episode_len_max)
        self.observation_space = gym.spaces.Box(0, 255, FRAME_SHAPE, np.uint8)
        self.action_space = gym.spaces.Discrete(N_ACTIONS)
        self.reward_range = (0.0, 1.0)
        self.index = -1  # frames emitted so far, minus one
        self.actions: list = []  # actions[i] was applied to frame i
        self._ends = episode_ends(self.seed, self.len_min, self.len_max, 4096)
        self._next_end = 0
        _ENVS.append(self)

    def _emit(self) -> np.ndarray:
        self.index += 1
        return frame_pixels(self.seed, self.env_id, self.index)

    def reset(self, *, seed: Optional[int] = None, options=None):
        if self.index >= 0:
            # a reset frame is acted on by nothing: the program writes a zero
            # action beside the terminal frame that precedes it
            self.actions.append(-1)
        return self._emit(), {}

    def step(self, action):
        t0 = time.perf_counter()
        if self.env_id == 0 and _RECORDER is not None:
            _RECORDER.on_env_step(t0)
        self.actions.append(int(action))
        frame = self._emit()
        if self._ends[-1] < self.index:
            self._ends = episode_ends(self.seed, self.len_min, self.len_max, 2 * self.index)
        while self._ends[self._next_end] < self.index:
            self._next_end += 1
        terminated = bool(self._ends[self._next_end] == self.index)
        reward = step_reward(self.seed, self.index)
        while time.perf_counter() - t0 < self.step_s:
            pass
        return frame, reward, terminated, False, {}

    def render(self):
        return None

    def close(self):
        pass
