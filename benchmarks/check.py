"""The comparison that decides ``correct``.

Three kinds of number, each held to a limit of its own:

- gaps between the program's train step and the plain reference's, at the
  timed sizes: each step's losses, the first gradient by the worst leaf, the
  parameters' change after the checked steps by the worst leaf;
- rows of the replay batches the program trained on that differ from what the
  benchmark's environment emitted (regenerated from the seed): limit 0;
- leaves of the acting parameters' host mirror that differ from the trained
  state: limit 0.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Tuple

import numpy as np

from benchmarks import traffic_env

#: a leaf whose reference gradient is under this share of the median leaf's
#: moves under Adam by round-off alone, and is left out of the change
DEAD_GRADIENT_SHARE = 1e-3


def relative_gap(program: float, reference: float) -> float:
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(
    program: Dict[str, float], reference: Dict[str, float], leave_out: Iterable[str] = ()
) -> Tuple[float, str]:
    """Largest gap between the two norms of one leaf, measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    skip = set(leave_out)
    names = [k for k in reference if k not in skip]
    median = statistics.median(float(reference[k]) for k in names)
    worst, worst_name = 0.0, ""
    for k in names:
        gap = abs(float(program[k]) - float(reference[k])) / max(float(reference[k]), median, 1e-30)
        if math.isnan(gap):  # a NaN gap is the worst there is
            return gap, k
        if gap > worst:
            worst, worst_name = gap, k
    return worst, worst_name


def dead_leaves(reference_grad_norms: Dict[str, float]) -> set:
    """Leaves whose gradient is nought to rounding in the reference."""
    median = statistics.median(float(v) for v in reference_grad_norms.values())
    return {k for k, v in reference_grad_norms.items() if float(v) < DEAD_GRADIENT_SHARE * median}


def staging_mismatches(batch: Dict[str, np.ndarray], env_params: dict, base_seed: int, envs: list) -> int:
    """Rows of a ``[T, B, ...]`` replay batch that are not what the
    environment emitted: the frame regenerated from the seed, its reward,
    its episode flags, the action the environment received for it, and
    frames in emission order along ``T``."""
    rgb = np.asarray(batch["rgb"])
    T, B = rgb.shape[:2]
    bad = 0
    ends_of: Dict[int, set] = {}
    for b in range(B):
        previous = None
        for t in range(T):
            index, env_id = traffic_env.frame_origin(rgb[t, b])
            if env_id >= len(envs):
                bad += 1
                previous = None
                continue
            seed = base_seed + env_id
            if env_id not in ends_of:
                upto = max(len(envs[env_id].actions), index) + 1
                ends_of[env_id] = set(
                    traffic_env.episode_ends(
                        seed, env_params["episode_len_min"], env_params["episode_len_max"], upto
                    ).tolist()
                )
            ends = ends_of[env_id]
            is_reset = index == 0 or (index - 1) in ends
            is_last = index in ends
            reward = 0.0 if is_reset else traffic_env.step_reward(seed, index)
            log = envs[env_id].actions
            action = log[index] if index < len(log) else None
            want_action = np.zeros(traffic_env.N_ACTIONS, np.float32)
            if action is not None and action >= 0:
                want_action[action] = 1.0
            ok = (
                np.array_equal(rgb[t, b].reshape(traffic_env.FRAME_SHAPE),
                               traffic_env.frame_pixels(seed, env_id, index))
                and float(batch["rewards"][t, b, 0]) == reward
                and float(batch["reward"][t, b, 0]) == reward
                and float(batch["dones"][t, b, 0]) == float(is_last)
                and float(batch["is_first"][t, b, 0]) == float(is_reset)
                and action is not None
                # a sampled action is one_hot + probs - probs: one, to an ulp
                and float(np.max(np.abs(np.asarray(batch["actions"][t, b]) - want_action))) < 1e-5
                and (previous is None or previous == (index - 1, env_id))
            )
            bad += 0 if ok else 1
            previous = (index, env_id)
    return bad


def verdict(numbers: Dict[str, float], limits: Dict[str, float], not_compared: Iterable[str] = ()) -> Tuple[bool, list]:
    """``correct`` and one ``[name, number, limit]`` row per number compared.
    A number with no limit, or a limit with no number, fails the run: a
    comparison that did not run is not a pass. The cell's limits file names
    under ``not_compared`` the numbers that are read and printed but held to
    nothing, because no control or fault gives them an upper reading
    (PERF.md has each with its readings); their rows carry the limit ``None``
    and come after the compared ones."""
    aside = set(not_compared)
    if aside & set(limits):
        raise ValueError(f"both limited and not compared: {sorted(aside & set(limits))}")
    rows, ok = [], True
    for name in sorted((set(numbers) | set(limits)) - aside):
        number, limit = numbers.get(name), limits.get(name)
        rows.append([name, number, limit])
        if number is None or limit is None or not (number <= limit):
            ok = False
    rows += [[name, numbers.get(name), None] for name in sorted(aside)]
    return ok, rows
