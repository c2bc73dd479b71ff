"""Checkpoint -> eval round trip over N episodes (greedy + sampled).

The single-episode `sheeprl-tpu-eval` CLI matches the reference's protocol
(one sampled test episode — reference `dreamer_v3/evaluate.py` ends in
`test(..., sample_actions=True)`), but one episode is not evidence of
sustained reward. This tool loads a checkpoint, rebuilds the player exactly
like the eval CLI, and runs N episodes in each action mode with distinct
seeds, printing a JSON summary line:

    python tools/walker_eval.py <ckpt_path> [--episodes 5] [--seed0 100]

Greedy mode is the number to quote for "eval reward" (the actor's mode,
no exploration noise); sampled mode shows the stochastic-policy spread.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _ckpt_hash(path: str) -> str:
    """Stable short hash over the checkpoint tree (file names + sizes + mtimes-free)."""
    h = hashlib.sha256()
    for root, _, files in sorted(os.walk(path)):
        for f in sorted(files):
            fp = os.path.join(root, f)
            h.update(os.path.relpath(fp, path).encode())
            with open(fp, "rb") as fh:
                while True:
                    chunk = fh.read(1 << 20)
                    if not chunk:
                        break
                    h.update(chunk)
    return h.hexdigest()[:16]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("ckpt")
    ap.add_argument("--episodes", type=int, default=5)
    ap.add_argument("--seed0", type=int, default=100)
    args = ap.parse_args()

    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

    import jax
    import jax.numpy as jnp

    import sheeprl_tpu
    from sheeprl_tpu.cli import _load_run_config
    from sheeprl_tpu.config.instantiate import instantiate
    from sheeprl_tpu.utils.utils import dotdict, migrate_dv3_checkpoint, params_on_device

    sheeprl_tpu.register_algorithms()
    ckpt_path = os.path.abspath(args.ckpt)
    cfg, log_dir = _load_run_config(ckpt_path)
    cfg.env.num_envs = 1
    cfg.env.capture_video = False
    run_fabric = cfg.get("fabric", {}) or {}
    cfg.fabric = dotdict(
        {
            "_target_": "sheeprl_tpu.fabric.Fabric",
            "devices": 1,
            "num_nodes": 1,
            "strategy": "auto",
            "accelerator": "auto",
            "precision": "32-true",
            "prng_impl": run_fabric.get("prng_impl", "rbg"),
            "callbacks": [],
        }
    )
    fabric = instantiate(cfg.fabric)
    state = fabric.load(ckpt_path)

    import gymnasium as gym

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent, build_player_fns
    from sheeprl_tpu.algos.dreamer_v3.utils import normalize_obs_jnp, prepare_obs
    from sheeprl_tpu.utils.env import make_env

    probe_env = make_env(cfg, cfg.seed, 0, log_dir, "eval_probe")()
    observation_space = probe_env.observation_space
    action_space = probe_env.action_space
    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    probe_env.close()

    world_model, actor, critic, _ = build_agent(
        cfg, actions_dim, is_continuous, observation_space, jax.random.PRNGKey(cfg.seed)
    )
    # park the params on the accelerator ONCE: numpy leaves would re-upload
    # the full ~40 MB param tree on EVERY jitted player call
    params = params_on_device(migrate_dv3_checkpoint(state["agent"]["params"]))
    player_fns = build_player_fns(world_model, actor, cfg, actions_dim, is_continuous)
    cnn_keys = list(cfg.cnn_keys.encoder)
    mlp_keys = list(cfg.mlp_keys.encoder)

    def episode(seed: int, sample: bool) -> float:
        env = make_env(cfg, seed, 0, log_dir, "eval_tool")()
        obs = env.reset(seed=seed)[0]
        ep_state = player_fns["init_states"](params["world_model"], 1)
        act_fn = (
            player_fns["exploration_action"] if sample else player_fns["greedy_action"]
        )
        key = jax.random.PRNGKey(seed)
        done, total = False, 0.0
        while not done:
            prepared = prepare_obs(obs, cnn_keys, mlp_keys, 1)
            norm = normalize_obs_jnp(prepared, cnn_keys)
            key, k = jax.random.split(key)
            if sample:
                actions, ep_state = act_fn(
                    params["world_model"], params["actor"], ep_state, norm, k, jnp.float32(0.0)
                )
            else:
                actions, ep_state = act_fn(
                    params["world_model"], params["actor"], ep_state, norm, k
                )
            if len(np.asarray(actions[0]).shape) > 1 and not isinstance(
                env.action_space, gym.spaces.Box
            ):
                real = np.array([np.argmax(np.asarray(a), axis=-1) for a in actions])
            else:
                real = np.concatenate([np.asarray(a) for a in actions], -1)
            obs, reward, terminated, truncated, _ = env.step(
                real.reshape(env.action_space.shape)
            )
            done = terminated or truncated
            total += float(reward)
        env.close()
        return total

    results = {}
    for mode, sample in (("greedy", False), ("sampled", True)):
        rewards = [episode(args.seed0 + i, sample) for i in range(args.episodes)]
        results[mode] = {
            "rewards": [round(r, 1) for r in rewards],
            "mean": round(float(np.mean(rewards)), 1),
            "std": round(float(np.std(rewards)), 1),
        }
        print(f"{mode}: {results[mode]}", flush=True)

    print(
        json.dumps(
            {
                "metric": "walker_eval_round_trip",
                "ckpt": os.path.relpath(ckpt_path, REPO),
                "ckpt_sha256_16": _ckpt_hash(ckpt_path),
                "episodes_per_mode": args.episodes,
                **results,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
