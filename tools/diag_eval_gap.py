"""Bisect a train-acting vs eval-acting reward gap on a DV3 checkpoint.

Round-5 postmortem tool. It drives several acting paths off one loaded
checkpoint; the variants that run are (in order):

  E. training-exact: template-ful restore, replicated device_put, packed
     player fns, the training loop's key-chain (SHEEPRL_DIAG_TRAIN_CHAIN=1
     replicates main()'s pre-loop split), optional greedy acting
     (SHEEPRL_ACT_GREEDY=1) and act-stream dump (SHEEPRL_ACT_DUMP=path)
  B. train-style vector acting with template-less-restored params
  A. eval-style single env (skipped with SHEEPRL_DIAG_ONLY_E=1)

Outcome of the round-5 investigation (record deleted in PR 21): with the DMC
seeding fix and the train key-chain, E reproduces the CLI training loop's
no-learning episodes BIT-EXACTLY — the historical gap came from the CLI
dropping resume overrides (so "no-learn" probes actually trained).

Usage: python tools/diag_eval_gap.py <ckpt> [--steps 4400]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("ckpt")
    ap.add_argument("--steps", type=int, default=2500)
    args = ap.parse_args()
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    import sheeprl_tpu
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent, build_player_fns
    from sheeprl_tpu.algos.dreamer_v3.utils import normalize_obs_jnp, prepare_obs
    from sheeprl_tpu.cli import _load_run_config
    from sheeprl_tpu.config.instantiate import instantiate
    from sheeprl_tpu.utils.env import make_env, vectorize_envs
    from sheeprl_tpu.utils.utils import dotdict, migrate_dv3_checkpoint, params_on_device

    sheeprl_tpu.register_algorithms()
    ckpt_path = os.path.abspath(args.ckpt)
    cfg, log_dir = _load_run_config(ckpt_path)
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

    probe = make_env(cfg, cfg.seed, 0, log_dir, "diag_probe")()
    observation_space, action_space = probe.observation_space, probe.action_space
    probe.close()
    actions_dim = tuple(action_space.shape)
    world_model, actor, critic, _ = build_agent(
        cfg, actions_dim, True, observation_space, jax.random.PRNGKey(cfg.seed)
    )
    params = params_on_device(migrate_dv3_checkpoint(state["agent"]["params"]))
    player_fns = build_player_fns(world_model, actor, cfg, actions_dim, True)
    cnn_keys, mlp_keys = list(cfg.cnn_keys.encoder), list(cfg.mlp_keys.encoder)

    def single_env_episode(seed: int, raw: bool):
        env = make_env(cfg, seed, 0, log_dir, "diag")()
        obs = env.reset(seed=seed)[0]
        ep_state = player_fns["init_states"](params["world_model"], 1)
        key = jax.random.PRNGKey(seed)
        fn = player_fns["exploration_action_raw" if raw else "exploration_action"]
        done, total, steps = False, 0.0, 0
        while not done:
            prepared = prepare_obs(obs, cnn_keys, mlp_keys, 1)
            feed = prepared if raw else normalize_obs_jnp(prepared, cnn_keys)
            key, k = jax.random.split(key)
            acts, ep_state = fn(
                params["world_model"], params["actor"], ep_state, feed, k, jnp.float32(0.0)
            )
            real = np.concatenate([np.asarray(a) for a in acts], -1)
            obs, r, term, trunc, _ = env.step(real.reshape(env.action_space.shape))
            done = term or trunc
            total += float(r)
            steps += 1
        env.close()
        return total, steps

    n_envs = int(cfg.env.num_envs)
    def vector_train_style(steps_budget: int):
        thunks = [
            make_env(cfg, cfg.seed + i, 0, log_dir, "diag_vec", vector_env_idx=i)
            for i in range(n_envs)
        ]
        envs = vectorize_envs(thunks, cfg)
        o = envs.reset(seed=cfg.seed)[0]
        obs = prepare_obs({k: np.asarray(o[k]) for k in o}, cnn_keys, mlp_keys, n_envs)
        ep_state = player_fns["init_states"](params["world_model"], n_envs)
        key = jax.random.PRNGKey(cfg.seed)
        rewards = []
        for _ in range(steps_budget // n_envs):
            key, k = jax.random.split(key)
            acts, ep_state = player_fns["exploration_action_raw"](
                params["world_model"], params["actor"], ep_state, obs, k,
                jnp.float32(0.0),
            )
            actions = np.concatenate([np.asarray(a) for a in acts], -1)
            o, r, term, trunc, infos = envs.step(actions.reshape(envs.action_space.shape))
            dones = np.logical_or(term, trunc).astype(np.float32)
            if "final_info" in infos:
                fi = infos["final_info"]
                if isinstance(fi, dict) and "episode" in fi:
                    mask = np.asarray(fi.get("_episode", []), dtype=bool)
                    for i in np.nonzero(mask)[0]:
                        rewards.append(float(fi["episode"]["r"][i]))
            obs = prepare_obs({k: np.asarray(o[k]) for k in o}, cnn_keys, mlp_keys, n_envs)
            if dones.any():
                reset_mask = dones.reshape(n_envs, 1)
                ep_state = player_fns["reset_states"](
                    params["world_model"], ep_state, jnp.asarray(reset_mask)
                )
        envs.close()
        return rewards

    # E/F: the bit-exact training acting path — template-ful restore,
    # replicated device_put, fresh-init packed template, packed player fns
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_optimizers_and_state

    wm2, actor2, critic2, fresh = build_agent(
        cfg, actions_dim, True, observation_space, jax.random.PRNGKey(cfg.seed)
    )
    _, _, _, agent_state_t = build_optimizers_and_state(cfg, fresh)
    template = {
        "agent": agent_state_t,
        "expl_decay_steps": 0, "update": 0, "batch_size": 0,
        "last_log": 0, "last_checkpoint": 0,
    }
    state_t = fabric.load(ckpt_path, template)
    agent_state = jax.device_put(state_t["agent"], fabric.replicated)
    packed_template = {"wm": fresh["world_model"], "actor": fresh["actor"]}
    player_fns2 = build_player_fns(
        wm2, actor2, cfg, actions_dim, True, packed_template=packed_template
    )
    from jax.flatten_util import ravel_pytree

    pack_fn = jax.jit(lambda t: ravel_pytree(t)[0])
    play_packed = pack_fn(
        {"wm": agent_state["params"]["world_model"], "actor": agent_state["params"]["actor"]}
    )

    def packed_vector(steps_budget: int):
        import pickle

        dump_path = os.environ.get("SHEEPRL_ACT_DUMP")
        thunks = [
            make_env(cfg, cfg.seed + i, 0, log_dir, "diag_packed", vector_env_idx=i)
            for i in range(n_envs)
        ]
        envs = vectorize_envs(thunks, cfg)
        o = envs.reset(seed=cfg.seed)[0]
        obs = prepare_obs({k: np.asarray(o[k]) for k in o}, cnn_keys, mlp_keys, n_envs)
        if dump_path:
            with open(dump_path, "ab") as _f:
                pickle.dump(
                    {"step": -1, **{k2: np.asarray(obs[k2]) for k2 in mlp_keys}}, _f
                )
        ep_state = player_fns2["init_states"](agent_state["params"]["world_model"], n_envs)
        key = jax.random.PRNGKey(cfg.seed)
        if os.environ.get("SHEEPRL_DIAG_TRAIN_CHAIN"):
            # replicate main()'s exact pre-loop key consumption (one split
            # for build_key at dreamer_v3.py:592) so act keys match the
            # training loop bit-for-bit
            key, _ = jax.random.split(key)
        rewards = []
        for t in range(steps_budget // n_envs):
            key, k = jax.random.split(key)
            if os.environ.get("SHEEPRL_ACT_GREEDY"):
                acts, ep_state = player_fns2["greedy_action_packed"](
                    play_packed, ep_state, obs, k
                )
            else:
                acts, ep_state = player_fns2["exploration_action_packed"](
                    play_packed, ep_state, obs, k, jnp.float32(0.0)
                )
            actions = np.concatenate([np.asarray(a) for a in acts], -1)
            o, r, term, trunc, infos = envs.step(actions.reshape(envs.action_space.shape))
            dones = np.logical_or(term, trunc).astype(np.float32)
            if "final_info" in infos:
                fi = infos["final_info"]
                if isinstance(fi, dict) and "episode" in fi:
                    mask = np.asarray(fi.get("_episode", []), dtype=bool)
                    for i in np.nonzero(mask)[0]:
                        rewards.append(float(fi["episode"]["r"][i]))
            obs = prepare_obs({k: np.asarray(o[k]) for k in o}, cnn_keys, mlp_keys, n_envs)
            if dump_path and t < 1000:
                with open(dump_path, "ab") as _f:
                    pickle.dump(
                        {
                            "step": t,
                            "actions": actions,
                            "act_key": np.asarray(jax.random.key_data(k)),
                            "rewards": np.asarray(r, np.float32).reshape(n_envs, 1),
                            "dones": dones,
                            "rec_norm": float(
                                np.linalg.norm(np.asarray(ep_state["recurrent"]))
                            ),
                            "packed_digest": float(np.abs(np.asarray(play_packed)).sum()),
                            **{k2: np.asarray(obs[k2]) for k2 in mlp_keys},
                        },
                        _f,
                    )
            if dones.any():
                ep_state = player_fns2["reset_states_packed"](
                    play_packed, ep_state, jnp.asarray(dones.reshape(n_envs, 1))
                )
        envs.close()
        return rewards

    rewards = packed_vector(args.steps)
    print(
        f"E training-exact packed {n_envs}-env vector over {args.steps} steps: "
        f"episodes={[round(x, 1) for x in rewards]}", flush=True
    )
    if os.environ.get("SHEEPRL_DIAG_ONLY_E"):
        return
    rewards = vector_train_style(args.steps)
    print(
        f"B train-style {n_envs}-env vector (template-less params) over {args.steps} steps: "
        f"episodes={[round(x, 1) for x in rewards]}", flush=True
    )
    r, steps = single_env_episode(100, raw=False)
    print(f"A eval-style single env (seed 100, normalized): {r:.1f} over {steps} steps", flush=True)


if __name__ == "__main__":
    main()
