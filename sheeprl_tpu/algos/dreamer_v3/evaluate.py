"""DreamerV3 evaluation (reference ``sheeprl/algos/dreamer_v3/evaluate.py``),
collapsed onto the shared eval service via the common dreamer-family
builder."""

from __future__ import annotations

from typing import Any, Dict

import gymnasium as gym
import jax

from sheeprl_tpu.algos.dreamer_v3.agent import build_agent, build_player_fns
from sheeprl_tpu.evals.builders import actions_dim_of, dreamer_eval_policy
from sheeprl_tpu.evals.service import EvalPolicy, register_eval_builder, run_eval_entrypoint
from sheeprl_tpu.utils.registry import register_evaluation
from sheeprl_tpu.utils.utils import migrate_dv3_checkpoint, params_on_device


@register_eval_builder(algorithms=["dreamer_v3"])
def dreamer_v3_eval_policy(fabric, cfg, state, observation_space, action_space) -> EvalPolicy:
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.cnn_keys.encoder) + len(cfg.mlp_keys.encoder) == 0:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`cnn_keys.encoder=[rgb]` or `mlp_keys.encoder=[state]`"
        )
    actions_dim, is_continuous = actions_dim_of(action_space)
    world_model, actor, _, _ = build_agent(
        cfg, actions_dim, is_continuous, observation_space, jax.random.PRNGKey(cfg.seed)
    )
    # device_put once: numpy param leaves would re-upload the whole tree on
    # every jitted player step
    params = params_on_device(migrate_dv3_checkpoint(state["agent"]["params"]))
    player_fns = build_player_fns(world_model, actor, cfg, actions_dim, is_continuous)
    # sample_actions=True with zero noise: DV3's historical test-time mode
    return dreamer_eval_policy(player_fns, params, cfg, is_continuous, sample_actions=True)


@register_evaluation(algorithms=["dreamer_v3"])
def evaluate_dreamer_v3(fabric, cfg: Dict[str, Any], state: Dict[str, Any]):
    run_eval_entrypoint(fabric, cfg, state)
