"""Plan2Explore-DV3 agent (reference ``sheeprl/algos/p2e_dv3/agent.py``
build_agent :33-219 and the ensemble construction in
``p2e_dv3_exploration.py:654-685``).

On top of the DV3 world model / actor / critic chassis this adds:

- an **ensemble** of N MLPs predicting the next stochastic state from
  ``(posterior, recurrent, action)`` — the reference builds N separate
  ``nn.Module``s with per-member seeds and loops over them; here the N
  parameter trees are *stacked* and applied with ``jax.vmap``, so all members
  run as one batched XLA program on the MXU instead of N kernel launches;
- a **dual actor** (task / exploration) sharing the Actor module definition
  (so one jitted player program serves both by swapping param trees);
- a dict of **exploration critics** (two-hot heads) keyed by name, each with
  its own EMA target and λ-return normalizer
  (``cfg.algo.critics_exploration``, reference agent.py:104-135).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import (
    ACTOR_UNIFORM_HEADS,
    CRITIC_UNIFORM_HEADS,
    WM_UNIFORM_HEADS,
    Actor,
    MLPWithHead,
    WorldModel,
    acting_params,  # noqa: F401  (the same player, so the same leaves)
    build_player_fns,  # noqa: F401  (players are identical; actor params select task/exploration)
    hafner_initialization,
    resolve_actor_distribution,
)
from sheeprl_tpu.models import MLP

import flax.linen as nn


class EnsembleMember(nn.Module):
    """One next-state predictor: MLP trunk + linear head emitting the flat
    stochastic state (reference exploration :658-681)."""

    output_dim: int
    mlp_layers: int
    dense_units: int
    layer_norm: bool = True
    activation: Any = "silu"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
        )(x)
        return nn.Dense(self.output_dim, name="head")(x)


def init_ensemble(
    member: EnsembleMember, n: int, input_dim: int, key: jax.Array
) -> Dict[str, Any]:
    """Stack N per-seed member param trees along a leading axis (the
    reference's per-member ``seed=cfg.seed + i``, exploration :656-681)."""
    keys = jax.random.split(key, n)
    dummy = jnp.zeros((1, input_dim), jnp.float32)
    trees = [member.init(k, dummy)["params"] for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def apply_ensemble(member: EnsembleMember, stacked_params: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    """``[N_members, ..., output_dim]`` — all members in one vmapped apply."""
    return jax.vmap(
        lambda p: member.apply({"params": p}, x), in_axes=0
    )(stacked_params)


def build_agent(
    cfg,
    actions_dim: Sequence[int],
    is_continuous: bool,
    observation_space,
    key: jax.Array,
) -> Tuple[WorldModel, Actor, MLPWithHead, EnsembleMember, Dict[str, Any]]:
    """Construct the P2E-DV3 module defs + initialized params.

    Returns ``(world_model, actor, critic, ensemble_member, params)`` with
    ``params = {world_model, actor_task, critic_task, target_critic_task,
    actor_exploration, critics_exploration: {k: {module, target}}, ensembles}``.
    """
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent as dv3_build_agent

    k_dv3, k_expl_actor, k_expl_critics, k_ens, k_ha, k_hc = jax.random.split(key, 6)
    world_model, actor, critic, dv3_params = dv3_build_agent(
        cfg, actions_dim, is_continuous, observation_space, k_dv3
    )
    wm_cfg = cfg.algo.world_model
    stoch_flat = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    rec_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    latent_size = stoch_flat + rec_size
    act_dim = int(np.sum(actions_dim))

    # exploration actor: same module def, fresh params
    actor_expl_params = actor.init(k_expl_actor, jnp.zeros((1, latent_size)))["params"]
    if bool(cfg.algo.hafner_initialization):
        actor_expl_params = hafner_initialization(actor_expl_params, k_ha, ACTOR_UNIFORM_HEADS)

    # exploration critics: one two-hot head + EMA target per configured name
    critics_expl: Dict[str, Any] = {}
    names = list(cfg.algo.critics_exploration.keys())
    critic_keys = jax.random.split(k_expl_critics, max(len(names), 1))
    hafner_keys = jax.random.split(k_hc, max(len(names), 1))
    for i, name in enumerate(names):
        cp = critic.init(critic_keys[i], jnp.zeros((1, latent_size)))["params"]
        if bool(cfg.algo.hafner_initialization):
            cp = hafner_initialization(cp, hafner_keys[i], CRITIC_UNIFORM_HEADS)
        critics_expl[name] = {
            "module": cp,
            "target": jax.tree_util.tree_map(jnp.copy, cp),
        }

    ens_cfg = cfg.algo.ensembles
    ensemble_member = EnsembleMember(
        output_dim=stoch_flat,
        mlp_layers=int(ens_cfg.mlp_layers),
        dense_units=int(ens_cfg.dense_units),
        layer_norm=bool(ens_cfg.layer_norm),
        activation=ens_cfg.dense_act,
    )
    ensembles = init_ensemble(
        ensemble_member, int(ens_cfg.n), latent_size + act_dim, k_ens
    )

    params = {
        "world_model": dv3_params["world_model"],
        "actor_task": dv3_params["actor"],
        "critic_task": dv3_params["critic"],
        "target_critic_task": dv3_params["target_critic"],
        "actor_exploration": actor_expl_params,
        "critics_exploration": critics_expl,
        "ensembles": ensembles,
    }
    return world_model, actor, critic, ensemble_member, params
