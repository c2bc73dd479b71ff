"""One builder for "a family's real train step on synthetic data".

The same setup — compose a config, stand up a 1-device Fabric, build the
agent through the family's *real* ``build_agent`` / ``build_train_fn``
wiring, synthesize a correctly-shaped batch, warm up — existed in four
places before this module (``bench_dreamer.py``, ``tools/profile_step.py``,
and the ``tools/diag_dv3_*`` one-offs), each hard-wired to one family.
:func:`build_harness` is the one implementation: every family the roofline
report profiles (all Dreamer generations, their P2E exploration variants,
SAC, PPO) builds through it, so a profiled number always measures the same
program the training loop dispatches.

The returned :class:`Harness` runs dispatches (threading the donated state
functionally), exposes the jitted program + pre-captured abstract arg specs
for ``cost_analysis`` (donation-safe), and hands back the raw pieces
(``world_model``/``actor``/…) for diagnostic tools that probe beyond
stepping.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

__all__ = ["FAMILIES", "Harness", "build_harness", "tiny_overrides"]

#: family -> (algo module, default exp override, train program takes tau)
FAMILIES: Dict[str, Tuple[str, str, bool]] = {
    "dv1": ("dreamer_v1", "dreamer_v1", False),
    "dv2": ("dreamer_v2", "dreamer_v2_ms_pacman", True),
    "dv3": ("dreamer_v3", "dreamer_v3_100k_ms_pacman", True),
    "p2e_dv1": ("p2e_dv1", "p2e_dv1_exploration", False),
    "p2e_dv2": ("p2e_dv2", "p2e_dv2_exploration", True),
    "p2e_dv3": ("p2e_dv3", "p2e_dv3_exploration", True),
    "sac": ("sac", "sac", False),
    "ppo": ("ppo", "ppo", False),
}

#: the tiny preset keeps a full-wiring train step CPU-feasible (the same
#: shrink the policy-improvement tests use); SAC/PPO are already small
_DREAMER_TINY = (
    "per_rank_batch_size=4",
    "per_rank_sequence_length=8",
    "algo.horizon=5",
    "algo.dense_units=32",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.transition_model.hidden_size=32",
    "algo.world_model.representation_model.hidden_size=32",
    "cnn_keys.encoder=[rgb]",
)
_FAMILY_TINY = {
    "dv1": ("algo.world_model.stochastic_size=8",),
    "dv2": ("algo.world_model.stochastic_size=8", "algo.world_model.discrete_size=8"),
    "dv3": ("algo.world_model.stochastic_size=8", "algo.world_model.discrete_size=8"),
    "p2e_dv1": ("algo.world_model.stochastic_size=8",),
    "p2e_dv2": ("algo.world_model.stochastic_size=8", "algo.world_model.discrete_size=8"),
    "p2e_dv3": ("algo.world_model.stochastic_size=8", "algo.world_model.discrete_size=8"),
    "sac": (),
    "ppo": (),
}


def tiny_overrides(family: str) -> Tuple[str, ...]:
    """Config overrides shrinking ``family``'s model to CPU scale."""
    if family in ("sac", "ppo"):
        return _FAMILY_TINY[family]
    return _DREAMER_TINY + _FAMILY_TINY[family]


class Harness:
    """A runnable train step: ``run(n)`` dispatches n programs and blocks.

    ``jit_fn``/``arg_specs`` feed ``cost_of`` (specs are captured before the
    first call — the programs donate their state buffers). ``pieces`` holds
    the family's raw build products for diagnostic probing.
    """

    def __init__(
        self,
        family: str,
        cfg,
        fabric,
        jit_fn,
        arg_specs: Tuple[Any, ...],
        step_fn: Callable[[int], Any],
        block_fn: Callable[[Any], None],
        pieces: Dict[str, Any],
    ):
        self.family = family
        self.cfg = cfg
        self.fabric = fabric
        self.jit_fn = jit_fn
        self.arg_specs = arg_specs
        self._step_fn = step_fn
        self._block_fn = block_fn
        self.pieces = pieces
        self.steps_per_dispatch = 1
        self.dispatches = 0

    def run(self, n: int = 1) -> None:
        """Dispatch ``n`` train programs and block on the last result."""
        out = None
        for _ in range(int(n)):
            out = self._step_fn(self.dispatches)
            self.dispatches += 1
        if out is not None:
            self._block_fn(out)

    @property
    def state(self):
        """The live (donated-and-rethreaded) train state, where exposed."""
        box = self.pieces.get("state_box")
        return box["state"] if box else None

    def cost(self) -> Optional[Dict[str, float]]:
        """``{"flops", "bytes_accessed"}`` of one dispatch, or None."""
        from sheeprl_tpu.obs.prof.roofline import cost_of

        return cost_of(self.jit_fn, *self.arg_specs)


def build_harness(
    family: str,
    overrides: Sequence[str] = (),
    tiny: bool = False,
    seed: int = 0,
    actions: Optional[int] = None,
    exp: Optional[str] = None,
) -> Harness:
    """Build ``family``'s train step on synthetic data (compiled, unwarmed —
    the first ``run`` pays the compile). ``actions`` overrides the dreamer
    families' synthetic discrete action count (default 9, MsPacman's);
    ``exp`` swaps the composed experiment preset (diagnostic tools pin the
    bare family exp instead of the benched 100k preset)."""
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; choose from {sorted(FAMILIES)}")
    import jax

    # same pin as Fabric.launch: uncommitted eager init work runs on the
    # host CPU; the step's inputs are committed to the mesh
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    ovr = list(tiny_overrides(family) if tiny else ()) + list(overrides)
    if family in ("sac", "ppo"):
        return _build_flat(family, ovr, seed)
    return _build_dreamer(family, ovr, seed, actions, exp)


def _compose(exp: str, overrides: Sequence[str]):
    from sheeprl_tpu.config.engine import compose

    return compose(
        "config",
        overrides=[
            f"exp={exp}",
            "env=dummy",
            "env.id=discrete_dummy",
            "metric.log_level=0",
            "buffer.checkpoint=False",
            "checkpoint.every=1000000",
            *overrides,
        ],
    )


def _fabric(cfg):
    from sheeprl_tpu.fabric import Fabric

    return Fabric(
        devices=cfg.fabric.get("devices", 1),
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=cfg.fabric.get("precision", "32-true"),
    )


# -- dreamer generations + their P2E exploration variants ---------------------


def _build_dreamer(
    family: str,
    overrides: Sequence[str],
    seed: int,
    actions: Optional[int] = None,
    exp: Optional[str] = None,
) -> Harness:
    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.obs.perf import shape_specs

    module_name, default_exp, has_tau = FAMILIES[family]
    cfg = _compose(exp or default_exp, overrides)
    fabric = _fabric(cfg)
    agent_mod = importlib.import_module(f"sheeprl_tpu.algos.{module_name}.agent")
    algo_name = module_name + ("_exploration" if family.startswith("p2e") else "")
    algo_mod = importlib.import_module(f"sheeprl_tpu.algos.{module_name}.{algo_name}")

    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (int(actions or dict(cfg.get("bench", {})).get("actions", 9)),)
    key = jax.random.PRNGKey(seed)

    pieces: Dict[str, Any] = {"cfg": cfg, "fabric": fabric}
    if family.startswith("p2e"):
        from sheeprl_tpu.config.instantiate import instantiate

        world_model, actor, critic, ensemble_member, params = agent_mod.build_agent(
            cfg, actions_dim, False, obs_space, key
        )
        per_critic = family == "p2e_dv3"  # dict of exploration critics
        txs = {
            "world_model": instantiate(
                cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients
            ),
            "ensembles": instantiate(
                cfg.algo.ensembles.optimizer, max_grad_norm=cfg.algo.ensembles.clip_gradients
            ),
            "actor_task": instantiate(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients),
            "critic_task": instantiate(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients),
            "actor_exploration": instantiate(
                cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients
            ),
            ("critics_exploration" if per_critic else "critic_exploration"): instantiate(
                cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients
            ),
        }
        opt = {
            "world_model": txs["world_model"].init(params["world_model"]),
            "ensembles": txs["ensembles"].init(params["ensembles"]),
            "actor_task": txs["actor_task"].init(params["actor_task"]),
            "critic_task": txs["critic_task"].init(params["critic_task"]),
            "actor_exploration": txs["actor_exploration"].init(params["actor_exploration"]),
        }
        if per_critic:
            opt["critics_exploration"] = {
                k: txs["critics_exploration"].init(params["critics_exploration"][k]["module"])
                for k in params["critics_exploration"]
            }
        else:
            opt["critic_exploration"] = txs["critic_exploration"].init(
                params["critic_exploration"]
            )
        agent_state: Dict[str, Any] = {"params": params, "opt": opt}
        if family == "p2e_dv3":
            from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import init_moments

            agent_state["moments"] = {
                "task": init_moments(),
                "exploration": {k: init_moments() for k in params["critics_exploration"]},
            }
        train_fn = algo_mod.build_train_fn(
            world_model, actor, critic, ensemble_member, txs, cfg, fabric, actions_dim, False
        )
        pieces.update(ensemble_member=ensemble_member)
    else:
        world_model, actor, critic, params = agent_mod.build_agent(
            cfg, actions_dim, False, obs_space, key
        )
        world_tx, actor_tx, critic_tx, agent_state = algo_mod.build_optimizers_and_state(
            cfg, params
        )
        train_fn = algo_mod.build_train_fn(
            world_model, actor, critic, world_tx, actor_tx, critic_tx,
            cfg, fabric, actions_dim, False,
        )
    pieces.update(
        world_model=world_model, actor=actor, critic=critic, params=params,
        train_fn=train_fn,
    )

    T, B = int(cfg.per_rank_sequence_length), int(cfg.per_rank_batch_size)
    rng = np.random.default_rng(seed)
    batch = jax.device_put(
        {
            "rgb": jnp.asarray(rng.integers(0, 256, (T, B, 3, 64, 64)).astype(np.uint8)),
            "actions": jnp.asarray(
                np.eye(actions_dim[0], dtype=np.float32)[
                    rng.integers(0, actions_dim[0], (T, B))
                ]
            ),
            "rewards": jnp.asarray(rng.normal(size=(T, B, 1)).astype(np.float32)),
            "dones": jnp.zeros((T, B, 1), jnp.float32),
            "is_first": jnp.zeros((T, B, 1), jnp.float32),
        },
        fabric.sharding(None, fabric.data_axis),
    )
    agent_state = jax.device_put(agent_state, fabric.replicated)
    pieces["batch"] = batch

    state_box = {"state": agent_state}
    pieces["state_box"] = state_box  # live train state (Harness.state)
    tau0 = jnp.float32(1.0)

    def step_fn(i: int):
        key_i = jax.random.PRNGKey(seed + 1 + i)
        tau = tau0 if i == 0 else jnp.float32(0.02)
        if has_tau:
            out = train_fn(state_box["state"], batch, key_i, tau)
        else:
            out = train_fn(state_box["state"], batch, key_i)
        state_box["state"] = out[0]
        return out[1]

    def block_fn(metrics):
        leaf = jax.tree_util.tree_leaves(metrics)[0]
        np.asarray(leaf)

    if has_tau:
        arg_specs = shape_specs((agent_state, batch, jax.random.PRNGKey(0), tau0))
    else:
        arg_specs = shape_specs((agent_state, batch, jax.random.PRNGKey(0)))

    return Harness(family, cfg, fabric, train_fn, tuple(arg_specs), step_fn, block_fn, pieces)


# -- SAC / PPO ----------------------------------------------------------------


def _build_flat(family: str, overrides: Sequence[str], seed: int) -> Harness:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.obs.perf import shape_specs
    from sheeprl_tpu.config.instantiate import instantiate

    _module, exp, _ = FAMILIES[family]
    rng = np.random.default_rng(seed)

    if family == "sac":
        from sheeprl_tpu.algos.sac.agent import SACActor, SACCritic, build_agent_state
        from sheeprl_tpu.algos.sac.sac import build_train_fn

        cfg = _compose(exp, overrides)
        fabric = _fabric(cfg)
        obs_dim, act_dim = 8, 2  # LunarLanderContinuous-v3, the exp's env
        actor = SACActor(action_dim=act_dim, hidden_size=cfg.algo.actor.hidden_size)
        critic = SACCritic(hidden_size=cfg.algo.critic.hidden_size, num_critics=1)
        agent_state = build_agent_state(
            actor, critic, jax.random.PRNGKey(seed), int(cfg.algo.critic.n),
            obs_dim, act_dim, cfg.algo.alpha.alpha,
        )
        qf_tx = instantiate(cfg.algo.critic.optimizer)
        actor_tx = instantiate(cfg.algo.actor.optimizer)
        alpha_tx = instantiate(cfg.algo.alpha.optimizer)
        opt_states = {
            "actor": actor_tx.init(agent_state["actor"]),
            "qf": qf_tx.init(agent_state["critics"]),
            "alpha": alpha_tx.init(agent_state["log_alpha"]),
        }
        scale, bias = np.ones(act_dim, np.float32), np.zeros(act_dim, np.float32)
        train_fn = build_train_fn(
            actor, critic, actor_tx, qf_tx, alpha_tx, cfg, fabric, scale, bias,
            target_entropy=-float(act_dim),
        )
        G, B = 1, int(cfg.per_rank_batch_size)
        batch = jax.device_put(
            {
                "observations": jnp.asarray(rng.normal(size=(G, B, obs_dim)).astype(np.float32)),
                "next_observations": jnp.asarray(rng.normal(size=(G, B, obs_dim)).astype(np.float32)),
                "actions": jnp.asarray(rng.uniform(-1, 1, (G, B, act_dim)).astype(np.float32)),
                "rewards": jnp.asarray(rng.normal(size=(G, B, 1)).astype(np.float32)),
                "dones": jnp.zeros((G, B, 1), jnp.float32),
            },
            fabric.sharding(None, fabric.data_axis),
        )
        agent_state = jax.device_put(agent_state, fabric.replicated)
        opt_states = jax.device_put(opt_states, fabric.replicated)
        box = {"state": agent_state, "opt": opt_states}
        do_ema = jnp.bool_(True)

        def step_fn(i: int):
            out = train_fn(
                box["state"], box["opt"], batch, jax.random.PRNGKey(seed + 1 + i), do_ema
            )
            box["state"], box["opt"] = out[0], out[1]
            return out[2]

        arg_specs = shape_specs(
            (agent_state, opt_states, batch, jax.random.PRNGKey(0), do_ema)
        )
        pieces = {"cfg": cfg, "fabric": fabric, "actor": actor, "critic": critic,
                  "train_fn": train_fn, "batch": batch}
    else:  # ppo
        from sheeprl_tpu.algos.ppo.agent import build_agent
        from sheeprl_tpu.algos.ppo.ppo import build_update_fn

        cfg = _compose(exp, overrides + ["cnn_keys.encoder=[]", "mlp_keys.encoder=[state]"])
        fabric = _fabric(cfg)
        actions_dim, obs_dim = (2,), 4  # CartPole-v1, the exp's env
        agent = build_agent(cfg, actions_dim, False, (), ("state",))
        params = agent.init(
            jax.random.PRNGKey(seed), {"state": jnp.zeros((1, obs_dim), jnp.float32)}
        )["params"]
        tx = instantiate(cfg.algo.optimizer, max_grad_norm=cfg.algo.max_grad_norm or None)
        opt_state = tx.init(params)
        n_local = int(cfg.algo.rollout_steps) * int(cfg.env.num_envs)
        update_fn = build_update_fn(agent, tx, cfg, fabric, n_local)
        data = jax.device_put(
            {
                "state": jnp.asarray(rng.normal(size=(n_local, obs_dim)).astype(np.float32)),
                "actions": jnp.asarray(
                    rng.integers(0, actions_dim[0], (n_local, 1)).astype(np.float32)
                ),
                "logprobs": jnp.asarray(rng.normal(size=(n_local, 1)).astype(np.float32)),
                "values": jnp.asarray(rng.normal(size=(n_local, 1)).astype(np.float32)),
                "advantages": jnp.asarray(rng.normal(size=(n_local, 1)).astype(np.float32)),
                "returns": jnp.asarray(rng.normal(size=(n_local, 1)).astype(np.float32)),
            },
            fabric.replicated if cfg.buffer.share_data else fabric.data_sharding,
        )
        params = jax.device_put(params, fabric.replicated)
        opt_state = jax.device_put(opt_state, fabric.replicated)
        box = {"params": params, "opt": opt_state}
        clip, ent = jnp.float32(cfg.algo.clip_coef), jnp.float32(cfg.algo.ent_coef)

        def step_fn(i: int):
            out = update_fn(
                box["params"], box["opt"], data, jax.random.PRNGKey(seed + 1 + i), clip, ent
            )
            box["params"], box["opt"] = out[0], out[1]
            return out[2]

        train_fn = update_fn
        arg_specs = shape_specs(
            (params, opt_state, data, jax.random.PRNGKey(0), clip, ent)
        )
        pieces = {"cfg": cfg, "fabric": fabric, "agent": agent, "train_fn": update_fn,
                  "batch": data}

    def block_fn(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(leaf)

    return Harness(
        family, pieces["cfg"], pieces["fabric"], pieces["train_fn"],
        tuple(arg_specs), step_fn, block_fn, pieces,
    )
