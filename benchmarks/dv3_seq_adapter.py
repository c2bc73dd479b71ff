"""Hooks for a DreamerV3 run whose world-model core is a sequence model
(``algo.world_model.sequence_model``), beside ``dv3_adapter.py``.

The family adapter of ``dv3_adapter`` does the work that is the same: the
benchmark's weights in place of the program's, the first gradient steps
through the burst's own executable one at a time, the faults. What differs:

- the configuration's sizes are held to other config paths (the core's block);
- acting runs on the device through ``DeviceActor.rollout``, which is where the
  window's end is noticed;
- no host mirror exists, so there is nothing of it to compare;
- **dropped pairs**: the token-expert pairs routed to a held expert that the
  expert layer left out, by the program's own counter over the whole run and
  by the checked steps' metrics: limit 0;
- **the recorded stretch**: when the window has closed the program's own loop
  goes on for a while with its train bursts left out, so that the acting
  parameters stand still, until one env has taken ``STRETCH_STEPS`` steps
  since its last reset. What the timed acting path computed over that stretch
  (the tokens it fed and the prior logits its one-token path gave, kept on the
  device by the program's ``DeviceActor``) is then held to the reference's full
  forward pass of the same tokens: the guide's prefill-then-decode test;
- the replay rows carry no ``reward`` observation key in this recipe, and the
  check of rows reads one: the batches handed to it repeat ``rewards`` there.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import dv3_adapter
from benchmarks.dv3_adapter import CHECK_STEPS, LOSSES, StopWindow, flat_leaves, gaps, program_readings  # noqa: F401 (run.py reads StopWindow here)

_T0 = time.perf_counter()


def _note(what: str) -> None:
    """Where a run's wall time goes after the window, on standard error."""
    print(f"seq adapter +{time.perf_counter() - _T0:.1f}s: {what}", file=sys.stderr, flush=True)


#: env steps since a reset that the recorded stretch has to hold, and the most it waits for them
STRETCH_STEPS = 40
STRETCH_LIMIT = 480

SIZE_PATHS = {
    "screen_size": "env.screen_size",
    "cnn_channels_multiplier": "algo.world_model.encoder.cnn_channels_multiplier",
    "dense_units": "algo.dense_units",
    "mlp_layers": "algo.mlp_layers",
    "posterior_hidden_size": "algo.world_model.representation_model.hidden_size",
    "stochastic_size": "algo.world_model.stochastic_size",
    "discrete_size": "algo.world_model.discrete_size",
    "bins": "algo.critic.bins",
    "unimix": "algo.unimix",
    "sequence_length": "per_rank_sequence_length",
    "batch_size": "per_rank_batch_size",
    "horizon": "algo.horizon",
    "gamma": "algo.gamma",
    "lmbda": "algo.lmbda",
    "kl_dynamic": "algo.world_model.kl_dynamic",
    "kl_representation": "algo.world_model.kl_representation",
    "kl_free_nats": "algo.world_model.kl_free_nats",
    "kl_regularizer": "algo.world_model.kl_regularizer",
    "continue_scale_factor": "algo.world_model.continue_scale_factor",
    "ent_coef": "algo.actor.ent_coef",
    "moments_decay": "algo.actor.moments.decay",
    "moments_max": "algo.actor.moments.max",
    "moments_low": "algo.actor.moments.percentile.low",
    "moments_high": "algo.actor.moments.percentile.high",
    "critic_tau": "algo.critic.tau",
    "prng_impl": "fabric.prng_impl",
    "precision": "fabric.precision",
    "sequence_model": "algo.world_model.sequence_model",
}
#: the core's published keys, as the configuration's file and the program's ``core`` block both name them
CORE_KEYS = (
    "hidden_size", "num_hidden_layers", "full_attention_interval", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta", "rms_norm_eps", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size", "vocab_size", "router_aux_loss_coef", "chunk",
)


class Adapter(dv3_adapter.Adapter):
    def __init__(self, config, reference, seed, recorder, trace, fault=""):
        super().__init__(config, reference, seed, recorder, trace, fault=fault)
        self.stretch: List[dict] = []
        self.stretch_params = None
        self.in_stretch = False
        self.dropped_pairs = 0.0

    def install(self) -> None:
        super().install()
        from sheeprl_tpu.envs.rollout import DeviceActor
        from sheeprl_tpu.obs import counters

        rollout = DeviceActor.rollout
        adapter = self

        def recorded_rollout(actor, params, carry, key, burst_len):
            reset = np.array(carry["player"]["reset"], np.float32).reshape(-1)
            out = rollout(actor, params, carry, key, burst_len)
            if adapter.recorder.is_closed:
                if adapter.in_stretch:
                    adapter.stretch.append({"reset": reset, **actor.last})
                    adapter.stretch_params = {"world_model": params["wm"]}
                adapter.in_stretch = True  # the next call's inputs are the first that count
                if len(adapter.stretch) == 0:
                    _note("window closed, the recorded stretch begins")
                if adapter._stretch_done():
                    _note(f"stretch of {len(adapter.stretch)} steps recorded")
                    counted = counters.installed()
                    adapter.dropped_pairs += counted.seq_core.get("dropped_pairs", 0.0) if counted else 0.0
                    raise StopWindow()
            return out

        self._originals.append((DeviceActor, "rollout", rollout))
        DeviceActor.rollout = recorded_rollout

    def _stretch_done(self) -> bool:
        if len(self.stretch) >= STRETCH_LIMIT:
            return True
        resets = np.stack([row["reset"] for row in self.stretch]) if self.stretch else np.zeros((0, 1))
        for env in range(resets.shape[1]):
            hits = np.nonzero(resets[:, env])[0]
            if len(hits) and len(self.stretch) - hits[-1] >= STRETCH_STEPS:
                return True
        return False

    def _hold_to_config(self, cfg, actions_dim, observation_space) -> None:
        wrong = []
        for name, dotted in SIZE_PATHS.items():
            ran = dv3_adapter._get(cfg, dotted)
            if ran != self.sizes[name]:
                wrong.append((name, self.sizes[name], ran))
        core = cfg["algo"]["world_model"]["core"]
        for name in CORE_KEYS:
            if core[name] != self.sizes[name]:
                wrong.append((f"core.{name}", self.sizes[name], core[name]))
        share = (int(core["held"]["index"]), int(core["held"]["of"]))
        held = int(core["num_experts"]) // share[1]
        for name, want, ran in (("router_outputs", self.sizes["router_outputs"], int(core["num_experts"])),
                                ("num_experts", self.sizes["num_experts"], held),
                                ("expert_share_index", self.sizes["expert_share_index"], share[0])):
            if want != ran:
                wrong.append((name, want, ran))
        for module in dv3_adapter.MODULES:
            want = self.sizes["optim"][module]
            opt = cfg["algo"][module]["optimizer"]
            ran = {"lr": opt["lr"], "eps": opt["eps"], "betas": list(opt["betas"]),
                   "clip": cfg["algo"][module]["clip_gradients"]}
            if ran != want:
                wrong.append((f"optim.{module}", want, ran))
        if tuple(actions_dim) != (self.sizes["actions"],):
            wrong.append(("actions", self.sizes["actions"], tuple(actions_dim)))
        if observation_space["rgb"].shape[0] != self.sizes["image_channels"]:
            wrong.append(("image_channels", self.sizes["image_channels"], observation_space["rgb"].shape))
        if wrong:
            raise RuntimeError(f"the run departs from the configuration's file (name, file, run): {wrong}")

    def _run_train_burst(self, original, train_fn, agent_state, data_stack, scanned, **kwargs):
        if self.in_stretch:
            return agent_state, None, ()  # the recorded stretch: the parameters stand still
        out = super()._run_train_burst(original, train_fn, agent_state, data_stack, scanned, **kwargs)
        # the rows check takes one step's batch of the last burst, not all: a
        # 512-step window is eight times the rows of the other configuration's
        self.last_stack = {**jax.tree_util.tree_map(lambda x: x[:1], self.last_stack)}
        self.last_stack["reward"] = self.last_stack["rewards"]
        return out

    def _record_step(self, state, metrics, data_stack, i, scanned) -> None:
        super()._record_step(state, metrics, data_stack, i, scanned)
        batch = self.steps[-1]["batch"]
        batch["reward"] = batch["rewards"]
        self.dropped_pairs += float(np.asarray(metrics["Core/dropped_pairs"]))

    def mirror_mismatches(self) -> int:
        return 0  # acting reads the trained leaves in place: there is no mirror

    def release(self) -> None:
        super().release()
        if self.steps:
            self.steps[-1]["stretch"] = (self.stretch, self.stretch_params)
            self.steps[-1]["dropped_pairs"] = self.dropped_pairs
        self.stretch, self.stretch_params = [], None


# -- the comparison with the plain reference ------------------------------------


def decode_gap(reference, config, stretch, params, device, mode="f32", held=True):
    """Acting's one-token prior logits over the recorded stretch against the
    reference's full forward pass of the same tokens: the worst position's
    largest difference over the reference's largest logit there. ``mode`` other
    than ``f32`` (or ``held=False``) reads the control: the reference so altered
    against itself."""
    sizes = config["sizes"]
    resets = np.stack([row["reset"] for row in stretch])
    best, start = 0, None
    for env in range(resets.shape[1]):
        hits = np.nonzero(resets[:, env])[0]
        if len(hits) and len(stretch) - hits[-1] > best:
            best, start = len(stretch) - hits[-1], (env, int(hits[-1]))
    if start is None:
        return None, 0
    env, first = start
    with jax.default_device(device):
        tokens = jnp.stack([row["tokens"][env] for row in stretch[first:]]).reshape(-1).astype(jnp.int32)
        mine = jnp.stack([row["prior_logits"][env] for row in stretch[first:]])
        n = tokens.shape[0]
        pad = -n % sizes["chunk"]
        reset = jnp.zeros((n + pad,), jnp.int32).at[0].set(1)
        flat = {k: v for k, v in flat_leaves(params).items()}
        frozen = reference.freeze(sizes)
        sound = reference.core_forward(flat, jnp.pad(tokens, (0, pad)), reset, sizes=frozen)[: n // 2]
        if mode != "f32" or not held:
            mine = reference.core_forward(flat, jnp.pad(tokens, (0, pad)), reset, sizes=frozen, mode=mode, held=held)[: n // 2]
        gap = jnp.max(jnp.max(jnp.abs(mine - sound), -1) / jnp.max(jnp.abs(sound), -1))
        return float(gap), n // 2


def reference_readings(reference, config, steps, seed, device, mode="f32", half_batch=False, held=True) -> dict:
    """The reference put through the recorded steps (see ``dv3_adapter``)."""
    sizes = config["sizes"]
    shapes = reference.param_shapes(sizes)
    items = tuple(sorted((name, tuple(shape)) for name, shape in shapes.items()))
    frozen = reference.freeze(sizes)
    losses = []
    with jax.default_device(device):
        state = jax.jit(lambda s: reference.init_state(shapes, s))(np.int32(seed))
        for k, step in enumerate(steps):
            batch = {name: value for name, value in step["batch"].items() if name != "reward"}
            if half_batch:
                half = np.asarray(batch["rewards"]).shape[1] // 2
                batch = jax.tree_util.tree_map(lambda x: np.concatenate([x[:, :half], x[:, :half]], 1), batch)
            state, report = reference.train_step(
                state, batch, step["key"], np.float32(step["tau"]), sizes=frozen, mode=mode, held=held,
            )
            report = jax.device_get(report)
            _note(f"reference step {k + 1} ({mode}{'' if held else ', no experts'}{', half batch' if half_batch else ''})")
            losses.append({name: float(report[name]) for name in LOSSES})
            if k == 0:
                grad_norms = {name: float(v) for name, v in report["grad_norms"].items()}
        change = jax.device_get(reference.change_norms(state["params"], np.int32(seed), items))
    del state
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": {n: float(v) for n, v in change.items()}}


#: what can stand in for the program to read a limit's upper end
CONTROLS = {
    "fp8": dict(mode="fp8"),
    "bf16": dict(mode="bf16"),
    "half_batch": dict(half_batch=True),
    "no_experts": dict(held=False),
}


def compare_with_reference(reference, config, steps, seed, chips, device, controls=()) -> dict:
    if len(steps) < CHECK_STEPS:
        raise RuntimeError(f"only {len(steps)} of {CHECK_STEPS} gradient steps were recorded")
    if chips != 1:
        raise RuntimeError("this configuration's cells run on one chip")
    stretch, params = steps[-1].pop("stretch")
    numbers: Dict[str, object] = {"dropped_pairs": steps[-1].pop("dropped_pairs")}
    control_numbers: Dict[str, dict] = {name: {} for name in controls}
    numbers["decode_gap"], compared = decode_gap(reference, config, stretch, params, device) if stretch else (None, 0)
    _note(f"decode check: {compared} action positions of {len(stretch)} recorded steps")
    for name in controls:
        kw = {k: v for k, v in CONTROLS[name].items() if k in ("mode", "held")}
        if kw and stretch:
            control_numbers[name]["decode_gap"] = decode_gap(reference, config, stretch, params, device, **kw)[0]
    del stretch, params
    sound = reference_readings(reference, config, steps, seed, device)
    found = gaps(program_readings(steps), sound)
    print(f"check worst leaves: {found.pop('_worst_leaves')}", file=sys.stderr)
    numbers.update(found)
    if controls:
        for name in controls:
            altered = reference_readings(reference, config, steps, seed, device, **CONTROLS[name])
            control_numbers[name].update(gaps(altered, sound))
            control_numbers[name].pop("_worst_leaves", None)
        numbers["_controls"] = control_numbers
    return numbers
