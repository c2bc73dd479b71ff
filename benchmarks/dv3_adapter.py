"""Hooks that put a DreamerV3 run of ``sheeprl_tpu.cli.run`` under the harness.

The program is not edited: the adapter wraps four names the program looks up
at call time (as ``chip_smoke.py`` does) and takes them off again:

- ``dreamer_v3.build_agent``: the program's freshly initialised parameters are
  replaced, leaf for leaf, by the benchmark's weights from the seed, after the
  tree's names and shapes have been held to the plain reference's;
- ``dreamer_v3.run_train_burst``: tells the recorder a burst has completed.
  For the run's first ``CHECK_STEPS`` gradient steps it drives the burst's own
  compiled program one step at a time (``start``/``count`` are runtime
  scalars of that one executable, so this is the window's own call) and keeps
  what the reference needs: the step's batch, key and target coefficient, its
  losses, and the per-leaf norms of the first gradient and of the change;
- ``BurstActor.rollout``: raises :class:`StopWindow` once the window closed;
- ``HostParamMirror.__call__`` (traced runs only): a span around the mirror
  refresh that blocks on the host copy.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check

CHECK_STEPS = 3
LOSSES = ("Loss/world_model_loss", "Loss/policy_loss", "Loss/value_loss")
MODULES = ("world_model", "actor", "critic")

#: where the composed program config states each of the configuration's sizes
SIZE_PATHS = {
    "screen_size": "env.screen_size",
    "cnn_channels_multiplier": "algo.world_model.encoder.cnn_channels_multiplier",
    "dense_units": "algo.dense_units",
    "mlp_layers": "algo.mlp_layers",
    "recurrent_state_size": "algo.world_model.recurrent_model.recurrent_state_size",
    "hidden_size": "algo.world_model.transition_model.hidden_size",
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
}


class StopWindow(Exception):
    """Raised out of the program's loop once the measured window has closed."""


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)


def flat_leaves(tree) -> Dict[str, Any]:
    return {path_str(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _get(cfg, dotted: str):
    node = cfg
    for part in dotted.split("."):
        node = node[part]
    return node


def _find_adam(opt_state):
    """The Adam moments inside an optax state, wherever the chain put them."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return opt_state
    children = opt_state if isinstance(opt_state, (tuple, list)) else ()
    if hasattr(opt_state, "inner_state"):
        children = (opt_state.inner_state,)
    for child in children:
        found = _find_adam(child)
        if found is not None:
            return found
    return None


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


class Adapter:
    def __init__(self, config: dict, reference, seed: int, recorder, trace: bool, fault: str = ""):
        self.config = config
        self.sizes = config["sizes"]
        self.reference = reference
        self.seed = int(seed)
        self.recorder = recorder
        self.trace = bool(trace)
        self.fault = fault  # tests plant a fault in the timed path through this
        self.shapes = reference.param_shapes(self.sizes)
        self.steps: List[dict] = []
        self.last_stack = None
        self.mirror_spans: List[tuple] = []
        self.mirror_mismatch = None
        self.cfg = None
        self._originals: list = []

    # -- install / remove -------------------------------------------------------

    def install(self) -> None:
        import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
        from sheeprl_tpu.envs.rollout import BurstActor
        from sheeprl_tpu.utils.host import HostParamMirror

        def patch(owner, name, new):
            self._originals.append((owner, name, getattr(owner, name)))
            setattr(owner, name, new)

        build_agent, run_train_burst, rollout = dv3.build_agent, dv3.run_train_burst, BurstActor.rollout
        patch(dv3, "build_agent", lambda *a, **k: self._build_agent(build_agent, *a, **k))
        patch(dv3, "run_train_burst", lambda *a, **k: self._run_train_burst(run_train_burst, *a, **k))
        adapter = self

        def stopping_rollout(actor, *a, **k):
            out = rollout(actor, *a, **k)
            if adapter.recorder.is_closed:
                raise StopWindow()
            return out

        patch(BurstActor, "rollout", stopping_rollout)
        mirror_call = HostParamMirror.__call__

        def checked_mirror(mirror, tree):
            t0 = time.perf_counter()
            out = mirror_call(mirror, tree)
            if adapter.trace and mirror.enabled:
                jax.block_until_ready(out)
                adapter.mirror_spans.append((t0, time.perf_counter()))
            if mirror.enabled:
                adapter.last_mirror[id(mirror)] = (tree, out)
            return out

        self.last_mirror: Dict[int, tuple] = {}
        patch(HostParamMirror, "__call__", checked_mirror)

    def uninstall(self) -> None:
        while self._originals:
            owner, name, original = self._originals.pop()
            setattr(owner, name, original)

    # -- weights ----------------------------------------------------------------

    def _build_agent(self, original, cfg, actions_dim, is_continuous, observation_space, key):
        world_model, actor, critic, params = original(cfg, actions_dim, is_continuous, observation_space, key)
        self.cfg = cfg
        self._hold_to_config(cfg, actions_dim, observation_space)
        mine = {k: tuple(v.shape) for k, v in flat_leaves(params).items()}
        if mine != {k: tuple(v) for k, v in self.shapes.items()}:
            odd = sorted(set(mine.items()) ^ set((k, tuple(v)) for k, v in self.shapes.items()))
            raise RuntimeError(f"the program's parameter tree is not the configuration's: {odd[:8]}")
        device = jax.devices()[0]
        with jax.default_device(device):
            weights = jax.jit(lambda seed: self.reference.make_weights(self.shapes, seed))(np.int32(self.seed))
        params = jax.tree_util.tree_map_with_path(lambda p, _: weights[path_str(p)], params)
        return world_model, actor, critic, params

    def _hold_to_config(self, cfg, actions_dim, observation_space) -> None:
        """The file of sizes is the configuration as run, or the run stops."""
        wrong = []
        for name, dotted in SIZE_PATHS.items():
            ran = _get(cfg, dotted)
            if ran != self.sizes[name]:
                wrong.append((name, self.sizes[name], ran))
        for module in MODULES:
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

    # -- train bursts -----------------------------------------------------------

    def _run_train_burst(self, original, train_fn, agent_state, data_stack, scanned, **kwargs):
        scanned = tuple(scanned)
        n = int(np.shape(scanned[0])[0])
        if self.fault == "altered_row":
            # an answer altered where it is produced: one reward of the batch
            data_stack = {**data_stack, "rewards": data_stack["rewards"].at[0, 0, 0, 0].add(1.0)}
        if len(self.steps) < CHECK_STEPS:
            burst = train_fn.burst
            train_fn.burst = lambda *a: self._split_burst(burst, *a)
            try:
                out = original(train_fn, agent_state, data_stack, scanned, **kwargs)
            finally:
                train_fn.burst = burst
        else:
            out = original(train_fn, agent_state, data_stack, scanned, **kwargs)
        self.last_stack = data_stack
        self.recorder.on_burst_done(n)
        return out

    def _split_burst(self, burst, state, data_stack, start, count, *scanned):
        """The burst's own executable, one gradient step to a dispatch while
        steps are still being recorded, and the rest of the burst in one."""
        i, end = int(start), int(start) + int(count)
        out = None
        while i < end and len(self.steps) < CHECK_STEPS:
            before = state["params"] if self.fault == "state_unchanged" else None
            if before is not None:
                before = jax.tree_util.tree_map(jnp.copy, before)
            stack = self._faulty_stack(data_stack)
            out = burst(state, stack, np.int32(i), np.int32(1), *scanned)
            if before is not None:
                out = ({**out[0], "params": before},) + tuple(out[1:])
            state = out[0]
            self._record_step(state, out[1], data_stack, i, scanned)
            i += 1
        if i < end:
            out = burst(state, data_stack, np.int32(i), np.int32(end - i), *scanned)
        return out

    def _faulty_stack(self, data_stack):
        if self.fault != "half_batch":
            return data_stack
        # half of the batch left out, the mean taken over the rest: the second
        # half of every step's rows repeats the first
        def halve(x):
            half = x.shape[2] // 2
            return jnp.concatenate([x[:, :, :half], x[:, :, :half]], 2)

        return jax.tree_util.tree_map(halve, data_stack)

    def _record_step(self, state, metrics, data_stack, i, scanned) -> None:
        k = len(self.steps)
        record = {
            "batch": jax.device_get(jax.tree_util.tree_map(lambda x: x[i], data_stack)),
            "key": np.asarray(scanned[0][i]),
            "tau": float(np.asarray(scanned[1][i])),
            "losses": {name: float(np.asarray(metrics[name])) for name in LOSSES},
        }
        if k == 0:
            record["grad_norms"] = self._first_gradient_norms(state)
        if k == CHECK_STEPS - 1:
            items = tuple(sorted((name, tuple(shape)) for name, shape in self.shapes.items()))
            norms = self.reference.change_norms(flat_leaves(state["params"]), np.int32(self.seed), items)
            record["change_norms"] = {name: float(v) for name, v in jax.device_get(norms).items()}
        self.steps.append(record)

    def _first_gradient_norms(self, state) -> Dict[str, float]:
        """Per-leaf norm of the gradient the optimiser was given at step one,
        from its state after that step: Adam's first moment is then
        ``(1 - b1) * gradient``."""
        out = {}
        for module in MODULES:
            adam = _find_adam(state["opt"][module])
            if adam is None:
                raise RuntimeError(f"no Adam moments in the {module} optimiser state")
            b1 = self.sizes["optim"][module]["betas"][0]
            norms = jax.device_get(_leaf_norms(adam.mu))
            out.update({f"{module}/{name}": float(v) / (1.0 - b1) for name, v in flat_leaves(norms).items()})
        return out

    # -- after the window ---------------------------------------------------------

    def mirror_mismatches(self) -> int:
        """Leaves of the newest host mirrors that differ from the device
        parameters they were refreshed from (read back: an exact comparison).
        Where the program keeps no mirror (it acts on the device's own packed
        vector when the mesh is the host CPU) there is nothing to differ."""
        bad = 0
        for tree, mirrored in self.last_mirror.values():
            for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(tree)), jax.tree_util.tree_leaves(mirrored)):
                bad += 0 if np.array_equal(np.asarray(a), np.asarray(b)) else 1
        return bad

    def release(self) -> None:
        """Drop every reference to the program's device state."""
        self.last_stack = None
        self.last_mirror = {}


# -- the comparison with the plain reference ------------------------------------


def program_readings(steps: List[dict]) -> dict:
    """What the timed path's first gradient steps produced."""
    return {
        "losses": [step["losses"] for step in steps],
        "grad_norms": steps[0]["grad_norms"],
        "change_norms": steps[-1]["change_norms"],
    }


def reference_readings(reference, config, steps, seed, chips, device, mode="f32",
                       half_batch=False, exchange=True) -> dict:
    """The reference put through the same steps: the benchmark's weights from
    the seed, and each recorded step's batch, key and target coefficient.
    ``mode`` other than ``f32`` makes it the control; ``half_batch`` and
    ``exchange=False`` plant the faults a training cell can have."""
    sizes = config["sizes"]
    shapes = reference.param_shapes(sizes)
    items = tuple(sorted((name, tuple(shape)) for name, shape in shapes.items()))
    frozen = reference.freeze(sizes)
    losses = []
    with jax.default_device(device):
        state = jax.jit(lambda s: reference.init_state(shapes, s))(np.int32(seed))
        for k, step in enumerate(steps):
            batch = step["batch"]
            if half_batch:
                per = np.asarray(batch["rewards"]).shape[1] // chips
                batch = jax.tree_util.tree_map(
                    lambda x: np.concatenate(
                        [x[:, c * per:c * per + per // 2] for c in range(chips) for _ in (0, 1)], 1
                    ),
                    batch,
                )
            state, report = reference.train_step(
                state, batch, step["key"], np.float32(step["tau"]),
                sizes=frozen, n_shards=chips, mode=mode, exchange=exchange,
            )
            report = jax.device_get(report)
            losses.append({name: float(report[name]) for name in LOSSES})
            if k == 0:
                grad_norms = {name: float(v) for name, v in report["grad_norms"].items()}
        change = jax.device_get(reference.change_norms(state["params"], np.int32(seed), items))
    del state
    return {
        "losses": losses,
        "grad_norms": grad_norms,
        "change_norms": {name: float(v) for name, v in change.items()},
    }


def gaps(readings: dict, reference: dict) -> dict:
    """The numbers ``correct`` compares, and the leaf each worst gap is on."""
    out = {}
    for short, name in (("wm_loss_gap", LOSSES[0]), ("policy_loss_gap", LOSSES[1]), ("value_loss_gap", LOSSES[2])):
        out[short] = max(
            check.relative_gap(mine[name], ref[name])
            for mine, ref in zip(readings["losses"], reference["losses"])
        )
    out["grad_gap"], grad_leaf = check.worst_leaf_gap(readings["grad_norms"], reference["grad_norms"])
    dead = check.dead_leaves(reference["grad_norms"])
    dead |= {name.replace("critic/", "target_critic/", 1) for name in dead if name.startswith("critic/")}
    out["update_gap"], update_leaf = check.worst_leaf_gap(
        readings["change_norms"], reference["change_norms"], leave_out=dead
    )
    out["_worst_leaves"] = {"grad_gap": grad_leaf, "update_gap": update_leaf, "left_out": len(dead)}
    return out


#: what can be put in the program's place to read a limit's upper end:
#: the control (the reference in the next precision down) and the faults
CONTROLS = {
    "fp8": dict(mode="fp8"),
    "bf16": dict(mode="bf16"),
    "half_batch": dict(half_batch=True),
    "no_exchange": dict(exchange=False),
}


def compare_with_reference(reference, config, steps, seed, chips, device, controls=()) -> dict:
    """The numbers of a run; with ``controls``, also the numbers each control
    or fault reads when the reference, so altered, stands in for the program
    (under ``_controls``: the calibration's readings, never a run's)."""
    if len(steps) < CHECK_STEPS:
        raise RuntimeError(f"only {len(steps)} of {CHECK_STEPS} gradient steps were recorded")
    sound = reference_readings(reference, config, steps, seed, chips, device)
    numbers = gaps(program_readings(steps), sound)
    print(f"check worst leaves: {numbers.pop('_worst_leaves')}", file=sys.stderr)
    if controls:
        numbers["_controls"] = {}
        for name in controls:
            altered = reference_readings(reference, config, steps, seed, chips, device, **CONTROLS[name])
            numbers["_controls"][name] = gaps(altered, sound)
    return numbers
