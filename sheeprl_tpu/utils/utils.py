"""General utilities.

TPU-native re-implementation of the helpers in the reference's
``sheeprl/utils/utils.py`` (dotdict :15, gae :38-74, normalize_tensor :95,
polynomial_decay :107, symlog/symexp :122-127, print_config :130-159) — same
behavior, jnp/lax instead of torch, GAE as a ``lax.scan`` instead of a Python
reverse loop.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class dotdict(dict):
    """A dict with attribute-style access, recursively applied.

    Mirrors the reference `dotdict` (sheeprl/utils/utils.py:15-35): nested
    dictionaries are converted on construction and on item assignment.
    """

    def __init__(self, *args, **kwargs):
        super().__init__()
        src = dict(*args, **kwargs)
        for k, v in src.items():
            self[k] = v

    @classmethod
    def _wrap(cls, value):
        if isinstance(value, dotdict):
            return value
        if isinstance(value, dict):
            return cls(value)
        if isinstance(value, (list, tuple)):
            return type(value)(cls._wrap(v) for v in value)
        return value

    def __setitem__(self, key, value):
        super().__setitem__(key, self._wrap(value))

    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __setattr__(self, name, value):
        self[name] = value

    def __delattr__(self, name):
        try:
            del self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def as_dict(self) -> Dict[str, Any]:
        """Convert back to plain nested dicts (for yaml dumps / orbax)."""
        out = {}
        for k, v in self.items():
            if isinstance(v, dotdict):
                out[k] = v.as_dict()
            elif isinstance(v, (list, tuple)):
                out[k] = type(v)(x.as_dict() if isinstance(x, dotdict) else x for x in v)
            else:
                out[k] = v
        return out


def symlog(x: jnp.ndarray) -> jnp.ndarray:
    """Symmetric log transform (reference utils.py:122-123)."""
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`symlog` (reference utils.py:126-127)."""
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def gae(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    dones: jnp.ndarray,
    next_value: jnp.ndarray,
    gamma: float,
    gae_lambda: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generalized advantage estimation over a rollout of shape ``[T, ...]``.

    Matches the reference semantics exactly (utils.py:38-74): ``dones[t]`` is
    the done flag of *transition t* (episode ended at step t), so the bootstrap
    from ``t+1`` is masked by ``1 - dones[t]``. Implemented as a single
    reversed ``lax.scan`` so XLA compiles one fused loop instead of T Python
    iterations.

    Returns ``(returns, advantages)``, both ``[T, ...]``.
    """
    rewards = jnp.asarray(rewards)
    values = jnp.asarray(values)
    dones = jnp.asarray(dones, dtype=rewards.dtype)
    next_value = jnp.asarray(next_value, dtype=rewards.dtype)

    # value of the next observation for every t.
    next_values = jnp.concatenate([values[1:], next_value[None]], axis=0)

    def step(carry, inp):
        lastgaelam = carry
        reward, value, nvalue, done = inp
        nonterminal = 1.0 - done
        delta = reward + gamma * nvalue * nonterminal - value
        lastgaelam = delta + gamma * gae_lambda * nonterminal * lastgaelam
        return lastgaelam, lastgaelam

    _, advantages = jax.lax.scan(
        step,
        jnp.zeros_like(next_value),
        (rewards, values, next_values, dones),
        reverse=True,
    )
    returns = advantages + values
    return returns, advantages


def normalize_tensor(x: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """Standardize to zero mean / unit variance (reference utils.py:95-104)."""
    return (x - x.mean()) / (x.std() + eps)


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Polynomial annealing schedule (reference utils.py:107-119)."""
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


def print_config(cfg, logger=print) -> None:
    """Print the run config as a tree (reference utils.py:130-159 uses rich)."""
    try:
        import rich.tree
        import rich.syntax
        import rich

        tree = rich.tree.Tree("CONFIG")
        import yaml

        data = cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg)
        for key, value in data.items():
            branch = tree.add(str(key))
            if isinstance(value, dict):
                branch.add(rich.syntax.Syntax(yaml.dump(value, sort_keys=False), "yaml"))
            else:
                branch.add(str(value))
        rich.print(tree)
    except Exception:
        import pprint

        logger(pprint.pformat(cfg))


def save_configs(cfg, log_dir: str) -> None:
    """Persist the composed config under ``<log_dir>/.hydra/config.yaml``.

    Checkpoint-resume and evaluation re-read this file (reference
    cli.py:26,280); we keep the same path layout.
    """
    import yaml

    os.makedirs(os.path.join(log_dir, ".hydra"), exist_ok=True)
    data = cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg)
    with open(os.path.join(log_dir, ".hydra", "config.yaml"), "w") as f:
        yaml.safe_dump(data, f, sort_keys=False)


def fetch_losses_if_observed(losses, aggregator=None):
    """Materialize a device loss vector only when something will read it —
    the metric aggregator — or when the global timer is live (the blocking
    fetch keeps Time/train_time honest). With both disabled the fetch is a
    blocking device->host fetch per update that nothing consumes, so the
    array is returned un-materialized."""
    from sheeprl_tpu.utils.timer import timer

    if not timer.disabled or (aggregator is not None and not aggregator.disabled):
        return np.asarray(losses)
    return losses


def params_on_device(tree):
    """Materialize a checkpoint param tree as numpy and park it on the
    default accelerator ONCE. Evaluation players are jitted fns called once
    per env step; numpy leaves would re-upload the whole tree on every call."""
    import jax

    return jax.device_put(
        jax.tree_util.tree_map(np.asarray, tree), jax.devices()[0]
    )


def pin_process_to_cpu() -> None:
    """First call of every child process the package starts (plane players,
    the in-run evaluator, serve clients, env workers): a chip belongs to one
    process, and that is the parent. A spawned child has already imported jax
    while unpickling its entry point, so the environment variable alone is too
    late — the config update is what keeps the TPU plug-in from initialising;
    the variable covers grandchildren."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def enable_persistent_compilation_cache() -> None:
    """Turn on jax's persistent XLA compilation cache so a second process
    skips the compiles of the first. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, jax reads it itself and no directory is set in code; otherwise the
    cache lives at one fixed path inside the checkout (``<repo>/.jax_cache``,
    git-ignored) — a path that does not move with the host or the user, so
    entries written by one run are found by the next."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir", os.path.join(repo_root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def unwrap_fabric(module):  # pragma: no cover - parity shim
    """Parity shim with the reference API: params are already plain pytrees."""
    return module


def conform_pytree(template: Any, restored: Any) -> Any:
    """Rebuild ``restored`` (raw containers from an orbax template-less
    restore: dicts and lists) in the *structure* of ``template`` — NamedTuples
    (optax states) are reconstructed from lists or field dicts, tuples from
    lists, and dict keys present on disk but absent from the template are
    dropped. Leaves come from ``restored``.
    """
    if isinstance(template, dict):
        return type(template)(
            {k: conform_pytree(template[k], restored[k]) for k in template}
        )
    if isinstance(template, tuple) and hasattr(template, "_fields"):  # NamedTuple
        if len(template) == 0 or restored is None:  # e.g. optax EmptyState
            return template
        vals = restored
        if isinstance(restored, dict):
            vals = [restored[f] for f in template._fields]
        return type(template)(*(conform_pytree(t, r) for t, r in zip(template, vals)))
    if isinstance(template, (list, tuple)):
        if restored is None:
            return template
        if len(template) != len(restored):
            raise ValueError(
                f"conform_pytree: structure length mismatch — template has "
                f"{len(template)} entries, restored has {len(restored)} "
                "(checkpoint saved with a different optimizer/transform chain?)"
            )
        return type(template)(conform_pytree(t, r) for t, r in zip(template, restored))
    return restored


def _rename_trunk_params(value: dict) -> None:
    mlp = value.pop("MLP_0")
    unexpected = set(mlp) - {"Dense_0", "LayerNorm_0"}
    if unexpected:
        # fail loudly instead of silently dropping parameters if the stored
        # trunk layout ever grows entries this migration doesn't carry over
        raise ValueError(
            "migrate_legacy_checkpoint: representation-model MLP_0 contains "
            f"unexpected entries {sorted(unexpected)}; refusing to migrate a "
            "layout this shim does not understand"
        )
    dense = mlp.get("Dense_0", {})
    if "kernel" in dense:
        value["trunk_kernel"] = dense["kernel"]
    if "bias" in dense:
        value["trunk_bias"] = dense["bias"]
    if "LayerNorm_0" in mlp:
        value["trunk_ln"] = mlp["LayerNorm_0"]


def migrate_legacy_checkpoint(template: Any, restored: Any) -> Any:
    """Rename pre-split posterior-trunk parameters in-place and return the tree.

    The DV3-family ``_RepresentationModel`` used to be a plain
    ``_StochasticModel`` (MLP + head); splitting the embed projection out of
    the RSSM scan renamed its parameters without changing the math — the
    joint first-layer kernel is still stored as one ``[h+embed, hidden]``
    matrix:

    - ``representation_model/MLP_0/Dense_0/kernel`` -> ``trunk_kernel``
    - ``representation_model/MLP_0/Dense_0/bias``   -> ``trunk_bias``
    - ``representation_model/MLP_0/LayerNorm_0``    -> ``trunk_ln``

    Checkpoints written before the rename load transparently through this
    shim (applied by ``Fabric.load`` before structure conforming).

    The walk is guided by ``template`` (the caller's live state pytree): a
    subtree is renamed only where the template *expects* the split layout
    (has ``trunk_kernel``) — DV1/DV2 still use the joint ``MLP_0`` layout
    under the same ``representation_model`` key and must pass through
    untouched.  Traversal mirrors ``conform_pytree``'s container handling so
    optimizer moments (optax NamedTuple chains restored as lists, whose
    mu/nu trees mirror the param structure) migrate too.
    """
    if isinstance(template, dict) and isinstance(restored, dict):
        for key, t_val in template.items():
            if key not in restored:
                continue
            r_val = restored[key]
            if (
                key == "representation_model"
                and isinstance(t_val, dict)
                and "trunk_kernel" in t_val
                and isinstance(r_val, dict)
                and "MLP_0" in r_val
                and "trunk_kernel" not in r_val
            ):
                _rename_trunk_params(r_val)
            migrate_legacy_checkpoint(t_val, r_val)
        return restored
    if isinstance(template, tuple) and hasattr(template, "_fields"):  # NamedTuple
        vals = restored
        if isinstance(restored, dict):
            vals = [restored.get(f) for f in template._fields]
        if isinstance(vals, (list, tuple)):
            for t_val, r_val in zip(template, vals):
                migrate_legacy_checkpoint(t_val, r_val)
        return restored
    if isinstance(template, (list, tuple)) and isinstance(restored, (list, tuple)):
        for t_val, r_val in zip(template, restored):
            migrate_legacy_checkpoint(t_val, r_val)
        return restored
    return restored


def migrate_dv3_checkpoint(restored: Any) -> Any:
    """Template-free variant of ``migrate_legacy_checkpoint`` for consumers
    that load a checkpoint *known* to be DV3-family without a live state tree
    (evaluation and P2E-DV3 finetuning load stateless, then build the agent
    from the stored config): every ``representation_model/MLP_0`` subtree in
    a DV3-family checkpoint is pre-rename by definition, so rename them all.
    Do NOT use on DV1/DV2 checkpoints — their current layout looks identical.
    """
    if isinstance(restored, dict):
        for key, value in restored.items():
            if (
                key == "representation_model"
                and isinstance(value, dict)
                and "MLP_0" in value
                and "trunk_kernel" not in value
            ):
                _rename_trunk_params(value)
            migrate_dv3_checkpoint(value)
    elif isinstance(restored, (list, tuple)):
        for value in restored:
            migrate_dv3_checkpoint(value)
    return restored
