"""Host-side parameter mirroring for the acting path.

Environment interaction is one jitted policy call per env step. With
``algo.player_on_host`` (default on) and a mesh on an accelerator, that call
runs on the host CPU instead of the mesh (SURVEY §5.8 — players live on CPU
hosts feeding the trainer mesh): :class:`HostParamMirror` keeps a CPU copy
of the acting parameters, refreshed once per update as a **single packed
transfer** — the pytree is raveled on the mesh (one jitted concat) so the
snapshot leaves the device as one array instead of one transfer per leaf,
then unraveled on the host. Whether acting on the mirror beats acting on the
device is ROADMAP S1/D4's measurement, not settled here.

The mirror copies the tree its caller hands it, all of it, and knows nothing
of what is in it: a refresh costs time in proportion to its bytes, so the
choice of leaves is the caller's, made where the code knows what acting reads.
DreamerV3 and P2E-DV3 hand it ``agent.acting_params(world_model)`` — encoder
and RSSM, not the decoders or the reward and continue heads — and the actor.

Usage::

    mirror = HostParamMirror(params, enabled=fabric.on_accelerator)
    play_params = mirror(params)          # CPU tree (or `params` if disabled)
    ...
    play_params = mirror(new_params)      # refresh after each update
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from sheeprl_tpu.obs.counters import add_publish
from sheeprl_tpu.obs.spans import span


class HostParamMirror:
    @staticmethod
    def enabled_for(fabric, cfg) -> bool:
        """The one enable rule shared by every algorithm: host acting is on
        unless ``algo.player_on_host=False``, and only matters when the mesh
        runs on an accelerator."""
        return bool(cfg.algo.get("player_on_host", True)) and fabric.on_accelerator

    @classmethod
    def from_cfg(cls, example_tree: Any, fabric, cfg) -> "HostParamMirror":
        """The one construction rule: enable per :meth:`enabled_for`,
        refresh cadence from ``algo.player_on_host_refresh_every``."""
        return cls(
            example_tree,
            enabled=cls.enabled_for(fabric, cfg),
            refresh_every=cfg.algo.get("player_on_host_refresh_every", 1),
        )

    def __init__(self, example_tree: Any, enabled: bool = True, refresh_every: int = 1):
        self.enabled = bool(enabled)
        # refreshing costs one full-model transfer; a cadence > 1 lets the
        # player act on a snapshot stale by up to refresh_every-1 updates
        # (algo.player_on_host_refresh_every)
        self.refresh_every = max(int(refresh_every or 1), 1)
        self._calls = 0
        self._cache: Any = None
        if self.enabled:
            from jax.flatten_util import ravel_pytree

            self._host = jax.devices("cpu")[0]
            _, self._unravel = ravel_pytree(jax.device_get(example_tree))
            self._pack = jax.jit(lambda p: ravel_pytree(p)[0])

    def __call__(self, tree: Any) -> Any:
        if not self.enabled:
            return tree
        if self._cache is None or self._calls % self.refresh_every == 0:
            # async D2H: device_put of the packed vector to the host enqueues
            # the transfer without blocking; the unravel runs on the CPU
            # backend and only waits when the player first reads the params,
            # by which time env bookkeeping has overlapped it
            # the span measures what the loop waits here, whatever part of
            # the copy is asynchronous: nothing in it blocks on the result
            with span("Time/publish_time", phase="publish"):
                flat = jax.device_put(self._pack(tree), self._host)
                self._cache = self._unravel(flat)
            add_publish(flat.nbytes)
        self._calls += 1
        return self._cache

    def put_key(self, key: jax.Array) -> jax.Array:
        """Commit a PRNG key next to the mirrored params."""
        return jax.device_put(key, self._host) if self.enabled else key
