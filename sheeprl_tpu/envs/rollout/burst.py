"""Burst acting for Python envs (rollout tier b).

The per-step acting path pays one policy dispatch per env step:
``policy_fn(...)`` → ``np.asarray(actions)`` → ``envs.step(...)``.
:class:`BurstActor` compiles K acting steps into ONE dispatched program: a
``lax.while_loop`` whose body runs the policy on device and hands the
actions to the host through an ordered
:func:`jax.experimental.io_callback`. The host callback is the *whole* old
loop body — ``envs.step`` (against the PR-5 shared-memory obs slabs),
episode bookkeeping, the replay-buffer ``add`` — and returns the prepared
next observation for the following in-loop act.

The burst length is a *traced scalar*, not a static loop bound: every K
runs the SAME compiled program, just with a different trip count. That is
what makes trajectories bitwise-independent of K (asserted for every
converted family in ``tests/test_envs/test_rollout.py``) — with one
program per length, XLA inlines the trip-count-1 loop and the changed
fusion context perturbs the acting math by an ulp, which a seeded bitwise
gate catches. One program also means one trace/compile, however often the
train-gating clamps vary the burst length mid-run.

So the data still crosses the link every step (the envs are Python), but
the per-step *dispatch* — trace-cache lookup, program launch, host sync on
the action fetch — is paid once per burst: ``K = env.act_burst`` acts per
dispatch. With ``K = 1`` this is the old per-step path, same key
discipline and the same trajectories; larger K trades train/log/checkpoint
*cadence granularity* (gates run per burst, not per step) for dispatch
amortization — see ``howto/rollout_engine.md``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import numpy as np

from sheeprl_tpu.obs.counters import add_rollout_burst, add_rollout_device_burst
from sheeprl_tpu.obs.spans import current_span, span

__all__ = ["BurstActor", "DeviceActor"]


class BurstActor:
    """Dispatch K acting steps as one jitted program.

    ``act_fn(params, obs, key) -> (callback_args, key)`` is the traced
    policy body — ``callback_args`` a tuple of arrays handed to the host.
    ``host_step(*np_args) -> next_obs`` is the Python loop body: it steps
    the vector env, does every piece of host bookkeeping (buffer add,
    episode logging, info stashing), and returns the prepared obs pytree
    for the next act. ``obs_example`` fixes the obs spec (shapes/dtypes the
    callback must return exactly).

    Each ``host_step`` runs under the span ``Time/act_host_step_time``, on
    whichever thread the runtime calls it back on (one of its own on a
    chip); its parent is the span that was open where :meth:`rollout` was
    called (the caller's ``Time/rollout_time``).
    """

    def __init__(
        self,
        act_fn: Callable[[Any, Any, Any], Tuple[Tuple[Any, ...], Any]],
        host_step: Callable[..., Any],
        obs_example: Any,
    ):
        import jax

        self._act_fn = act_fn
        self._host_step = host_step
        self._obs_spec = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype),
            obs_example,
        )
        self._rollout_fn: Any = None
        self._device: Any = None
        self._caller: Any = None  # the span open where rollout was called

    @staticmethod
    def _params_device(params):
        """The device the acting params are committed to (first by id for
        mesh-replicated trees); CPU when nothing is committed (numpy trees)."""
        import jax

        for leaf in jax.tree_util.tree_leaves(params):
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                devices = sorted(sharding.device_set, key=lambda d: d.id)
                if devices:
                    return devices[0]
        try:
            return jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            return jax.devices()[0]

    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental import io_callback

        act_fn = self._act_fn
        obs_spec = self._obs_spec

        def host_step(*args):
            with span("Time/act_host_step_time", phase="rollout", parent=self._caller):
                return self._host_step(*args)

        def rollout(params, obs, key, n):
            # n is traced: one compiled program serves every burst length,
            # so the acting math cannot depend on K (bitwise K-invariance)
            def cond(carry):
                i, _, _ = carry
                return i < n

            def body(carry):
                i, obs, key = carry
                cb_args, key = act_fn(params, obs, key)
                # ordered: env steps must run in sequence, and the next act
                # consumes exactly this step's observation
                next_obs = io_callback(host_step, obs_spec, *cb_args, ordered=True)
                return (i + jnp.int32(1), next_obs, key)

            _, obs, key = jax.lax.while_loop(cond, body, (jnp.int32(0), obs, key))
            return obs, key

        return jax.jit(rollout)

    def rollout(self, params: Any, obs: Any, key: Any, burst_len: int) -> Tuple[Any, Any]:
        """Run ``burst_len`` acting steps with one device dispatch; returns
        ``(next_obs, key)`` after the burst. The host sees every step via
        ``host_step`` exactly as the per-step loop would have."""
        import jax

        burst_len = int(burst_len)
        if self._rollout_fn is None:
            self._rollout_fn = self._build()
        fn = self._rollout_fn
        # The burst program must be SINGLE-device: this jax version's SPMD
        # sharding propagation CHECK-aborts on io_callback programs with
        # multi-device (mesh-replicated) inputs. Pin to wherever the acting
        # params already live — the CPU host mirror when player_on_host is
        # on, the accelerator otherwise (algo.player_on_host=False keeps
        # its meaning). The put moves no parameter: a leaf already on that
        # device is handed on as it is, and of a mesh-replicated leaf jax
        # takes the first device's own shard in place (the same buffer, no
        # copy: tests/test_envs/test_rollout.py holds it to that).
        if self._device is None:
            self._device = self._params_device(params)
        params, obs, key = jax.device_put((params, obs, key), self._device)
        self._caller = current_span()
        obs, key = fn(params, obs, key, np.int32(burst_len))
        # FENCE: dispatch is async — the caller is about to read host state
        # the callbacks mutate (replay buffer, episode stats). The returned
        # obs is data-dependent on the LAST ordered callback, so readiness
        # here proves every host_step of the burst has run.
        jax.block_until_ready(obs)
        add_rollout_burst(act_dispatches=1)
        if self._device.platform != "cpu":
            add_rollout_device_burst()
        return obs, key


class DeviceActor:
    """Acting whose per-env state never leaves the device.

    For a policy whose state is too large to ride a host callback (the
    sequence cores' per-env state, megabytes an env: a delta-rule matrix and a
    key-value ring a layer, or a latent ring a layer):
    ``step(params, state, obs, key) -> (to_host, on_device, state, key)`` is
    one jitted program a policy step, its ``state`` donated; ``to_host`` (the
    actions) is fetched and handed to ``host_step(to_host) -> next_obs``, the
    same Python loop body a :class:`BurstActor` calls; ``on_device`` (what the
    step computed besides: tokens, logits) is kept as ``self.last`` and never
    waited for. The dispatch and the fetch run under the span
    ``Time/act_decode_time``, ``host_step`` under ``Time/act_host_step_time``
    after it: both children of the caller's ``Time/rollout_time``.
    """

    def __init__(self, step: Callable, host_step: Callable[[Any], Any], state: Any):
        import jax

        self._step = jax.jit(step, donate_argnums=(1,))
        self._host_step = host_step
        self.state = state
        self.last: Any = None
        self._on_device: Any = None

    def rollout(self, params: Any, obs: Any, key: Any, burst_len: int) -> Tuple[Any, Any]:
        """``burst_len`` policy steps; returns ``(next_obs, key)``."""
        import jax

        if self._on_device is None:
            self._on_device = BurstActor._params_device(params).platform != "cpu"
        for _ in range(int(burst_len)):
            with span("Time/act_decode_time", phase="rollout"):
                to_host, self.last, self.state, key = self._step(params, self.state, obs, key)
                to_host = jax.device_get(to_host)
            with span("Time/act_host_step_time", phase="rollout"):
                obs = self._host_step(to_host)
            add_rollout_burst(act_dispatches=1)
            if self._on_device:
                add_rollout_device_burst()
        return obs, key
