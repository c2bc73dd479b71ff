"""On-device rollout engine: the two-tier acting plane behind the factory.

Round-5 benchmarks left one architectural loss standing: every env step paid
one host→device round trip for action inference (SAC full-protocol e2e at
0.153x the reference baseline), even though replay staging (PR 3) and env
execution (PR 5) were already framework planes. This package closes the loop
with the SEED-RL / EnvPool acting pattern, in two tiers:

- **Tier (a) — pure-JAX envs** (:mod:`jax_envs`, :mod:`engine`): envs whose
  dynamics are ``(state, action, key) -> (state, obs, reward, ...)`` jax
  functions (a native CartPole/Pendulum, plus a Brax adapter). The whole
  act→step→buffer-add loop runs inside ONE ``lax.scan`` under jit, writing
  collection bursts straight into the PR-3 device ring via its in-jit
  :func:`~sheeprl_tpu.data.device_ring.scatter_append` — zero host
  involvement for an entire burst. Selected with ``env.backend=jax``.
- **Tier (b) — Python envs** (:mod:`burst`): the acting loop body (policy →
  env.step → buffer bookkeeping) is compiled as a K-step ``lax.scan`` whose
  env step is an ordered ``io_callback`` into the host — K sequential acts
  against the shared-memory obs slabs with ONE device dispatch per burst
  (``K = env.act_burst``), instead of one dispatch per step.

Telemetry: each burst bumps ``rollout_bursts``/``act_dispatches`` (and
``env_steps_jax`` for tier a) and runs under the ``Time/rollout_time`` span
(phase ``rollout``). See ``howto/rollout_engine.md``.
"""

from sheeprl_tpu.envs.rollout.burst import BurstActor, DeviceActor
from sheeprl_tpu.envs.rollout.engine import JaxRolloutEngine
from sheeprl_tpu.envs.rollout.jax_envs import (
    JaxCartPole,
    JaxPendulum,
    jax_env_ids,
    make_jax_env,
)

__all__ = [
    "BurstActor",
    "DeviceActor",
    "JaxCartPole",
    "JaxPendulum",
    "JaxRolloutEngine",
    "jax_env_ids",
    "make_jax_env",
]
