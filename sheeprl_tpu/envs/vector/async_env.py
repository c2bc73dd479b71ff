"""Fault-tolerant shared-memory async vector env.

The EnvPool/SEED-RL-inspired host half of the actor loop: every sub-env runs
in its own worker process and writes its step results straight into the
preallocated shared blocks of :class:`~sheeprl_tpu.envs.vector.shmem.
SharedStepSlabs`, in the exact ``[num_envs, ...]`` layout the replay buffers
and the staging facade consume. ``step()`` returns numpy *views* into the
current slot — zero copies between the simulator writing an observation and
``ReplayBuffer.add`` landing it in ring storage (the slabs are
double-buffered, so the previous step's views stay valid for the
obs→next_obs pattern every entrypoint uses).

Semantics are bitwise-compatible with ``SyncVectorEnv(...,
autoreset_mode=SAME_STEP)`` — same per-env seeding, same SAME_STEP autoreset
(``final_obs``/``final_info`` emitted on the terminal step), same
``_``-masked info aggregation — which is what the seeded parity tests in
``tests/test_envs/test_vector.py`` pin down.

Fault tolerance (the part gymnasium's ``AsyncVectorEnv`` does not have):

- a worker that crashes (env exception, dead process) or hangs past
  ``worker_timeout_s`` is killed and restarted, and the lost step is replaced
  by an auto-reset of that env — reward 0, not terminated/truncated, with
  ``info["env_worker_restart"]`` flagged (the in-process
  ``RestartOnException`` contract);
- restarts are bounded: past ``max_worker_restarts`` the pool **degrades to
  sync** — every worker is torn down and the envs are rebuilt in-process,
  stepped serially from then on (slow beats dead);
- workers ignore SIGTERM/SIGINT, so a preemption signal (PR-2 path:
  checkpoint, drain, exit) is handled solely by the parent — ``close()``
  drains workers cleanly, with a short join budget when
  ``preemption_requested()`` so the grace window is spent on the checkpoint,
  not on env teardown.

Observability: the collective wait for worker results is a
``Time/env_wait_time`` span (per-phase p50/p95/p99 via obs/hist.py), async
steps and worker restarts are run counters in telemetry.json/live.json, and
every restart fires the flight recorder.
"""

from __future__ import annotations

import atexit
import multiprocessing
import os
import time
import warnings
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import gymnasium as gym
import numpy as np
from gymnasium.vector import AutoresetMode, VectorEnv
from gymnasium.vector.utils import CloudpickleWrapper, batch_space, iterate

from sheeprl_tpu.envs.vector.shmem import N_SLOTS, SharedStepSlabs

__all__ = ["AsyncSharedMemVectorEnv"]

#: extra patience for worker boot (module imports + env build dominate)
_BOOT_TIMEOUT_FLOOR_S = 120.0


def _close_at_exit(env_ref) -> None:
    """atexit hook: a run that crashes between pool construction and
    ``envs.close()`` must not wedge at interpreter exit. multiprocessing's
    own atexit handler SIGTERMs daemon children and then join()s them
    *without timeout* — but the workers ignore SIGTERM by design, so the
    join would block forever. This hook is registered after (= runs before)
    multiprocessing's, closing the pool properly first. Weakref so the hook
    never keeps a collected pool alive; close() is idempotent."""
    env = env_ref()
    if env is not None:
        try:
            env.close()
        except Exception:
            pass
#: seed offset applied per restart so a rebuilt env does not bitwise-replay
#: the episode that crashed it
_RESTART_SEED_STRIDE = 1_000_003


def _worker(
    index: int,
    thunk: CloudpickleWrapper,
    conn,
    slabs: SharedStepSlabs,
    autoreset: bool,
    trace_spec: Optional[Tuple[str, int, str]] = None,
) -> None:
    """Worker loop: build the env, then serve reset/step commands, writing
    results into the shared slot the parent names on each command.

    Per-worker observability (obs/dist): every worker counts its served
    steps and busy seconds and reports them on the ``close`` handshake (the
    parent folds them into the pool's ``envpool_*`` telemetry source), and
    — on instrumented runs with tracing — writes its own clock-aligned
    trace file (``trace_envworker<i>*.jsonl``) so ``tools/trace_view.py``
    shows learner + players + workers on one Perfetto timeline."""
    import signal

    from sheeprl_tpu.utils.utils import pin_process_to_cpu

    pin_process_to_cpu()  # an env that touches jax must not claim the parent's chip

    # the parent owns shutdown: a preemption SIGTERM/SIGINT fans out to the
    # process group, and a worker that died mid-drain would turn a clean
    # checkpoint-and-exit into a crashed run
    try:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGINT, signal.SIG_IGN)
    except (ValueError, OSError):  # pragma: no cover - non-main-thread spawn
        pass

    tracer = None
    if trace_spec is not None:
        try:
            # the obs package is jax-free at import time, so this stays a
            # lightweight import inside the env host process
            from sheeprl_tpu.obs.spans import TraceWriter

            path, pid, name = trace_spec
            tracer = TraceWriter(path, xla_annotations=False, pid=pid, process_name=name)
        except Exception:
            tracer = None
    stats = {"steps": 0, "busy_s": 0.0}

    env: Optional[gym.Env] = None
    try:
        env = thunk()
        obs_view, rew_view, term_view, trunc_view = slabs.views()
        conn.send(("ready", None, None, None))
        while True:
            cmd, payload = conn.recv()
            if cmd == "close":
                try:
                    conn.send(("stats", dict(stats), None, None))
                except (BrokenPipeError, OSError):
                    pass
                break
            slot = payload["slot"]
            if cmd == "reset":
                obs, info = env.reset(seed=payload["seed"], options=payload["options"])
                for key, arr in obs_view.items():
                    arr[slot, index] = obs[key]
                rew_view[slot, index] = 0.0
                term_view[slot, index] = False
                trunc_view[slot, index] = False
                conn.send(("ok", info, None, None))
            elif cmd == "step":
                t0 = time.perf_counter()
                obs, reward, terminated, truncated, info = env.step(payload["action"])
                final_obs = final_info = None
                if autoreset and (terminated or truncated):
                    # SAME_STEP autoreset: the terminal obs/info travel in the
                    # info channel, the slab gets the freshly-reset obs
                    final_obs, final_info = obs, info
                    obs, info = env.reset()
                stats["steps"] += 1
                stats["busy_s"] += time.perf_counter() - t0
                if tracer is not None:
                    tracer.complete("env_step", "env", t0)
                for key, arr in obs_view.items():
                    arr[slot, index] = obs[key]
                rew_view[slot, index] = reward
                term_view[slot, index] = terminated
                trunc_view[slot, index] = truncated
                conn.send(("ok", info, final_obs, final_info))
            else:  # pragma: no cover - protocol bug
                raise RuntimeError(f"unknown worker command {cmd!r}")
    except (KeyboardInterrupt, SystemExit):  # pragma: no cover
        pass
    except Exception as exc:
        try:
            conn.send(("error", f"{type(exc).__name__}: {exc}", None, None))
        except (BrokenPipeError, OSError):
            pass
    finally:
        if tracer is not None:
            try:
                tracer.close()
            except Exception:
                pass
        if env is not None:
            try:
                env.close()
            except Exception:
                pass
        try:
            conn.close()
        except OSError:
            pass


class AsyncSharedMemVectorEnv(VectorEnv):
    """``env.vectorization=async``: one worker process per sub-env, shared-
    memory step results, bounded worker restarts, degrade-to-sync fallback.

    Parameters
    ----------
    env_fns: env thunks (cloudpickled to the workers).
    env_seeds: the canonical per-env seeds the factory computed; used to
        re-seed the replacement env after a worker restart (offset per
        restart so the crashed episode is not replayed verbatim).
    context: multiprocessing start method (never ``fork`` — the parent has
        live jax threads).
    worker_timeout_s: per-step **collective** deadline before a worker
        counts as hung (one step may not take longer than this in total, so
        a shared external stall can fail several workers at once — size it
        for the slowest legitimate step, not the average); ``<= 0`` disables
        the timeout.
    max_worker_restarts: restart budget within a rolling
        ``restart_window_s`` window (the ``RestartOnException`` semantics —
        sparse transient failures over a long run are forgiven); one more
        failure inside the window degrades the pool to in-process sync
        stepping.
    """

    metadata = {"autoreset_mode": AutoresetMode.SAME_STEP}

    def __init__(
        self,
        env_fns: Sequence[Callable[[], gym.Env]],
        env_seeds: Optional[Sequence[int]] = None,
        context: str = "forkserver",
        worker_timeout_s: float = 60.0,
        max_worker_restarts: int = 3,
        restart_window_s: float = 300.0,
        trace_dir: Optional[str] = None,
        pool_name: Optional[str] = None,
    ):
        self.env_fns = list(env_fns)
        self.num_envs = len(self.env_fns)
        # distributed observability (obs/dist): per-worker trace files land
        # under trace_dir when the run is tracing; pool_name keys the pool's
        # telemetry source in the merged per-source breakdown
        self._trace_dir = trace_dir
        self.pool_name = pool_name or f"envpool_{os.getpid()}"
        self.worker_stats: Dict[int, Dict[str, Any]] = {}
        self.worker_timeout_s = float(worker_timeout_s)
        self.max_worker_restarts = int(max_worker_restarts)
        self.restart_window_s = float(restart_window_s)
        #: restart timestamps inside the rolling window (true sliding-window
        #: budget); ``worker_restarts`` stays the lifetime total for telemetry
        self._restart_times: deque = deque()
        self._env_seeds = list(env_seeds) if env_seeds is not None else [None] * self.num_envs
        self._ctx = multiprocessing.get_context(context)

        # spaces from a probe env built (and closed) in the parent — the shm
        # layout must exist before any worker can be spawned
        probe = self.env_fns[0]()
        self.single_observation_space = probe.observation_space
        self.single_action_space = probe.action_space
        self.metadata = dict(getattr(probe, "metadata", {}) or {})
        self.metadata["autoreset_mode"] = AutoresetMode.SAME_STEP
        self.render_mode = getattr(probe, "render_mode", None)
        probe.close()
        del probe
        self.observation_space = batch_space(self.single_observation_space, self.num_envs)
        self.action_space = batch_space(self.single_action_space, self.num_envs)

        self._slabs = SharedStepSlabs(self._ctx, self.single_observation_space, self.num_envs)
        self._obs_view, self._rew_view, self._term_view, self._trunc_view = self._slabs.views()
        self._slot = 0
        self.worker_restarts = 0
        self.degraded_to_sync = False
        self._sync_envs: Optional[List[gym.Env]] = None
        self._closed = False

        self._procs: List[Optional[Any]] = [None] * self.num_envs
        self._conns: List[Optional[Any]] = [None] * self.num_envs
        self._restart_counts = [0] * self.num_envs
        boot = max(self.worker_timeout_s, _BOOT_TIMEOUT_FLOOR_S)
        for i in range(self.num_envs):
            self._spawn_worker(i)
        for i in range(self.num_envs):
            self._await_ready(i, boot)
        atexit.register(_close_at_exit, weakref.ref(self))

    # -- worker lifecycle ---------------------------------------------------

    def _spawn_worker(self, index: int) -> None:
        parent_conn, child_conn = self._ctx.Pipe(duplex=True)
        trace_spec = None
        if self._trace_dir:
            gen = self._restart_counts[index] if hasattr(self, "_restart_counts") else 0
            suffix = f"_g{gen}" if gen else ""
            trace_spec = (
                os.path.join(self._trace_dir, f"trace_envworker{index}{suffix}.jsonl"),
                1000 + index,  # distinct Perfetto track vs learner/players
                f"envworker{index}",
            )
        proc = self._ctx.Process(
            target=_worker,
            name=f"vecenv-worker-{index}",
            args=(
                index,
                CloudpickleWrapper(self.env_fns[index]),
                child_conn,
                self._slabs,
                True,
                trace_spec,
            ),
            daemon=True,
        )
        proc.start()
        child_conn.close()
        self._procs[index] = proc
        self._conns[index] = parent_conn

    def _await_ready(self, index: int, timeout_s: float) -> None:
        conn = self._conns[index]
        if not conn.poll(timeout_s):
            raise TimeoutError(
                f"async env worker {index} did not come up within {timeout_s:.0f}s"
            )
        try:
            msg = conn.recv()
        except (EOFError, OSError) as exc:
            raise RuntimeError(
                f"async env worker {index} died during boot (import/env-build "
                "failure — run with env.vectorization=sync to see the traceback)"
            ) from exc
        if msg[0] != "ready":
            raise RuntimeError(f"async env worker {index} failed during boot: {msg[1]}")

    def _kill_worker(self, index: int) -> None:
        proc, conn = self._procs[index], self._conns[index]
        self._procs[index] = self._conns[index] = None
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass
        if proc is not None and proc.is_alive():
            # workers ignore SIGTERM by design (the parent owns preemption),
            # so SIGTERM would only stall here — SIGKILL outright; a killed
            # worker cannot corrupt anything: its slab slot is rewritten by
            # the revive (or degrade) reset
            proc.kill()
            proc.join(timeout=2.0)

    def _restart_seed(self, index: int) -> Optional[int]:
        seed = self._env_seeds[index]
        if seed is None:
            return None
        return int(seed) + self._restart_counts[index] * _RESTART_SEED_STRIDE

    def _note_restart(self, index: int, reason: str) -> None:
        from sheeprl_tpu.obs import counters as _counters
        from sheeprl_tpu.obs.telemetry import get_telemetry

        now = time.monotonic()
        self._restart_times.append(now)
        if self.restart_window_s > 0:
            # true sliding window: only failures clustered inside the last
            # restart_window_s seconds spend the degrade budget — sparse
            # transient failures over a long run are forgiven
            while self._restart_times and now - self._restart_times[0] > self.restart_window_s:
                self._restart_times.popleft()
        self.worker_restarts += 1
        self._restart_counts[index] += 1
        _counters.add_env_worker_restart()
        warnings.warn(
            f"async env worker {index} {reason}; restart "
            f"{len(self._restart_times)}/{self.max_worker_restarts} in window "
            "(auto-reset replaces the lost step)"
        )
        tel = get_telemetry()
        if tel is not None and tel.flight is not None:
            tel.flight.trigger(
                "env_worker_restart",
                {
                    "worker": index,
                    "reason": reason,
                    "restarts": self.worker_restarts,
                    "restarts_in_window": len(self._restart_times),
                    "max_worker_restarts": self.max_worker_restarts,
                },
            )

    def _restart_budget_exhausted(self) -> bool:
        """Degrade when the restarts inside the rolling window (or the
        lifetime total when the window is disabled) exceed the budget."""
        in_budget = (
            len(self._restart_times) if self.restart_window_s > 0 else self.worker_restarts
        )
        return in_budget > self.max_worker_restarts

    def _revive_worker(self, index: int, slot: int) -> Dict[str, Any]:
        """Replace a dead/hung worker and fill its step slot with a reset
        obs (reward 0, not terminated — the ``RestartOnException`` contract);
        returns the info dict for the replacement step."""
        self._kill_worker(index)
        self._spawn_worker(index)
        boot = max(self.worker_timeout_s, _BOOT_TIMEOUT_FLOOR_S)
        self._await_ready(index, boot)
        conn = self._conns[index]
        conn.send(("reset", {"seed": self._restart_seed(index), "options": None, "slot": slot}))
        if not conn.poll(boot):
            raise TimeoutError(f"restarted env worker {index} hung on its first reset")
        msg = conn.recv()
        if msg[0] != "ok":
            raise RuntimeError(f"restarted env worker {index} failed its first reset: {msg[1]}")
        info = dict(msg[1] or {})
        info["env_worker_restart"] = True
        return info

    # -- degrade-to-sync ----------------------------------------------------

    def _degrade_to_sync(self, reason: str) -> None:
        from sheeprl_tpu.obs import counters as _counters
        from sheeprl_tpu.obs.telemetry import get_telemetry

        warnings.warn(
            f"async env pool exceeded its restart budget "
            f"({self.max_worker_restarts}): degrading to in-process sync "
            f"stepping ({reason}); every env is auto-reset in place of the lost step"
        )
        for i in range(self.num_envs):
            self._kill_worker(i)
        self.degraded_to_sync = True
        _counters.add_env_degraded()
        tel = get_telemetry()
        if tel is not None and tel.flight is not None:
            tel.flight.trigger(
                "env_degrade_sync",
                {"reason": reason, "restarts": self.worker_restarts},
            )
        self._sync_envs = [fn() for fn in self.env_fns]
        for i, env in enumerate(self._sync_envs):
            # bump every env's restart generation: resetting a healthy env
            # with its ORIGINAL seed would bitwise-replay trajectories the
            # buffer already holds from run start
            self._restart_counts[i] += 1
            obs, _ = env.reset(seed=self._restart_seed(i))
            for key, arr in self._obs_view.items():
                arr[self._slot, i] = obs[key]
            self._rew_view[self._slot, i] = 0.0
            self._term_view[self._slot, i] = False
            self._trunc_view[self._slot, i] = False

    def _step_sync(self, actions_per_env: List[Any]) -> Dict[int, Tuple]:
        """In-process serial stepping after degrade (same slab layout, same
        SAME_STEP autoreset, so callers never notice beyond the speed)."""
        slot = self._slot
        results: Dict[int, Tuple] = {}
        for i, env in enumerate(self._sync_envs):
            obs, reward, terminated, truncated, info = env.step(actions_per_env[i])
            final_obs = final_info = None
            if terminated or truncated:
                final_obs, final_info = obs, info
                obs, info = env.reset()
            for key, arr in self._obs_view.items():
                arr[slot, i] = obs[key]
            self._rew_view[slot, i] = reward
            self._term_view[slot, i] = terminated
            self._trunc_view[slot, i] = truncated
            results[i] = (info, final_obs, final_info)
        return results

    # -- VectorEnv API ------------------------------------------------------

    def reset(
        self,
        *,
        seed: Optional[Any] = None,
        options: Optional[Dict[str, Any]] = None,
    ):
        self._assert_open()
        if seed is None:
            seeds: List[Optional[int]] = [None] * self.num_envs
        elif isinstance(seed, int):
            seeds = [seed + i for i in range(self.num_envs)]
        else:
            seeds = list(seed)
            assert len(seeds) == self.num_envs
        # flip the slot like step() does, so views from a preceding step
        # survive a reset too (the documented double-buffer contract)
        self._slot = (self._slot + 1) % N_SLOTS
        slot = self._slot
        infos: Dict[str, Any] = {}
        if self._sync_envs is not None:
            for i, env in enumerate(self._sync_envs):
                obs, info = env.reset(seed=seeds[i], options=options)
                for key, arr in self._obs_view.items():
                    arr[slot, i] = obs[key]
                infos = self._add_info(infos, info, i)
        else:
            for i in range(self.num_envs):
                self._conns[i].send(
                    ("reset", {"seed": seeds[i], "options": options, "slot": slot})
                )
            results = self._collect(slot)
            for i in range(self.num_envs):
                infos = self._add_info(infos, results[i][0], i)
        return self._slot_obs(slot), infos

    def step(self, actions):
        self._assert_open()
        actions_per_env = [
            np.asarray(a) for a in iterate(self.action_space, actions)
        ]
        self._slot = (self._slot + 1) % N_SLOTS
        slot = self._slot
        if self._sync_envs is not None:
            results = self._step_sync(actions_per_env)
        else:
            for i in range(self.num_envs):
                self._conns[i].send(("step", {"action": actions_per_env[i], "slot": slot}))
            results = self._collect(slot)
            from sheeprl_tpu.obs import counters as _counters

            _counters.add_env_async_steps(self.num_envs)
        infos: Dict[str, Any] = {}
        for i in range(self.num_envs):
            info, final_obs, final_info = results[i]
            if final_obs is not None or final_info is not None:
                infos = self._add_info(
                    infos, {"final_obs": final_obs, "final_info": final_info}, i
                )
            infos = self._add_info(infos, info, i)
        return (
            self._slot_obs(slot),
            np.copy(self._rew_view[slot]),
            np.copy(self._term_view[slot]),
            np.copy(self._trunc_view[slot]),
            infos,
        )

    def _collect(self, slot: int) -> Dict[int, Tuple]:
        """Gather one reply per worker under the collective step deadline,
        reviving (or degrading past the windowed budget) crashed/hung
        workers. A revived worker is not handed the lost command again — the
        step is replaced by the auto-reset contract.

        The wait is the ``Time/env_wait_time`` span — on a healthy overlap
        run its histogram hugs zero while the accelerator trains; when it
        grows, the envs are the bottleneck again.
        """
        from sheeprl_tpu.obs.spans import span

        deadline = (
            time.perf_counter() + self.worker_timeout_s
            if self.worker_timeout_s > 0
            else None
        )
        results: Dict[int, Tuple] = {}
        failed: List[Tuple[int, str]] = []
        with span("Time/env_wait_time", phase="env_wait"):
            for i in range(self.num_envs):
                conn = self._conns[i]
                remaining = None if deadline is None else max(deadline - time.perf_counter(), 0.0)
                try:
                    if remaining is not None and not conn.poll(remaining):
                        failed.append((i, "hung past worker_timeout_s"))
                        continue
                    msg = conn.recv()
                except (EOFError, BrokenPipeError, ConnectionResetError, OSError):
                    failed.append((i, "process died"))
                    continue
                if msg[0] == "ok":
                    results[i] = (msg[1], msg[2], msg[3])
                else:
                    failed.append((i, f"env raised ({msg[1]})"))
        for i, reason in failed:
            self._note_restart(i, reason)
            if self._restart_budget_exhausted():
                self._degrade_to_sync(reason)
                # the degrade auto-reset EVERY env into the current slot, so
                # every env reports the restart-step contract for this step
                return {
                    j: ({"env_worker_restart": True}, None, None)
                    for j in range(self.num_envs)
                }
            try:
                results[i] = (self._revive_worker(i, slot), None, None)
            except Exception as exc:
                self._degrade_to_sync(f"worker {i} restart failed: {exc}")
                return {
                    j: ({"env_worker_restart": True}, None, None)
                    for j in range(self.num_envs)
                }
        return results

    def _slot_obs(self, slot: int) -> Dict[str, np.ndarray]:
        """Zero-copy: views into the shared slot, ``[num_envs, ...]`` per key."""
        return {key: arr[slot] for key, arr in self._obs_view.items()}

    def _assert_open(self) -> None:
        if self._closed:
            raise RuntimeError("AsyncSharedMemVectorEnv is closed")

    # -- teardown -----------------------------------------------------------

    def close_extras(self, **kwargs) -> None:
        if self._closed:
            return
        self._closed = True
        if self._sync_envs is not None:
            for env in self._sync_envs:
                try:
                    env.close()
                except Exception:
                    pass
            self._sync_envs = None
            self._publish_pool_source()
            return
        try:
            from sheeprl_tpu.ckpt import preemption_requested

            draining = preemption_requested()
        except Exception:  # pragma: no cover - ckpt subsystem absent
            draining = False
        # under preemption the grace window belongs to the final checkpoint:
        # ask workers to exit but only wait briefly before terminating them
        join_budget = 2.0 if draining else 10.0
        for conn in self._conns:
            if conn is not None:
                try:
                    conn.send(("close", {}))
                except (BrokenPipeError, OSError):
                    pass
        deadline = time.perf_counter() + join_budget
        # collect the per-worker stats reply each worker sends on the close
        # handshake (steps served, env busy seconds) — best-effort within
        # the join budget, a dead/hung worker just reports nothing. Drain
        # any unconsumed step/reset replies first (a teardown between
        # dispatch and collect leaves a stale 'ok' queued ahead of 'stats').
        for i, conn in enumerate(self._conns):
            if conn is None:
                continue
            try:
                while True:
                    remaining = max(min(deadline - time.perf_counter(), 1.0), 0.0)
                    if not conn.poll(remaining):
                        break
                    msg = conn.recv()
                    if msg[0] == "stats" and isinstance(msg[1], dict):
                        self.worker_stats[i] = msg[1]
                        break
            except (EOFError, BrokenPipeError, ConnectionResetError, OSError):
                pass
        for proc in self._procs:
            remaining = deadline - time.perf_counter()
            if proc is None or remaining <= 0:
                continue  # budget spent: straight to SIGKILL below
            proc.join(timeout=remaining)
        for i in range(self.num_envs):
            self._kill_worker(i)
        self._publish_pool_source()

    def _publish_pool_source(self) -> None:
        """Fold this pool's per-worker stats into the merged telemetry view
        (obs/dist/aggregate): published into the process-local source
        registry — the learner's telemetry picks it up directly; inside a
        plane player it lands in the player's final sidecar as
        ``env_pools`` and is lifted to ``player<k>/<pool>`` at the merge —
        and mirrored as a sidecar file when a telemetry run dir exists."""
        try:
            from sheeprl_tpu.obs.dist import aggregate as _aggregate
            from sheeprl_tpu.obs.telemetry import get_telemetry

            snap = {
                "num_envs": self.num_envs,
                "worker_restarts": self.worker_restarts,
                "degraded_to_sync": bool(self.degraded_to_sync),
                "workers": {
                    str(i): {
                        "steps": int(self.worker_stats.get(i, {}).get("steps", 0)),
                        "busy_s": round(
                            float(self.worker_stats.get(i, {}).get("busy_s", 0.0)), 3
                        ),
                        "restarts": int(self._restart_counts[i]),
                    }
                    for i in range(self.num_envs)
                },
            }
            _aggregate.publish_source(self.pool_name, snap)
            tel = get_telemetry()
            if tel is not None and tel.run_dir:
                _aggregate.write_sidecar(
                    os.path.join(tel.run_dir, "telemetry"), self.pool_name, snap
                )
        except Exception:
            pass  # telemetry must never break env teardown

    def close(self, **kwargs) -> None:
        self.close_extras(**kwargs)

    def __del__(self):  # pragma: no cover - interpreter teardown best effort
        try:
            if not getattr(self, "_closed", True):
                self.close()
        except Exception:
            pass
