"""Player-process bootstrap and the context handed to algo player loops.

A player process is a fresh interpreter (non-fork start method, like the
PR-5 env workers): :func:`child_main` pins jax to the **CPU backend before
jax ever imports** (players must never initialize — or fight over — the
trainer's accelerator), ignores SIGTERM/SIGINT (preemption is the learner's
business; players exit through the plane's stop event during the PR-2
drain), restores the run's PRNG implementation so key arithmetic matches
the learner bitwise, and then imports the algorithm's player loop *by
dotted name* — the algo registers a module-level ``run_player(ctx)``;
nothing is cloudpickled.

:class:`PlayerContext` is the one surface an algo player loop sees, in both
execution modes: config + identity, the policy channel
(``wait_min_version``), a trajectory writer (``acquire``/``commit`` —
shared-memory slab views in process mode, fresh arrays over a bounded queue
in thread mode), the stop event, and the protocol scalars. Loops written
against it cannot tell the transports apart — by design (the bitwise
thread-vs-plane regression gate).
"""

from __future__ import annotations

import importlib
import os
import signal
import sys
import traceback
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["PlayerContext", "SlabWriter", "LocalWriter", "child_main"]


class SlabWriter:
    """Process-mode trajectory writer: credited shared-memory slab slots."""

    def __init__(self, ring):
        self._ring = ring

    def acquire(self, stop=None) -> Tuple[Any, Dict[str, np.ndarray]]:
        slot = self._ring.acquire(stop)
        return slot, self._ring.writer_views(slot)

    def commit(self, token, first_update, n_valid, version, ep_stats, stop=None) -> None:
        self._ring.commit(token, first_update, n_valid, version, ep_stats)


class LocalWriter:
    """Thread-mode trajectory writer: fresh arrays per burst over a bounded
    queue (the commit blocks when the learner is behind — same backpressure,
    no shared memory needed inside one process)."""

    def __init__(self, burst_queue, spec):
        self._q = burst_queue
        self._spec = spec

    def acquire(self, stop=None) -> Tuple[Any, Dict[str, np.ndarray]]:
        views = {
            key: np.empty(shape, dtype=np.dtype(dtype))
            for key, shape, dtype in self._spec.keys
        }
        return None, views

    def commit(self, token_views, first_update, n_valid, version, ep_stats, stop=None) -> None:
        import time

        from sheeprl_tpu.plane.local import BurstPayload

        data, views = token_views
        self._q.commit(
            BurstPayload(
                data=views,
                first_update=int(first_update),
                n_valid=int(n_valid),
                policy_version=int(version),
                ep_stats=list(ep_stats or []),
                commit_ts=time.time(),
            ),
            stop=stop,
        )


class _HaltSignal:
    """Event-like view over ``stop | orphaned`` for blocking player waits.

    A player blocked inside ``TrajSlabRing.acquire`` or
    ``PolicyPoller.wait_min_version`` polls only the object passed as
    ``stop`` — if the learner dies without running ``drain()`` (SIGKILL,
    OOM), the stop event is never set and no credit/version will ever
    arrive, so the orphan watch must trip these waits too or the
    non-daemonic player (and its env worker pool) spins forever."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx: "PlayerContext"):
        self._ctx = ctx

    def is_set(self) -> bool:
        ctx = self._ctx
        return (ctx.stop is not None and ctx.stop.is_set()) or ctx.orphaned()


@dataclass
class PlayerContext:
    """Everything an algo player loop needs, transport-agnostic."""

    cfg: Any
    player_idx: int
    n_players: int
    n_envs: int  # this player's share of the env fleet
    env_rank: int  # seed-partition rank handed to env_seeds()
    start_update: int
    restart_count: int
    log_dir: Optional[str]
    channel: Any  # wait_min_version(min_version, stop, use_exact)
    writer: Any  # SlabWriter | LocalWriter
    stop: Any  # threading.Event | mp.Event
    player_key: np.ndarray  # raw PRNG key data (same key both modes)
    scalars: Dict[str, int] = field(default_factory=dict)
    process_mode: bool = False  # True inside a spawned player process
    parent_pid: Optional[int] = None  # ppid observed at player start
    # stall-watchdog binding (thread mode only: the learner injects its own
    # RUNNING watchdog — `Telemetry.watchdog()` constructs a fresh unstarted
    # one per call, so the player must not fetch its own. A player process
    # has no telemetry installed and is covered by the learner-side
    # plane.recv_timeout_s deadline instead.)
    watchdog: Any = None
    #: process mode only: rate-limited callable pushing this player's
    #: cumulative counter snapshot to the learner's event queue, so the
    #: merged live.json carries a fresh per-player breakdown mid-run
    #: (obs/dist/aggregate; the supervisor folds counter DELTAS)
    telemetry_sink: Any = None
    _wd_role: str = field(default="", init=False, repr=False)

    def orphaned(self) -> bool:
        """A player whose parent died must exit instead of lingering (the
        players are non-daemonic so they can own env worker pools). Under
        forkserver the observed parent is the forkserver process — it dies
        with the learner, reparenting this player, which is what we watch."""
        return (
            self.process_mode
            and self.parent_pid is not None
            and os.getppid() != self.parent_pid
        )

    @property
    def halt(self) -> _HaltSignal:
        """What every blocking player wait must poll: the plane's stop event
        OR the orphan watch (see :class:`_HaltSignal`)."""
        return _HaltSignal(self)

    # -- stall-watchdog heartbeats -------------------------------------------

    def _watchdog(self):
        wd = self.watchdog
        if wd is not None and not self._wd_role:
            self._wd_role = f"plane-player-{self.player_idx}"
            wd.register(self._wd_role)
        return wd

    def beat(self) -> None:
        """Once per unit of player progress (an env step) — a hung env wedges
        the player mid-burst, and without this the stall goes silent."""
        wd = self._watchdog()
        if wd is not None:
            wd.beat(self._wd_role)

    def pause_watchdog(self) -> None:
        """Before blocking on the learner (slab credit, policy wait):
        waiting for the peer is idleness, not a stall."""
        wd = self._watchdog()
        if wd is not None:
            wd.pause(self._wd_role)

    def close_watchdog(self) -> None:
        """A finished player is not a stalled one."""
        if self.watchdog is not None and self._wd_role:
            self.watchdog.unregister(self._wd_role)

    # -- protocol sugar ------------------------------------------------------

    @property
    def num_updates(self) -> int:
        return int(self.scalars["num_updates"])

    @property
    def learning_starts(self) -> int:
        return int(self.scalars.get("learning_starts", 0))

    @property
    def first_train_update(self) -> int:
        return int(self.scalars["first_train_update"])

    @property
    def act_burst(self) -> int:
        return max(int(self.scalars.get("act_burst", 1)), 1)

    @property
    def max_policy_lag(self) -> int:
        return max(int(self.scalars.get("max_policy_lag", 0)), 0)

    def wait_policy(self, first_update: int) -> Tuple[int, Any]:
        """Block for the version acting at ``first_update`` requires (minus
        the allowed lag); deterministic exact-version load at lag 0."""
        from sheeprl_tpu.plane.protocol import required_version

        req = required_version(first_update, self.first_train_update)
        lag = self.max_policy_lag
        self.pause_watchdog()  # waiting on the learner's publish
        got = self.channel.wait_min_version(
            max(req - lag, 0), stop=self.halt, use_exact=(lag == 0)
        )
        self.beat()
        return got

    def acquire_slab(self) -> Tuple[Any, Dict[str, np.ndarray]]:
        """One slab credit + its write views; blocks under backpressure
        (paused for the watchdog — a slow learner is not a player stall)."""
        self.pause_watchdog()
        token, views = self.writer.acquire(self.halt)
        self.beat()
        return token, views

    def emit(self, token, views, first_update, n_valid, version, ep_stats) -> None:
        self.pause_watchdog()  # a full queue blocks here — learner's pace
        self.writer.commit(
            (token, views) if isinstance(self.writer, LocalWriter) else token,
            first_update,
            n_valid,
            version,
            ep_stats,
            stop=self.halt,
        )
        if self.telemetry_sink is not None:
            try:
                self.telemetry_sink()
            except Exception:
                pass  # telemetry must never take a player down
        self.beat()


# ---------------------------------------------------------------------------
# process-mode bootstrap
# ---------------------------------------------------------------------------


def _install_player_telemetry() -> Tuple[Any, Any]:
    from sheeprl_tpu.obs import counters as _counters
    from sheeprl_tpu.obs import hist as _hist

    counters = _counters.Counters()
    hists = _hist.HistogramSet()
    _counters.install(counters)
    _hist.install(hists)
    return counters, hists


def child_main(spec: Dict[str, Any]) -> None:
    """Entry point of a player process (target of the supervisor's spawn)."""
    # preemption signals go to the learner; players drain via the stop event
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    import jax

    from sheeprl_tpu.utils.utils import enable_persistent_compilation_cache, pin_process_to_cpu

    pin_process_to_cpu()  # players live on the host CPU, never the mesh
    if spec.get("prng_impl"):
        jax.config.update("jax_default_prng_impl", str(spec["prng_impl"]))
    enable_persistent_compilation_cache()

    idx = int(spec["player_idx"])
    events = spec["events"]
    counters = hists = None
    tracer = None
    if spec.get("telemetry"):
        counters, hists = _install_player_telemetry()
        if spec.get("trace") and spec.get("log_dir"):
            # the player's own span timeline (env steps, rollout bursts,
            # policy waits) — clock_sync-anchored so tools/trace_view.py
            # merges it onto the learner's Perfetto view; pid 100+idx keeps
            # the track distinct from the learner (pid 0) and env workers
            from sheeprl_tpu.obs.spans import TraceWriter, set_tracer

            try:
                tracer = TraceWriter(
                    os.path.join(
                        spec["log_dir"], "telemetry", f"trace_rank0_player{idx}.jsonl"
                    ),
                    xla_annotations=False,
                    pid=100 + idx,
                    process_name=f"player{idx}",
                )
                set_tracer(tracer)
            except OSError:
                tracer = None

    from sheeprl_tpu.plane.slabs import PlaneClosed
    from sheeprl_tpu.plane.publish import PolicyPoller

    ctx = PlayerContext(
        cfg=spec["cfg"],
        player_idx=idx,
        n_players=int(spec["n_players"]),
        n_envs=int(spec["n_envs"]),
        env_rank=int(spec["env_rank"]),
        start_update=int(spec["start_update"]),
        restart_count=int(spec["restart_count"]),
        log_dir=spec.get("log_dir"),
        channel=PolicyPoller(
            spec["policy_root"], poll_interval_s=float(spec.get("poll_interval_s", 0.05))
        ),
        writer=SlabWriter(spec["ring"]),
        stop=spec["stop"],
        player_key=np.asarray(spec["player_key"]),
        scalars=dict(spec["scalars"]),
        process_mode=True,
        parent_pid=os.getppid(),
    )

    if counters is not None:
        # periodic cumulative snapshots → the learner folds counter deltas
        # and publishes the raw snapshot as source `player<idx>` (live.json
        # breakdown while the run is still going)
        sink_state = {"last": 0.0}

        def _telemetry_sink(min_interval_s: float = 10.0) -> None:
            import time as _time

            now = _time.monotonic()
            if now - sink_state["last"] < min_interval_s:
                return
            sink_state["last"] = now
            events.put((idx, "telemetry", counters.as_dict()))

        ctx.telemetry_sink = _telemetry_sink

    module_name, fn_name = str(spec["entry"]).split(":")
    run_player = getattr(importlib.import_module(module_name), fn_name)

    rc = 0
    try:
        run_player(ctx)
    except PlaneClosed:
        pass  # clean shutdown mid-wait
    except BaseException:
        rc = 1
        try:
            events.put((idx, "error", traceback.format_exc(limit=20)))
        except Exception:
            pass
    finally:
        if counters is not None:
            try:
                events.put((idx, "telemetry", counters.as_dict()))
            except Exception:
                pass
        if hists is not None and spec.get("log_dir"):
            # picked up by the learner's finalize-time hist merge (the glob
            # in Telemetry._sync_rank_hists matches hist_rank*.json)
            try:
                from sheeprl_tpu.obs.live import atomic_write_json

                atomic_write_json(
                    os.path.join(
                        spec["log_dir"], "telemetry", f"hist_rank0_player{idx}.json"
                    ),
                    hists.to_dict(),
                )
            except Exception:
                pass
        if counters is not None and spec.get("log_dir"):
            # final per-player sidecar for the learner's finalize-time merge
            # (obs/dist/aggregate): the whole counter dict, phase tails, and
            # the env pools this player ran in-process (the pool published
            # into this process's source registry at close — run_player's
            # finally closed the envs before we got here)
            try:
                from sheeprl_tpu.obs.dist import aggregate as _aggregate

                sidecar = dict(counters.as_dict())
                sidecar["phase_percentiles"] = hists.percentiles() if hists else {}
                sidecar["restart_count"] = int(spec.get("restart_count", 0))
                pools = _aggregate.source_snapshots()
                if pools:
                    sidecar["env_pools"] = pools
                _aggregate.write_sidecar(
                    os.path.join(spec["log_dir"], "telemetry"), f"player{idx}", sidecar
                )
            except Exception:
                pass
        if tracer is not None:
            try:
                tracer.close()
            except Exception:
                pass
    sys.exit(rc)
