"""The policy-serving gateway: model + batcher + transports + lifecycle.

:class:`ServeGateway` owns one :class:`~sheeprl_tpu.serve.model.GatewayModel`
(loaded through the eval-builder registry from a checkpoint path or a
``registry:best:<algo>:<env id>`` ref), one
:class:`~sheeprl_tpu.serve.batcher.RequestBatcher` (fill-or-deadline request
coalescing), and optionally

- a :class:`~sheeprl_tpu.serve.model.PolicySwapper` watching a policy
  publication channel for in-place hot-swaps (``watch``), and
- an :class:`~sheeprl_tpu.serve.rings.ActSlabRing` server thread for
  cross-process clients (``start_ring``).

``drain()`` is the SIGTERM contract: stop accepting, finish every in-flight
request, stop the threads — asserted in ``tests/test_serve``.

:func:`rescore_through_gateway` is the gateway-path parity check: it runs
the eval service's exact frozen-greedy protocol (same pool, same seed
ladder, same per-step key schedule) but routes every episode row through
its own serve client, so the batcher coalesces each pool step into one
dispatch. Matched seeds ⇒ bitwise the returns
:func:`~sheeprl_tpu.evals.service.evaluate_checkpoint` produces — the
evidence that the serving path adds transport, not math
(``tools/bench_serve.py --matrix-parity`` commits it).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional

import numpy as np

from sheeprl_tpu.obs.reqtrace import now as _now
from sheeprl_tpu.obs.reqtrace import unix_now as _unix_now
from sheeprl_tpu.utils.utils import dotdict

__all__ = [
    "ServeContext",
    "ServeGateway",
    "rescore_through_gateway",
    "run_serve_entrypoint",
    "serve_settings",
]

#: shipped defaults for the ``serve`` config group (configs/serve/default.yaml)
_SERVE_DEFAULTS: Dict[str, Any] = {
    "max_batch": 64,
    "deadline_ms": 10.0,
    "seed": 42,
    "max_clients": 1024,
    "registry_dir": "logs/registry",
    "poll_root": None,  # policy publication dir to watch for hot-swaps
    "poll_interval_s": 0.2,
    "drain_timeout_s": 30.0,
    "duration_s": 0.0,  # 0 → serve until signaled
    # ---- request-path observability (all off by default: ops stays None
    # ---- and the request path is byte-identical to the pre-ops gateway)
    "trace_sample_rate": 0.0,  # fraction of requests emitting span chains
    "access_log_sample_rate": 0.0,  # fraction of requests logged to access.jsonl
    "obs_dir": None,  # where traces/alerts/access/serve_live.json land
    "metrics_port": None,  # /metrics endpoint port (0 → ephemeral)
    "inject_dispatch_delay_s": 0.0,  # fault injection: stall device_dispatch
    "slo": {  # burn-rate objectives (obs/slo.py fills the rest)
        "enabled": False,
        "objectives": {
            "act_latency_p99_ms": 250.0,
            "availability": 0.999,
            "swap_staleness_s": 30.0,
        },
    },
}


def serve_settings(cfg) -> dotdict:
    """The ``serve`` knobs with shipped defaults filled in."""
    merged = dict(_SERVE_DEFAULTS)
    try:
        user = cfg.get("serve", {}) or {}
    except AttributeError:
        user = {}
    for key, value in dict(user).items():
        merged[key] = value
    return dotdict(merged)


class ServeGateway:
    """One serving endpoint: coalesced batched inference over one model."""

    def __init__(
        self,
        model,
        cfg=None,
        observation_space=None,
        action_space=None,
        max_batch: int = 64,
        deadline_s: float = 0.010,
        seed: int = 42,
    ):
        from sheeprl_tpu.serve.batcher import RequestBatcher

        self.cfg = cfg
        self.observation_space = observation_space
        self.action_space = action_space
        self.batcher = RequestBatcher(
            model, max_batch=max_batch, deadline_s=deadline_s, seed=seed
        )
        self._swapper = None
        self._ring = None
        self._ring_stop = threading.Event()
        self._ring_thread: Optional[threading.Thread] = None
        self.ops = None

    @classmethod
    def from_checkpoint(
        cls,
        checkpoint_ref: str,
        registry_dir: str = "logs/registry",
        max_batch: int = 64,
        deadline_s: float = 0.010,
        seed: int = 42,
    ) -> "ServeGateway":
        """Cold start: manifest-validated load via the eval-builder registry."""
        from sheeprl_tpu.serve.model import load_gateway_model

        model, cfg, obs_space, act_space = load_gateway_model(
            checkpoint_ref, registry_dir=registry_dir
        )
        return cls(
            model,
            cfg=cfg,
            observation_space=obs_space,
            action_space=act_space,
            max_batch=max_batch,
            deadline_s=deadline_s,
            seed=seed,
        )

    # ------------------------------------------------------------- client API

    def client(self, client_id: Optional[str] = None):
        """An in-process serve client (the sanctioned access path)."""
        from sheeprl_tpu.serve.client import LocalServeClient

        return LocalServeClient(self.batcher, client_id=client_id)

    # ------------------------------------------------------------ ops surface

    def enable_ops(self, settings: Dict[str, Any], out_dir: Optional[str] = None):
        """Attach the request-path observability planes (tracing, SLO engine,
        access log, ``/metrics``) per the ``serve.*`` knobs. Returns the
        :class:`~sheeprl_tpu.serve.ops.ServeOps` — or None when every knob is
        off, in which case the request path is untouched."""
        from sheeprl_tpu.serve.ops import ServeOps

        if self.ops is not None:
            raise RuntimeError("gateway ops surface is already enabled")
        out = out_dir or settings.get("obs_dir") or "logs/serve_obs"
        self.ops = ServeOps.build(
            settings,
            str(out),
            status_fn=self.status,
            staleness_fn=self._swap_staleness,
        )
        if self.ops is not None:
            self.batcher.attach_ops(self.ops)
            if self._ring is not None and self.ops.tracer is not None:
                self._ring.trace_every = int(self.ops.tracer._every)
        return self.ops

    def _swap_staleness(self) -> float:
        """Seconds the serving model has lagged the newest published policy:
        0 when no swapper is attached or serving is current; otherwise the
        age of the newest unpicked-up publication."""
        swapper = self._swapper
        if swapper is None:
            return 0.0
        try:
            latest = swapper._poller.latest_version()
            if latest is None or int(latest) <= int(swapper._last_version):
                return 0.0
            from sheeprl_tpu.plane.publish import policy_path

            mtime = os.path.getmtime(policy_path(swapper._poller.root, int(latest)))
            return max(0.0, _unix_now() - mtime)
        except Exception:
            return 0.0

    # --------------------------------------------------------------- hot-swap

    def watch(self, policy_root: str, poll_interval_s: float = 0.2):
        """Start hot-swapping from a policy publication channel."""
        from sheeprl_tpu.serve.model import PolicySwapper

        if self._swapper is not None:
            raise RuntimeError("gateway is already watching a policy channel")
        self._swapper = PolicySwapper(
            policy_root,
            self.cfg,
            self.observation_space,
            self.action_space,
            swap_fn=self.batcher.swap,
            base_model=self.batcher.model,
            poll_interval_s=poll_interval_s,
        )
        return self._swapper

    # ----------------------------------------------------------- ring serving

    def start_ring(self, n_clients: int, ctx=None):
        """Create the shared-memory ring for ``n_clients`` external clients
        and start the server thread pumping it into the batcher."""
        from sheeprl_tpu.serve.rings import ActSlabRing

        if self._ring is not None:
            raise RuntimeError("gateway already serves a ring")
        if self.observation_space is None or self.action_space is None:
            raise RuntimeError("ring serving needs the gateway's env spaces")
        obs_row = {
            k: np.asarray(space.sample())
            for k, space in self.observation_space.spaces.items()
        }
        act_row = np.asarray(self.action_space.sample())
        self._ring = ActSlabRing.from_example(obs_row, act_row, n_clients, ctx=ctx)
        if self.ops is not None and self.ops.tracer is not None:
            # the ring carries the sampling knob: child-process clients have
            # no tracer installed, they stamp every trace_every-th request
            self._ring.trace_every = int(self.ops.tracer._every)
        self._ring_thread = threading.Thread(
            target=self._serve_ring, name="serve-ring", daemon=True
        )
        self._ring_thread.start()
        return self._ring

    def _serve_ring(self) -> None:
        from sheeprl_tpu.serve.batcher import ServeClosed

        ring = self._ring
        while not self._ring_stop.is_set():
            requests = ring.next_requests(timeout=0.05)
            if not requests:
                continue
            tickets = []
            for slot, seq, reset in requests:
                obs = ring.read_obs_row(slot)
                trace = ring.read_meta(slot)
                try:
                    ticket = self.batcher.submit(f"ring{slot}", obs, reset=reset, trace=trace)
                except ServeClosed as exc:
                    ring.respond(slot, seq, None, -1, error=str(exc))
                    continue
                tickets.append((slot, seq, ticket))
            # the tickets resolve together (one coalesced dispatch covers
            # them); waiting here costs nothing extra and keeps the pump
            # single-threaded
            for slot, seq, ticket in tickets:
                try:
                    action, version = self.batcher.wait(ticket, timeout=60.0)
                except Exception as exc:
                    ring.respond(slot, seq, None, -1, error=str(exc))
                    continue
                ring.respond(slot, seq, action, version)

    # -------------------------------------------------------------- lifecycle

    def status(self) -> Dict[str, Any]:
        model = self.batcher.model
        status = {
            "algo": model.algo,
            "env": model.env_id,
            "model_version": int(model.version),
            "checkpoint": model.checkpoint,
            "swapper": self._swapper is not None,
            **self.batcher.stats(),
        }
        ops = self.ops
        if ops is not None:
            if ops.tracer is not None:
                status["trace"] = {
                    "sample_rate": float(ops.tracer.sample_rate),
                    "sampled_requests": int(ops.tracer.sampled),
                }
            if ops.slo is not None:
                status["slo"] = ops.slo.status()
        return status

    def drain(self, timeout: float = 30.0) -> bool:
        """SIGTERM path: finish in-flight requests, then stop everything."""
        drained = self.batcher.drain(timeout=timeout)
        self._shutdown_aux()
        return drained

    def close(self) -> None:
        self.batcher.close()
        self._shutdown_aux()

    def _shutdown_aux(self) -> None:
        if self.ops is not None:
            self.ops.close()
            self.ops = None
        if self._swapper is not None:
            self._swapper.close()
            self._swapper = None
        self._ring_stop.set()
        if self._ring is not None:
            self._ring.close()
        if self._ring_thread is not None:
            self._ring_thread.join(timeout=10.0)
            self._ring_thread = None


class ServeContext:
    """Spawn-picklable bundle for running a serve client in a child process
    (the :class:`~sheeprl_tpu.plane.worker.PlayerContext` shape, collapsed to
    the client side): the ring, the client's slot, and a ``module:function``
    entry point called as ``entry(client, spec)``. ``child_main`` pins the
    child to the CPU jax backend first — serve clients never touch the
    device."""

    def __init__(self, ring, slot: int, entry: str, spec: Optional[Dict[str, Any]] = None):
        self.ring = ring
        self.slot = int(slot)
        self.entry = str(entry)
        self.spec = dict(spec or {})


def child_main(ctx: ServeContext) -> None:
    """Client-process entry point (spawned, never forked)."""
    import importlib

    from sheeprl_tpu.serve.client import RingServeClient
    from sheeprl_tpu.utils.utils import pin_process_to_cpu

    pin_process_to_cpu()  # serve clients never touch the device

    module_name, _, fn_name = ctx.entry.partition(":")
    fn = getattr(importlib.import_module(module_name), fn_name)
    client = RingServeClient(ctx.ring, ctx.slot)
    fn(client, ctx.spec)


# ---------------------------------------------------------------------------
# gateway-path parity rescore
# ---------------------------------------------------------------------------


def rescore_through_gateway(
    checkpoint_ref: str,
    episodes: int = 10,
    seed0: int = 1000,
    registry_dir: str = "logs/registry",
    max_steps: int = 0,
) -> Dict[str, Any]:
    """Frozen-greedy protocol with every episode row behind a serve client.

    Same pool, same seed ladder, same per-dispatch key schedule as
    :func:`~sheeprl_tpu.evals.service.run_parallel_episodes` — one full
    coalesced batch per pool step — so matched seeds reproduce the eval
    service's returns bitwise. Returns the eval-shaped result dict plus the
    gateway's ``versions_served`` / occupancy stats.
    """
    from sheeprl_tpu.evals.service import eval_settings, iqm, make_eval_pool

    n = int(episodes)
    gateway = ServeGateway.from_checkpoint(
        checkpoint_ref,
        registry_dir=registry_dir,
        max_batch=n,  # every pool step coalesces into exactly one dispatch
        deadline_s=5.0,
        seed=int(seed0),  # the runner's PRNGKey(seed0) act-key schedule
    )
    try:
        cfg = gateway.cfg
        settings = eval_settings(cfg)
        max_steps = int(max_steps or settings.max_steps or 0)
        pool, seeds = make_eval_pool(cfg, None, n, int(seed0), prefix="serve")
        try:
            single_space = getattr(pool, "single_action_space", None)
            act_shape = tuple(single_space.shape) if single_space is not None else ()
            clients = [gateway.client(f"episode{i}") for i in range(n)]
            obs, _ = pool.reset(seed=[int(s) for s in seeds])
            returns = np.zeros(n, dtype=np.float64)
            lengths = np.zeros(n, dtype=np.int64)
            alive = np.ones(n, dtype=bool)
            need_reset = np.zeros(n, dtype=bool)
            steps = 0
            while alive.any():
                tickets = [
                    clients[i]._batcher.submit(
                        clients[i].client_id,
                        {k: np.asarray(v[i]) for k, v in obs.items()},
                        reset=bool(need_reset[i]),
                    )
                    for i in range(n)
                ]
                rows = [gateway.batcher.wait(t, timeout=60.0) for t in tickets]
                actions = np.stack([np.asarray(a) for a, _v in rows])
                real_actions = actions.reshape((n,) + act_shape)
                obs, rewards, terminated, truncated, _ = pool.step(real_actions)
                done = np.logical_or(
                    np.asarray(terminated).reshape(n), np.asarray(truncated).reshape(n)
                )
                rewards = np.asarray(rewards, dtype=np.float64).reshape(n)
                returns += rewards * alive
                lengths += alive.astype(np.int64)
                alive &= ~done
                # autoreset re-enters finished rows next step: fresh recurrent
                # state then, exactly the runner's reset_fn(state, ~done)
                need_reset = done.copy()
                steps += 1
                if max_steps and steps >= max_steps:
                    break
        finally:
            pool.close()
        stats = gateway.batcher.stats()
        return {
            "protocol": "frozen-greedy/gateway",
            "checkpoint": gateway.batcher.model.checkpoint,
            "algo": gateway.batcher.model.algo,
            "env": gateway.batcher.model.env_id,
            "n": n,
            "seed0": int(seed0),
            "seeds": [int(s) for s in seeds],
            "returns": [float(r) for r in returns],
            "lengths": [int(l) for l in lengths],
            "mean": float(np.mean(returns)),
            "std": float(np.std(returns)),
            "iqm": iqm(returns),
            "versions_served": stats["versions_served"],
            "batches": stats["batches"],
            "mean_batch_occupancy": stats["mean_batch_occupancy"],
            "failed_requests": stats["failed_requests"],
        }
    finally:
        gateway.close()


# ---------------------------------------------------------------------------
# CLI entrypoint body
# ---------------------------------------------------------------------------


def run_serve_entrypoint(serve_cfg) -> None:
    """The ``sheeprl_tpu.cli.serve`` body: build the gateway, serve the ring,
    hot-swap when a channel is configured, drain cleanly on SIGTERM."""
    import signal

    settings = serve_settings(serve_cfg)
    gateway = ServeGateway.from_checkpoint(
        serve_cfg.checkpoint_path,
        registry_dir=str(settings.registry_dir),
        max_batch=int(settings.max_batch),
        deadline_s=float(settings.deadline_ms) / 1e3,
        seed=int(settings.seed),
    )
    if settings.poll_root:
        gateway.watch(str(settings.poll_root), poll_interval_s=float(settings.poll_interval_s))
    ops = gateway.enable_ops(settings)
    gateway.start_ring(int(settings.max_clients))
    status = gateway.status()
    print(
        f"[serve] gateway up: {status['algo']} on {status['env']} "
        f"v{status['model_version']} (max_batch={settings.max_batch}, "
        f"deadline={settings.deadline_ms}ms, max_clients={settings.max_clients})",
        flush=True,
    )
    if ops is not None:
        port = ops.prom.port if ops.prom is not None else None
        print(
            f"[serve] ops surface on: dir={ops.out_dir} "
            f"trace_rate={settings.trace_sample_rate} "
            f"slo={'on' if ops.slo is not None else 'off'} "
            f"metrics_port={port}",
            flush=True,
        )

    stop = threading.Event()

    def _on_term(_signum, _frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    deadline = _now() + float(settings.duration_s) if settings.duration_s else None
    while not stop.is_set():
        if deadline is not None and _now() >= deadline:
            break
        stop.wait(timeout=5.0)
        s = gateway.status()
        print(
            f"[serve] v{s['model_version']} requests={s['requests']} "
            f"batches={s['batches']} occupancy={s['mean_batch_occupancy']} "
            f"p95={s['act_latency'].get('p95_ms')}ms swaps={s['swaps']} "
            f"failed={s['failed_requests']}",
            flush=True,
        )
    drained = gateway.drain(timeout=float(settings.drain_timeout_s))
    print(f"[serve] drained={'clean' if drained else 'TIMEOUT'}; gateway down", flush=True)
