"""Shared replay-staging facade: one code path from host buffer to HBM.

Every off-policy train loop used to hand-roll the same block — ``rb.sample``
on the host, reshape, ``jax.device_put`` to the burst sharding — which is
exactly the synchronous host→HBM staging the paper's thesis says to avoid
(transitions should cross the link once, at collection time). This module is
the single chokepoint for that decision:

``make_replay_staging(cfg, fabric, rb, ...)`` returns a staging object whose
``sample_device(...)`` yields the train burst as **device** arrays:

- ``buffer.device_ring=True`` (single-process): the replay buffer is wrapped
  in a device-resident ring (:mod:`sheeprl_tpu.data.device_ring`) — sequence
  mode for the Dreamer family's ``EnvIndependentReplayBuffer``, flat
  transition mode for SAC-style ``ReplayBuffer`` — and bursts are *gathered
  on device*; the only per-burst upload is the int32 index plan.
- otherwise (ring off, multi-process, or an unsupported buffer type): a
  **double-buffered prefetch pipeline** — a worker thread plans indices,
  samples, and ``device_put``\\ s burst *k+1* while the train program runs
  burst *k* (the same overlap measured at 1.43–3.1× in BENCH_DECOUPLED.md),
  so even the host fallback hides sampling + H2D behind device compute.
  ``buffer.prefetch=False`` degrades to the plain synchronous path (useful
  when bitwise run-to-run determinism matters more than overlap: prefetching
  draws burst *k+1*'s indices before the env steps collected during burst
  *k+1* land, and the worker's rng interleaving is scheduling-dependent).

The ``n_samples`` axis this facade stages is the contract with the fused
train-burst engine (:mod:`sheeprl_tpu.train`, howto/train_burst.md): the
``[n_samples, ...]`` stack ``sample_device`` returns is consumed as ONE
scanned device program per gradient burst — staging produces the block,
the burst scans it, and neither side pays a per-gradient-step dispatch.

Telemetry: ring gathers bump ``ring_gathers``; pipeline bursts bump
``prefetch_hits``/``prefetch_misses`` and ``prefetch_wait_ms`` (the residue a
train step still blocked on a not-yet-ready prefetched batch) — all beside
``bytes_staged_h2d`` in telemetry.json, so "is the data path overlapped?" is
a number, not a guess. Enforced as the only staging path in ``algos/`` by
``tools/lint_staging.py``.
"""

from __future__ import annotations

import threading
import time
import warnings
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Optional, Tuple

import numpy as np

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, EpisodeBuffer
from sheeprl_tpu.data.device_ring import DeviceRingReplay, DeviceRingTransitions
from sheeprl_tpu.obs.counters import add_prefetch, add_ring_gather, count_h2d
from sheeprl_tpu.obs.dist.staleness import note_queue_depth
from sheeprl_tpu.obs.spans import span

__all__ = ["HostStaging", "ReplayStaging", "RingStaging", "make_replay_staging"]

# burst spec: (batch_size, sequence_length, n_samples, sample_next_obs)
_Spec = Tuple[int, int, int, bool]


class ReplayStaging:
    """Common surface of the two staging strategies.

    ``rb`` is the buffer the train loop should keep using for ``add`` /
    checkpointing — the ring wrapper when the ring is on (it mirrors every
    ``add`` to HBM and proxies ``state_dict``), the original host buffer
    otherwise.
    """

    is_ring = False

    def __init__(self, rb: Any):
        self._rb = rb

    @property
    def rb(self) -> Any:
        return self._rb

    @property
    def supports_adoption(self) -> bool:
        """True when :meth:`adopt_slab` can land a slab straight in HBM."""
        return False

    def adopt_slab(self, rows: Dict[str, np.ndarray], n_valid: Optional[int] = None) -> int:
        """Zero-dispatch slab adoption (device ring only) — see
        :meth:`~sheeprl_tpu.data.device_ring.DeviceRingTransitions.adopt_slab`."""
        raise NotImplementedError(
            "slab adoption needs the single-group device ring "
            "(buffer.device_ring=True on a 1-group mesh)"
        )

    def update_priorities(self, td_errors: np.ndarray) -> None:
        """TD-priority writeback for the last sampled burst (no-op unless the
        buffer is a prioritized ShardedReplay)."""
        if hasattr(self._rb, "update_priorities"):
            self._rb.update_priorities(td_errors)

    def last_weights(self) -> Optional[np.ndarray]:
        """Importance weights aligned with the last burst's flat row order
        (``None`` for unweighted sampling)."""
        if hasattr(self._rb, "last_weights"):
            return self._rb.last_weights()
        return None

    def sample_device(
        self,
        batch_size: int,
        *,
        sequence_length: Optional[int] = None,
        n_samples: int = 1,
        sample_next_obs: bool = False,
    ) -> Dict[str, Any]:
        raise NotImplementedError

    def force_done_last(self, env: int) -> None:
        """Mark env's most recent stored step terminal (restart-on-exception
        fault patch) on every copy of the data this staging keeps."""
        raise NotImplementedError

    def close(self) -> None:
        """Release background resources (prefetch worker). Idempotent."""


class RingStaging(ReplayStaging):
    """Device-ring staging: bursts are gathered from HBM-resident data."""

    is_ring = True

    def sample_device(
        self,
        batch_size: int,
        *,
        sequence_length: Optional[int] = None,
        n_samples: int = 1,
        sample_next_obs: bool = False,
    ) -> Dict[str, Any]:
        add_ring_gather()
        if isinstance(self._rb, DeviceRingReplay):
            return self._rb.sample_device(
                batch_size,
                sequence_length=int(sequence_length or 1),
                n_samples=n_samples,
            )
        return self._rb.sample_device(
            batch_size, sample_next_obs=sample_next_obs, n_samples=n_samples
        )

    @property
    def supports_adoption(self) -> bool:
        return isinstance(self._rb, DeviceRingTransitions) and self._rb.n_groups == 1

    def adopt_slab(self, rows: Dict[str, np.ndarray], n_valid: Optional[int] = None) -> int:
        return self._rb.adopt_slab(rows, n_valid)

    def force_done_last(self, env: int) -> None:
        self._rb.force_done_last(env)


class HostStaging(ReplayStaging):
    """Host-path staging: ``rb.sample`` → ``device_put``, double-buffered.

    With ``prefetch=True`` each ``sample_device`` call returns the burst the
    worker prepared during the previous train burst (when the burst spec
    repeats — the steady state) and immediately schedules the next one. The
    worker samples under a lock shared with the buffer's ``add`` (bound via
    ``bind_write_lock``), with ``clone=True`` so a later ring-wrap overwrite
    can never tear the staged rows; the ``device_put`` runs outside the lock.
    A spec is only prefetched once it has been requested twice, so one-off
    bursts (e.g. SAC's big learning-starts catch-up) don't leave a dead
    device-sized batch pinned in HBM.
    """

    #: bound on concurrently pending prefetched bursts (DroQ alternates two
    #: specs per update — critic and actor batches — so two slots are live)
    MAX_PENDING = 2

    def __init__(
        self,
        rb: Any,
        sharding: Any = None,
        *,
        sequence_mode: bool,
        prefetch: bool = True,
        lock: Optional[Any] = None,
    ):
        super().__init__(rb)
        self._sharding = sharding
        self._seq = bool(sequence_mode)
        self._lock = lock if lock is not None else threading.RLock()
        # another thread may mutate the buffer between a sample and its
        # device_put only when a worker or an external (decoupled) writer
        # exists; clone staged rows exactly then
        self._concurrent = bool(prefetch or lock is not None)
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending: Dict[_Spec, Future] = {}
        self._spec_counts: Dict[_Spec, int] = {}
        if prefetch:
            if hasattr(rb, "bind_write_lock"):
                rb.bind_write_lock(self._lock)
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="replay-prefetch"
            )

    # -- produce one burst -------------------------------------------------

    def _target(self):
        if self._sharding is not None:
            return self._sharding
        import jax

        return jax.devices()[0]

    def _produce(self, spec: _Spec, clone: bool) -> Dict[str, Any]:
        import jax

        batch_size, seq_len, n_samples, sample_next_obs = spec
        with self._lock:
            if self._seq:
                np_batch = self._rb.sample(
                    batch_size,
                    n_samples=n_samples,
                    sequence_length=seq_len,
                    clone=clone,
                )
            else:
                # one plan of batch*n transitions, reshaped sample-major —
                # bitwise the layout the loops used to build by hand
                np_batch = self._rb.sample(
                    batch_size * n_samples,
                    sample_next_obs=sample_next_obs,
                    clone=clone,
                )
                np_batch = {
                    k: v.reshape((n_samples, batch_size) + v.shape[2:])
                    for k, v in np_batch.items()
                }
        # ship native dtypes (uint8 pixels = 4x less than f32 over the
        # host→HBM link) straight to the burst sharding; train steps
        # normalize on device
        with span("Time/stage_h2d_time", phase="stage_h2d"):
            out = jax.device_put(np_batch, self._target())
        count_h2d(np_batch)
        return out

    # -- public surface ----------------------------------------------------

    def sample_device(
        self,
        batch_size: int,
        *,
        sequence_length: Optional[int] = None,
        n_samples: int = 1,
        sample_next_obs: bool = False,
    ) -> Dict[str, Any]:
        spec: _Spec = (
            int(batch_size),
            int(sequence_length or 0),
            int(n_samples),
            bool(sample_next_obs),
        )
        # what the train loop waits for its batch: host sampling and the H2D
        # put on the synchronous path or a prefetch miss (Time/stage_h2d_time
        # is then this span's child), the wait for the worker on a hit
        with span("Time/replay_sample_time", phase="sample"):
            return self._sample_device(spec)

    def _sample_device(self, spec: _Spec) -> Dict[str, Any]:
        if self._pool is None:
            return self._produce(spec, clone=self._concurrent)
        batch: Optional[Dict[str, Any]] = None
        fut = self._pending.pop(spec, None)
        if fut is not None:
            t0 = time.perf_counter()
            try:
                batch = fut.result()
            except Exception:
                # fall through to the sync produce: a genuine sampling error
                # re-raises there, on the caller thread with the caller's spec
                batch = None
            else:
                add_prefetch(hit=True, wait_ms=(time.perf_counter() - t0) * 1000.0)
        if batch is None:
            add_prefetch(hit=False)
            batch = self._produce(spec, clone=self._concurrent)
        count = self._spec_counts.get(spec, 0) + 1
        self._spec_counts[spec] = count
        if count >= 2 and spec not in self._pending:
            self._pending[spec] = self._pool.submit(self._produce, spec, True)
            while len(self._pending) > self.MAX_PENDING:
                # a stale pending burst pins device memory; drop oldest-first
                self._pending.pop(next(iter(self._pending))).cancel()
        # staleness gauge (obs/dist): in-flight prefetched bursts — 0 means
        # the pipeline is running dry, MAX_PENDING means it is saturated
        note_queue_depth("staging_prefetch", len(self._pending))
        return batch

    def update_priorities(self, td_errors: np.ndarray) -> None:
        # under the shared lock: the writeback touches the same per-shard
        # tables a concurrent planner reads
        if hasattr(self._rb, "update_priorities"):
            with self._lock:
                self._rb.update_priorities(td_errors)

    def force_done_last(self, env: int) -> None:
        if not isinstance(self._rb, EnvIndependentReplayBuffer):
            raise NotImplementedError(
                "force_done_last is only defined for per-env sequence buffers"
            )
        with self._lock:
            sub = self._rb.buffer[env]
            last_idx = (sub._pos - 1) % sub.buffer_size
            sub["dones"][last_idx] = np.ones_like(sub["dones"][last_idx])
            if "is_first" in sub:
                sub["is_first"][last_idx] = np.zeros_like(sub["is_first"][last_idx])

    def close(self) -> None:
        if self._pool is not None:
            for fut in self._pending.values():
                fut.cancel()
            self._pending.clear()
            self._pool.shutdown(wait=False)
            self._pool = None


def make_replay_staging(
    cfg: Any,
    fabric: Any,
    rb: Any,
    *,
    sequence_length: Optional[int] = None,
    batch_sharding: Any = None,
    seed: Optional[int] = None,
    lock: Optional[Any] = None,
) -> ReplayStaging:
    """Build the replay staging for one train loop.

    ``batch_sharding`` is the burst sharding the train step consumes —
    ``P(None, 'data')`` over ``[n_samples, batch, ...]`` for transition
    algos, ``P(None, None, 'data')`` over ``[n_samples, seq, batch, ...]``
    for sequence algos. ``lock`` lets decoupled loops share their
    player↔trainer buffer lock with the staging (pass an ``RLock``).
    """
    import jax

    sequence_mode = isinstance(rb, (EnvIndependentReplayBuffer, EpisodeBuffer))
    world_size = int(getattr(fabric, "world_size", 1) or 1) if fabric is not None else 1
    device = getattr(fabric, "device", None) if fabric is not None else None
    # sharded/prioritized replay (sheeprl_tpu/replay): the facade plans its
    # own cross-shard bursts on the host — duck-typed so data/ never imports
    # the replay package (replay imports data, not the reverse)
    is_sharded = hasattr(rb, "plan_burst")

    use_ring = bool(cfg.buffer.get("device_ring", False))
    if use_ring and is_sharded:
        warnings.warn(
            "buffer.device_ring=True is not supported with sharded or "
            "prioritized replay (replay.shards>1 or a non-uniform "
            "replay.strategy): the cross-shard planner samples on the host; "
            "falling back to the host prefetch pipeline."
        )
        use_ring = False
    if use_ring and jax.process_count() > 1:
        warnings.warn(
            "buffer.device_ring=True is not supported on multi-process "
            f"(multi-host) runs yet ({jax.process_count()} processes); "
            "falling back to the host prefetch pipeline."
        )
        use_ring = False
    if use_ring and isinstance(rb, EpisodeBuffer):
        warnings.warn(
            "buffer.device_ring=True is not supported for the episode buffer "
            "(buffer.type=episode): whole-episode storage has no fixed ring "
            "geometry to mirror; falling back to the host prefetch pipeline."
        )
        use_ring = False
    if use_ring:
        try:
            if sequence_mode:
                ring: Any = DeviceRingReplay(
                    rb,
                    device=device,
                    seed=seed,
                    sequence_overlap=int(sequence_length or 64),
                    batch_sharding=batch_sharding if world_size > 1 else None,
                )
            else:
                ring = DeviceRingTransitions(
                    rb,
                    device=device,
                    seed=seed,
                    batch_sharding=batch_sharding if world_size > 1 else None,
                )
        except ValueError as exc:
            # e.g. n_envs does not divide over the mesh's batch shards —
            # degrade to the pipelined host path instead of refusing to run
            warnings.warn(
                f"buffer.device_ring=True could not be enabled ({exc}); "
                "falling back to the host prefetch pipeline."
            )
        else:
            if lock is not None:
                ring.bind_write_lock(lock)
            return RingStaging(ring)
    # the ring paths seed the buffer's sampler at construction; the host
    # path must too, or replay draws come from OS entropy and seeded runs
    # are not reproducible (the plane's thread-vs-process bitwise gate
    # depends on this)
    if seed is not None and hasattr(rb, "seed"):
        rb.seed(int(seed))
    prefetch = bool(cfg.buffer.get("prefetch", True))
    if prefetch and bool(getattr(rb, "needs_writeback", False)):
        # TD-priority writeback must see the plan of the batch being trained
        # on; prefetching would draw burst k+1's plan before burst k's
        # priorities land, so the pipeline runs synchronous under it
        warnings.warn(
            "buffer.prefetch=True is disabled under a priority-writeback "
            "replay strategy (replay.strategy=td_priority): the post-train "
            "writeback must align with the last sampled plan."
        )
        prefetch = False
    return HostStaging(
        rb,
        batch_sharding,
        sequence_mode=sequence_mode,
        prefetch=prefetch,
        lock=lock,
    )
