"""Device-resident replay ring for sequence training.

TPU-native replacement for the reference's host-only replay staging
(``sheeprl/data/buffers.py:528-690`` + per-gradient-step host→device batch
copies): every transition crosses the host→HBM link **once**, when it is
collected, and gradient-step batches are *gathered on device* from a
resident uint8 ring — a [64, 16] pixel batch that costs a 12.6 MB upload
per gradient step becomes an 8 KB index upload.

Design:

- The **host** :class:`~sheeprl_tpu.data.buffers.EnvIndependentReplayBuffer`
  stays the source of truth (checkpointing, fault-tolerance patches); this
  class wraps it and mirrors every ``add`` into a device ring of the same
  per-env geometry.
- **Index planning stays on the host and reuses the host buffers' own
  logic** (:meth:`SequentialReplayBuffer.plan_starts`,
  :meth:`EnvIndependentReplayBuffer.pick_envs` semantics), so sampling
  semantics can never diverge between the two paths; only the final
  *gather* runs on device.
- Writes are **staged and flushed lazily** (one scatter per training burst,
  padded to shape buckets so XLA compiles a handful of programs); padding
  rows carry out-of-bounds targets and are dropped by the scatter
  (``mode="drop"``).
- **Multi-chip**: pass ``batch_sharding`` (the train burst's
  ``NamedSharding``, batch axis sharded over the mesh ``data`` axis) and the
  ring shards itself over the mesh: envs are split into one contiguous group
  per data-axis device — group *g* homed on exactly the device that consumes
  batch slice *g* (derived from the sharding's index map) — and every device
  owns a private ring shard with device-local scatter/gather jits.
  ``sample_device`` plans each device's batch columns among its *local* envs
  (uniform within the group, like the host's ``pick_envs`` is uniform
  globally) and assembles the global ``[n, L, B, ...]`` batch with
  :func:`jax.make_array_from_single_device_arrays` — transitions cross the
  host link once to their home device, gathers are local DMA, and the
  assembled batch needs **no resharding collective** inside the train step.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, ReplayBuffer, _as_np
from sheeprl_tpu.obs.counters import add_replay_adoption, staged_device_put

__all__ = ["DeviceRingReplay", "DeviceRingTransitions", "scatter_append"]


def scatter_append(bufs: Dict[str, Any], pos: Any, rows: Dict[str, Any], capacity: int) -> Dict[str, Any]:
    """In-jit ring append: write ``rows`` (leaves ``[T, n_envs, ...]``) at
    time slots ``(pos + t) % capacity`` of ``bufs`` (leaves ``[capacity,
    n_envs, ...]``) and return the updated buffers.

    This is the write half of the jitted-scan collection path
    (:mod:`sheeprl_tpu.envs.rollout.engine`): traceable, so an entire
    collection burst — act, env step, ring add — stays inside one XLA
    program with zero host involvement. ``pos`` may be a traced int32
    scalar; ``capacity`` must be static. ``T`` (static, from the row
    shapes) must not exceed ``capacity``: a longer burst would land
    duplicate slot indices in one scatter, whose winner XLA leaves
    undefined (the host ``add`` keeps only the trailing window in that
    case — split the burst instead).
    """
    import jax.numpy as jnp

    first = next(iter(rows.values()))
    t = int(first.shape[0])
    if t > capacity:
        raise ValueError(
            f"scatter_append burst of {t} rows exceeds the ring capacity "
            f"{capacity}; split the burst (duplicate slots in one scatter "
            "are undefined)"
        )
    t_idx = (pos + jnp.arange(t, dtype=jnp.int32)) % capacity
    return {k: v.at[t_idx].set(rows[k]) for k, v in bufs.items()}


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _pad_rows(n: int) -> int:
    """Flush-scatter padding: next power of two.

    A fixed 32-row bucket compiled few programs but uploaded up to 32x the
    staged bytes in the steady state (one collected row per training burst,
    padded to a full bucket, every burst); power-of-two buckets bound the
    padding waste at <2x while still reusing ~log2(max flush) compiled
    scatter programs."""
    return 1 << max(n - 1, 0).bit_length()


def _batch_shard_count(batch_sharding, batch_dim: int = 2, layout: str = "[n_samples, seq, batch, ...]") -> int:
    """Distinct shards along the batch axis (``batch_dim``) of the sharding.

    The ring expects only the batch dim sharded (e.g. ``P(None, None, 'data')``
    for the sequence burst, ``P(None, 'data')`` for the transition burst). A
    spec that shards some other dim — say a caller passed ``P('data')`` meant
    for a different layout — would quietly build one shard here and then blow
    up deep inside ``make_array_from_single_device_arrays`` at sample time,
    far from the mistake, so validate eagerly.
    """
    spec = tuple(batch_sharding.spec)
    for dim, entry in enumerate(spec):
        if dim != batch_dim and entry is not None:
            raise ValueError(
                "Device-ring batch_sharding must shard only the batch "
                f"axis (dim {batch_dim}) of the {layout} burst; got "
                f"PartitionSpec{spec} which shards dim {dim}."
            )
    entry = spec[batch_dim] if len(spec) > batch_dim else None
    if entry is None:
        return 1
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    size = 1
    for a in axes:
        size *= int(batch_sharding.mesh.shape[a])
    return size


def _homes_for_sharding(batch_sharding, batch_dim: int, n_groups: int) -> Tuple[List[Any], List[List[Any]]]:
    """Device that OWNS batch slice g (plus replicas along other mesh axes):
    probe the index map with a shape of ``n_groups`` along the batch dim —
    slice starts enumerate the shard order along that dim."""
    probe_shape = tuple(n_groups if d == batch_dim else 1 for d in range(batch_dim + 1))
    probe = batch_sharding.addressable_devices_indices_map(probe_shape)
    by_slice: Dict[int, List[Any]] = {}
    for dev, idx in probe.items():
        start = idx[batch_dim].start or 0
        by_slice.setdefault(int(start), []).append(dev)
    if sorted(by_slice) != list(range(n_groups)):
        raise ValueError(
            "Device ring: batch sharding is not addressable shard-per-slice "
            "from this process (multi-host meshes must pass a process-local "
            "batch sharding)"
        )
    homes = [sorted(by_slice[g], key=lambda d: d.id)[0] for g in range(n_groups)]
    replicas = [
        [d for d in sorted(by_slice[g], key=lambda d: d.id) if d is not homes[g]]
        for g in range(n_groups)
    ]
    return homes, replicas


def _check_hbm_budget(device, rows: int, bytes_per_row: int, kind: str, fit_rows, n_envs: int) -> None:
    """Pre-allocation HBM guard shared by both rings: ``rows x bytes_per_row``
    is the largest single-device shard. Fail with the computed size (and the
    ``buffer.size`` that would fit in half the device, via ``fit_rows(limit)``)
    instead of an opaque XLA allocation error later; warn when the ring would
    crowd the device. With DV3's default buffer.size=1e6 of 64x64x3 uint8
    pixels the whole ring is ~12 GB before model/optimizer state."""
    import warnings

    from sheeprl_tpu.obs.counters import device_memory_stats

    total = rows * bytes_per_row
    stats = device_memory_stats(device)
    limit = stats.get("bytes_limit") if stats else None
    if limit and total > 0.95 * limit:
        # certain OOM: the ring alone leaves no room for params/optimizer
        rows_fit = max(int(fit_rows(limit)), 0)
        raise ValueError(
            f"{kind} would allocate {total / 2**30:.2f} GiB "
            f"({rows} rows x {bytes_per_row} B) on a device with a "
            f"{limit / 2**30:.2f} GiB limit; a ring of <= {rows_fit} per-env "
            f"rows fits in half the device (buffer.size <= "
            f"{rows_fit * n_envs} under the buffer.size//n_envs "
            "convention), or disable buffer.device_ring"
        )
    if (limit and total > 0.6 * limit) or total > 4 * 2**30:
        warnings.warn(
            f"{kind} allocating {total / 2**30:.2f} GiB of HBM "
            f"per device ({rows} per-env rows x {bytes_per_row} B"
            + (f", device limit {limit / 2**30:.2f} GiB" if limit else "")
            + "); lower buffer.size if the device OOMs",
            UserWarning,
        )


def _assemble_global(parts: List[Dict[str, Any]], sharding, replicas, batch_dim: int, batch_size: int) -> Dict[str, Any]:
    """Assemble per-group device gathers into global sharded Arrays: shard
    *g* is already resident on its home device; replicas along non-batch
    mesh axes (if any) receive a copy. No resharding collective."""
    import jax

    out: Dict[str, Any] = {}
    for k in parts[0]:
        shape = parts[0][k].shape
        global_shape = shape[:batch_dim] + (batch_size,) + shape[batch_dim + 1 :]
        arrays = []
        for g, part in enumerate(parts):
            arrays.append(part[k])
            for dev in replicas[g]:
                arrays.append(jax.device_put(part[k], dev))
        out[k] = jax.make_array_from_single_device_arrays(global_shape, sharding, arrays)
    return out


class DeviceRingReplay:
    """Wrap an :class:`EnvIndependentReplayBuffer` with a device-side mirror.

    ``add`` forwards to the host buffer and stages the same rows for the
    device ring; ``sample_device`` returns a dict of **device** arrays shaped
    ``[n_samples, sequence_length, batch, ...]`` (the same layout as the host
    ``sample``), produced by an on-device gather. With ``batch_sharding`` the
    arrays are global jax Arrays sharded batch-wise over the mesh.
    """

    #: host-side staging threshold: a flush is forced once 8x this many
    #: rows are staged (bounds staging memory during collection-only phases);
    #: the scatter itself pads to power-of-two buckets (_pad_rows)
    FLUSH_BUCKET = 32

    def __init__(
        self,
        host_rb: EnvIndependentReplayBuffer,
        device: Optional[Any] = None,
        seed: Optional[int] = None,
        sequence_overlap: int = 64,
        batch_sharding: Optional[Any] = None,
    ):
        import jax

        self._rb = host_rb
        self._capacity = int(host_rb.buffer_size)
        self._n_envs = int(host_rb.n_envs)
        # Shadow region: the first `overlap` rows are mirrored past the tail
        # so every sequence of length ≤ overlap is PHYSICALLY contiguous even
        # when it wraps, and sampling can read contiguous blocks (vmapped
        # dynamic_slice) instead of row-scattered gathers — on TPU a gather
        # of thousands of random 12 KB rows from a GB-scale ring is ~100x
        # slower than the same bytes as contiguous block DMA (measured:
        # ~0.5 s/sample at 100k rows vs ~ms for blocks).
        self._overlap = max(0, min(int(sequence_overlap), self._capacity))
        self._rng = np.random.default_rng(seed)
        self._sharding = batch_sharding

        if batch_sharding is not None:
            n_groups = _batch_shard_count(batch_sharding)
            if self._n_envs < n_groups or self._n_envs % n_groups != 0:
                # uneven groups would silently oversample the smaller groups'
                # envs relative to the host path's global-uniform pick_envs
                raise ValueError(
                    f"DeviceRingReplay needs the same number of envs on every "
                    f"batch shard: n_envs={self._n_envs} does not divide over "
                    f"{n_groups} data-axis shards"
                )
            self._homes, self._replicas = _homes_for_sharding(batch_sharding, 2, n_groups)
        else:
            self._homes = [device if device is not None else jax.devices()[0]]
            self._replicas = [[]]

        n_groups = len(self._homes)
        self._groups: List[np.ndarray] = [
            np.asarray(g, np.int64) for g in np.array_split(np.arange(self._n_envs), n_groups)
        ]
        self._env_group = np.empty(self._n_envs, np.int64)
        self._env_col = np.empty(self._n_envs, np.int64)
        for g, envs in enumerate(self._groups):
            self._env_group[envs] = g
            self._env_col[envs] = np.arange(len(envs))

        # per-group device storage, allocated lazily on the first add
        # (dtypes/shapes are discovered from the data, like the host buffer)
        self._shards: Optional[List[Dict[str, Any]]] = None
        # staged (env, target_index) slots; row *values* are read back from
        # the host buffer at flush time (it owns the newest copy of every
        # slot, so no per-step duplicate row copies are held here)
        self._staged: List[Tuple[int, int]] = []
        self._scatter_fns: Dict[int, Any] = {}
        self._gather_fns: Dict[Tuple[int, int, int], Any] = {}
        self._write_lock: Optional[Any] = None
        # wrapping a buffer that already holds data (e.g. restored from a
        # checkpoint before the ring was constructed): mirror it now instead
        # of depending on wrap-then-load call order
        if any(not sub.empty for sub in host_rb.buffer):
            self._remirror_from_host()

    # -- proxied host surface ---------------------------------------------

    @property
    def host(self) -> EnvIndependentReplayBuffer:
        return self._rb

    @property
    def buffer(self):
        return self._rb.buffer

    @property
    def buffer_size(self) -> int:
        return self._rb.buffer_size

    @property
    def n_envs(self) -> int:
        return self._rb.n_envs

    @property
    def _device(self):
        return self._homes[0]

    @property
    def _buf(self) -> Optional[Dict[str, Any]]:
        """Single-shard view (tests / single-device introspection)."""
        if self._shards is None:
            return None
        if len(self._shards) != 1:
            raise AttributeError("_buf is only defined for single-shard rings")
        return self._shards[0]

    def seed(self, seed: Optional[int] = None) -> None:
        self._rb.seed(seed)
        self._rng = np.random.default_rng(seed)

    def bind_write_lock(self, lock: Any) -> None:
        """Serialize ``add``/``force_done_last`` against a concurrent
        ``sample_device`` (decoupled player/trainer threads): the staged-slot
        list and the host mirror are shared mutable state."""
        self._write_lock = lock

    def state_dict(self) -> Dict[str, Any]:
        return self._rb.state_dict()

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        """Restore the host buffer, then re-mirror its filled region to the
        device shards as one contiguous block upload per key per shard."""
        self._rb.load_state_dict(state)
        self._remirror_from_host()

    def _remirror_from_host(self) -> None:
        """Rebuild the device shards from whatever the host buffer holds —
        after a checkpoint restore, or at construction when wrapping a buffer
        that was filled/restored before the ring existed."""
        import jax

        self._shards = None
        self._staged.clear()
        n_rows = np.zeros(self._n_envs, np.int64)
        example: Optional[Dict[str, np.ndarray]] = None
        for env, sub in enumerate(self._rb.buffer):
            if sub._buf is None:
                continue
            n_rows[env] = sub.buffer_size if sub.full else sub._pos
            if example is None:
                example = {k: _as_np(v)[0, 0] for k, v in sub._buf.items()}
        if example is None or int(n_rows.max()) == 0:
            return
        self._allocate(example)
        cap, ov = self._capacity, self._overlap

        def _set(v, b):
            v = v.at[: b.shape[0]].set(b)
            if ov:
                # mirror the head into the shadow region
                v = v.at[cap:].set(v[:ov])
            return v

        set_block = jax.jit(
            lambda buf, blk: {k: _set(v, blk[k]) for k, v in buf.items()},
            donate_argnums=(0,),
        )
        for g, envs in enumerate(self._groups):
            max_rows = int(n_rows[envs].max()) if len(envs) else 0
            if max_rows == 0:
                continue
            blocks: Dict[str, np.ndarray] = {}
            for k, v0 in example.items():
                block = np.zeros(
                    (max_rows, len(envs)) + np.asarray(v0).shape, np.asarray(v0).dtype
                )
                for col, env in enumerate(envs):
                    sub = self._rb.buffer[env]
                    if sub._buf is not None and n_rows[env] > 0:
                        block[: n_rows[env], col] = _as_np(sub._buf[k])[: n_rows[env], 0]
                blocks[k] = block
            blocks = staged_device_put(blocks, self._homes[g])
            self._shards[g] = set_block(self._shards[g], blocks)

    # -- write path --------------------------------------------------------

    def add(
        self,
        data: Dict[str, np.ndarray],
        env_idxes: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        if env_idxes is None:
            env_idxes = list(range(self._n_envs))
        with self._write_lock or nullcontext():
            # capture write targets before the host add advances them (and let
            # a failing host add leave the mirror untouched)
            targets = [int(self._rb.buffer[env]._pos) for env in env_idxes]
            self._rb.add(data, env_idxes, validate_args=validate_args)
            rows = next(iter(data.values())).shape[0]
            for col, env in enumerate(env_idxes):
                for r in range(rows):
                    self._staged.append((env, (targets[col] + r) % self._capacity))
            # bound host-side staging memory (and batch the upload) during long
            # collection-only phases such as the learning_starts prefill
            if len(self._staged) >= 8 * self.FLUSH_BUCKET:
                self._flush()

    def force_done_last(self, env: int) -> None:
        """Fault-tolerance patch (reference dreamer_v3.py:642-650): mark the
        most recent stored step of ``env`` as terminal on both copies."""
        with self._write_lock or nullcontext():
            sub = self._rb.buffer[env]
            last_idx = (sub._pos - 1) % sub.buffer_size
            sub["dones"][last_idx] = np.ones_like(sub["dones"][last_idx])
            if "is_first" in sub:
                # DV1-family buffers store no is_first column; keep behavior
                # identical to the host-path patch (staging.py)
                sub["is_first"][last_idx] = np.zeros_like(sub["is_first"][last_idx])
            self._staged.append((env, int(last_idx)))

    # -- device plumbing ---------------------------------------------------

    def _allocate(self, example_row: Dict[str, np.ndarray]) -> None:
        import jax
        import jax.numpy as jnp

        # every shard is (capacity + overlap) x group_envs of EVERY key in HBM
        rows = self._capacity + self._overlap
        max_group = max(len(g) for g in self._groups)
        bytes_per_row = sum(
            int(np.prod(np.asarray(v).shape)) * np.asarray(v).dtype.itemsize * max_group
            for v in example_row.values()
        )
        _check_hbm_budget(
            self._homes[0],
            rows,
            bytes_per_row,
            "DeviceRingReplay",
            lambda limit: int(0.5 * limit / max(bytes_per_row, 1)) - self._overlap,
            self._n_envs,
        )
        self._shards = []
        for g, envs in enumerate(self._groups):
            with jax.default_device(self._homes[g]):
                self._shards.append(
                    {
                        k: jnp.zeros(
                            (rows, len(envs)) + np.asarray(v).shape, np.asarray(v).dtype
                        )
                        for k, v in example_row.items()
                    }
                )

    def _scatter_fn(self, n_rows: int):
        import jax

        fn = self._scatter_fns.get(n_rows)
        if fn is None:
            def scatter(buf, t_idx, e_idx, rows):
                return {
                    k: v.at[t_idx, e_idx].set(rows[k], mode="drop")
                    for k, v in buf.items()
                }

            fn = jax.jit(scatter, donate_argnums=(0,))
            self._scatter_fns[n_rows] = fn
        return fn

    def _flush(self) -> None:
        import jax

        if not self._staged:
            return
        # dedupe (env, t) slots: XLA's scatter leaves the winner among
        # duplicate indices undefined, and duplicates are legal here
        # (force_done_last re-stages the slot its add() just wrote; a ring
        # can wrap within one staging window). Values are read from the host
        # buffer, which always holds the newest write for a slot.
        slots = list(dict.fromkeys(self._staged))
        sub0 = self._rb.buffer[slots[0][0]]
        if self._shards is None:
            self._allocate({k: _as_np(v)[0, 0] for k, v in sub0._buf.items()})
        # head rows are mirrored into the shadow region past the tail so
        # wrapped sequences stay physically contiguous (value read from the
        # same host slot)
        slots.extend([(env, t + self._capacity) for env, t in slots if t < self._overlap])
        slots_arr = np.asarray(slots, np.int64).reshape(len(slots), 2)
        envs, ts = slots_arr[:, 0], slots_arr[:, 1] % self._capacity
        oob = self._capacity + self._overlap
        for g in range(len(self._groups)):
            sel = np.nonzero(self._env_group[envs] == g)[0]
            if sel.size == 0:
                continue
            n = int(sel.size)
            padded = _pad_rows(n)
            t_idx = np.full(padded, oob, np.int32)  # OOB → dropped
            e_idx = np.zeros(padded, np.int32)
            t_idx[:n] = slots_arr[sel, 1]
            e_idx[:n] = self._env_col[envs[sel]]
            # group slots by env and gather each env's rows with one
            # fancy-index read (a per-row Python loop was thousands of small
            # copies per flush on a 1-core host, inside the acting timer);
            # the (src rows, dst positions) maps depend only on the env split
            by_env = {}
            for env in np.unique(envs[sel]):
                pos = sel[np.nonzero(envs[sel] == env)[0]]
                by_env[int(env)] = (pos, np.searchsorted(sel, pos))
            rows: Dict[str, np.ndarray] = {}
            for k, v0 in sub0._buf.items():
                first = _as_np(v0)[0, 0]
                stack = np.zeros((padded,) + first.shape, first.dtype)
                for env, (pos, dst) in by_env.items():
                    stack[dst] = _as_np(self._rb.buffer[env]._buf[k])[ts[pos], 0]
                rows[k] = stack
            payload = staged_device_put((t_idx, e_idx, rows), self._homes[g])
            self._shards[g] = self._scatter_fn(padded)(self._shards[g], *payload)
        self._staged.clear()

    # -- sample path -------------------------------------------------------

    def _plan_group(
        self, envs: np.ndarray, batch: int, sequence_length: int, n_samples: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side index plan for one group, reusing the host buffers' own
        sampling logic (``pick_envs`` restricted to the group's envs + per-env
        ``plan_starts``).

        Returns ``(starts [n_samples * batch], cols [n_samples * batch])``
        ordered sample-major with per-env column groups, matching the host
        ``EnvIndependentReplayBuffer.sample`` concat layout. Starts are
        physical ring rows; a sequence always occupies the ``L`` contiguous
        rows from its start thanks to the shadow region.
        """
        L = sequence_length
        try:
            with_data, counts = self._rb.pick_envs(batch, self._rng, envs=[int(e) for e in envs])
        except ValueError as exc:
            # Intended behavior, made diagnosable: each device can only gather
            # from its LOCAL ring shard, so an empty group cannot borrow
            # another group's envs (the host path would sample globally; here
            # that would require a cross-device read that defeats the ring's
            # no-collective design). Groups fill in lockstep during normal
            # collection — this only triggers when e.g. a checkpoint taken
            # before every env had collected is restored under sharding.
            raise ValueError(
                f"Device-ring group {sorted(int(e) for e in envs)} has no "
                "samples while sampling was requested. Sharded rings sample "
                "per-group by design (device-local gathers); collect at least "
                "one sequence on every env group before sampling, or restore "
                "a checkpoint whose buffer covers all env groups."
            ) from exc
        starts_by_env: List[np.ndarray] = []
        envs_order: List[int] = []
        for j, env in enumerate(with_data):
            c = int(counts[j])
            if c == 0:
                continue
            starts = self._rb.buffer[env].plan_starts(c * n_samples, L, rng=self._rng)
            starts_by_env.append(np.asarray(starts).reshape(n_samples, c))
            envs_order.append(env)
        all_starts = np.concatenate(starts_by_env, axis=1)  # [n_samples, B]
        all_cols = np.concatenate(
            [
                np.full((n_samples, s.shape[1]), self._env_col[e], np.int32)
                for s, e in zip(starts_by_env, envs_order)
            ],
            axis=1,
        )
        return all_starts.reshape(-1).astype(np.int32), all_cols.reshape(-1).astype(np.int32)

    def _gather_fn(self, n_rows: int, L: int, n_samples: int):
        import jax

        key = (n_rows, L, n_samples)
        fn = self._gather_fns.get(key)
        if fn is None:
            def gather(buf, starts, e_idx):
                # contiguous-block reads (thanks to the shadow region): a
                # vmapped dynamic_slice lowers to a gather of [L, ...] BLOCKS,
                # not L scattered rows — the difference between ~ms and
                # ~hundreds of ms per sample on a GB-scale TPU ring
                def one(s, e):
                    return {
                        k: jax.lax.dynamic_slice(
                            v, (s, e) + (0,) * (v.ndim - 2), (L, 1) + v.shape[2:]
                        )[:, 0]
                        for k, v in buf.items()
                    }

                sel = jax.vmap(one)(starts, e_idx)  # {k: [total, L, ...]}
                out = {}
                for k, v in sel.items():
                    v = v.reshape((n_samples, n_rows // n_samples, L) + v.shape[2:])
                    out[k] = v.swapaxes(1, 2)  # [n_samples, L, B, ...]
                return out

            fn = jax.jit(gather)
            self._gather_fns[key] = fn
        return fn

    def sample_device(
        self, batch_size: int, sequence_length: int = 1, n_samples: int = 1
    ) -> Dict[str, Any]:
        """Gather ``[n_samples, sequence_length, batch, ...]`` batches on
        device. The only host→device traffic is the int32 index plan. With a
        ``batch_sharding`` the result is a global sharded Array whose batch
        slice *g* was gathered (and stays) on the device that consumes it."""
        import jax

        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        if sequence_length <= 0:
            raise ValueError(f"'sequence_length' ({sequence_length}) must be greater than 0")
        if sequence_length > max(self._overlap, 1) and any(
            b.full for b in self._rb.buffer
        ):
            raise ValueError(
                f"sequence_length {sequence_length} exceeds the ring's "
                f"sequence_overlap {self._overlap}; construct DeviceRingReplay "
                "with sequence_overlap >= the training sequence length"
            )
        n_groups = len(self._groups)
        if batch_size % n_groups != 0:
            raise ValueError(
                f"batch_size {batch_size} must divide evenly over the "
                f"{n_groups} batch shards"
            )
        with self._write_lock or nullcontext():
            self._flush()
            if self._shards is None:
                raise ValueError("No sample has been added to the buffer")
            b_local = batch_size // n_groups
            parts: List[Dict[str, Any]] = []
            for g, envs in enumerate(self._groups):
                starts, cols = self._plan_group(envs, b_local, sequence_length, n_samples)
                fn = self._gather_fn(starts.shape[0], sequence_length, n_samples)
                # the index plan is the ONLY host→device traffic of a ring
                # sample; counting it keeps the telemetry's bytes_staged_h2d an
                # honest total (and shows how little the ring ships vs host
                # staging)
                starts, cols = staged_device_put((starts, cols), self._homes[g])
                parts.append(fn(self._shards[g], starts, cols))
        if self._sharding is None:
            return parts[0]
        return _assemble_global(parts, self._sharding, self._replicas, 2, batch_size)


class DeviceRingTransitions:
    """Flat-transition device ring: wrap a :class:`ReplayBuffer` with a
    device-side mirror for SAC-style ``[n_samples, batch, ...]`` bursts.

    The sequence ring above serves the Dreamer family's
    ``EnvIndependentReplayBuffer``; this class serves the flat uniform-replay
    algos (SAC, SAC-AE, DroQ): ``add`` forwards to the host buffer and stages
    the written time rows for a lazy scatter; ``sample_device`` plans
    ``(t, env)`` pairs **with the host buffer's own**
    :meth:`ReplayBuffer.plan_transitions` (so the valid-window and
    ``sample_next_obs`` semantics cannot diverge from the host path) and
    gathers the batch on device — including the derived ``next_<obs_key>``
    rows at ``(t + 1) % capacity``, which never cross the host link at all.

    With ``batch_sharding`` (a ``[n_samples, batch, ...]`` sharding with only
    dim 1 sharded, e.g. ``P(None, 'data')``) the ring shards env-wise over the
    mesh exactly like the sequence ring: each device stores the columns of the
    envs homed on it and gathers the batch slice it consumes, assembled with
    ``make_array_from_single_device_arrays`` — no resharding collective.
    """

    #: host-side staging threshold: a flush is forced once 8x this many
    #: time rows are staged (bounds staging memory during collection-only
    #: phases); the scatter itself pads to power-of-two buckets (_pad_rows)
    FLUSH_BUCKET = 32

    def __init__(
        self,
        host_rb: ReplayBuffer,
        device: Optional[Any] = None,
        seed: Optional[int] = None,
        batch_sharding: Optional[Any] = None,
    ):
        import jax

        if isinstance(host_rb, EnvIndependentReplayBuffer):
            raise TypeError(
                "DeviceRingTransitions wraps a flat ReplayBuffer; use "
                "DeviceRingReplay for EnvIndependentReplayBuffer sequence rings"
            )
        self._rb = host_rb
        self._capacity = int(host_rb.buffer_size)
        self._n_envs = int(host_rb.n_envs)
        self._rng = np.random.default_rng(seed)
        self._sharding = batch_sharding

        if batch_sharding is not None:
            n_groups = _batch_shard_count(batch_sharding, 1, "[n_samples, batch, ...]")
            if self._n_envs < n_groups or self._n_envs % n_groups != 0:
                raise ValueError(
                    f"DeviceRingTransitions needs the same number of envs on "
                    f"every batch shard: n_envs={self._n_envs} does not divide "
                    f"over {n_groups} data-axis shards"
                )
            self._homes, self._replicas = _homes_for_sharding(batch_sharding, 1, n_groups)
        else:
            self._homes = [device if device is not None else jax.devices()[0]]
            self._replicas = [[]]

        n_groups = len(self._homes)
        self._groups: List[np.ndarray] = [
            np.asarray(g, np.int64) for g in np.array_split(np.arange(self._n_envs), n_groups)
        ]
        self._env_col = np.empty(self._n_envs, np.int64)
        for envs in self._groups:
            self._env_col[envs] = np.arange(len(envs))
        # index plans ship as ONE packed int32 per transition (t * width + col,
        # decoded on device): the plan is the only recurring host→device
        # upload of a ring sample, so halving it doubles the staging win
        self._group_width = len(self._groups[0])
        if self._capacity * self._group_width >= 2**31:
            raise ValueError(
                f"DeviceRingTransitions index plan would overflow int32: "
                f"{self._capacity} rows x {self._group_width} envs per shard "
                "(such a ring cannot fit in HBM anyway; lower buffer.size)"
            )

        # per-group device storage, allocated lazily on the first flush
        self._shards: Optional[List[Dict[str, Any]]] = None
        # staged time rows; values are read back from the host buffer at
        # flush time (it owns the newest copy of every slot)
        self._staged: List[int] = []
        self._scatter_fns: Dict[int, Any] = {}
        self._gather_fns: Dict[Tuple[int, int, bool], Any] = {}
        self._write_lock: Optional[Any] = None
        # True while the DEVICE shard holds rows the host buffer never saw
        # (jitted-scan collection writes via scatter_append/adopt_jit_state);
        # host reads (checkpoint state_dict) sync first
        self._host_stale = False
        # wrapping a buffer that already holds data (e.g. restored from a
        # checkpoint before the ring was constructed): mirror it now instead
        # of depending on wrap-then-load call order
        if not host_rb.empty:
            self._remirror_from_host()

    # -- proxied host surface ---------------------------------------------

    @property
    def host(self) -> ReplayBuffer:
        return self._rb

    @property
    def buffer(self):
        return self._rb.buffer

    @property
    def buffer_size(self) -> int:
        return self._rb.buffer_size

    @property
    def n_envs(self) -> int:
        return self._rb.n_envs

    @property
    def full(self) -> bool:
        return self._rb.full

    @property
    def empty(self) -> bool:
        return self._rb.empty

    @property
    def is_memmap(self) -> bool:
        return self._rb.is_memmap

    @property
    def n_groups(self) -> int:
        """Mesh batch shards this ring is split over (1 = single device)."""
        return len(self._groups)

    @property
    def _device(self):
        return self._homes[0]

    @property
    def _buf(self) -> Optional[Dict[str, Any]]:
        """Single-shard view (tests / single-device introspection)."""
        if self._shards is None:
            return None
        if len(self._shards) != 1:
            raise AttributeError("_buf is only defined for single-shard rings")
        return self._shards[0]

    def seed(self, seed: Optional[int] = None) -> None:
        self._rb.seed(seed)
        self._rng = np.random.default_rng(seed)

    def bind_write_lock(self, lock: Any) -> None:
        """Serialize ``add`` against a concurrent ``sample_device``."""
        self._write_lock = lock

    def state_dict(self) -> Dict[str, Any]:
        self.sync_host()
        return self._rb.state_dict()

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        """Restore the host buffer, then re-mirror its filled region to the
        device shards as one contiguous block upload per key per shard."""
        self._rb.load_state_dict(state)
        self._remirror_from_host()

    # -- in-jit write path (jitted-scan collection, envs/rollout) -----------

    #: the in-jit append the rollout engine composes into its lax.scan
    scatter_append = staticmethod(scatter_append)

    def jit_state(self, example_rows: Optional[Dict[str, np.ndarray]] = None) -> Tuple[Dict[str, Any], Any]:
        """Hand the ring's device storage to an in-jit writer.

        Returns ``(bufs, pos)``: the device arrays (leaves ``[capacity,
        n_envs, ...]``) and the int32 write head. The writer appends with
        :func:`scatter_append` (typically inside a ``lax.scan``, donating
        ``bufs``) and gives the result back via :meth:`adopt_jit_state`.
        ``example_rows`` (leaves ``[n_envs, ...]``) allocates storage on the
        first call of an empty ring. Single-shard rings only: an in-jit
        writer owns exactly one device's storage.
        """
        if len(self._groups) != 1:
            raise ValueError(
                "jit_state requires a single-shard ring: the jitted-scan "
                "collection path owns one device's storage (env-sharded "
                "multi-device collection is not supported yet)"
            )
        with self._write_lock or nullcontext():
            self._flush()
            if self._shards is None:
                if example_rows is None:
                    raise ValueError(
                        "jit_state on an empty ring needs example_rows to "
                        "allocate storage"
                    )
                self._allocate({k: np.asarray(v) for k, v in example_rows.items()})
        import jax.numpy as jnp

        return self._shards[0], jnp.int32(self._rb._pos)

    def adopt_jit_state(self, bufs: Dict[str, Any], steps: int, example_rows: Dict[str, np.ndarray]) -> None:
        """Take back ring storage an in-jit writer advanced by ``steps`` time
        rows: the device arrays become the ring's storage and the host
        buffer's ring counters advance (``ReplayBuffer.advance_external``)
        so index planning stays correct — the rows themselves stay on
        device until a host read forces :meth:`sync_host`."""
        if len(self._groups) != 1:
            raise ValueError("adopt_jit_state requires a single-shard ring")
        with self._write_lock or nullcontext():
            self._shards = [bufs]
            self._rb.advance_external(example_rows, int(steps))
            self._host_stale = True

    def adopt_slab(self, rows: Dict[str, np.ndarray], n_valid: Optional[int] = None) -> int:
        """Zero-dispatch slab adoption: land a trajectory slab's valid rows
        in HBM directly — the plane's shared-memory slab views are the
        *source* of one ``device_put`` at their exact size, scattered into
        the ring at the positions a host ``add`` would have written.

        This removes both costs of the historical slab → host rb → ring
        path: the host-buffer row copy, and the flush's power-of-two row
        padding (``_pad_rows``) on the host→HBM upload — ``bytes_staged_h2d``
        for an adopted burst is the payload size, not up to 2×. The host
        ring counters advance via ``advance_external`` (planning and the
        staleness stamp stay correct); the host *data* goes stale until
        :meth:`sync_host`, exactly like the jitted-scan adoption path.

        ``rows`` leaves are ``[T, n_envs, ...]``; ``n_valid`` adopts only the
        first ``n_valid`` rows (a partial slab). Single-shard rings only.
        Returns the bytes staged over the host→HBM link.
        """
        if len(self._groups) != 1:
            raise ValueError(
                "adopt_slab requires a single-shard ring: a slab lands on "
                "one device's storage (env-sharded adoption is not "
                "supported yet)"
            )
        rows = {k: np.asarray(v) for k, v in rows.items()}
        first = next(iter(rows.values()))
        steps = int(first.shape[0] if n_valid is None else n_valid)
        if steps <= 0:
            return 0
        with self._write_lock or nullcontext():
            self._flush()  # earlier host-buffered adds must land first
            if self._shards is None:
                self._allocate({k: v[0] for k, v in rows.items()})
            # same trailing-window rule as ReplayBuffer.add for oversize data
            write_len = min(steps, self._capacity)
            start = int(self._rb._pos) + steps - write_len
            t_idx = (np.arange(start, start + write_len) % self._capacity).astype(np.int32)
            payload = {
                k: np.ascontiguousarray(v[steps - write_len : steps]) for k, v in rows.items()
            }
            dev = staged_device_put((t_idx, payload), self._homes[0])
            self._shards[0] = self._scatter_fn(write_len)(self._shards[0], *dev)
            self._rb.advance_external({k: v[0] for k, v in rows.items()}, steps)
            self._host_stale = True
        add_replay_adoption()
        return int(sum(v.nbytes for v in payload.values()) + t_idx.nbytes)

    def sync_host(self) -> None:
        """Download the device ring into the host buffer (one device_get per
        key) if in-jit writes left it stale. Called before any host read of
        the buffer data — checkpoint ``state_dict`` does it automatically.
        Only the valid window (``capacity`` if full, else ``_pos`` rows,
        padded to a power of two to bound slice-program compiles like
        ``_pad_rows``) crosses the link — an early checkpoint of a large
        HBM ring must not download gigabytes of unwritten zeros."""
        if not self._host_stale:
            return
        import jax

        with self._write_lock or nullcontext():
            if self._shards is not None and self._rb.buffer is not None:
                n_rows = self._capacity if self._rb.full else int(self._rb._pos)
                n_get = min(self._capacity, _pad_rows(n_rows)) if n_rows else 0
                if n_get:
                    rows = jax.device_get(
                        {k: v[:n_get] for k, v in self._shards[0].items()}
                    )
                    for k, v in rows.items():
                        self._rb.buffer[k][:n_get] = v
            self._host_stale = False

    def _remirror_from_host(self) -> None:
        """Rebuild the device shards from whatever the host buffer holds —
        after a checkpoint restore, or at construction when wrapping a buffer
        that was filled/restored before the ring existed."""
        import jax

        self._shards = None
        self._staged.clear()
        self._host_stale = False
        if self._rb.buffer is None:
            return
        n_rows = self._capacity if self._rb.full else int(self._rb._pos)
        if n_rows == 0:
            return
        example = {k: _as_np(v)[0] for k, v in self._rb.buffer.items()}
        self._allocate(example)
        set_block = jax.jit(
            lambda buf, blk: {k: v.at[: next(iter(blk.values())).shape[0]].set(blk[k]) for k, v in buf.items()},
            donate_argnums=(0,),
        )
        for g, envs in enumerate(self._groups):
            blocks = {
                k: np.ascontiguousarray(_as_np(v)[:n_rows][:, envs])
                for k, v in self._rb.buffer.items()
            }
            blocks = staged_device_put(blocks, self._homes[g])
            self._shards[g] = set_block(self._shards[g], blocks)

    # -- write path --------------------------------------------------------

    def add(self, data: Dict[str, np.ndarray], validate_args: bool = False) -> None:
        with self._write_lock or nullcontext():
            pos_before = int(self._rb._pos)
            self._rb.add(data, validate_args=validate_args)
            data_len = next(iter(data.values())).shape[0]
            # the host keeps only the trailing window of an oversized insert;
            # mirror exactly the rows it wrote
            write_len = min(data_len, self._capacity)
            start = pos_before + data_len - write_len
            self._staged.extend((start + r) % self._capacity for r in range(write_len))
            # bound host-side staging memory (and batch the upload) during
            # long collection-only phases such as the learning_starts prefill
            if len(self._staged) >= 8 * self.FLUSH_BUCKET:
                self._flush()

    # -- device plumbing ---------------------------------------------------

    def _allocate(self, example_row: Dict[str, np.ndarray]) -> None:
        """``example_row`` leaves are per-env rows ``[n_envs, ...]``."""
        import jax
        import jax.numpy as jnp

        max_group = max(len(g) for g in self._groups)
        bytes_per_row = sum(
            int(np.prod(np.asarray(v).shape[1:], dtype=np.int64))
            * np.asarray(v).dtype.itemsize
            * max_group
            for v in example_row.values()
        )
        _check_hbm_budget(
            self._homes[0],
            self._capacity,
            bytes_per_row,
            "DeviceRingTransitions",
            lambda limit: int(0.5 * limit / max(bytes_per_row, 1)),
            self._n_envs,
        )
        self._shards = []
        for g, envs in enumerate(self._groups):
            with jax.default_device(self._homes[g]):
                self._shards.append(
                    {
                        k: jnp.zeros(
                            (self._capacity, len(envs)) + np.asarray(v).shape[1:],
                            np.asarray(v).dtype,
                        )
                        for k, v in example_row.items()
                    }
                )

    def _scatter_fn(self, n_rows: int):
        import jax

        fn = self._scatter_fns.get(n_rows)
        if fn is None:
            def scatter(buf, t_idx, rows):
                return {
                    k: v.at[t_idx].set(rows[k], mode="drop") for k, v in buf.items()
                }

            fn = jax.jit(scatter, donate_argnums=(0,))
            self._scatter_fns[n_rows] = fn
        return fn

    def _flush(self) -> None:
        if not self._staged:
            return
        # dedupe staged rows: a ring can wrap within one staging window, and
        # XLA's scatter leaves the winner among duplicate indices undefined;
        # values are read from the host buffer, which holds the newest write
        rows_t = np.asarray(list(dict.fromkeys(self._staged)), np.int64)
        host = self._rb.buffer
        if self._shards is None:
            self._allocate({k: _as_np(v)[0] for k, v in host.items()})
        n = int(rows_t.size)
        padded = _pad_rows(n)
        t_idx = np.full(padded, self._capacity, np.int32)  # OOB → dropped
        t_idx[:n] = rows_t
        for g, envs in enumerate(self._groups):
            rows: Dict[str, np.ndarray] = {}
            for k, v in host.items():
                arr = _as_np(v)
                stack = np.zeros((padded, len(envs)) + arr.shape[2:], arr.dtype)
                # fused row+column gather: copies only this group's columns
                # (arr[rows_t][:, envs] would materialize the full width
                # n_groups times per flush)
                stack[:n] = arr[np.ix_(rows_t, envs)]
                rows[k] = stack
            payload = staged_device_put((t_idx, rows), self._homes[g])
            self._shards[g] = self._scatter_fn(padded)(self._shards[g], *payload)
        self._staged.clear()

    # -- sample path -------------------------------------------------------

    def _gather_fn(self, total: int, n_samples: int, sample_next_obs: bool):
        import jax
        import jax.numpy as jnp

        key = (total, n_samples, sample_next_obs)
        fn = self._gather_fns.get(key)
        if fn is None:
            capacity = self._capacity
            width = self._group_width
            obs_keys = tuple(self._rb._obs_keys)

            def gather(buf, plan):
                # plan rows are packed t * width + col (int32): one upload
                # word per transition instead of two
                t_idx = plan // width
                c_idx = plan % width
                out = {}
                for k, v in buf.items():
                    sel = v[t_idx, c_idx]
                    out[k] = sel.reshape((n_samples, total // n_samples) + sel.shape[1:])
                    if sample_next_obs and k in obs_keys:
                        nxt = v[jnp.mod(t_idx + 1, capacity), c_idx]
                        out[f"next_{k}"] = nxt.reshape(
                            (n_samples, total // n_samples) + nxt.shape[1:]
                        )
                return out

            fn = jax.jit(gather)
            self._gather_fns[key] = fn
        return fn

    def sample_device(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        n_samples: int = 1,
    ) -> Dict[str, Any]:
        """Gather ``[n_samples, batch, ...]`` transition batches on device.

        The only host→device traffic is the int32 index plan. With a
        ``batch_sharding`` the result is a global sharded Array whose batch
        slice *g* was gathered (and stays) on the device that consumes it."""
        import jax

        n_groups = len(self._groups)
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        if batch_size % n_groups != 0:
            raise ValueError(
                f"batch_size {batch_size} must divide evenly over the "
                f"{n_groups} batch shards"
            )
        with self._write_lock or nullcontext():
            self._flush()
            if self._shards is None:
                raise ValueError("No sample has been added to the buffer")
            b_local = batch_size // n_groups
            parts: List[Dict[str, Any]] = []
            for g, envs in enumerate(self._groups):
                # the host buffer's own planner: valid-window semantics live in
                # exactly one place; per-group runs restrict the env draw to
                # the group's columns (uniform within the group, like the
                # sequence ring's per-group pick_envs)
                t_idx, e_idx = self._rb.plan_transitions(
                    b_local,
                    sample_next_obs=sample_next_obs,
                    n_samples=n_samples,
                    rng=self._rng,
                    envs=None if n_groups == 1 else envs,
                )
                packed = (
                    t_idx.astype(np.int64) * self._group_width + self._env_col[e_idx]
                ).astype(np.int32)
                fn = self._gather_fn(packed.shape[0], n_samples, sample_next_obs)
                plan = staged_device_put(packed, self._homes[g])
                parts.append(fn(self._shards[g], plan))
        if self._sharding is None:
            return parts[0]
        return _assemble_global(parts, self._sharding, self._replicas, 1, batch_size)
