"""The TPU runtime: mesh, precision, collectives, checkpoint I/O, callbacks.

This is the TPU-native replacement for Lightning Fabric as the reference uses
it (SURVEY §1 L5; ``sheeprl/cli.py:93,139,156``, ``ppo.py:96-201``). The
design is SPMD-first instead of process-per-rank:

- **One process drives all local chips.** The reference spawns one process per
  device and wraps modules in DDP; here a single :class:`Fabric` owns a
  ``jax.sharding.Mesh`` over every device (all hosts) with a ``data`` axis.
  Train steps are jitted with batch inputs sharded over ``data``; XLA inserts
  the gradient ``psum`` (the DDP allreduce) over ICI automatically from the
  shardings. Multi-host runs use ``jax.distributed`` — same code, the mesh
  just spans hosts and collectives ride ICI within a slice / DCN across.
- **"rank" semantics.** ``world_size`` is the number of devices in the mesh
  (matches the reference's world_size = #ranks = #devices); ``global_rank``
  is the *process* index, used only for host-side concerns (logging,
  checkpoint ownership, video capture). Per-rank batch/env counts from the
  reference configs are interpreted per-device, preserving the step-accounting
  contract (``howto/work_with_steps.md``).
- ``fabric.load`` restores both the ``sheeprl_tpu/ckpt`` manifest layout
  (checksum-verified npz shards) and legacy Orbax pytree checkpoints;
  ``fabric.save`` remains the legacy synchronous Orbax writer — train loops
  checkpoint through ``fabric.call("on_checkpoint_*")``, which routes into
  the async, atomic checkpoint subsystem (reference callback.py).
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.parallel import mesh as _mesh
from sheeprl_tpu.parallel import shard as _shard


def _select_devices(devices: Any, accelerator: str) -> List[jax.Device]:
    """Resolve the device list from the fabric config.

    ``devices`` may be "auto" (all), an int (first N), or a list of indices.
    ``accelerator`` ∈ {auto, cpu, gpu, cuda, tpu}: ``auto`` is whatever
    platform jax chose; a named platform that this process does not have
    raises — a run asked onto a TPU never lands on the CPU instead.
    """
    accelerator = (accelerator or "auto").lower()
    if accelerator == "auto":
        all_devices = jax.devices()
    elif accelerator in ("tpu", "gpu", "cuda", "cpu"):
        platform = {"cuda": "gpu"}.get(accelerator, accelerator)
        try:
            all_devices = jax.devices(platform)
        except RuntimeError as exc:
            raise RuntimeError(
                f"fabric.accelerator={accelerator} but this process has no '{platform}' "
                f"platform (jax default backend: {jax.default_backend()}). Use "
                "fabric.accelerator=auto to run on whatever jax finds."
            ) from exc
    else:
        raise ValueError(
            f"Unknown fabric.accelerator {accelerator!r}; expected auto, cpu, gpu, cuda or tpu"
        )
    if devices in (None, "auto", -1, "-1"):
        return list(all_devices)
    if isinstance(devices, (list, tuple)):
        return [all_devices[i] for i in devices]
    n = int(devices)
    if n > len(all_devices):
        raise ValueError(f"Requested {n} devices but only {len(all_devices)} are available")
    return list(all_devices[:n])


def compute_dtype_from_precision(precision: Any):
    """The one precision→compute-dtype mapping (shared by Fabric and the
    model builders): "32-true" → None (f32 everywhere), "bf16-mixed" → bf16
    compute with f32 params/losses. Anything else raises — silently
    reinterpreting fp16/true-bf16 requests would mislead."""
    p = str(precision or "32-true").lower()
    if p in ("32-true", "32"):
        return None
    if p == "bf16-mixed":
        return jnp.bfloat16
    raise ValueError(
        f"Unsupported fabric.precision {precision!r}: use '32-true' or 'bf16-mixed'"
    )


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize ``jax.distributed`` for multi-host meshes (the TPU-native
    replacement for the reference's NCCL/Gloo process groups, SURVEY §5.8).

    MUST run before anything touches a jax backend (the CLI calls it right
    after config composition when ``fabric.num_nodes > 1``); once backends
    are up it is a no-op reporting the current state. On TPU pods the
    runtime auto-discovers topology, so all arguments stay ``None``; jax
    itself honors ``JAX_COORDINATOR_ADDRESS`` & friends for everything
    else. Launch one process per host — collectives then ride ICI within a
    slice and DCN across hosts with the same SPMD program. Returns True
    when a multi-process runtime is (or already was) up.
    """
    import jax.distributed

    try:
        from jax._src import xla_bridge

        backends_up = xla_bridge.backends_are_initialized()
    except Exception:  # pragma: no cover - private-API drift
        # assume not-yet-up: at the CLI call site that is true, and a wrong
        # guess surfaces as initialize()'s own "must be called before any
        # backend" error instead of silently skipping multi-host init
        backends_up = False
    if backends_up:
        # initialize() would raise; just report what we're running under
        return jax.process_count() > 1
    # Cross-process collectives on the CPU backend need an explicit
    # implementation (default 'none' fails at execute time) — this is the
    # multi-process CPU test mode, the analog of the reference's 2-process
    # Gloo CI (reference tests/test_algos/test_algos.py:16-52; same Gloo!).
    # The knob only affects the CPU backend, so set it unconditionally.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # pragma: no cover - knob renamed upstream
        pass
    # an explicitly requested multi-host run must not silently degrade to N
    # independent single-host trainings racing on the same run dir — let
    # coordinator failures propagate
    jax.distributed.initialize(coordinator_address, num_processes, process_id)
    return jax.process_count() > 1


class Fabric:
    """Mesh-owning runtime handed to every algorithm entrypoint as ``fabric``."""

    def __init__(
        self,
        devices: Any = "auto",
        num_nodes: int = 1,
        strategy: str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        callbacks: Optional[Sequence[Any]] = None,
        data_axis: str = "data",
        prng_impl: Optional[str] = "rbg",
        model_axis: int = 1,
        shard_min_bytes: Optional[int] = None,
        shard_overrides: Optional[Dict[str, Any]] = None,
    ):
        if prng_impl:
            # rbg (default): XLA-native random bits, markedly cheaper than
            # threefry on TPU (pre-drawn scan/imagination noise is ~0.4 ms of
            # the DV3 step under threefry). Still deterministic per seed; set
            # fabric.prng_impl=threefry for jax's default counter-based keys.
            # NOTE: this is process-global jax config — when two Fabrics with
            # different impls coexist in one process, the last constructed
            # wins for subsequently created keys.
            prng_impl = {"threefry": "threefry2x32"}.get(prng_impl, prng_impl)
            if prng_impl not in ("rbg", "threefry2x32", "unsafe_rbg"):
                raise ValueError(
                    f"Unknown fabric.prng_impl {prng_impl!r}; expected one of "
                    "'rbg', 'threefry' (threefry2x32), 'unsafe_rbg'"
                )
            jax.config.update("jax_default_prng_impl", prng_impl)
        self.strategy = strategy or "auto"
        self.accelerator = accelerator or "auto"
        self.precision = precision or "32-true"
        self.callbacks = list(callbacks or [])
        self.num_nodes = num_nodes
        self._devices = _select_devices(devices, self.accelerator)
        self.data_axis = data_axis
        self.model_axis = int(model_axis) if model_axis is not None else 1
        if self.model_axis < 1:
            raise ValueError(f"parallel.model_axis must be >= 1, got {model_axis}")
        self.shard_min_bytes = (
            int(shard_min_bytes)
            if shard_min_bytes is not None
            else _shard.DEFAULT_MIN_SHARD_BYTES
        )
        self.shard_overrides = dict(shard_overrides) if shard_overrides else None
        if self.model_axis > 1:
            # {'data': -1, 'model': N} — the GSPMD parameter-sharding mesh.
            # make_mesh raises when N does not divide the device count.
            self.mesh = _mesh.make_mesh(
                {data_axis: -1, _mesh.MODEL_AXIS: self.model_axis}, self._devices
            )
        else:
            # model_axis=1 keeps the 1-D mesh byte-identical to the pure
            # data-parallel runtime: same jaxpr, same reduction order, so
            # sharded-vs-replicated bitwise parity holds by construction.
            self.mesh = Mesh(np.asarray(self._devices), (data_axis,))
        self._launched = False

    # ------------------------------------------------------------------
    # topology
    # ------------------------------------------------------------------

    @property
    def world_size(self) -> int:
        """Number of devices in the mesh (reference: number of DDP ranks)."""
        return len(self._devices)

    @property
    def on_accelerator(self) -> bool:
        """True when the mesh runs on an accelerator (acting then mirrors
        parameters to the CPU host — see utils/host.py)."""
        return self.mesh.devices.flat[0].platform != "cpu"

    @property
    def global_rank(self) -> int:
        """Process index — host-side identity for logging/checkpointing."""
        return jax.process_index()

    @property
    def local_rank(self) -> int:
        return 0

    @property
    def node_rank(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    @property
    def device(self) -> jax.Device:
        return self._devices[0]

    @property
    def local_devices(self) -> List[jax.Device]:
        return [d for d in self._devices if d.process_index == jax.process_index()]

    # ------------------------------------------------------------------
    # precision
    # ------------------------------------------------------------------

    @property
    def compute_dtype(self):
        """None for f32, bf16 under mixed precision — params stay f32,
        activations bf16 (the TPU-native analog of fabric's "bf16-mixed")."""
        return compute_dtype_from_precision(self.precision)

    @property
    def param_dtype(self):
        return jnp.float32

    # ------------------------------------------------------------------
    # shardings
    # ------------------------------------------------------------------

    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def data_sharding(self) -> NamedSharding:
        """Leading axis split over the mesh's data axis."""
        return NamedSharding(self.mesh, P(self.data_axis))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_data(self, tree: Any) -> Any:
        """Host→HBM: place a pytree with leading-axis data-parallel sharding."""
        return jax.device_put(tree, self.data_sharding)

    def to_device(self, tree: Any) -> Any:
        """Host→HBM replicated placement."""
        return jax.device_put(tree, self.replicated)

    # ------------------------------------------------------------------
    # parameter sharding (the {'data','model'} mesh)
    # ------------------------------------------------------------------

    @property
    def model_axis_size(self) -> int:
        """Size of the ``'model'`` parameter-sharding axis (1 = replicated)."""
        return self.model_axis

    @property
    def data_parallel_size(self) -> int:
        """Size of the data axis — the gradient-pmean world. Equals
        ``world_size`` unless ``model_axis`` carves devices out of it."""
        return int(self.mesh.shape[self.data_axis])

    def shard_plan(self, tree: Any) -> Optional["_shard.ShardingPlan"]:
        """Spec-assign ``tree``'s leaves over the ``'model'`` axis.

        Returns ``None`` when ``model_axis`` is 1 so call sites can branch
        ``plan is None`` onto the byte-identical replicated path. Honors the
        ``parallel.shard_min_bytes`` / ``parallel.shard_overrides`` knobs.
        """
        if self.model_axis <= 1:
            return None
        return _shard.make_plan(
            tree,
            self.mesh,
            min_shard_bytes=self.shard_min_bytes,
            overrides=self.shard_overrides,
        )

    # ------------------------------------------------------------------
    # launch & module setup (reference-API parity shims)
    # ------------------------------------------------------------------

    def launch(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Run the entrypoint. No process spawning: SPMD jit covers all local
        devices, and multi-host launch is external (one process per host via
        ``jax.distributed``), so this just validates topology and calls in."""
        self._launched = True
        if self.num_nodes > 1 and jax.process_count() == 1:
            # too late to bring up jax.distributed here (backends are already
            # initialized by the device query in __init__) — the CLI calls
            # init_distributed() before constructing Fabric
            warnings.warn(
                f"fabric.num_nodes={self.num_nodes} but jax.distributed is not initialized; "
                "running single-host (call sheeprl_tpu.fabric.init_distributed() before "
                "creating Fabric, or launch via the CLI which does)"
            )
        # Uncommitted eager work in the entrypoint (flax param init, PRNG key
        # math, optax state init) runs on the host CPU, one small XLA:CPU
        # program per op. The mesh programs are unaffected: they carry
        # explicit shardings and their inputs are committed with device_put
        # (agent state -> fabric.replicated, replay bursts -> the batch
        # sharding, acting params -> wherever algo.player_on_host says), so
        # nothing the config places on the mesh can land here instead.
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        return fn(self, *args, **kwargs)

    def setup_module(self, module: Any) -> Any:
        """Parity shim: flax params are plain pytrees; DP is expressed via
        shardings at jit boundaries, not module wrappers."""
        return module

    def setup_optimizers(self, *optimizers: Any):
        return optimizers if len(optimizers) > 1 else optimizers[0]

    # ------------------------------------------------------------------
    # host-level collectives (cross-process; in-step collectives are XLA's).
    # Every multi-process branch runs inside a measured comms span
    # (obs/dist/comms.py): payload bytes, wall time, and achieved wire GB/s
    # land in telemetry.json as comms_ms/comms_bytes + a per-kind breakdown
    # — the instrumentation ROADMAP item 2's measured scaling study needs.
    # ------------------------------------------------------------------

    def barrier(self, name: str = "") -> None:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            from sheeprl_tpu.obs.dist.comms import collective_span

            with collective_span("barrier"):
                multihost_utils.sync_global_devices(name or "fabric-barrier")

    def all_gather(self, tree: Any) -> Any:
        """Gather a host-side pytree across processes → leaves with a new
        leading process axis. Single-process: adds the axis (world view)."""
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(lambda x: np.asarray(x)[None], tree)
        from jax.experimental import multihost_utils

        from sheeprl_tpu.obs.counters import tree_nbytes
        from sheeprl_tpu.obs.dist.comms import collective_span

        tree = jax.tree_util.tree_map(np.asarray, tree)
        with collective_span(
            "all_gather", payload_bytes=tree_nbytes(tree) * jax.process_count()
        ):
            return jax.tree_util.tree_map(
                lambda x: np.asarray(multihost_utils.process_allgather(x)), tree
            )

    def broadcast(self, tree: Any, src: int = 0) -> Any:
        if jax.process_count() == 1:
            return tree
        from jax.experimental import multihost_utils

        from sheeprl_tpu.obs.counters import tree_nbytes
        from sheeprl_tpu.obs.dist.comms import collective_span

        tree = jax.tree_util.tree_map(np.asarray, tree)
        with collective_span("broadcast", payload_bytes=tree_nbytes(tree)):
            return jax.tree_util.tree_map(
                lambda x: np.asarray(multihost_utils.broadcast_one_to_all(x)), tree
            )

    def all_reduce(self, tree: Any, op: str = "sum") -> Any:
        """Sum (or mean) a host-side float pytree across processes with a
        REAL on-the-wire all-reduce: leaves are committed to the world mesh
        sharded over ``data`` and reduced by one jitted cross-process
        program — the same collective XLA inserts for gradient syncs, so
        timing this call measures the actual link (``tools/bench_comms.py``
        times the 33 MB gradient payload through exactly this path).
        Single-process: identity for ``sum``/``mean`` over one participant.
        """
        if op not in ("sum", "mean"):
            raise ValueError(f"fabric.all_reduce supports op='sum'|'mean', got {op!r}")
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(np.asarray, tree)
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        from sheeprl_tpu.obs.counters import tree_nbytes
        from sheeprl_tpu.obs.dist.comms import collective_span

        n_local = max(len(self.local_devices), 1)
        denom = np.float32(n_local * (jax.process_count() if op == "mean" else 1))
        reduce_fn = getattr(self, "_allreduce_fn", None)
        if reduce_fn is None:
            # cached so repeated calls (the bench's timed repeats) hit the
            # jit cache instead of recompiling per call
            reduce_fn = jax.jit(
                lambda g, d: jnp.sum(g, axis=0) / d,
                out_shardings=NamedSharding(self.mesh, P()),
            )
            self._allreduce_fn = reduce_fn

        def _reduce_one(x: Any) -> np.ndarray:
            x = np.asarray(x)  # plain float/int/list leaves are fine
            if x.dtype.kind != "f":
                x = x.astype(np.float32)
            # every local device contributes one copy of this process's
            # leaf; the global sum therefore counts each process n_local
            # times — divided back out through `denom`
            local = np.broadcast_to(x[None], (n_local, *x.shape))
            garr = multihost_utils.host_local_array_to_global_array(
                local, self.mesh, P(self.data_axis)
            )
            out = reduce_fn(garr, denom)
            return np.asarray(jax.device_get(out.addressable_data(0)))

        payload = tree_nbytes(jax.tree_util.tree_map(np.asarray, tree))
        with collective_span("all_reduce", payload_bytes=payload):
            return jax.tree_util.tree_map(_reduce_one, tree)

    # ------------------------------------------------------------------
    # checkpointing (reference fabric.save/load → Orbax pytree checkpoint)
    # ------------------------------------------------------------------

    def save(self, path: str, state: Dict[str, Any]) -> None:
        """Checkpoint a state pytree. EVERY process must call this: Orbax's
        Checkpointer.save runs its own cross-process sync barriers
        (multihost.sync_global_processes) even for host-local numpy state —
        gating the call to one process deadlocks the world at save_start.
        For replicated (non-sharded) values only the primary host writes
        bytes; the final barrier below keeps any immediate reader from
        racing the atomic rename (exercised end-to-end by
        tests/test_runtime/distributed_worker.py)."""
        import orbax.checkpoint as ocp

        path = os.path.abspath(path)
        state = jax.device_get(state)
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save(path, state, force=True)
        self.barrier("fabric-save")  # no-op single-process

    def load(self, path: str, state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Restore a checkpoint pytree (reference fabric.load semantics).

        Manifest-format checkpoints (the ``sheeprl_tpu.ckpt`` subsystem's
        atomic npz layout) are read with per-array checksum verification;
        legacy orbax directories restore as before. With ``state`` given,
        the raw restore is conformed to its structure (NamedTuple optimizer
        states rebuilt, extra on-disk keys like the optional replay-buffer
        snapshot kept raw at top level)."""
        from sheeprl_tpu.utils.utils import conform_pytree, migrate_legacy_checkpoint

        path = os.path.abspath(path)
        from sheeprl_tpu.ckpt.resume import is_manifest_checkpoint, read_checkpoint

        if is_manifest_checkpoint(path):
            restored = read_checkpoint(path, rank=self.global_rank)
        else:
            import orbax.checkpoint as ocp

            with ocp.PyTreeCheckpointer() as ckptr:
                restored = ckptr.restore(path)
        if state is not None:
            restored = migrate_legacy_checkpoint(state, restored)
            out = conform_pytree(state, restored)
            if isinstance(restored, dict):
                for k in restored:
                    if k not in out:
                        out[k] = restored[k]
            return out
        return restored

    # ------------------------------------------------------------------
    # callbacks (reference fabric.call → utils/callback.py)
    # ------------------------------------------------------------------

    def call(self, hook_name: str, **kwargs: Any) -> None:
        for cb in self.callbacks:
            hook = getattr(cb, hook_name, None)
            if callable(hook):
                hook(fabric=self, **kwargs)

    # ------------------------------------------------------------------
    # misc parity helpers
    # ------------------------------------------------------------------

    def seed_everything(self, seed: int) -> jax.Array:
        """Seed numpy/python and return the root jax PRNG key."""
        import random

        random.seed(seed)
        np.random.seed(seed)
        return jax.random.PRNGKey(seed)

    def print(self, *args: Any, **kwargs: Any) -> None:
        if self.is_global_zero:
            print(*args, **kwargs)
