"""Host-side parameter mirroring for the acting path.

Environment interaction is one jitted policy call per env step. With
``algo.player_on_host`` (the global default; the DreamerV3 family's recipe
turns it off) and a mesh on an accelerator, that call
runs on the host CPU instead of the mesh (SURVEY §5.8 — players live on CPU
hosts feeding the trainer mesh): :class:`HostParamMirror` keeps a CPU copy
of the acting parameters, and every call of it refreshes that copy (the
entrypoints call it once per update). For DreamerV3-XL acting on the device
won in every cell (PERF.md §6, PR 35); for the other families neither side
has a chip run (ROADMAP D1/D4).

**The route of a refresh.** The leaves are fetched from the device with all
their copies in flight together (``jax.device_get``: a leaf sharded over the
mesh comes back whole), each is copied once into its place in a *landing set*,
and that place is handed to the CPU backend by ``jax.device_put``, which
aliases host memory whose pointer is 64-byte aligned and copies any other.
What a refresh costs beyond the link is decided by whose memory it writes:
memory the allocator hands out fresh is first touched at under 1 GB/s, memory
written before takes the copy several times faster (PERF.md, PR 27 and PR 29:
432 MB land in 69 ms, against 662 ms into a fresh CPU-backend buffer). So the
landing memory is the mirror's own — allocated at construction, every leaf at
an aligned offset (``numpy``'s allocator aligns to 16), written once so that
its pages exist — and is reused from refresh to refresh.

**A snapshot somebody still holds keeps its values.** There are two landing
sets, used in turn, so the snapshot acting holds is never the one being
written. A set is rewritten only when nothing handed out from it is alive: the
CPU backend keeps the ``numpy`` view it aliased for as long as the array it
made of it, or a computation reading that array, lives, and the mirror watches
those views. If a caller kept the older snapshot, the refresh leaves that
memory to its holder and takes a fresh set in its place. ``publish_copied_leaves``
counts the leaves of such a refresh, and the leaves the backend copied instead
of aliasing: it reads 0 while the mechanism engages.

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

import gc
import weakref
from typing import Any, List, Sequence, Tuple

import jax
import numpy as np

from sheeprl_tpu.obs.counters import add_publish
from sheeprl_tpu.obs.spans import span

# what the CPU backend asks of a host buffer before it aliases it
_ALIGN = 64


class _LandingSet:
    """Host memory for one snapshot: one allocation, each leaf's place in it
    at a 64-byte aligned offset, written once so that its pages exist."""

    def __init__(self, specs: Sequence[Tuple[Tuple[int, ...], np.dtype]]):
        sizes = [int(np.prod(shape)) * dtype.itemsize for shape, dtype in specs]
        self.nbytes = sum(sizes)
        arena = np.empty(sum(-(-size // _ALIGN) * _ALIGN for size in sizes) + _ALIGN, np.uint8)
        arena.fill(0)
        at = -arena.ctypes.data % _ALIGN
        self._places = []
        for size, (shape, dtype) in zip(sizes, specs):
            self._places.append(arena[at : at + size].view(dtype).reshape(shape))
            at += -(-size // _ALIGN) * _ALIGN
        self._aliased: List[weakref.ref] = []

    def held(self) -> bool:
        """Whether a CPU-backend array made of this memory, or a computation
        reading one, is alive."""
        return any(view() is not None for view in self._aliased)

    def hand_out(self, leaves: Sequence[np.ndarray], device) -> Tuple[List[jax.Array], int]:
        """Copy ``leaves`` into place and wrap each place as an array of
        ``device``. Returns the arrays and how many of them the backend
        copied instead of aliasing."""
        # a view object of its own for every hand-out: the backend keeps the
        # object it aliased, so these die with this snapshot and no other
        views = [place[...] for place in self._places]
        for view, leaf in zip(views, leaves):
            if leaf.shape != view.shape or leaf.dtype != view.dtype:
                raise ValueError(
                    f"the mirror was built for a leaf {view.shape} {view.dtype} "
                    f"and was handed {leaf.shape} {leaf.dtype}"
                )
            np.copyto(view, leaf)
        arrays = jax.device_put(views, device)
        copied = sum(
            view.size > 0 and array.unsafe_buffer_pointer() != view.ctypes.data for view, array in zip(views, arrays)
        )
        self._aliased = [weakref.ref(view) for view in views]
        return arrays, copied


class HostParamMirror:
    @staticmethod
    def enabled_for(fabric, cfg) -> bool:
        """The one enable rule shared by every algorithm: host acting is on
        unless ``algo.player_on_host=False``, and only matters when the mesh
        runs on an accelerator."""
        return bool(cfg.algo.get("player_on_host", True)) and fabric.on_accelerator

    @classmethod
    def from_cfg(cls, example_tree: Any, fabric, cfg) -> "HostParamMirror":
        """The one construction rule: enable per :meth:`enabled_for`."""
        return cls(example_tree, enabled=cls.enabled_for(fabric, cfg))

    def __init__(self, example_tree: Any, enabled: bool = True):
        self.enabled = bool(enabled)
        if self.enabled:
            self._host = jax.devices("cpu")[0]
            leaves, self._treedef = jax.tree_util.tree_flatten(example_tree)
            self._specs = [(tuple(leaf.shape), np.dtype(leaf.dtype)) for leaf in leaves]
            self._sets = [_LandingSet(self._specs), _LandingSet(self._specs)]

    def __call__(self, tree: Any) -> Any:
        if not self.enabled:
            return tree
        # the route is synchronous: the span holds all of a refresh
        with span("Time/publish_time", phase="publish"):
            snapshot, copied = self._refresh(tree)
        add_publish(self._sets[0].nbytes, copied_leaves=copied)
        return snapshot

    def _refresh(self, tree: Any) -> Tuple[Any, int]:
        leaves = jax.device_get(self._treedef.flatten_up_to(tree))
        # the backend lets go of a view it aliased at its next collection of
        # Python references, which it runs with the interpreter's: a
        # youngest-generation pass, so that a snapshot dropped since the last
        # refresh does not read as held
        gc.collect(0)
        reused = not self._sets[0].held()
        if not reused:
            self._sets[0] = _LandingSet(self._specs)
        arrays, copied = self._sets[0].hand_out(leaves, self._host)
        self._sets.reverse()  # the other set takes the next refresh
        return self._treedef.unflatten(arrays), copied if reused else len(arrays)

    def put_key(self, key: jax.Array) -> jax.Array:
        """Commit a PRNG key next to the mirrored params."""
        return jax.device_put(key, self._host) if self.enabled else key
