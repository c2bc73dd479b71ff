"""HostParamMirror unit tests — the enabled (accelerator) path is otherwise
only exercised on real TPU hardware, so the route of a refresh (fetch, land in
the mirror's own reused memory, hand out aliased) is pinned here on CPU."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.utils.host import HostParamMirror


def _tree():
    return {
        "dense": {"kernel": jnp.arange(12, dtype=jnp.float32).reshape(3, 4), "bias": jnp.ones(4)},
        "scale": jnp.float32(2.5),
        "embed": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
    }


@pytest.mark.parametrize(
    "select",
    [lambda tree: tree, lambda tree: {"dense": tree["dense"], "embed": tree["embed"]}],
    ids=["whole", "subtree"],
)
def test_enabled_roundtrip_is_exact(select):
    """The mirror copies the tree its caller hands it, whole or a selection the
    caller made of a larger one (DreamerV3 hands it the leaves acting reads)."""
    tree = select(_tree())
    mirror = HostParamMirror(tree, enabled=True)
    out = mirror(tree)
    # identical structure and bit-exact leaves
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype
    # mirrored leaves live on the CPU host
    cpu = jax.devices("cpu")[0]
    assert all(cpu in leaf.devices() for leaf in jax.tree_util.tree_leaves(out))


@pytest.fixture
def run_counters():
    from sheeprl_tpu.obs import counters as obs_counters

    counters = obs_counters.Counters()
    obs_counters.install(counters)
    yield counters
    obs_counters.install(None)


def _odd_tree(offset=0):
    """Mixed shapes and dtypes: a scalar, a zero-size leaf, leaves whose byte
    sizes are not multiples of 64 (28, 3, 10, 20 bytes) and one that is."""
    return {
        "scalar": jnp.float32(2.5 + offset),
        "empty": jnp.zeros((0, 3), jnp.float32),
        "seven": jnp.arange(7, dtype=jnp.float32) + offset,
        "bytes": jnp.asarray([1, 2, 3], dtype=jnp.int8) + offset,
        "half": (jnp.arange(5, dtype=jnp.float32) + offset).astype(jnp.bfloat16),
        "nested": [jnp.arange(5, dtype=jnp.int32) + offset, jnp.full((4, 16), 1.5 + offset, jnp.float32)],
    }


def _assert_same(out, tree):
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _pointers(out):
    return [leaf.unsafe_buffer_pointer() for leaf in jax.tree_util.tree_leaves(out) if leaf.size]


def test_roundtrip_of_odd_leaves_is_exact(run_counters):
    mirror = HostParamMirror(_odd_tree(), enabled=True)
    for offset in range(3):
        tree = _odd_tree(offset)
        _assert_same(mirror(tree), tree)
    assert run_counters.publish_copied_leaves == 0


def test_handed_out_leaves_are_cpu_arrays_a_jitted_function_accepts():
    tree = _odd_tree()
    out = HostParamMirror(tree, enabled=True)(tree)
    cpu = jax.devices("cpu")[0]
    for leaf in jax.tree_util.tree_leaves(out):
        assert isinstance(leaf, jax.Array) and leaf.committed and leaf.devices() == {cpu}
    doubled = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x + x, t))(out)
    _assert_same(doubled, jax.tree_util.tree_map(lambda x: x + x, tree))


def test_landing_memory_is_reused_every_other_refresh(run_counters):
    """Two landing sets used in turn: refresh k + 2 lands where refresh k did,
    every place 64-byte aligned (what the CPU backend asks before it aliases),
    and no leaf is copied by the backend or lands in fresh memory."""
    mirror = HostParamMirror(_odd_tree(), enabled=True)
    seen = []
    for offset in range(6):
        tree = _odd_tree(offset)
        out = mirror(tree)
        _assert_same(out, tree)
        seen.append(_pointers(out))
    assert all(pointer % 64 == 0 for pointers in seen for pointer in pointers)
    assert seen[0] == seen[2] == seen[4] and seen[1] == seen[3] == seen[5]
    assert not set(seen[0]) & set(seen[1])
    assert len(set(seen[0])) == len(seen[0])
    assert (run_counters.publish_refreshes, run_counters.publish_copied_leaves) == (6, 0)


@pytest.mark.parametrize("keep", ["tree", "one_leaf"])
def test_a_snapshot_the_caller_keeps_is_not_rewritten(run_counters, keep):
    """A set is rewritten only when nothing handed out from it is alive: a
    caller that keeps a snapshot (or one leaf of it) across two further
    refreshes still reads its own values; that refresh takes fresh memory and
    the counter counts its leaves."""
    mirror = HostParamMirror(_odd_tree(), enabled=True)
    n_leaves = len(jax.tree_util.tree_leaves(_odd_tree()))
    first = mirror(_odd_tree(0))
    kept_pointers = _pointers(first)
    if keep == "tree":
        kept, want = first, _odd_tree(0)
    else:
        kept, want = first["nested"][1], _odd_tree(0)["nested"][1]
    del first
    mirror(_odd_tree(1))
    assert run_counters.publish_copied_leaves == 0
    third = mirror(_odd_tree(2))
    assert run_counters.publish_copied_leaves == n_leaves
    assert not set(_pointers(third)) & set(kept_pointers)
    _assert_same(third, _odd_tree(2))
    _assert_same(kept, want)
    # the held memory was left to its holder, the fresh set took its turn:
    # from here on the two sets in rotation are reused again
    del kept, third
    for offset in range(3, 7):
        _assert_same(mirror(_odd_tree(offset)), _odd_tree(offset))
    assert run_counters.publish_copied_leaves == n_leaves


def test_a_snapshot_dropped_in_a_reference_cycle_is_found_by_the_collector(run_counters):
    """Nothing but reference counts decides reuse; a snapshot that died in a
    cycle is held until the interpreter's collector has run."""
    mirror = HostParamMirror(_odd_tree(), enabled=True)
    cycle = {"out": mirror(_odd_tree(0))}
    cycle["self"] = cycle
    del cycle
    gc.collect()
    mirror(_odd_tree(1))
    mirror(_odd_tree(2))
    assert run_counters.publish_copied_leaves == 0


def test_a_tree_of_other_shapes_is_refused():
    mirror = HostParamMirror(_odd_tree(), enabled=True)
    wrong = dict(_odd_tree(), seven=jnp.arange(8, dtype=jnp.float32))
    with pytest.raises(ValueError, match="built for a leaf"):
        mirror(wrong)


def test_a_leaf_sharded_over_a_mesh_comes_back_whole(run_counters):
    """Under a {'data', 'model'} plan a leaf may be sharded over the mesh: the
    fetch assembles it, as the jitted pack did on the device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the 8 forced CPU devices of tests/conftest.py")
    mesh = Mesh(np.asarray(devices[:8]).reshape(4, 2), ("data", "model"))
    kernel = np.arange(16 * 6, dtype=np.float32).reshape(16, 6)
    tree = {
        "sharded": jax.device_put(kernel, NamedSharding(mesh, PartitionSpec(None, "model"))),
        "both": jax.device_put(kernel, NamedSharding(mesh, PartitionSpec("data", "model"))),
        "replicated": jax.device_put(np.float32(3.0), NamedSharding(mesh, PartitionSpec())),
    }
    assert len(tree["sharded"].sharding.device_set) == 8 and not tree["sharded"].is_fully_replicated
    mirror = HostParamMirror(tree, enabled=True)
    for _ in range(3):
        out = mirror(tree)
    cpu = jax.devices("cpu")[0]
    assert all(leaf.devices() == {cpu} for leaf in jax.tree_util.tree_leaves(out))
    np.testing.assert_array_equal(np.asarray(out["sharded"]), kernel)
    np.testing.assert_array_equal(np.asarray(out["both"]), kernel)
    np.testing.assert_array_equal(np.asarray(out["replicated"]), np.float32(3.0))
    assert run_counters.publish_copied_leaves == 0


def test_enabled_refresh_tracks_new_values():
    tree = _tree()
    mirror = HostParamMirror(tree, enabled=True)
    updated = jax.tree_util.tree_map(lambda x: x + 1.0, tree)
    out = mirror(updated)
    np.testing.assert_array_equal(
        np.asarray(out["dense"]["kernel"]),
        np.arange(12, dtype=np.float32).reshape(3, 4) + 1.0,
    )


def test_put_key_placement():
    mirror = HostParamMirror(_tree(), enabled=True)
    key = mirror.put_key(jax.random.PRNGKey(0))
    assert jax.devices("cpu")[0] in key.devices()


def test_disabled_is_identity():
    tree = _tree()
    mirror = HostParamMirror(tree, enabled=False)
    assert mirror(tree) is tree
    key = jax.random.PRNGKey(0)
    assert mirror.put_key(key) is key


def test_enabled_for_rule():
    class FakeFabric:
        on_accelerator = True

    class FakeCfg:
        algo = {"player_on_host": True}

    assert HostParamMirror.enabled_for(FakeFabric(), FakeCfg())
    FakeCfg.algo = {"player_on_host": False}
    assert not HostParamMirror.enabled_for(FakeFabric(), FakeCfg())
    FakeFabric.on_accelerator = False
    FakeCfg.algo = {}
    assert not HostParamMirror.enabled_for(FakeFabric(), FakeCfg())


def test_refresh_is_a_publish_span_and_counts_its_bytes(tmp_path):
    """Every call of an enabled mirror (not of a disabled one) refreshes: one
    ``Time/publish_time`` span, the tree's bytes added to ``publish_bytes``."""
    import json

    from sheeprl_tpu.obs import counters as obs_counters
    from sheeprl_tpu.obs.spans import TraceWriter, set_tracer

    tree = _tree()
    tree_bytes = sum(np.asarray(leaf).nbytes for leaf in jax.tree_util.tree_leaves(tree))
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    run_counters = obs_counters.Counters()
    obs_counters.install(run_counters)
    try:
        mirror = HostParamMirror(tree, enabled=True)
        seen = []
        for _ in range(4):
            mirror(tree)
            seen.append((run_counters.publish_refreshes, run_counters.publish_bytes))
        HostParamMirror(tree, enabled=False)(tree)
    finally:
        obs_counters.install(None)
        set_tracer(None)
        writer.close()
    assert seen == [(n, n * tree_bytes) for n in (1, 2, 3, 4)]
    assert run_counters.as_dict()["publish_bytes"] == 4 * tree_bytes
    with open(writer.path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    spans = [e for e in events if e.get("ph") == "X"]
    assert [(e["name"], e["cat"]) for e in spans] == [("Time/publish_time", "publish")] * 4
