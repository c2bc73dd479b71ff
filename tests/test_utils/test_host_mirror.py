"""HostParamMirror unit tests — the enabled (accelerator) path is otherwise
only exercised on real TPU hardware, so the pack/unravel round-trip is
pinned here on CPU."""

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


def test_refresh_every_caches_between_refreshes():
    tree = _tree()
    mirror = HostParamMirror(tree, enabled=True, refresh_every=3)
    first = mirror(tree)
    updated = jax.tree_util.tree_map(lambda x: x + 1.0, tree)
    # calls 2 and 3 return the cached (stale) snapshot
    assert mirror(updated) is first
    assert mirror(updated) is first
    # call 4 starts a new cadence window → fresh values
    out = mirror(updated)
    assert out is not first
    np.testing.assert_array_equal(
        np.asarray(out["scale"]), np.asarray(updated["scale"])
    )


def test_refresh_is_a_publish_span_and_counts_its_bytes(tmp_path):
    """Every refresh (not a cache hit, not a disabled mirror) is one
    ``Time/publish_time`` span and adds the packed vector's bytes to
    ``publish_bytes``."""
    import json

    from sheeprl_tpu.obs import counters as obs_counters
    from sheeprl_tpu.obs.spans import TraceWriter, set_tracer

    tree = _tree()
    packed_bytes = sum(np.asarray(leaf).nbytes for leaf in jax.tree_util.tree_leaves(tree))
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    run_counters = obs_counters.Counters()
    obs_counters.install(run_counters)
    try:
        mirror = HostParamMirror(tree, enabled=True, refresh_every=2)
        seen = []
        for _ in range(4):
            mirror(tree)
            seen.append((run_counters.publish_refreshes, run_counters.publish_bytes))
        HostParamMirror(tree, enabled=False)(tree)
    finally:
        obs_counters.install(None)
        set_tracer(None)
        writer.close()
    assert seen == [(1, packed_bytes), (1, packed_bytes), (2, 2 * packed_bytes), (2, 2 * packed_bytes)]
    assert run_counters.as_dict()["publish_bytes"] == 2 * packed_bytes
    with open(writer.path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    spans = [e for e in events if e.get("ph") == "X"]
    assert [(e["name"], e["cat"]) for e in spans] == [("Time/publish_time", "publish")] * 2
