"""Runtime-layer tests: Fabric mesh/sharding/checkpoint, metrics, timer, optim."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sheeprl_tpu.fabric import Fabric
from sheeprl_tpu.utils.metric import (
    MaxMetric,
    MeanMetric,
    MetricAggregator,
    MinMetric,
    SumMetric,
)
from sheeprl_tpu.utils.optim import Adam, SGD, get_lr, set_lr
from sheeprl_tpu.utils.timer import timer


def test_fabric_mesh_sizes():
    fabric = Fabric(devices=8, accelerator="cpu")
    assert fabric.world_size == 8
    assert fabric.mesh.shape == {"data": 8}
    fabric2 = Fabric(devices=2, accelerator="cpu")
    assert fabric2.world_size == 2


def test_fabric_too_many_devices():
    with pytest.raises(ValueError):
        Fabric(devices=1024, accelerator="cpu")


def test_fabric_named_accelerator_without_its_platform_raises():
    # the suite runs on a CPU-only backend: asking for a TPU must fail, not
    # warn and train on the CPU under the TPU's name
    with pytest.raises(RuntimeError, match="fabric.accelerator=tpu"):
        Fabric(devices=1, accelerator="tpu")
    assert Fabric(devices=1, accelerator="auto").device.platform == jax.default_backend()


def test_fabric_shard_data_places_on_mesh():
    fabric = Fabric(devices=8, accelerator="cpu")
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    sharded = fabric.shard_data(x)
    assert sharded.sharding == fabric.data_sharding
    # a jitted psum-style reduction over the sharded batch matches numpy
    total = jax.jit(lambda a: a.sum())(sharded)
    assert float(total) == x.sum()


def test_fabric_precision_dtypes():
    import pytest

    # None == "compute in the params' dtype" (f32)
    assert Fabric(devices=1, accelerator="cpu").compute_dtype is None
    assert Fabric(devices=1, accelerator="cpu", precision="bf16-mixed").compute_dtype == jnp.bfloat16
    assert Fabric(devices=1, accelerator="cpu", precision="bf16-mixed").param_dtype == jnp.float32
    with pytest.raises(ValueError):
        Fabric(devices=1, accelerator="cpu", precision="16-mixed").compute_dtype


def test_fabric_save_load_roundtrip(tmp_path):
    fabric = Fabric(devices=2, accelerator="cpu")
    state = {
        "params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "update": np.asarray(7),
    }
    path = os.path.join(tmp_path, "ckpt_7")
    fabric.save(path, state)
    restored = fabric.load(path)
    np.testing.assert_array_equal(restored["params"]["w"], state["params"]["w"])
    assert int(restored["update"]) == 7


def test_fabric_launch_calls_entrypoint():
    fabric = Fabric(devices=1, accelerator="cpu")
    seen = {}

    def entry(fab, cfg):
        seen["fabric"] = fab
        seen["cfg"] = cfg
        return 42

    assert fabric.launch(entry, {"a": 1}) == 42
    assert seen["fabric"] is fabric


def test_fabric_all_gather_single_process_adds_axis():
    fabric = Fabric(devices=1, accelerator="cpu")
    out = fabric.all_gather({"x": np.ones((3,))})
    assert out["x"].shape == (1, 3)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_mean_sum_max_min_metrics():
    m = MeanMetric()
    m.update(1.0)
    m.update(jnp.asarray(3.0))
    assert m.compute() == 2.0
    s = SumMetric()
    s.update(2)
    s.update(5)
    assert s.compute() == 7
    mx, mn = MaxMetric(), MinMetric()
    for v in (1.0, 5.0, -2.0):
        mx.update(v)
        mn.update(v)
    assert mx.compute() == 5.0 and mn.compute() == -2.0


def test_aggregator_updates_and_nan_drop():
    agg = MetricAggregator({"a": MeanMetric(), "b": MeanMetric()})
    agg.update("a", 2.0)
    agg.update("missing", 1.0)  # silently skipped
    out = agg.compute()
    assert out == {"a": 2.0}  # 'b' never updated -> NaN dropped
    agg.reset()
    assert agg.compute() == {}


def test_aggregator_raise_on_missing():
    agg = MetricAggregator({}, raise_on_missing=True)
    with pytest.raises(KeyError):
        agg.update("nope", 1.0)


def test_aggregator_add_pop():
    agg = MetricAggregator({})
    agg.add("x", SumMetric())
    with pytest.raises(ValueError):
        agg.add("x", SumMetric())
    agg.update("x", 3.0)
    assert agg.compute() == {"x": 3.0}
    agg.pop("x")
    assert "x" not in agg


# ---------------------------------------------------------------------------
# timer
# ---------------------------------------------------------------------------


def test_timer_accumulates_and_resets():
    timer.reset()
    with timer("Time/test"):
        pass
    with timer("Time/test"):
        pass
    out = timer.compute()
    assert "Time/test" in out and out["Time/test"] >= 0
    assert timer.timers == {}


def test_timer_disabled():
    timer.reset()
    timer.disabled = True
    try:
        with timer("Time/skip"):
            pass
        assert timer.timers == {}
    finally:
        timer.disabled = False


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------


def test_adam_lr_injection_roundtrip():
    tx = Adam(lr=1e-3)
    params = {"w": jnp.ones((3,))}
    state = tx.init(params)
    assert get_lr(state) == pytest.approx(1e-3)
    state = set_lr(state, 5e-4)
    assert get_lr(state) == pytest.approx(5e-4)
    grads = {"w": jnp.ones((3,))}
    updates, state = tx.update(grads, state, params)
    new_params = optax.apply_updates(params, updates)
    assert not jnp.allclose(new_params["w"], params["w"])


def test_sgd_with_clipping_steps():
    tx = SGD(lr=0.1, momentum=0.9, max_grad_norm=1.0)
    params = {"w": jnp.zeros((2,))}
    state = tx.init(params)
    big_grads = {"w": jnp.full((2,), 100.0)}
    updates, state = tx.update(big_grads, state, params)
    # grad clipped to norm 1 then scaled by lr
    assert float(jnp.linalg.norm(updates["w"])) == pytest.approx(0.1, rel=1e-4)


def test_init_distributed_after_backend_is_noop(monkeypatch):
    """Once jax backends are up (always true inside the test process),
    init_distributed must not raise or attempt initialization — it reports
    the current (single-process) state."""
    import jax

    from sheeprl_tpu.fabric import init_distributed

    jax.devices()  # ensure backends are initialized
    assert init_distributed() is (jax.process_count() > 1)


def test_fabric_num_nodes_warns_single_host():
    import warnings as w

    from sheeprl_tpu.fabric import Fabric

    fabric = Fabric(devices=1, accelerator="cpu", num_nodes=2)
    with w.catch_warnings(record=True) as caught:
        w.simplefilter("always")
        fabric.launch(lambda f: None)
    assert any("single-host" in str(c.message) for c in caught)
