"""The reduction from a trace to busy time, self times and idle gaps."""

import os
import time

import pytest

from benchmarks import reduce


def test_union_merges_overlaps_and_keeps_gaps():
    seconds, merged = reduce.union_seconds([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert seconds == pytest.approx(3.0)
    assert merged == [(0.0, 2.0), (3.0, 4.0)]


def test_self_seconds_take_children_out_of_their_parent():
    events = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("fusion.2", 4.0, 6.0), ("fusion.1", 7.0, 8.0), ("copy", 11.0, 12.0)]
    assert reduce.self_seconds(events) == pytest.approx({"while": 4.0, "fusion.1": 4.0, "fusion.2": 2.0, "copy": 1.0})


def test_device_summary_averages_busy_over_the_chips_used():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("fusion", 0.0, 1.0), ("all-reduce.1", 1.0, 1.5)], "XLA Modules": [("jit_local_burst(1)", 0.0, 1.5)]},
        "/device:TPU:1": {"XLA Ops": [("fusion", 0.0, 0.5), ("all-reduce.1", 0.5, 1.5)], "XLA Modules": [("jit_local_burst(1)", 0.0, 1.5)]},
        "/host:CPU": {"main": [("Time/train_time", 0.0, 3.0)]},
    }
    out = reduce.device_summary(planes, window_s=4.0, n_devices=2)
    assert out["busy_s"] == pytest.approx(1.5) and out["start"] == 0.0
    # from the harness's opening mark on, and no longer than the window
    marked = dict(planes, **{"/host:CPU": {"main": [(reduce.WINDOW_OPEN_MARK, 0.25, 0.25)]}})
    clipped = reduce.device_summary(marked, window_s=1.0, n_devices=2)
    assert clipped["start"] == 0.25 and clipped["busy_s"] == pytest.approx(1.0)
    assert clipped["collective_s"] == pytest.approx((0.25 + 0.75) / 2)
    assert clipped["modules"] == {"jit_local_burst(1)": pytest.approx(1.0)}
    assert out["collective_s"] == pytest.approx(0.75)
    assert out["modules"] == {"jit_local_burst(1)": pytest.approx(1.5)}
    assert out["top_ops"][0] == ["fusion", pytest.approx(1.0)]
    assert reduce.device_summary({"/host:CPU": {}}, 1.0, 1) is None


def test_idle_gaps_go_to_the_innermost_host_span():
    busy = [(1.0, 2.0), (5.0, 6.0)]
    spans = [("Time/rollout_time", 2.0, 4.0), ("Time/env_interaction_time", 3.0, 3.5), ("Time/train_time", 4.0, 5.5)]
    gaps = dict(reduce.idle_gaps_by_span(busy, spans, 0.0, 7.0))
    assert gaps == pytest.approx({
        "Time/rollout_time": 1.5, "Time/env_interaction_time": 0.5, "Time/train_time": 1.0, "unattributed": 2.0,
    })


def test_a_device_event_is_named_by_its_operation():
    text = "%select_add_fusion.28 = f32[5120,12288]{1,0:T(8,128)} fusion(f32[5120,12288] %get-tuple-element.36196), kind=kOutput"
    assert reduce.short_name(text) == "select_add_fusion.28"
    assert reduce.short_name("Time/train_time") == "Time/train_time"
    assert reduce.short_name("all-reduce.1") == "all-reduce.1"


def test_a_device_that_is_not_in_the_table_has_no_peak():
    assert reduce.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        reduce.peak_flops("cpu")


def test_reduction_agrees_with_profile_data_on_a_recorded_trace(tmp_path):
    """Record a small trace here and hold ``read_planes``/``host_spans`` to a
    direct walk of ``jax.profiler.ProfileData`` over the same file."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    trace = reduce.DeviceTrace(str(tmp_path / "profile"))
    trace.start()
    with jax.profiler.TraceAnnotation("Time/train_time"):
        jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
        time.sleep(0.05)
    with jax.profiler.TraceAnnotation("Time/rollout_time"):
        time.sleep(0.02)
    trace.stop()
    planes = reduce.read_planes(trace.path())
    direct = {}
    for plane in ProfileData.from_file(trace.path()).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("Time/"):
                    direct[event.name] = direct.get(event.name, 0.0) + event.duration_ns / 1e9
    mine = {}
    for name, start, end in reduce.host_spans(planes):
        mine[name] = mine.get(name, 0.0) + (end - start)
    assert set(mine) == {"Time/train_time", "Time/rollout_time"}
    assert mine == pytest.approx(direct)
    assert mine["Time/train_time"] >= 0.05 and 0.02 <= mine["Time/rollout_time"] < 0.05
    # every event of every line, against the brute-force total
    for plane in ProfileData.from_file(trace.path()).planes:
        for line in plane.lines:
            total = sum(e.duration_ns for e in line.events) / 1e9
            got = sum(e - s for _n, s, e in planes[plane.name][line.name])
            assert len(planes[plane.name][line.name]) == len(list(line.events))
            assert got == pytest.approx(total)
    trace.discard()
    assert not os.path.exists(trace.directory)


def test_spans_are_put_on_the_host_clock(tmp_path):
    path = tmp_path / "spans.jsonl"
    path.write_text(
        '{"ph": "M", "name": "clock_sync"}\n'
        '{"name": "Time/train_time", "ph": "X", "ts": 1000000.0, "dur": 500000.0}\n'
        '{"name": "bench/sync", "ph": "i", "ts": 2000000.0}\n'
        '{"name": "Time/rollout_time", "ph": "X", "ts": 2500000.0, "dur": 250000.0}\n'
    )
    spans = reduce.read_spans(str(path), sync_at=50.0)
    assert spans == [("Time/train_time", pytest.approx(49.0), pytest.approx(49.5)),
                     ("Time/rollout_time", pytest.approx(50.5), pytest.approx(50.75))]
