"""The legs of a policy step, the device's wait for the host step and the idle
time no host span covers, on hand-built spans and planes."""

import os
import shutil

import pytest

import bench_tiny
from benchmarks import host_time, reduce, run
from benchmarks.manifest import ROOT, load_module

LEG_READERS = [f"collect.act_{leg}_ms_p50.{cell}" for leg in ("out", "back") for cell in ("loop", "learn")]
DEVICE_READERS = [f"device.{name}.{cell}" for name in ("host_wait_pct", "unattributed_idle_ms_per_cycle")
                  for cell in ("loop", "learn")]


def reader(name):
    return load_module(os.path.join(ROOT, "metrics", name + ".py")).read


class Recorder:
    def __init__(self, opened_at, closed_at):
        self.opened_at, self.closed_at = opened_at, closed_at


class Run:
    """What the readers ask of ``reduce.RunRecord``: spans on the host clock,
    the device's planes and their summary over the window, the window's cycles."""

    def __init__(self, spans=(), planes=None, bursts=2, opened_at=0.0, closed_at=20.0):
        self._spans, self._planes, self.bursts = list(spans), planes, bursts
        self.recorder = Recorder(opened_at, closed_at)

    def spans(self):
        return self._spans

    def planes(self):
        return self._planes

    def device_summary(self):
        if self._planes is None:
            return None
        return reduce.device_summary(self._planes, self.recorder.closed_at - self.recorder.opened_at, 1)


def test_a_burst_of_one_step_splits_its_rollout_into_three_legs():
    spans = [("Time/rollout_time", 10.0, 20.0), ("Time/act_host_step_time", 14.0, 17.0),
             ("Time/env_interaction_time", 14.5, 16.5)]
    assert host_time.step_legs(spans, 0.0, 100.0) == ([4.0], [3.0])


def test_in_a_burst_of_k_steps_the_gaps_between_host_steps_are_outbound_legs():
    steps = [(3.0, 5.0), (8.0, 10.0), (12.0, 15.0)]
    spans = [("Time/rollout_time", 0.0, 20.0)] + [("Time/act_host_step_time", s, e) for s, e in steps]
    outbound, back = host_time.step_legs(spans, 0.0, 100.0)
    assert outbound == [3.0, 3.0, 2.0] and back == [0.0, 0.0, 5.0]
    # the three legs of its steps tile the rollout span
    assert sum(outbound) + sum(e - s for s, e in steps) + sum(back) == pytest.approx(20.0)


def test_a_device_actor_step_leaves_from_its_decode():
    spans = [("Time/rollout_time", 0.0, 30.0),
             ("Time/act_decode_time", 0.1, 3.0), ("Time/act_host_step_time", 3.0, 8.0),
             ("Time/act_decode_time", 9.0, 12.0), ("Time/act_host_step_time", 12.5, 20.0)]
    outbound, back = host_time.step_legs(spans, 0.0, 100.0)
    assert outbound == pytest.approx([2.9, 3.5]) and back == pytest.approx([1.0, 10.0])


def test_only_rollouts_wholly_inside_the_window_count():
    spans = [("Time/rollout_time", -5.0, 5.0), ("Time/act_host_step_time", 1.0, 2.0),
             ("Time/rollout_time", 10.0, 20.0), ("Time/act_host_step_time", 11.0, 12.0),
             ("Time/rollout_time", 95.0, 105.0), ("Time/act_host_step_time", 96.0, 97.0)]
    assert host_time.step_legs(spans, 0.0, 100.0) == ([1.0], [8.0])


def test_the_leg_readers_read_milliseconds_and_nothing_without_the_host_step():
    spans = [("Time/rollout_time", 1.0, 1.010), ("Time/act_host_step_time", 1.004, 1.007),
             ("Time/rollout_time", 2.0, 2.020), ("Time/act_host_step_time", 2.012, 2.015)]
    run = Run(spans)
    for cell in ("loop", "learn"):
        # p50 of two steps: the mean of their legs
        assert reader(f"collect.act_out_ms_p50.{cell}")(run) == pytest.approx(8.0)
        assert reader(f"collect.act_back_ms_p50.{cell}")(run) == pytest.approx(4.0)
    bare = Run([sp for sp in spans if sp[0] == "Time/rollout_time"])
    assert all(reader(name)(bare) is None for name in LEG_READERS)


def test_the_wait_counts_only_a_recv_done_alone_under_a_host_step():
    ops = [
        ("while.1", 0.0, 10.0),  # the acting loop holds its wait: a parent, not a neighbour
        ("recv-done.5", 2.0, 6.0),
        ("fusion.1", 5.0, 7.0),  # overlaps the wait's last second
        ("recv-done.5", 8.0, 9.0),  # no host step is open
    ]
    assert host_time.host_wait_seconds(ops, [(1.0, 6.5)], 0.0, 10.0) == pytest.approx(3.0)
    assert host_time.host_wait_seconds(ops, [], 0.0, 10.0) == 0.0
    assert host_time.host_wait_seconds(ops, [(1.0, 6.5), (7.5, 9.5)], 0.0, 8.5) == pytest.approx(3.5)


def test_the_wait_reader_reads_the_first_chip_over_the_window():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("while.1", 0.0, 10.0), ("recv-done.5", 2.0, 6.0), ("fusion.1", 12.0, 18.0)]},
        "/device:TPU:1": {"XLA Ops": [("recv-done.9", 0.0, 20.0)]},
        "/host:CPU": {"callback": [("Time/act_host_step_time", 1.0, 7.0)], "main": [("Time/rollout_time", 0.0, 10.0)]},
    }
    for cell in ("loop", "learn"):
        assert reader(f"device.host_wait_pct.{cell}")(Run(planes=planes)) == pytest.approx(100.0 * 4.0 / 20.0)
    no_step = {**planes, "/host:CPU": {"main": [("Time/rollout_time", 0.0, 10.0)]}}
    assert reader("device.host_wait_pct.learn")(Run(planes=no_step)) is None


def test_unattributed_idle_counts_gaps_beyond_the_ten_largest():
    # twelve idle gaps of 2 s, each under a span of its own name, and 1 s that no span covers
    busy = [(3.0 * i, 3.0 * i + 1.0) for i in range(13)]
    spans = [(f"Time/part_{i}_time", 3.0 * i + 1.0, 3.0 * i + 3.0) for i in range(12)]
    end = 3.0 * 12 + 2.0
    named = dict(reduce.idle_gaps_by_span(busy, spans, 0.0, end))
    assert len(named) == 10 and "unattributed" not in named
    assert host_time.uncovered_idle_seconds(busy, spans, 0.0, end) == pytest.approx(1.0)
    # where the ten names hold it, both say the same
    few = spans[:3]
    assert host_time.uncovered_idle_seconds(busy, few, 0.0, end) == pytest.approx(
        dict(reduce.idle_gaps_by_span(busy, few, 0.0, end))["unattributed"])


def test_the_unattributed_reader_divides_by_the_window_cycles():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 0.0, 4.0), ("fusion.2", 10.0, 16.0)]},
        "/host:CPU": {"main": [("Time/train_time", 4.0, 7.0), ("bench/other", 7.0, 10.0)]},
    }
    # idle: 4-10 (3 s under a span) and 16-20: 7 s no span covers, over two cycles
    for cell in ("loop", "learn"):
        assert reader(f"device.unattributed_idle_ms_per_cycle.{cell}")(Run(planes=planes, bursts=2)) == pytest.approx(3500.0)


def test_the_spans_open_when_the_window_closed_come_from_the_span_file():
    """The profiler stops inside the closing rollout's host step and records
    none of its spans: the device readers take them from the span file, put on
    the trace's clock by the window's opening mark."""
    planes = {
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 5.0, 8.0), ("while.2", 11.0, 15.0), ("recv-done.3", 12.0, 15.0)]},
        "/host:CPU": {"main": [(reduce.WINDOW_OPEN_MARK, 5.0, 5.0), ("Time/train_time", 5.0, 8.0)]},
    }
    # on the host's clock the window is 100-110; the closing rollout opens at 105.5
    spans = [("Time/train_time", 100.0, 103.0), ("Time/rollout_time", 105.5, 111.0),
             ("Time/act_host_step_time", 106.5, 110.5)]
    run = Run(spans, planes, bursts=1, opened_at=100.0, closed_at=110.0)
    # idle 8-11 on the trace's clock, of which the rollout (10.5-16) covers the last half second
    assert reader("device.unattributed_idle_ms_per_cycle.learn")(run) == pytest.approx(2500.0)
    # the device waits 12-15 inside the host step (11.5-15.5)
    assert reader("device.host_wait_pct.learn")(run) == pytest.approx(30.0)
    recorded_only = Run([], planes, bursts=1, opened_at=100.0, closed_at=110.0)
    assert reader("device.unattributed_idle_ms_per_cycle.learn")(recorded_only) == pytest.approx(3000.0)
    assert reader("device.host_wait_pct.learn")(recorded_only) is None


@pytest.mark.parametrize("name", LEG_READERS + DEVICE_READERS)
def test_every_reader_reads_nothing_where_its_spans_or_its_device_are_absent(name):
    assert reader(name)(Run()) is None


def test_a_traced_tiny_run_reads_both_legs_and_leaves_out_the_device_readers(tmp_path):
    """The four readers of the ``learn`` cells added to the tiny benchmark: on
    the CPU the host-step span is read, and the readers of the device's planes
    are left out of the line."""
    root = str(tmp_path)
    manifest, cell = bench_tiny.write_tiny_benchmark(root)
    names = [n for n in LEG_READERS + DEVICE_READERS if n.endswith(".learn")]
    for name in names:
        shutil.copy(os.path.join(ROOT, "metrics", name + ".py"), os.path.join(root, "bench", "metrics"))
    manifest.data["per_layer"] += [{"name": n, "unit": "ms", "better": "lower", "source": "program_span",
                                    "layer": "test", "moves": "replay_steps_per_s"} for n in names]
    result = run.run_cell(cell, 9, 0.5, True, manifest=manifest, require_chip=False, accelerator="cpu")
    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    assert "device.host_wait_pct.learn" not in metrics and "device.unattributed_idle_ms_per_cycle.learn" not in metrics
    assert metrics["collect.act_out_ms_p50.learn"]["value"] > 0
    assert metrics["collect.act_back_ms_p50.learn"]["value"] >= 0
