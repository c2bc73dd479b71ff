"""Device-time profiler unit tests (sheeprl_tpu/obs/prof/): the xplane
parser against checked-in wire-format fixtures, the roofline verdict
classifier, the cost-analysis fallbacks, and the capture scheduler.

The fixtures are real protobuf wire bytes generated by an independent
hand-rolled encoder (``fixtures/make_mini_xplane.py``) — regenerate with
``python tests/test_obs/fixtures/make_mini_xplane.py``.
"""

import os

import pytest

from sheeprl_tpu.obs.prof.capture import StepProfiler, analyze_trace
from sheeprl_tpu.obs.prof.roofline import (
    cost_bytes,
    cost_of,
    detect_peaks,
    roofline_analyze,
)
from sheeprl_tpu.obs.prof.xplane import (
    find_xplane,
    load_xspace,
    phase_of,
    summarize,
    summarize_space,
)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
DEVICE_PB = os.path.join(FIXTURES, "mini.xplane.pb")
HOST_PB = os.path.join(FIXTURES, "mini_host.xplane.pb")


# -- parser golden ------------------------------------------------------------


def test_device_fixture_golden():
    s = summarize_space(load_xspace(DEVICE_PB))
    assert s["source"] == "device"
    # 'jit_train_step(1)' cleans to 'train_step' and classifies as train
    mod = s["modules"]["train_step"]
    assert mod == {"execs": 3, "total_ms": 12.0, "ms_per_exec": 4.0, "phase": "train"}
    assert s["train_module"] == "train_step"
    # window = first module start -> last module end (0..14 ms)
    assert s["window_ms"] == 14.0
    assert s["busy_frac"] == pytest.approx(12.0 / 14.0, abs=1e-3)
    assert s["steps_ms_total"] == pytest.approx(13.5)
    # stack-sweep self-time: fusion.1 (4 ms) minus nested fusion.2 (1 ms)
    assert s["top_ops"] == {"fusion.1": 3.0, "fusion.2": 1.0, "copy.3": 0.5}


def test_host_fixture_merges_nested_duplicate_dispatch_spans():
    s = summarize_space(load_xspace(HOST_PB))
    assert s["source"] == "host"
    # each dispatch is a nested near-duplicate PjitFunction pair (observed
    # jax CPU behavior) plus an Execute span the parser must ignore
    mod = s["modules"]["shmapped"]
    assert mod["execs"] == 2
    assert mod["ms_per_exec"] == pytest.approx(2.0)
    assert mod["phase"] == "train"
    assert s["top_ops"] == {}  # host planes carry no 'XLA Ops' line


def test_legacy_divide_by_n_keys():
    s = summarize(DEVICE_PB, n_steps=3)
    assert s["modules_us_per_step"] == pytest.approx(4000.0)  # 12 ms / 3
    assert s["steps_us_per_step"] == pytest.approx(4500.0)
    assert s["top_ops"]["fusion.1"] == pytest.approx(1000.0)  # 3 ms / 3 steps


def test_find_xplane_accepts_file_and_rejects_empty(tmp_path):
    assert find_xplane(DEVICE_PB) == DEVICE_PB
    with pytest.raises(FileNotFoundError):
        find_xplane(str(tmp_path))


def test_phase_mapping_heuristics():
    assert phase_of("shmapped") == "train"
    assert phase_of("local_step") == "train"
    assert phase_of("burst") == "train"
    assert phase_of("rollout_collect") == "rollout"
    assert phase_of("ring_gather") == "staging"
    assert phase_of("convert_element_type") == "other"


# -- roofline verdicts --------------------------------------------------------

PEAKS = {"label": "test", "peak_tflops": 100.0, "peak_gbps": 1000.0}


def test_verdict_unmeasured_without_device_time():
    out = roofline_analyze(1e12, 1e9, None, busy_frac=0.9, peaks=PEAKS)
    assert out["verdict"] == "unmeasured"
    assert out["mfu_pct"] is None
    assert out["ridge_intensity"] == pytest.approx(100.0)


def test_verdict_dispatch_bound_when_device_idles():
    # 30% busy: whatever the per-module utilization, the constraint is the
    # host dispatch path
    out = roofline_analyze(1e12, 1e9, 20.0, busy_frac=0.3, peaks=PEAKS)
    assert out["verdict"] == "dispatch-bound"
    assert out["mfu_pct"] == pytest.approx(50.0)  # still reported


def test_verdict_compute_bound_when_mfu_dominates():
    # 1e12 flops in 20 ms vs 100 TFLOP/s peak -> 50% MFU; 1e9 bytes in
    # 20 ms = 50 GB/s vs 1000 -> 5% bandwidth
    out = roofline_analyze(1e12, 1e9, 20.0, busy_frac=0.9, peaks=PEAKS)
    assert out["verdict"] == "compute-bound"
    assert out["achieved_gbps"] == pytest.approx(50.0)
    assert out["bandwidth_util_pct"] == pytest.approx(5.0)


def test_verdict_memory_bound_when_bandwidth_dominates():
    # 1e10 flops in 20 ms -> 0.5% MFU; 1e10 bytes in 20 ms = 500 GB/s -> 50%
    out = roofline_analyze(1e10, 1e10, 20.0, busy_frac=0.9, peaks=PEAKS)
    assert out["verdict"] == "memory-bound"
    assert out["arithmetic_intensity"] == pytest.approx(1.0)


def test_verdict_unknown_without_cost_analysis():
    out = roofline_analyze(None, None, 20.0, busy_frac=0.9, peaks=PEAKS)
    assert out["verdict"] == "unknown"


def test_detect_peaks_unlisted_device_has_no_peak_and_no_mfu():
    """A device outside DEVICE_PEAKS (the CPU these tests run on, or any
    unknown kind) gets no peak — so no MFU and no roofline share — never an
    estimate and never the v5e number by default."""
    from types import SimpleNamespace

    from sheeprl_tpu.obs.perf import mfu_pct
    from sheeprl_tpu.obs.prof.roofline import detect_link_peaks

    for peaks in (
        detect_peaks(),
        detect_peaks(device=SimpleNamespace(platform="tpu", device_kind="TPU v99x")),
    ):
        assert peaks["peak_tflops"] is None and peaks["peak_gbps"] is None
        assert "estimated" not in peaks
        assert mfu_pct(1e12, 10, 1.0, peaks["peak_tflops"]) is None
        out = roofline_analyze(1e9, 1e6, 2.0, busy_frac=0.9, peaks=peaks)
        assert out["mfu_pct"] is None and out["bandwidth_util_pct"] is None
        assert out["ridge_intensity"] is None and out["verdict"] == "unknown"
    assert detect_link_peaks()["link_gbps"] is None
    # the one table, keyed by device_kind: the attached chip reports itself
    # as "TPU v5 lite"
    v5e = detect_peaks(device=SimpleNamespace(platform="tpu", device_kind="TPU v5 lite"))
    assert (v5e["peak_tflops"], v5e["peak_gbps"]) == (197.0, 819.0)
    # an explicit override keeps working, on any device
    assert detect_peaks(peak_tflops=197.0)["peak_tflops"] == 197.0
    assert mfu_pct(1e12, 10, 1.0, 197.0) == pytest.approx(5.076, abs=1e-3)


# -- cost-analysis fallbacks --------------------------------------------------


class _FakeCompiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        return self._ca


class _FakeLowered:
    def __init__(self, ca):
        self._ca = ca

    def compile(self):
        return _FakeCompiled(self._ca)


class _FakeJit:
    def __init__(self, ca):
        self._ca = ca

    def lower(self, *args, **kwargs):
        return _FakeLowered(self._ca)


def test_cost_of_reads_flops_and_bytes_list_or_dict():
    # some backends return a list of per-computation dicts
    assert cost_of(_FakeJit([{"flops": 10.0, "bytes accessed": 4.0}])) == {
        "flops": 10.0,
        "bytes_accessed": 4.0,
    }
    assert cost_of(_FakeJit({"flops": 7.0})) == {"flops": 7.0, "bytes_accessed": 0.0}


def test_cost_of_none_when_backend_has_no_cost_model():
    class Broken:
        def lower(self, *a, **k):
            raise NotImplementedError("no cost model")

    assert cost_of(Broken()) is None
    assert cost_bytes(_FakeCompiled({"bytes accessed": 3.0})) == 3.0


def test_register_train_cost_scales_by_dispatches_and_world_size():
    from sheeprl_tpu.obs.perf import register_train_cost
    from sheeprl_tpu.obs.telemetry import Telemetry

    tel = Telemetry({})
    register_train_cost(
        tel, _FakeJit([{"flops": 10.0, "bytes accessed": 4.0}]),
        world_size=2, dispatches_per_step=3,
    )
    assert tel.flops_per_train_step == pytest.approx(15.0)
    assert tel.bytes_per_train_step == pytest.approx(6.0)
    assert tel.dispatches_per_train_step == 3
    assert not tel.needs_train_flops()

    # a backend without cost analysis records the attempt, stays None
    tel2 = Telemetry({})
    register_train_cost(tel2, _FakeJit(None))
    assert tel2.flops_per_train_step is None
    assert not tel2.needs_train_flops()  # probed once, not every update

    register_train_cost(None, _FakeJit(None))  # telemetry-off path is a no-op


# -- analyze_trace ------------------------------------------------------------


def test_analyze_trace_maps_executions_to_train_step_units():
    # fixture: 4 ms/exec; cost registered per unit with ws=1, dps=2 -> the
    # per-unit device time is 8 ms and per-exec cost is cost/2
    rec = analyze_trace(
        DEVICE_PB,
        flops_per_step=8e8,
        bytes_per_step=8e6,
        world_size=1,
        dispatches_per_step=2,
        peaks=PEAKS,
    )
    assert rec["train_module"] == "train_step"
    assert rec["device_ms_per_step"] == pytest.approx(8.0)
    # per-exec: 4e8 flops / 4 ms / 100 TFLOP/s = 0.1% MFU
    assert rec["mfu_device_pct"] == pytest.approx(0.1)
    assert rec["achieved_gbps"] == pytest.approx(1.0)  # 4e6 B / 4 ms
    assert rec["roofline_verdict"] == "compute-bound"
    assert rec["modules"]["train_step"]["execs"] == 3


# -- capture scheduler --------------------------------------------------------


def test_step_profiler_schedules_and_caps_captures(monkeypatch):
    prof = StepProfiler({"every_n_steps": 100, "max_captures": 2}, telemetry=None)
    started = []
    monkeypatch.setattr(
        prof, "_start", lambda step, ws: (started.append(step), _fake_active(prof))[0]
    )
    monkeypatch.setattr(prof, "_finalize", lambda: _clear_active(prof, finalized))
    finalized = []

    prof.tick(50)  # below the first schedule point
    assert started == []
    prof.tick(120)  # crosses 100 -> capture 1
    assert started == [120]
    prof.tick(130)  # active -> finalize, no new start
    assert finalized == [1]
    prof.captures = 1
    prof.tick(250)  # crosses 200 -> capture 2
    assert started == [120, 250]
    prof.tick(260)
    prof.captures = 2
    prof.tick(500)  # max_captures reached -> no more starts
    assert started == [120, 250]


def _fake_active(prof):
    prof._active = {"dir": "x", "step": 0, "world_size": 1, "timer": None, "stopped": True}


def _clear_active(prof, finalized):
    prof._active = None
    finalized.append(1)


def test_record_prof_never_downgrades_a_measured_capture_to_null():
    from sheeprl_tpu.obs.telemetry import Telemetry

    tel = Telemetry({})
    tel.record_prof({"device_ms_per_step": 4.0, "roofline_verdict": "compute-bound"})
    # a later window that caught no train execution (run-end close(), an
    # anomaly capture during pure acting) must not erase the evidence
    tel.record_prof({"device_ms_per_step": None, "roofline_verdict": "unmeasured"})
    assert tel._prof_last["device_ms_per_step"] == 4.0
    # but a later MEASURED capture does replace it
    tel.record_prof({"device_ms_per_step": 5.0, "roofline_verdict": "memory-bound"})
    assert tel._prof_last["device_ms_per_step"] == 5.0


def test_step_profiler_disabled_is_inert():
    prof = StepProfiler({}, telemetry=None)
    assert not prof.enabled
    prof.tick(10**9)  # no jax import, no trace, nothing
    prof.close()
