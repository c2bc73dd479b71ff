"""End-to-end telemetry: a short PPO run with ``metric.telemetry.enabled=true``
must produce a valid Chrome trace-event JSONL, at least one live snapshot
(``telemetry/live.json`` with rolling rates and per-phase percentiles), and a
``telemetry.json`` with the headline keys (the ISSUE's acceptance criteria);
a crashing entrypoint must still leave a ``telemetry.json`` recording the
crash; and the config group must compose."""

import glob
import json
import os

import jax
import pytest

from sheeprl_tpu import cli
from sheeprl_tpu.config.engine import compose


def test_metric_telemetry_group_composes():
    cfg = compose("config", overrides=["exp=ppo", "env=dummy", "metric=telemetry"])
    assert cfg.metric.telemetry.enabled is True
    assert cfg.metric.telemetry.health.nan_guard is True
    # live-plane knobs ride the same group
    assert cfg.metric.telemetry.live_interval_s == 30.0
    assert cfg.metric.telemetry.serve_port == 0
    assert cfg.metric.telemetry.histograms is True
    assert cfg.metric.telemetry.flight.enabled is True
    assert cfg.metric.telemetry.flight.slow_span_factor == 8.0
    # and the default stays off
    cfg = compose("config", overrides=["exp=ppo", "env=dummy"])
    assert cfg.metric.telemetry.enabled is False


def test_ppo_run_with_telemetry_writes_trace_and_summary(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cli.run(
        [
            "exp=ppo",
            "env=gym",
            "env.id=CartPole-v1",
            "env.sync_env=True",
            "env.capture_video=False",
            "env.num_envs=2",
            "total_steps=128",
            "algo.rollout_steps=8",
            "per_rank_batch_size=8",
            "algo.update_epochs=1",
            "algo.run_test=False",
            "fabric.devices=1",
            "fabric.accelerator=cpu",
            "buffer.memmap=False",
            "checkpoint.every=1000000",
            "checkpoint.save_last=False",
            "metric.log_every=32",
            "metric.telemetry.enabled=true",
            "metric.telemetry.poll_interval_s=0.2",
            "metric.telemetry.live_interval_s=0.2",
            f"root_dir={tmp_path}/logs",
            "run_name=telemetry_e2e",
        ]
    )

    (summary_path,) = glob.glob(
        os.path.join("logs", "runs", f"{tmp_path}/logs", "telemetry_e2e", "*", "telemetry.json")
    )
    summary = json.load(open(summary_path))
    for key in ("sps", "mfu", "bytes_staged_h2d", "recompiles", "peak_hbm_bytes"):
        assert key in summary, key
    # the summary names the device of the Fabric mesh it was measured on
    assert (summary["platform"], summary["device_kind"], summary["device_count"]) == (
        "cpu",
        jax.devices("cpu")[0].device_kind,
        1,
    )
    assert summary["policy_steps"] == 128
    assert summary["train_steps"] >= 1
    assert summary["sps"] > 0
    assert summary["bytes_staged_h2d"] > 0  # the PPO batch staging was counted
    assert summary["recompiles"] >= 1  # at least the update program compiled
    assert summary["flops_per_train_step"]  # cost-analysis MFU plumbing ran
    assert summary["crashed"] is False
    # per-phase percentiles from the streaming histograms
    for phase in ("Time/train_time", "Time/env_interaction_time"):
        pct = summary["phase_percentiles"][phase]
        assert pct["count"] >= 1
        assert pct["p50_ms"] is not None and pct["p50_ms"] <= pct["p99_ms"]

    # the live plane produced at least one atomic snapshot with rolling
    # rates, percentiles, and watchdog beat ages (the acceptance criterion)
    (live_path,) = glob.glob(
        os.path.join(os.path.dirname(summary_path), "telemetry", "live.json")
    )
    live = json.load(open(live_path))
    assert live["policy_steps"] == 128
    assert "sps" in live["rolling"] and "window_s" in live["rolling"]
    assert live["phase_percentiles"]["Time/train_time"]["count"] >= 1
    assert "watchdog_beat_age_s" in live
    assert not glob.glob(os.path.join(os.path.dirname(live_path), "live.json.tmp*"))

    (trace_path,) = glob.glob(
        os.path.join(os.path.dirname(summary_path), "telemetry", "trace.jsonl")
    )
    events = [json.loads(line) for line in open(trace_path) if line.strip()]
    complete = [e for e in events if e.get("ph") == "X"]
    names = {e["name"] for e in complete}
    assert {"Time/env_interaction_time", "Time/stage_h2d_time", "Time/train_time"} <= names
    for e in complete:
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}

    # telemetry must be torn down after the run (cli finalizes)
    from sheeprl_tpu.obs.spans import get_tracer
    from sheeprl_tpu.obs.telemetry import get_telemetry

    assert get_telemetry() is None
    assert get_tracer() is None


def test_sac_profiled_run_lands_device_ms_in_telemetry(tmp_path, monkeypatch):
    """In-run device profiling end-to-end (obs/prof): a SAC CPU run with
    ``metric.telemetry.profile.every_n_steps`` set must capture an xplane
    window at a log boundary, auto-parse it (CPU host-plane fallback), and
    land ``device_ms_per_step`` + a roofline verdict in telemetry.json plus
    a per-capture artifact under telemetry/prof/."""
    monkeypatch.chdir(tmp_path)
    cli.run(
        [
            "exp=sac",
            "env=gym",
            "env.id=Pendulum-v1",
            "env.sync_env=True",
            "env.capture_video=False",
            "env.num_envs=1",
            "dry_run=False",
            "total_steps=64",
            "per_rank_batch_size=4",
            "algo.learning_starts=2",
            "algo.hidden_size=8",
            "algo.run_test=False",
            "fabric.devices=1",
            "fabric.accelerator=cpu",
            "buffer.size=128",
            "buffer.memmap=False",
            "checkpoint.every=1000000",
            "checkpoint.save_last=False",
            "metric.log_every=16",
            "metric.telemetry.enabled=true",
            "metric.telemetry.live_interval_s=0",
            "metric.telemetry.poll_interval_s=0",
            "metric.telemetry.profile.every_n_steps=8",
            f"root_dir={tmp_path}/logs",
            "run_name=prof_e2e",
        ]
    )

    (summary_path,) = glob.glob(
        os.path.join("logs", "runs", f"{tmp_path}/logs", "prof_e2e", "*", "telemetry.json")
    )
    summary = json.load(open(summary_path))
    assert summary["prof_captures"] >= 1
    assert summary["device_ms_per_step"] is not None
    assert summary["device_ms_per_step"] > 0
    # the CPU has no entry in DEVICE_PEAKS: the cost side registers, but no
    # MFU and no compute/memory share is produced for it — not measured
    assert summary["roofline_verdict"] in ("dispatch-bound", "unknown")
    assert summary["flops_per_train_step"]
    assert summary["bytes_per_train_step"]
    assert summary["mfu_device_pct"] is None
    assert summary["mfu"] is None and summary["mfu_peak_tflops"] is None
    prof = summary["prof"]
    assert prof["source"] in ("host", "device")
    assert prof["train_module"]  # the SAC train program was attributed
    # per-capture artifact next to the trace
    artifacts = glob.glob(
        os.path.join(os.path.dirname(summary_path), "telemetry", "prof", "capture_*.json")
    )
    assert artifacts, "expected a telemetry/prof/capture_<step>.json artifact"
    # the summary holds the LAST capture; glob order is filesystem-dependent
    latest = max(artifacts, key=lambda p: int(p.rsplit("_", 1)[1].split(".")[0]))
    record = json.load(open(latest))
    assert record["device_ms_per_step"] == summary["device_ms_per_step"]

    from sheeprl_tpu.obs.telemetry import get_telemetry

    assert get_telemetry() is None  # torn down


def test_crash_path_records_exception_in_telemetry_json(tmp_path, monkeypatch):
    """When the entrypoint raises, the finally-path finalize must still write
    telemetry.json, with ``crashed: true`` and the exception type next to the
    partial counters (the summary path is passed explicitly because the
    crash may happen before the run dir exists)."""
    monkeypatch.chdir(tmp_path)
    summary_path = tmp_path / "crash_telemetry.json"
    with pytest.raises(Exception) as excinfo:
        cli.run(
            [
                "exp=ppo",
                "env=gym",
                "env.id=DefinitelyNotAGymEnv-v0",  # raises at env creation
                "env.capture_video=False",
                "fabric.devices=1",
                "fabric.accelerator=cpu",
                "buffer.memmap=False",
                "metric.telemetry.enabled=true",
                "metric.telemetry.poll_interval_s=0",
                f"metric.telemetry.summary_path={summary_path}",
                f"root_dir={tmp_path}/logs",
                "run_name=crash_e2e",
            ]
        )
    summary = json.load(open(summary_path))
    assert summary["crashed"] is True
    assert type(excinfo.value).__name__ in summary["exception"]
    # partial counters are still present and well-formed
    assert summary["run_wall_s"] > 0
    assert "bytes_staged_h2d" in summary

    # and the telemetry was torn down despite the crash
    from sheeprl_tpu.obs.telemetry import get_telemetry

    assert get_telemetry() is None


def test_run_without_telemetry_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cli.run(
        [
            "dry_run=True",
            "exp=ppo",
            "env=gym",
            "env.id=CartPole-v1",
            "env.sync_env=True",
            "env.capture_video=False",
            "env.num_envs=2",
            "algo.rollout_steps=4",
            "per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.run_test=False",
            "fabric.devices=1",
            "fabric.accelerator=cpu",
            "buffer.memmap=False",
            "checkpoint.every=1000000",
            "metric.log_level=0",
            f"root_dir={tmp_path}/logs",
            "run_name=no_telemetry",
        ]
    )
    assert not glob.glob(os.path.join("logs", "runs", "**", "telemetry.json"), recursive=True)
    assert not glob.glob(os.path.join("logs", "runs", "**", "trace.jsonl"), recursive=True)
    assert not glob.glob(os.path.join("logs", "runs", "**", "live.json"), recursive=True)
    assert not glob.glob(os.path.join("logs", "runs", "**", "flight_*.json"), recursive=True)
