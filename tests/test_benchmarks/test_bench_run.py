"""A whole run of a cell, less the look for a chip, at tiny widths on the CPU:
the result line's keys, the window, ``correct`` on a sound run and on each
fault planted in the timed path, and the refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

import bench_tiny
from benchmarks import run

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return bench_tiny.write_tiny_benchmark(str(tmp_path_factory.mktemp("bench")))


def drive(tiny, **kw):
    manifest, cell = tiny
    return run.run_cell(cell, kw.pop("seed", 2**31 + 5), 0.5, kw.pop("trace", False), manifest=manifest,
                        require_chip=False, accelerator="cpu", **kw)


def test_a_sound_run_is_correct_and_its_line_has_the_contracts_keys(tiny):
    result = drive(tiny)
    assert list(result)[:5] == RESULT_KEYS and list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"replay_steps_per_s", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    window = result["window"]
    assert window["compiles_in_window"] == 0 and window["cycles"] >= 1
    assert window["seconds"] >= 0.5 and window["grad_steps"] == 4 * window["cycles"]
    # the rate is the window's work over its measured seconds
    rate = window["grad_steps"] * 8 * 4 / window["seconds"]
    assert result["metrics"]["replay_steps_per_s"]["value"] == pytest.approx(rate)
    assert result["attempted"] == window["grad_steps"] and result["failed"] == 0
    assert all(set(row) == {"value", "limit"} for row in result["checks"].values())
    json.dumps(result)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "altered_row"])
def test_a_fault_in_the_timed_path_comes_out_as_not_correct(tiny, fault):
    result = drive(tiny, fault=fault, seed=7)
    assert result["correct"] is False, result["checks"]
    failed = {k for k, row in result["checks"].items() if not row["value"] <= row["limit"]}
    expected = {"state_unchanged": "update_gap", "half_batch": "grad_gap", "altered_row": "staging_bad_rows"}
    assert expected[fault] in failed, result["checks"]


def test_a_traced_run_reads_the_per_layer_metrics_a_cell_lists(tiny):
    """The dummy metric exists only as a file and a manifest entry of the
    temporary benchmark; a reader with nothing to read is left out."""
    result = drive(tiny, trace=True, seed=9)
    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    assert metrics["dummy.cycles"] == {"value": 2.0, "unit": "cycles"}
    assert metrics["entry.compiles_in_window.learn"]["value"] == 0.0
    assert metrics["train.host_ms_per_burst.learn"]["value"] > 0
    assert "device.idle_pct.learn" not in metrics  # no operation ran on a chip here
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result


def test_the_command_exits_non_zero_with_no_result_where_there_is_no_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.BENCH, "run.py"), "--workload", "dv3-XL.learn",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=bench_tiny.REPO,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
