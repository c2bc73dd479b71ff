"""Observability tooling tests: the clock-aligned trace merger
(``tools/trace_view.py``), the bench-round regression differ
(``tools/bench_compare.py``), and the extended telemetry lint's ad-hoc
wall-clock rule."""

import importlib.util
import json
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "tools")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(TOOLS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- trace_view ---------------------------------------------------------------


def _write_jsonl(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_trace_view_merges_and_clock_aligns_rank_files(tmp_path):
    trace_view = _load_tool("trace_view")
    tel = tmp_path / "telemetry"
    tel.mkdir()
    # rank 0 started at unix t=1000 (its perf_counter origin), rank 1 at
    # t=1002.5 — rank 1's local ts must shift by +2.5 s on the merged line
    _write_jsonl(
        tel / "trace.jsonl",
        [
            {"ph": "M", "name": "clock_sync", "pid": 0, "args": {"unix_ts": 1000.0}},
            {"name": "a", "cat": "env", "ph": "X", "ts": 100.0, "dur": 5.0, "pid": 0, "tid": 1},
            {"name": "b", "cat": "train", "ph": "X", "ts": 4e6, "dur": 5.0, "pid": 0, "tid": 1},
        ],
    )
    _write_jsonl(
        tel / "trace_rank1.jsonl",
        [
            {"ph": "M", "name": "clock_sync", "pid": 1, "args": {"unix_ts": 1002.5}},
            {"name": "c", "cat": "env", "ph": "X", "ts": 100.0, "dur": 5.0, "pid": 1, "tid": 9},
        ],
    )
    out = tmp_path / "trace.json"
    rc = trace_view.main([str(tmp_path), "-o", str(out)])
    assert rc == 0
    events = json.load(open(out))["traceEvents"]
    assert [e["name"] for e in events] == ["a", "c", "b"]  # sorted, aligned
    by_name = {e["name"]: e for e in events}
    assert by_name["a"]["ts"] == 100.0  # earliest tracer keeps its origin
    assert by_name["c"]["ts"] == pytest.approx(100.0 + 2.5e6)
    assert not any(e.get("name") == "clock_sync" for e in events)


def test_trace_view_single_file_without_anchor_passes_through(tmp_path):
    trace_view = _load_tool("trace_view")
    path = tmp_path / "trace.jsonl"
    _write_jsonl(path, [{"name": "a", "ph": "X", "ts": 7.0, "dur": 1.0}])
    out = tmp_path / "out.json"
    assert trace_view.main([str(path), "-o", str(out)]) == 0
    events = json.load(open(out))["traceEvents"]
    assert events == [{"name": "a", "ph": "X", "ts": 7.0, "dur": 1.0}]


# -- bench_compare ------------------------------------------------------------


def _write_round(repo, k, lines):
    tail = "\n".join(json.dumps(line) for line in lines)
    with open(os.path.join(repo, f"BENCH_r{k:02d}.json"), "w") as f:
        json.dump({"n": k, "cmd": "bench", "rc": 0, "tail": tail}, f)


def test_bench_compare_flags_regressions_by_unit_direction(tmp_path, capsys):
    bench_compare = _load_tool("bench_compare")
    _write_round(
        tmp_path,
        1,
        [
            {"metric": "ppo", "value": 10.0, "unit": "s"},
            {"metric": "dv3", "value": 50.0, "unit": "steps/s"},
            {"metric": "sac", "value": 100.0, "unit": "s"},
        ],
    )
    _write_round(
        tmp_path,
        2,
        [
            {"metric": "ppo", "value": 12.0, "unit": "s"},  # 20% slower: flag
            {"metric": "dv3", "value": 48.0, "unit": "steps/s"},  # 4%: fine
            {"metric": "sac", "value": 95.0, "unit": "s"},  # faster: fine
        ],
    )
    rc = bench_compare.main(["--dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION ppo" in out and "SLOWER" in out
    assert "dv3" in out and "REGRESSION dv3" not in out
    assert "REGRESSION sac" not in out


def test_bench_compare_uses_last_occurrence_and_tolerates_torn_tail(tmp_path, capsys):
    bench_compare = _load_tool("bench_compare")
    _write_round(
        tmp_path,
        4,
        [
            {"metric": "ppo", "value": 10.0, "unit": "s"},
            {"metric": "dv1", "value": 5.0, "unit": "s"},
        ],
    )
    # bench.py re-prints the matrix at the end: the LAST ppo line wins; the
    # tail may also start mid-line (driver truncation) and carry skip lines
    tail_lines = [
        '{"metric": "ppo", "val',  # torn first line
        json.dumps({"metric": "ppo", "value": 99.0, "unit": "s"}),
        json.dumps({"metric": "dv1", "value": None, "skipped": "budget"}),
        json.dumps({"metric": "ppo", "value": 10.5, "unit": "s"}),
    ]
    with open(os.path.join(tmp_path, "BENCH_r05.json"), "w") as f:
        json.dump({"n": 5, "tail": "\n".join(tail_lines)}, f)
    rc = bench_compare.main(["--dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0  # 10.0 -> 10.5 is 5%, below threshold
    assert "skipped" in out
    assert bench_compare.main(["--dir", str(tmp_path), "--threshold", "0.01"]) == 1


def test_bench_compare_threshold_is_exact_at_documented_slowdown(tmp_path, capsys):
    """'>10% slowdown flagged' must mean new = 1.1x old crosses the line —
    not the ~11.1% the inverted-ratio formulation would require."""
    bench_compare = _load_tool("bench_compare")
    _write_round(tmp_path, 1, [{"metric": "ppo", "value": 100.0, "unit": "s"}])
    _write_round(tmp_path, 2, [{"metric": "ppo", "value": 110.5, "unit": "s"}])
    assert bench_compare.main(["--dir", str(tmp_path)]) == 1
    assert "REGRESSION" in capsys.readouterr().out


def test_bench_compare_with_fewer_than_two_rounds_is_a_noop(tmp_path):
    bench_compare = _load_tool("bench_compare")
    assert bench_compare.main(["--dir", str(tmp_path)]) == 0


def test_bench_compare_diffs_profiled_device_time_and_mfu(tmp_path, capsys):
    """The roofline sub-metrics ride the evidence lines unit-directionally:
    device_ms_per_step is lower-better, mfu_pct higher-better — a line whose
    wall-clock held steady but whose profiled device time bloated >10% must
    still flag."""
    bench_compare = _load_tool("bench_compare")
    _write_round(
        tmp_path,
        1,
        [
            {"metric": "dv3", "value": 50.0, "unit": "steps/s",
             "device_ms_per_step": 10.0, "mfu_pct": 30.0},
            {"metric": "sac", "value": 20.0, "unit": "s",
             "telemetry": {"device_ms_per_step": 4.0, "mfu_device_pct": 12.0}},
        ],
    )
    _write_round(
        tmp_path,
        2,
        [
            # wall rate unchanged, device time 20% slower + MFU 20% lower
            {"metric": "dv3", "value": 50.0, "unit": "steps/s",
             "device_ms_per_step": 12.0, "mfu_pct": 24.0},
            # telemetry-folded variant improves: no flag
            {"metric": "sac", "value": 20.0, "unit": "s",
             "telemetry": {"device_ms_per_step": 3.8, "mfu_device_pct": 13.0}},
        ],
    )
    rc = bench_compare.main(["--dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION dv3.device_ms_per_step" in out
    assert "REGRESSION dv3.mfu_pct" in out
    assert "REGRESSION sac" not in out
    assert "telemetry.device_ms_per_step" in out  # diffed, just not flagged


# -- lint_telemetry ad-hoc clock rule ----------------------------------------


def test_lint_flags_ad_hoc_clock_reads_under_any_alias(tmp_path):
    lint = _load_tool("lint_telemetry")
    bad = tmp_path / "bad_algo.py"
    bad.write_text(
        "import time\n"
        "import time as _time\n"
        "from time import perf_counter as pc\n"
        "def loop():\n"
        "    t0 = time.time()\n"
        "    t1 = _time.perf_counter()\n"
        "    t2 = pc()\n"
        "    return t0, t1, t2\n"
    )
    findings = lint.lint_file(str(bad))
    assert len(findings) == 3
    assert all("ad-hoc" in message for _, message in findings)
    assert {line for line, _ in findings} == {5, 6, 7}


def test_lint_allows_span_scopes_and_docstring_mentions(tmp_path):
    lint = _load_tool("lint_telemetry")
    good = tmp_path / "good_algo.py"
    good.write_text(
        '"""Uses time.perf_counter() only in prose."""\n'
        "from sheeprl_tpu.obs import span\n"
        "def loop(rb, step_data):\n"
        "    with span('Time/train_time', phase='train'):\n"
        "        with span('Time/replay_add_time', phase='store'):\n"
        "            rb.add(step_data)\n"
    )
    assert lint.lint_file(str(good)) == []


def test_repo_algos_pass_the_extended_lint():
    lint = _load_tool("lint_telemetry")
    assert lint.main() == 0
