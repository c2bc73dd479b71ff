"""Span/trace-writer unit tests: JSONL schema round-trip and the
timer-registry layering (including the concurrent-reset path the decoupled
algorithms exercise, utils/timer.py:10-13)."""

import json
import threading
import time

import pytest

from sheeprl_tpu.obs.spans import TraceWriter, current_span, set_tracer, span
from sheeprl_tpu.utils.metric import SumMetric
from sheeprl_tpu.utils.timer import timer


@pytest.fixture(autouse=True)
def _clean_timer_registry():
    timer.reset()
    yield
    timer.reset()


def _read_events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_trace_jsonl_schema_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    writer = TraceWriter(path, xla_annotations=False)
    set_tracer(writer)
    try:
        with span("Time/env_interaction_time", phase="env"):
            time.sleep(0.01)
        with span("Time/train_time", phase="train"):
            pass
        writer.counter("hbm_bytes_in_use", {"0": 123.0})
        writer.instant("stall", args={"role": "player"})
    finally:
        set_tracer(None)
        writer.close()

    events = _read_events(path)
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {
        "Time/env_interaction_time",
        "Time/train_time",
    }
    for e in complete:
        # the complete-event subset of the Chrome trace-event format
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
        assert e["dur"] >= 0 and e["ts"] >= 0
    env = next(e for e in complete if e["cat"] == "env")
    assert env["dur"] >= 10_000 * 0.5  # slept 10ms, µs scale
    assert any(e["ph"] == "C" and e["args"] == {"0": 123.0} for e in events)
    assert any(e["ph"] == "i" and e["name"] == "stall" for e in events)
    # thread-name metadata emitted once per thread, plus the one clock_sync
    # wall-clock anchor tools/trace_view.py aligns per-rank files on
    metas = [e for e in events if e["ph"] == "M"]
    assert sum(e["name"] == "thread_name" for e in metas) == 1
    syncs = [e for e in metas if e["name"] == "clock_sync"]
    assert len(syncs) == 1 and syncs[0]["args"]["unix_ts"] > 0


def test_span_accumulates_into_timer_registry(tmp_path):
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    try:
        with span("Time/train_time", SumMetric(sync_on_compute=False), phase="train"):
            time.sleep(0.005)
    finally:
        set_tracer(None)
        writer.close()
    computed = timer.compute()
    assert computed["Time/train_time"] >= 0.004


def test_span_without_tracer_is_plain_timer():
    with span("Time/train_time"):
        pass
    assert "Time/train_time" in timer.compute()


def test_span_survives_concurrent_registry_reset(tmp_path):
    """The decoupled player times env interaction while the trainer calls
    ``timer.compute()``; a span whose registry entry vanished mid-scope must
    re-register on exit instead of raising (utils/timer.py:10-13)."""
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    entered = threading.Event()
    release = threading.Event()
    errors = []

    def scoped():
        try:
            with span("Time/env_interaction_time", phase="env"):
                entered.set()
                release.wait(timeout=5)
        except Exception as exc:  # pragma: no cover - the regression itself
            errors.append(exc)

    worker = threading.Thread(target=scoped)
    worker.start()
    try:
        assert entered.wait(timeout=5)
        timer.compute()  # concurrent reset: wipes the in-flight scope's entry
        release.set()
        worker.join(timeout=5)
        assert not errors
        # the scope re-registered and recorded its elapsed time
        assert timer.compute()["Time/env_interaction_time"] > 0
    finally:
        set_tracer(None)
        writer.close()
    events = _read_events(writer.path)
    assert any(
        e["ph"] == "X" and e["name"] == "Time/env_interaction_time" for e in events
    )


def test_disabled_timer_still_emits_trace_events(tmp_path):
    """metric.log_level=0 disables the rate timers, but an active tracer
    (telemetry explicitly on) still sees the phases."""
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    timer.disabled = True
    try:
        with span("Time/train_time", phase="train"):
            pass
    finally:
        timer.disabled = False
        set_tracer(None)
        writer.close()
    assert timer.compute() == {}
    events = _read_events(writer.path)
    assert any(e.get("name") == "Time/train_time" for e in events)


# -- what caused a span, and the cycle it belongs to ---------------------------


def test_span_events_carry_parent_and_burst(tmp_path):
    """``args.parent`` is the enclosing open span on the same thread (null at
    the top, and on another thread), ``args.burst`` the run counter
    ``train_bursts`` when the span opened (null with no counters)."""
    from sheeprl_tpu.obs import counters as obs_counters

    def staged():
        with span("Time/stage_h2d_time", phase="stage_h2d"):
            pass

    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    run_counters = obs_counters.Counters()
    try:
        with span("Time/rollout_time", phase="rollout"):
            pass  # no counters installed yet
        obs_counters.install(run_counters)
        obs_counters.add_train_burst(steps=4, dispatches=1)
        with span("Time/train_time", phase="train"):
            with span("Time/train_dispatch_time", phase="train"):
                obs_counters.add_train_burst(steps=4, dispatches=1)
            with span("Time/publish_time", phase="publish"):
                worker = threading.Thread(target=staged)
                worker.start()
                worker.join(timeout=5)
    finally:
        obs_counters.install(None)
        set_tracer(None)
        writer.close()
    args = {e["name"]: e["args"] for e in _read_events(writer.path) if e["ph"] == "X"}
    assert args["Time/rollout_time"] == {"parent": None, "burst": None}
    assert args["Time/train_time"] == {"parent": None, "burst": 1}
    assert args["Time/train_dispatch_time"] == {"parent": "Time/train_time", "burst": 1}
    # opened after the dispatch counted its burst; the stack popped the dispatch
    assert args["Time/publish_time"] == {"parent": "Time/train_time", "burst": 2}
    # a pool thread's span has no parent on its own thread
    assert args["Time/stage_h2d_time"] == {"parent": None, "burst": 2}


def test_span_without_tracer_keeps_no_stack_and_makes_no_jax_call(monkeypatch):
    """Telemetry off: a span (and the compile-key scope) is a timer and
    nothing else — no stack of open spans, no call into jax."""
    import jax

    from sheeprl_tpu.obs import spans as spans_mod

    def refuse(*_a, **_k):
        raise AssertionError("a jax call with no tracer installed")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.config, "update", refuse)
    spans_mod._OPEN.__dict__.pop("stack", None)
    with span("Time/train_time", phase="train"), spans_mod.scoped_compile_key():
        with span("Time/train_dispatch_time", phase="train"):
            pass
    assert not hasattr(spans_mod._OPEN, "stack")
    assert set(timer.compute()) == {"Time/train_time", "Time/train_dispatch_time"}


def test_a_span_run_on_another_thread_names_the_span_that_caused_it(tmp_path):
    """Work a caller hands to another thread (a dispatched program's host
    callback) carries the caller's open span as its parent; the spans it
    opens itself are its children, on its own thread's stack."""
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=False)
    set_tracer(writer)
    try:
        assert current_span() is None
        with span("Time/rollout_time", phase="rollout"):
            caller = current_span()

            def host_step():
                with span("Time/act_host_step_time", phase="rollout", parent=caller):
                    with span("Time/env_interaction_time", phase="env"):
                        pass

            worker = threading.Thread(target=host_step)
            worker.start()
            worker.join(timeout=5)
        assert current_span() is None
    finally:
        set_tracer(None)
        writer.close()
    events = {e["name"]: e for e in _read_events(writer.path) if e["ph"] == "X"}
    assert caller == "Time/rollout_time"
    step, env = events["Time/act_host_step_time"], events["Time/env_interaction_time"]
    assert step["args"]["parent"] == "Time/rollout_time" and step["tid"] != events["Time/rollout_time"]["tid"]
    assert env["args"]["parent"] == "Time/act_host_step_time" and env["tid"] == step["tid"]


def test_a_span_with_a_parent_and_no_tracer_looks_up_no_span(monkeypatch):
    """Telemetry off, the host step's span and the look for its parent touch
    neither the stacks of open spans nor jax."""
    import jax

    from sheeprl_tpu.obs import spans as spans_mod

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"the stack of open spans was read ({name}) with no tracer installed")

        def __setattr__(self, name, value):
            raise AssertionError(f"a stack of open spans was made ({name}) with no tracer installed")

    def refuse(*_a, **_k):
        raise AssertionError("a jax call with no tracer installed")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(spans_mod, "_OPEN", Untouchable())
    with span("Time/rollout_time", phase="rollout"):
        with span("Time/act_host_step_time", phase="rollout", parent=current_span()):
            pass
    assert set(timer.compute()) == {"Time/rollout_time", "Time/act_host_step_time"}


@pytest.mark.parametrize("annotations", [True, False])
def test_compile_key_includes_metadata_only_under_an_annotating_tracer(tmp_path, annotations):
    import jax

    from sheeprl_tpu.obs.spans import scoped_compile_key

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=annotations)
    set_tracer(writer)
    try:
        with scoped_compile_key():
            assert getattr(jax.config, flag) is (True if annotations else before)
    finally:
        set_tracer(None)
        writer.close()
    assert getattr(jax.config, flag) is before
