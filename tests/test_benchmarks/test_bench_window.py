"""The window closes on a cycle boundary and divides by measured seconds."""

import pytest

from benchmarks.window import Recorder, rates


def drive(recorder, cycle_s, steps_per_cycle, n_cycles, stall_at=None, stall_s=0.0, grad_steps=4):
    """Feed synthetic stamps: ``steps_per_cycle`` env steps, then a burst."""
    t = 100.0
    for cycle in range(n_cycles):
        for step in range(steps_per_cycle):
            recorder.on_env_step(t)
            if recorder.is_closed:
                return
            t += cycle_s / (steps_per_cycle + 1)
        t += cycle_s / (steps_per_cycle + 1)
        if cycle == stall_at:
            t += stall_s
        recorder.on_burst_done(grad_steps)


def test_window_opens_after_warm_up_and_closes_on_a_boundary():
    calls = []
    rec = Recorder(10.0, warm_bursts=4, before_open=lambda: calls.append("open"), after_close=lambda: calls.append("close"))
    drive(rec, cycle_s=3.0, steps_per_cycle=2, n_cycles=20)
    assert calls == ["open", "close"]
    assert rec.is_closed
    # opened at the first env step after the fourth burst, closed four cycles later:
    # 9 s is short of 10 s, 12 s is the first boundary at or after it
    assert rec.opened_at == pytest.approx(100.0 + 4 * 3.0)
    assert rec.window_s == pytest.approx(12.0)
    assert rec.cycles == 4 and rec.policy_steps == 8 and rec.grad_steps == 16
    assert all(c == pytest.approx(3.0) for c in rec.cycle_seconds())


def test_rate_is_work_of_whole_cycles_over_their_measured_seconds():
    rec = Recorder(10.0, warm_bursts=1)
    drive(rec, cycle_s=3.0, steps_per_cycle=2, n_cycles=20)
    out = rates(rec, n_envs=16, seq_len=64, global_batch=16)
    assert out["env_steps_per_s"] == pytest.approx(8 * 16 / 12.0)
    assert out["replay_steps_per_s"] == pytest.approx(16 * 64 * 16 / 12.0)


def test_a_stall_lowers_the_rate():
    steady, stalled = Recorder(10.0, warm_bursts=1), Recorder(10.0, warm_bursts=1)
    drive(steady, 3.0, 2, 20)
    drive(stalled, 3.0, 2, 20, stall_at=2, stall_s=5.0)
    a = rates(steady, 1, 64, 16)["replay_steps_per_s"]
    b = rates(stalled, 1, 64, 16)["replay_steps_per_s"]
    # the stalled window holds 2 cycles in 3 + 8 = 11 s, not 4 in 12 s
    assert stalled.window_s == pytest.approx(11.0) and stalled.cycles == 2
    assert b == pytest.approx(a * (2 / 11.0) / (4 / 12.0))
    assert max(stalled.cycle_seconds()) == pytest.approx(8.0)


def test_a_traced_run_closes_after_its_cycles():
    rec = Recorder(100.0, warm_bursts=2, max_cycles=3)
    drive(rec, 3.0, 2, 20)
    assert rec.is_closed and rec.cycles == 3 and rec.window_s == pytest.approx(9.0)


def test_rates_refuse_a_window_that_never_closed():
    rec = Recorder(100.0, warm_bursts=1)
    drive(rec, 3.0, 2, 5)
    with pytest.raises(RuntimeError):
        rates(rec, 1, 64, 16)
