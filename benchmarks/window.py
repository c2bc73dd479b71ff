"""The measured window: whole train cycles, and the seconds they really took.

A *cycle* is the policy steps up to and including one train burst and the
parameter-mirror refresh that follows it. Its boundary is the stamp of the
first environment step after the burst: acting waits for the refreshed
parameters, so by then everything the cycle dispatched has finished.

The window opens at the boundary that follows the warm-up cycles and closes at
the first boundary at or after ``seconds`` later. Every rate is work done in
the window's whole cycles over ``closed_at - opened_at``: never over the
nominal ``seconds``, never from a median of cycles.
"""

from __future__ import annotations

from typing import Callable, List, Optional


class Recorder:
    """Collects env-step stamps and burst completions; decides open and close.

    ``warm_bursts`` train bursts (the pretrain burst and the warm-up cycles)
    complete before the window may open. ``before_open`` runs once, when the
    last warm-up burst has completed and before the opening stamp (garbage
    collection, counter snapshots, the profiler's start), so that its cost
    falls into set-up. ``after_close`` runs once at the closing stamp. A traced
    run gives ``max_cycles`` and closes after that many, if that comes first.
    """

    def __init__(
        self,
        seconds: float,
        warm_bursts: int,
        before_open: Optional[Callable[[], None]] = None,
        after_close: Optional[Callable[[], None]] = None,
        max_cycles: Optional[int] = None,
        at_open: Optional[Callable[[], None]] = None,
    ):
        self.seconds = float(seconds)
        self.warm_bursts = int(warm_bursts)
        self.before_open = before_open
        self.after_close = after_close
        self.max_cycles = max_cycles  # a traced run closes after this many cycles
        self.at_open = at_open  # runs at the opening stamp (a mark in the trace)
        self.bursts_done = 0
        self.grad_steps_done = 0
        self.opened_at: Optional[float] = None
        self.closed_at: Optional[float] = None
        self.step_stamps: List[float] = []  # env-0 step stamps inside the window
        self.boundaries: List[float] = []  # cycle boundaries inside the window, opening one first
        self.grad_steps_at_boundary: List[int] = []
        self._burst_pending = False

    @property
    def is_open(self) -> bool:
        return self.opened_at is not None and self.closed_at is None

    @property
    def is_closed(self) -> bool:
        return self.closed_at is not None

    def on_burst_done(self, grad_steps: int) -> None:
        """A train burst has returned to the host with its result seen."""
        self.bursts_done += 1
        self.grad_steps_done += int(grad_steps)
        self._burst_pending = True
        if self.bursts_done == self.warm_bursts and self.before_open is not None:
            self.before_open()

    def on_env_step(self, stamp: float) -> None:
        """Env 0 is about to step, at ``stamp`` on ``time.perf_counter``."""
        if self.is_closed:
            return
        boundary = self._burst_pending
        self._burst_pending = False
        if self.opened_at is None:
            if boundary and self.bursts_done >= self.warm_bursts:
                self.opened_at = stamp
                self.boundaries.append(stamp)
                self.grad_steps_at_boundary.append(self.grad_steps_done)
                self.step_stamps.append(stamp)
                if self.at_open is not None:
                    self.at_open()
            return
        if boundary:
            self.boundaries.append(stamp)
            self.grad_steps_at_boundary.append(self.grad_steps_done)
            traced_out = self.max_cycles is not None and self.cycles >= self.max_cycles
            if stamp - self.opened_at >= self.seconds or traced_out:
                self.closed_at = stamp
                if self.after_close is not None:
                    self.after_close()
                return
        self.step_stamps.append(stamp)

    # -- what the window holds ------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.closed_at - self.opened_at

    @property
    def cycles(self) -> int:
        return len(self.boundaries) - 1

    @property
    def policy_steps(self) -> int:
        """Vector-env steps in the window's whole cycles."""
        return len(self.step_stamps)

    @property
    def grad_steps(self) -> int:
        return self.grad_steps_at_boundary[-1] - self.grad_steps_at_boundary[0]

    def cycle_seconds(self) -> List[float]:
        return [b - a for a, b in zip(self.boundaries, self.boundaries[1:])]


def rates(rec: Recorder, n_envs: int, seq_len: int, global_batch: int) -> dict:
    """The end-to-end rates of a closed window, over its measured seconds."""
    if not rec.is_closed or rec.cycles < 1:
        raise RuntimeError("the window did not close on a cycle boundary")
    return {
        "env_steps_per_s": rec.policy_steps * n_envs / rec.window_s,
        "replay_steps_per_s": rec.grad_steps * seq_len * global_batch / rec.window_s,
    }
