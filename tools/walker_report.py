"""Summarize a walker campaign's TB events into learning-curve evidence.

Reads every events file under the campaign run dirs (all versions/segments),
merges the `Rewards/rew_avg` scalars by policy step, and prints:

- the merged curve (step -> mean episode reward, downsampled),
- sustained-performance stats (best, last-10k-step mean),
- the success verdict against the round-5 bar (sustained >= 5x random).

Usage: python tools/walker_report.py [run_glob]
"""

from __future__ import annotations

import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_GLOB = os.path.join(
    REPO, "logs", "runs", "dreamer_v3", "*", "*walker_campaign_r4*", "*"
)
RANDOM_REWARD = 40.0  # upper end of walker_walk random-policy reward


def main() -> None:
    run_glob = sys.argv[1] if len(sys.argv) > 1 else DEFAULT_GLOB
    from tensorboard.backend.event_processing.event_accumulator import EventAccumulator

    points: list[tuple[int, float, float]] = []  # (step, event wall time, value)
    for version_dir in sorted(glob.glob(run_glob)):
        for ev in glob.glob(os.path.join(version_dir, "events.out.tfevents.*")):
            acc = EventAccumulator(ev)
            acc.Reload()
            if "Rewards/rew_avg" not in acc.Tags().get("scalars", []):
                continue
            for s in acc.Scalars("Rewards/rew_avg"):
                points.append((int(s.step), float(s.wall_time), float(s.value)))
    if not points:
        print("no Rewards/rew_avg scalars found under", run_glob)
        return
    # segments overlap at resume boundaries: keep the chronologically LAST
    # value per step (ordered by the event's own wall time)
    points.sort(key=lambda p: (p[0], p[1]))
    merged = {step: value for step, _, value in points}
    steps = sorted(merged)
    print(f"{len(steps)} reward points over steps {steps[0]}..{steps[-1]}")
    for st in steps:
        print(f"  step {st:>7d}  rew_avg {merged[st]:8.1f}")
    vals = [merged[s] for s in steps]
    best = max(vals)
    tail = [merged[s] for s in steps if s >= steps[-1] - 10000]
    tail_mean = sum(tail) / len(tail)
    print(f"\nbest rew_avg: {best:.1f}")
    print(f"last-10k-steps mean: {tail_mean:.1f} over {len(tail)} points")
    bar = 5 * RANDOM_REWARD
    verdict = "PASS" if tail_mean >= bar else ("PARTIAL" if best >= bar else "FAIL")
    print(f"bar (5x random={RANDOM_REWARD:.0f}): {bar:.0f} -> {verdict}")


if __name__ == "__main__":
    main()
