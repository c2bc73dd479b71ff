"""Segmented, auto-resuming DreamerV3 walker_walk learning campaign.

Round-3 post-mortem: seven open-loop walker attempts died ≤4k/100k steps
with no checkpoint and no diagnosable artifact — a long run must be
ENGINEERED. This driver:

- runs the training CLI in bounded segments (default 25 min) so any crash
  or kill loses at most one segment;
- checkpoints (+ replay buffer) every 2000 policy steps inside each segment
  (`exp=dreamer_v3_dmc_walker_walk_proprio`), and resumes the next segment
  from the newest checkpoint;
- appends a heartbeat JSON line per segment (step reached, episode rewards
  seen, exit code, stderr tail) to ``logs/walker_campaign.jsonl`` so a dead
  campaign is diagnosable from artifacts alone.

Usage:
    python tools/walker_campaign.py [--segments N] [--segment-seconds S]
        [--total-steps T] [--exp EXP] [overrides...]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEARTBEAT = os.path.join(REPO, "logs", "walker_campaign.jsonl")


def _beat(payload: dict) -> None:
    os.makedirs(os.path.dirname(HEARTBEAT), exist_ok=True)
    payload["wall_time"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(HEARTBEAT, "a") as f:
        f.write(json.dumps(payload) + "\n")
    print(f"[campaign] {json.dumps(payload)}", flush=True)


def _latest_checkpoint(run_glob: str) -> tuple[str | None, int]:
    """Newest ckpt_<step>.* under any matching run dir, with its step."""
    best, best_step = None, -1
    for path in glob.glob(run_glob):
        m = re.search(r"ckpt_(\d+)", os.path.basename(path))
        step = int(m.group(1)) if m else 0
        key = (step, os.path.getmtime(path))
        if best is None or key > (best_step, os.path.getmtime(best)):
            best, best_step = path, step
    return best, max(best_step, 0)


def _rewards_from_stdout(text: str) -> list[float]:
    return [float(m) for m in re.findall(r"reward_env_\d+=([-\d.]+)", text)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--segments", type=int, default=24)
    ap.add_argument("--segment-seconds", type=int, default=1500)
    ap.add_argument("--total-steps", type=int, default=100000)
    ap.add_argument("--exp", default="dreamer_v3_dmc_walker_walk_proprio")
    ap.add_argument("--run-name", default="walker_campaign_r4")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args()

    run_name = args.run_name
    # layout: logs/runs/<algo>/<env_id>/<run_name>/version_K/checkpoint/ckpt_N_0
    ckpt_glob = os.path.join(
        REPO, "logs", "runs", "dreamer_v3", "*", f"*{run_name}*", "*", "checkpoint", "ckpt_*"
    )
    base = [
        f"exp={args.exp}",
        f"total_steps={args.total_steps}",
        f"run_name={run_name}",
        "buffer.device_ring=True",
        "algo.player_on_host=False",
        "metric.fetch_train_metrics_every=0",
        *args.overrides,
    ]

    all_rewards: list[float] = []
    # previous segment's outcome, tracked in locals: the heartbeat file is the
    # wrong place to re-read it from (its last lines are the current segment's
    # own segment_start/segment_end beats)
    prev_rc: object = None
    prev_step_after: int | None = None
    for seg in range(args.segments):
        ckpt, step = _latest_checkpoint(ckpt_glob)
        if step >= args.total_steps:
            _beat({"event": "done", "segment": seg, "step": step})
            break
        cmd = [sys.executable, "-m", "sheeprl_tpu", *base]
        if ckpt:
            cmd.append(f"checkpoint.resume_from={ckpt}")
        _beat({"event": "segment_start", "run": run_name, "segment": seg, "resume_from": ckpt, "step": step})
        t0 = time.time()
        try:
            proc = subprocess.run(
                cmd,
                cwd=REPO,
                capture_output=True,
                text=True,
                timeout=args.segment_seconds,
            )
            rc, out, err = proc.returncode, proc.stdout, proc.stderr
        except subprocess.TimeoutExpired as exc:
            # expected end-of-segment: the run is killed mid-flight and the
            # next segment resumes from the newest in-run checkpoint
            rc = "timeout"
            out = (exc.stdout or b"").decode() if isinstance(exc.stdout, bytes) else (exc.stdout or "")
            err = (exc.stderr or b"").decode() if isinstance(exc.stderr, bytes) else (exc.stderr or "")
        rewards = _rewards_from_stdout(out)
        all_rewards.extend(rewards)
        _, new_step = _latest_checkpoint(ckpt_glob)
        _beat(
            {
                "event": "segment_end",
                "run": run_name,
                "segment": seg,
                "rc": rc,
                "seconds": round(time.time() - t0, 1),
                "step_before": step,
                "step_after": new_step,
                "episodes_seen": len(rewards),
                "last_rewards": [round(r, 1) for r in rewards[-8:]],
                "best_reward": round(max(all_rewards), 1) if all_rewards else None,
                # drop the XLA AOT-cache warning spam (KBs per line) so the
                # heartbeat stays readable and small
                "stderr_tail": [
                    l[:300]
                    for l in (err or "").strip().splitlines()
                    if "cpu_aot_loader" not in l
                ][-3:],
            }
        )
        if (
            rc not in ("timeout", 0)
            and new_step == step
            and prev_rc not in (None, "timeout", 0)
            and prev_step_after == step
        ):
            # crashed without progress twice in a row -> give up loudly
            _beat({"event": "abort_no_progress", "segment": seg, "step": step})
            break
        prev_rc, prev_step_after = rc, new_step


if __name__ == "__main__":
    main()
