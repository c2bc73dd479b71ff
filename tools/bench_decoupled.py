"""Coupled vs decoupled PPO/SAC throughput on the virtual CPU mesh.

Measures the player-thread/double-buffering win (round 1): the
decoupled runner overlaps env stepping with the update program, so at
identical configs its wall-clock should beat the strictly-alternating
coupled loop whenever env interaction is a non-trivial fraction of the
update period.

    python tools/bench_decoupled.py [total_steps] [devices] [family]

``family`` is ``ppo`` (default, CartPole) or ``sac`` (Pendulum).

Runs each variant once and prints one JSON line per variant plus a summary
line with the speedup. Uses the 8-virtual-device CPU mesh (the same
environment the algo test suite runs on); on real hardware the player runs
on the host CPU while the mesh computes, so the overlap win there is
strictly larger than what this one-box measurement can show.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    total_steps = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    devices = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    family = sys.argv[3] if len(sys.argv) > 3 else "ppo"

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={max(devices, 2)}"
        ).strip()

    from sheeprl_tpu import cli

    common = [
        "env=gym",
        "env.id=CartPole-v1" if family == "ppo" else "env.id=Pendulum-v1",
        "env.sync_env=True",
        "env.capture_video=False",
        f"total_steps={total_steps}",
        "env.num_envs=8",
        "per_rank_batch_size=64",
        f"fabric.devices={devices}",
        "fabric.accelerator=cpu",
        "metric.log_level=0",
        "buffer.memmap=False",
        "checkpoint.save_last=False",
        "checkpoint.every=1000000000",
        "algo.run_test=False",
        "seed=7",
    ]
    if family == "ppo":
        common.append("algo.rollout_steps=128")
    else:
        common.append("algo.learning_starts=1000")
    results = {}
    for exp in (family, f"{family}_decoupled"):
        start = time.perf_counter()
        cli.run([f"exp={exp}", f"exp_name=bench_{exp}", *common])
        elapsed = time.perf_counter() - start
        results[exp] = elapsed
        print(
            json.dumps(
                {
                    "metric": f"{exp}_{'cartpole' if family == 'ppo' else 'pendulum'}_{total_steps}_steps",
                    "value": round(elapsed, 2),
                    "unit": "s",
                    "devices": devices,
                }
            ),
            flush=True,
        )
    print(
        json.dumps(
            {
                "metric": f"{family}_decoupled_overlap_speedup",
                "value": round(results[family] / results[f"{family}_decoupled"], 3),
                "unit": "x",
                "coupled_s": round(results[family], 2),
                "decoupled_s": round(results[f"{family}_decoupled"], 2),
            }
        )
    )


if __name__ == "__main__":
    main()
