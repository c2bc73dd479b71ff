"""Capture + summarize an XLA device profile of any family's train step.

    python tools/profile_step.py --exp dv3 [config overrides...]
    python tools/profile_step.py --exp sac --tiny --steps 10

Was hard-wired to DV3's agent/train builders; now any family in
``sheeprl_tpu.obs.prof.harness.FAMILIES`` (dv1/dv2/dv3, the P2E exploration
variants, sac, ppo) builds through the shared harness — the same real
``build_agent``/``build_train_fn`` wiring the training loop dispatches.
The capture uses the same ``profiler_capture`` scope the flight recorder
and the in-run ``StepProfiler`` open; parsing + roofline go through
``sheeprl_tpu.obs.prof`` (no tensorflow needed, CPU host-plane fallback).

The host wall clock includes dispatch; the profiled per-execution device
time is the number to read. See howto/profiling.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def profile_family(
    family: str,
    overrides=(),
    tiny: bool = False,
    steps: int = 5,
    out_dir: str = None,
    warmup: int = 1,
):
    """Build, warm up, capture ``steps`` dispatches, parse, roofline.

    Returns the :func:`sheeprl_tpu.obs.prof.capture.analyze_trace` record
    (plus ``family``/``flops_per_dispatch``/``bytes_per_dispatch``).
    """
    from sheeprl_tpu.obs.live import profiler_capture
    from sheeprl_tpu.obs.prof.capture import analyze_trace
    from sheeprl_tpu.obs.prof.harness import build_harness
    from sheeprl_tpu.obs.prof.roofline import detect_peaks

    harness = build_harness(family, overrides=overrides, tiny=tiny)
    out_dir = out_dir or f"/tmp/{family}_trace"
    harness.run(warmup)  # compile + warmup outside the capture window
    with profiler_capture(out_dir):
        harness.run(steps)
    cost = harness.cost() or {}
    record = analyze_trace(
        out_dir,
        flops_per_step=cost.get("flops"),
        bytes_per_step=cost.get("bytes_accessed"),
        world_size=1,
        dispatches_per_step=1,
        peaks=detect_peaks(),
    )
    record["family"] = family
    record["flops_per_dispatch"] = cost.get("flops")
    record["bytes_per_dispatch"] = cost.get("bytes_accessed")
    # UNIT NOTE: the harness dispatches the single-gradient-step program, so
    # this record's device_ms_per_step is per GRADIENT STEP. The in-run key
    # in telemetry.json is per train-step UNIT — per_rank_gradient_steps
    # dispatches for the looped families (DV1/DV2/P2E), a whole burst for
    # DV3 — so the two differ by that factor on multi-step configs.
    record["unit"] = "ms per gradient step (one dispatch)"
    return record


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--exp", default="dv3",
        help="family to profile (sheeprl_tpu.obs.prof.harness.FAMILIES)",
    )
    parser.add_argument("--steps", type=int, default=5, help="captured dispatches")
    parser.add_argument("--warmup", type=int, default=1, help="uncaptured warmup dispatches")
    parser.add_argument("--tiny", action="store_true", help="CPU-scale model sizes")
    parser.add_argument("--out", default=None, help="trace dir (default /tmp/<exp>_trace)")
    parser.add_argument(
        "overrides", nargs="*", help="extra config overrides (hydra-style k=v)"
    )
    args = parser.parse_args(argv)

    record = profile_family(
        args.exp, overrides=args.overrides, tiny=args.tiny,
        steps=args.steps, out_dir=args.out, warmup=args.warmup,
    )
    print(json.dumps(record, indent=2, default=str))
    print(
        f"\ntrace in {record['trace_dir']} — re-parse with "
        "tools/parse_xplane.py", file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
