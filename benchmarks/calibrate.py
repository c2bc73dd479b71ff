"""Read the two ends a limit of ``correct`` is set between, on the chip.

    python3 benchmarks/calibrate.py --workload <name> --seeds 12 --control-seeds 3

One process and one set-up's worth of compiling: for each seed, the cell's own
run (short window) gives the program's gaps to the reference, the lower
reading; on the first ``--control-seeds`` seeds the control (the reference in
fp8, standing in for the program) and the faults a training cell can have
(half of the batch left out; on several chips, the exchange left out) give the
upper readings, at the cell's own size and on its own recorded batches. The
table goes to standard output and to ``chiprun_out/calibrate/<name>.json``;
the limits files are written by hand from it (see PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.manifest import Manifest

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_200_000_001)
    parser.add_argument("--seconds", type=float, default=1.0)
    args = parser.parse_args(argv)
    manifest = Manifest()
    chips = int(manifest.workload(args.workload)["chips"])
    controls = ("fp8", "half_batch") + (("no_exchange",) if chips > 1 else ())
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        result = run.run_cell(
            args.workload, seed, args.seconds, False, manifest=manifest,
            controls=controls if i < args.control_seeds else (),
        )
        row = {
            "seed": seed,
            "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["checks"].items()},
            "controls": result.get("controls", {}),
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }
        rows.append(row)
        print("calibrate:", json.dumps(row), flush=True)
    names = sorted(rows[0]["program"])
    summary = {"program_max": {n: max(r["program"][n] for r in rows) for n in names}}
    for control in controls:
        have = [r["controls"][control] for r in rows if control in r["controls"]]
        summary[f"{control}_min"] = {n: min(h[n] for h in have) for n in names if n in have[0]}
    out_dir = os.path.join(REPO, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    print("calibrate summary:", json.dumps(summary, indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
