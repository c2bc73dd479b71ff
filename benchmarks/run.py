"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the one that holds the chip. It drives the program's own entry,
``sheeprl_tpu.cli.run``, with the cell's configuration and traffic as Hydra
overrides; warms up (prefill, the pretrain burst, the warm-up cycles) and
counts that as set-up; measures a window of whole train cycles; stops the
loop; then checks what the timed path produced against the plain reference.
The last line of standard output is one JSON object.

Exits non-zero, with no result, unless JAX's default backend is a TPU with
the chips the cell asks for.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import gc
import json
import os
import sys
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import check, reduce, traffic_env  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from benchmarks.window import Recorder, rates  # noqa: E402

#: the program seeds numpy and its envs (seed + env index) from one number
SEED_SPACE = 2**31 - 1024


def require_chips(chips: int):
    """Print what JAX found, first; exit unless it is a TPU with the chips."""
    import jax

    devices = jax.devices()
    print(
        f"benchmark: jax {jax.__version__} backend {jax.default_backend()} "
        f"device_kind {devices[0].device_kind} x{len(devices)}",
        flush=True,
    )
    if jax.default_backend() != "tpu":
        sys.exit(f"benchmark: no TPU (backend is {jax.default_backend()!r}); nothing is measured elsewhere")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), JAX sees {len(devices)}")
    return devices


def program_overrides(config: dict, traffic: dict, chips: int, seed: int, accelerator: str,
                      run_dir: str, name: str, trace: bool) -> list:
    env = dict(traffic["env"])
    wrapper = ", ".join(
        ["_target_: benchmarks.traffic_env.PixelEnv", "seed: null", f"base_seed: {seed}"]
        + [f"{k}: {v}" for k, v in env.items()]
    )
    out = list(config["overrides"]) + list(traffic["overrides"])
    out += [f"{k}={v * chips}" for k, v in traffic.get("overrides_per_chip", {}).items()]
    out += [
        f"env.wrapper={{{wrapper}}}",
        f"seed={seed}",
        f"fabric.accelerator={accelerator}",
        f"fabric.devices={chips}",
        "root_dir=benchmarks",
        f"run_name={name}",
        f"metric.telemetry.enabled={'true' if trace else 'false'}",
    ]
    if trace:
        # spans and counters, which the per-layer readers use, and nothing else of
        # the telemetry plane: the learn probes are gated when the train program is
        # built, so with them on the traced program would not be the timed one
        out += [
            f"metric.telemetry.trace_file={os.path.join(run_dir, 'spans.jsonl')}",
            f"metric.telemetry.summary_path={os.path.join(run_dir, 'telemetry.json')}",
            "metric.telemetry.learn.enabled=false",
            "metric.telemetry.flight.enabled=false",
            "metric.telemetry.live_interval_s=0",
            "metric.telemetry.poll_interval_s=0",
        ]
    return out


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    manifest: Optional[Manifest] = None,
    require_chip: bool = True,
    accelerator: str = "tpu",
    fault: str = "",
    process_start: Optional[float] = None,
    controls: tuple = (),
) -> dict:
    """Run one cell and return the result object (``main`` prints it).

    ``require_chip=False`` and ``accelerator="cpu"`` are for the tier-1 tests,
    which drive everything but the look for a chip at tiny widths; ``fault``
    plants one in the timed path (see the adapter); ``controls`` are for
    ``calibrate.py``, which reads the limits' upper ends. A number taken on the CPU
    is never written under a device metric's name: such a result names its
    device, and the driver refuses it."""
    process_start = _PROCESS_START if process_start is None else process_start
    manifest = manifest or Manifest()
    workload = manifest.workload(name)
    chips = int(workload["chips"])
    import jax

    devices = require_chips(chips) if require_chip else jax.devices()
    used = devices[:chips]
    config = manifest.config(workload)
    traffic = manifest.traffic(workload)
    limits = manifest.limits(workload)
    reference = manifest.reference(config)
    run_seed = int(seed) % SEED_SPACE
    run_dir = os.path.join(REPO, "logs", "benchmarks", f"{name}-{int(trace)}")
    os.makedirs(run_dir, exist_ok=True)

    compiles = reduce.CompileCounter.installed()
    tracer = reduce.DeviceTrace(os.path.join(run_dir, "profile")) if trace else None
    marks = {}

    def before_open():
        gc.collect()
        gc.freeze()
        marks["compiles_open"] = compiles.snapshot()
        marks["counters_open"] = reduce.program_counters()
        if tracer is not None:
            marks["span_origin"] = reduce.span_clock_origin()
            tracer.start()

    def after_close():
        if tracer is not None:
            tracer.stop()
        marks["compiles_close"] = compiles.snapshot()
        marks["counters_close"] = reduce.program_counters()

    recorder = Recorder(
        seconds,
        warm_bursts=1 + int(traffic["warm_cycles"]),
        before_open=before_open,
        after_close=after_close,
        max_cycles=int(traffic["traced_cycles"]) if trace else None,
        at_open=tracer.mark_open if trace else None,
    )
    adapter_module = __import__(config["adapter"], fromlist=["Adapter"])
    adapter = adapter_module.Adapter(config, reference, run_seed, recorder, trace, fault=fault)
    traffic_env.attach(recorder)
    overrides = program_overrides(config, traffic, chips, run_seed, accelerator, run_dir, name, trace)

    from sheeprl_tpu import cli

    adapter.install()
    try:
        cli.run(overrides)
    except adapter_module.StopWindow:
        pass
    else:
        raise RuntimeError("the program's run ended before the window closed")
    finally:
        adapter.uninstall()
        gc.unfreeze()
    if not recorder.is_closed:
        raise RuntimeError("the window did not close")

    phases = {"window_closed": time.perf_counter()}
    sizes = config["sizes"]
    envs = traffic_env.built_envs()
    n_envs = len(envs)
    end_to_end = rates(recorder, n_envs, sizes["sequence_length"], sizes["batch_size"] * chips)
    end_to_end["setup_s"] = recorder.opened_at - process_start
    memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"]) for d in used) if require_chip else 0

    # -- what the timed path produced, against the reference ---------------------
    numbers = {}
    batches = [step["batch"] for step in adapter.steps]
    last = jax.device_get(adapter.last_stack)
    batches += [jax.tree_util.tree_map(lambda x: x[i], last) for i in range(len(last["rewards"]))]
    numbers["staging_bad_rows"] = sum(
        check.staging_mismatches(b, traffic["env"], run_seed, envs) for b in batches
    )
    numbers["mirror_bad_leaves"] = adapter.mirror_mismatches()
    steps = adapter.steps
    adapter.release()
    del last, batches
    gc.collect()
    numbers.update(
        adapter_module.compare_with_reference(
            reference, config, steps, run_seed, chips, device=used[0], controls=controls
        )
    )
    control_readings = numbers.pop("_controls", None)
    correct, rows = check.verdict(numbers, limits["limits"], limits.get("not_compared", ()))
    phases["checked"] = time.perf_counter()

    run = reduce.RunRecord(
        workload=workload, config=config, traffic=traffic, chips=chips, recorder=recorder,
        end_to_end=end_to_end, marks=marks, memory_peak=memory_peak, run_dir=run_dir,
        mirror_spans=adapter.mirror_spans, tracer=tracer, device_kind=used[0].device_kind,
        n_envs=n_envs,
    )
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for metric in manifest.metrics_for(workload, group):
        value = end_to_end.get(metric["name"]) if group == "end_to_end" else manifest.reader(metric["name"])(run)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": recorder.grad_steps,
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        summary = run.device_summary()
        if summary is None:
            if require_chip:
                raise RuntimeError("the traced window holds no device operation")
            summary = {"busy_s": 0.0, "window_s": recorder.window_s, "top_ops": []}  # CPU tests only
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["top_ops"], "idle_gaps": run.idle_gaps()}
        tracer.discard()
    result["window"] = {
        "cycles": recorder.cycles, "seconds": recorder.window_s, "policy_steps": recorder.policy_steps,
        "grad_steps": recorder.grad_steps, "compiles_in_window": marks["compiles_close"]["count"] - marks["compiles_open"]["count"],
    }
    # where a run's wall time goes after set-up (the driver ignores this key)
    result["phases_s"] = {
        "window": recorder.window_s,
        "stop_to_check_done": phases["checked"] - phases["window_closed"],
        "read_metrics": time.perf_counter() - phases["checked"],
    }
    if control_readings is not None:
        result["controls"] = control_readings
    aside = {row[0]: row[1] for row in rows if row[2] is None and row[0] in limits.get("not_compared", ())}
    if aside:
        result["not_compared"] = aside
    result["checks"] = {row[0]: {"value": row[1], "limit": row[2]} for row in rows if row[0] not in aside}
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    for name, value in result.get("not_compared", {}).items():
        print(f"read {name}: {value} (not compared: no upper reading, see PERF.md)", file=sys.stderr)
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']} (limit {row['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
