"""A whole benchmark in a temporary directory, at widths a CPU test can hold.

It is also the proof that the harness is driven by data: this configuration,
traffic mix, cell, limits file and per-layer metric exist only as the files
and manifest entries written here; no file of the benchmark is edited.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")

TINY_SIZES = dict(
    cnn_channels_multiplier=2, dense_units=16, mlp_layers=2, recurrent_state_size=32, hidden_size=16,
    stochastic_size=4, discrete_size=4, sequence_length=8, batch_size=4, horizon=3, precision="32-true",
)
TINY_OVERRIDES = [
    "fabric.precision=32-true", "algo.dense_units=16", "algo.mlp_layers=2",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4",
    "per_rank_sequence_length=8", "per_rank_batch_size=4", "algo.horizon=3", "buffer.size=4096",
]
#: float32 program against float32 reference on one CPU: rounding only
TINY_LIMITS = {
    "staging_bad_rows": 0, "mirror_bad_leaves": 0, "wm_loss_gap": 1e-4, "policy_loss_gap": 5e-3,
    "value_loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 5e-3,
}
DUMMY_METRIC = '''"""A metric that exists only in this temporary benchmark."""


def read(run):
    return float(run.recorder.cycles)
'''


def tiny_config() -> dict:
    with open(os.path.join(BENCH, "configs", "dv3-XL.json")) as f:
        config = json.load(f)
    config["name"] = "dv3-tiny"
    config["sizes"].update(TINY_SIZES)
    config["overrides"] = [o for o in config["overrides"] if not o.startswith("fabric.precision")] + TINY_OVERRIDES
    return config


def write_tiny_benchmark(root: str, chips: int = 1):
    """Write the temporary benchmark under ``root`` and return its manifest."""
    from benchmarks.manifest import Manifest

    bench = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "limits", "metrics"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", "dv3-tiny.json"), "w") as f:
        json.dump(tiny_config(), f)
    shutil.copy(os.path.join(BENCH, "configs", "dv3-XL.reference.py"), os.path.join(bench, "configs"))
    traffic = {
        "overrides": ["env.num_envs=2", "algo.per_rank_gradient_steps=4"],
        "overrides_per_chip": {"algo.train_every": 4, "algo.learning_starts": 64},
        "env": {"step_ms": 0.2, "episode_len_min": 10, "episode_len_max": 20},
        "warm_cycles": 3,
        "traced_cycles": 2,
    }
    with open(os.path.join(bench, "traffic", "burst4.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "limits", "tiny.burst4.json"), "w") as f:
        json.dump({"limits": TINY_LIMITS}, f)
    for name in ("entry.compiles_in_window.learn", "train.host_ms_per_burst.learn", "device.idle_pct.learn"):
        shutil.copy(os.path.join(BENCH, "metrics", name + ".py"), os.path.join(bench, "metrics"))
    with open(os.path.join(bench, "metrics", "dummy.cycles.py"), "w") as f:
        f.write(DUMMY_METRIC)
    cell = "tiny.burst4"
    manifest = {
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "dv3-tiny", "source": "test", "file": "bench/configs/dv3-tiny.json", "reduced": [], "why": "test"}],
        "workloads": [{"name": cell, "config": "dv3-tiny", "traffic": "burst4", "chips": chips, "why": "test"}],
        "end_to_end": [
            {"name": "replay_steps_per_s", "unit": "steps/s", "better": "higher", "bound": 0.03, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"},
        ],
        "per_layer": [
            {"name": n, "unit": u, "better": "lower", "source": "program_span", "layer": "test", "moves": "replay_steps_per_s"}
            for n, u in (("entry.compiles_in_window.learn", "compiles"), ("train.host_ms_per_burst.learn", "ms"),
                         ("device.idle_pct.learn", "%"), ("dummy.cycles", "cycles"))
        ],
    }
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return Manifest(path, root=bench), cell
