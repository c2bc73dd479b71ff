"""The convolution-attention sequence-core cell in a temporary directory, at
widths a CPU test can hold: hidden 64, six layers as the cell's own (two
convolution layers with a dense MLP, an attention layer and three convolution
layers with experts), 4 query over 2 key-value heads of 16, 16 router outputs
of which this share holds 4, a vocabulary of 256 (239 codes and the
environment's 17 actions), windows of 32 steps. As ``bench_tiny``, it exists
only as the files and manifest entries written here."""

import json
import os
import shutil

from bench_tiny import BENCH

CONFIG, REFERENCE = "dv3-lfm2.ep4.json", "dv3-lfm2.ep4.reference.py"
TINY_CORE = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=2, num_experts_per_tok=3,
    moe_intermediate_size=32, vocab_size=256, chunk=16, cache_len=32,
)
TINY_SIZES = dict(
    cnn_channels_multiplier=2, dense_units=16, mlp_layers=2, posterior_hidden_size=16, discrete_size=239,
    sequence_length=32, batch_size=2, horizon=3, precision="32-true", router_outputs=16, num_experts=4,
    expert_share_index=1, **TINY_CORE,
)
TINY_OVERRIDES = [
    "fabric.precision=32-true", "algo.dense_units=16", "algo.mlp_layers=2", "algo.horizon=3", "buffer.size=4096",
    "algo.world_model.encoder.cnn_channels_multiplier=2", "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=239", "algo.world_model.core.num_experts=16",
    "algo.world_model.core.held.index=1", "algo.world_model.core.held.of=4",
] + [f"algo.world_model.core.{k}={v}" for k, v in TINY_CORE.items()]
#: float32 program against float32 reference on one CPU: rounding, and a sample that rounding flips
TINY_LIMITS = {
    "staging_bad_rows": 0, "mirror_bad_leaves": 0, "dropped_pairs": 0, "wm_loss_gap": 2e-4, "policy_loss_gap": 2e-2,
    "value_loss_gap": 1e-3, "grad_gap": 5e-3, "update_gap": 5e-2, "decode_gap": 1e-3, "decode_gap_worst": 1e-3, "bias_bad_entries": 0,
    "bias_entries_left_out": 12,
}
CELL = "tinylfm2.learn32"
READERS = (
    "entry.compiles_in_window.learn", "moe.router_max_over_mean_load", "replay.episode_ends_per_window",
    "collect.decode_ms_p50", "collect.state_mib_per_env", "train.core_conv_ms_per_grad_step",
    "train.core_attn_ms_per_grad_step", "train.core_mlp_ms_per_grad_step", "kernel.shortconv_roofline_pct",
    "kernel.gqa_window_roofline_pct", "kernel.moe_grouped_roofline_pct", "train.mfu_device_pct.learn512",
)


def tiny_config() -> dict:
    with open(os.path.join(BENCH, "configs", CONFIG)) as f:
        config = json.load(f)
    config["name"] = "dv3-tinylfm2"
    config["sizes"].update(TINY_SIZES)
    config["overrides"] = config["overrides"] + TINY_OVERRIDES
    return config


def write_tiny_benchmark(root: str, limits=None):
    from benchmarks.manifest import Manifest

    bench = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "limits", "metrics"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", "dv3-tinylfm2.json"), "w") as f:
        json.dump(tiny_config(), f)
    shutil.copy(os.path.join(BENCH, "configs", REFERENCE), os.path.join(bench, "configs"))
    traffic = {
        "overrides": ["env.num_envs=2", "algo.per_rank_gradient_steps=2", "per_rank_sequence_length=32",
                      "per_rank_batch_size=2", "algo.player_on_host=False"],
        "overrides_per_chip": {"algo.train_every": 4, "algo.learning_starts": 128},
        "env": {"step_ms": 0.2, "episode_len_min": 10, "episode_len_max": 20},
        "warm_cycles": 3,
        "traced_cycles": 2,
    }
    with open(os.path.join(bench, "traffic", "learn32.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({"limits": limits or TINY_LIMITS}, f)
    for name in READERS:
        shutil.copy(os.path.join(BENCH, "metrics", name + ".py"), os.path.join(bench, "metrics"))
    manifest = {
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "dv3-tinylfm2", "source": "test", "file": "bench/configs/dv3-tinylfm2.json", "reduced": [], "why": "test"}],
        "workloads": [{"name": CELL, "config": "dv3-tinylfm2", "traffic": "learn32", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "replay_steps_per_s", "unit": "steps/s", "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"},
        ],
        "per_layer": [
            {"name": n, "unit": "x", "better": "lower", "source": "program_counter", "layer": "test", "moves": "replay_steps_per_s"}
            for n in READERS
        ],
    }
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return Manifest(path, root=bench), CELL
