"""The sequence-core cell's whole run, less the look for a chip, at tiny widths
on the CPU: ``correct`` on a sound run (the gradient steps against the
reference's losses and gradients, the recorded stretch of acting against its
full forward pass), the control and every fault coming out as not correct, the
counters its per-layer metrics read, and the manifest entries of this PR's two
cells resolving their files."""

import json
import os

import pytest

import bench_tiny
import bench_tiny_seq
from benchmarks import dv3_seq_adapter, run
from benchmarks.manifest import Manifest

CONTROLS = ("fp8", "half_batch", "no_experts")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tiny cell; its episodes last 10 to 20 steps, so the recorded stretch is cut to fit one."""
    steps, dv3_seq_adapter.STRETCH_STEPS = dv3_seq_adapter.STRETCH_STEPS, 6
    yield bench_tiny_seq.write_tiny_benchmark(str(tmp_path_factory.mktemp("benchseq")))
    dv3_seq_adapter.STRETCH_STEPS = steps


@pytest.fixture(scope="module")
def traced(tiny):
    manifest, cell = tiny
    return run.run_cell(cell, 2**31 + 5, 0.5, True, manifest=manifest, require_chip=False, accelerator="cpu",
                        controls=CONTROLS)


def test_a_sound_run_is_correct_against_the_reference(traced):
    assert traced["correct"] is True, traced["checks"]
    checks = traced["checks"]
    assert set(checks) == set(bench_tiny_seq.TINY_LIMITS)
    assert checks["staging_bad_rows"]["value"] == 0 and checks["mirror_bad_leaves"]["value"] == 0
    assert checks["dropped_pairs"]["value"] == 0
    # float32 against float32: the program's losses and gradients are the reference's
    assert checks["wm_loss_gap"]["value"] < 2e-4 and checks["grad_gap"]["value"] < 5e-3
    # acting's one-token path against the full forward pass of the tokens it fed
    assert checks["decode_gap"]["value"] < 1e-3
    window = traced["window"]
    # a traced window closes after two cycles, or after one that outlasted the half second
    assert window["compiles_in_window"] == 0 and window["cycles"] in (1, 2)
    assert window["grad_steps"] == 2 * window["cycles"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_control_and_each_fault_come_out_as_not_correct(traced, control):
    """The reference in fp8, with half of the batch left out, or with the held
    experts left out, standing in for the program: each fails a limit."""
    readings = traced["controls"][control]
    limits = bench_tiny_seq.TINY_LIMITS
    failed = {name for name, value in readings.items() if name in limits and not value <= limits[name]}
    expected = {"fp8": "decode_gap", "half_batch": "wm_loss_gap", "no_experts": "update_gap"}[control]
    assert expected in failed, readings


def test_the_traced_run_reads_the_sequence_cores_counters(traced):
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    assert metrics["entry.compiles_in_window.learn"] == 0.0
    assert metrics["moe.max_over_mean_load"] >= 1.0
    assert 0.0 <= metrics["replay.episode_ends_per_window"] <= 32 / 10 + 1
    assert metrics["collect.decode_ms_p50"] > 0
    # three delta-rule states of 4 x 16 x 16 floats, their tails, a 32-token cache of 2 x 32 values, two counters
    assert metrics["collect.state_mib_per_env"] == pytest.approx((3 * (4 * 256 + 3 * 128) * 4 + 2 * 32 * 64 * 4 + 8) / 2**20)
    # nothing ran on a chip here: the device readers have nothing to read, and say nothing
    for name in ("train.core_gdn_ms_per_grad_step", "kernel.gdn_scan_roofline_pct", "train.mfu_device_pct.learn512"):
        assert name not in metrics


def test_a_state_left_unchanged_in_the_timed_path_comes_out_as_not_correct(tiny):
    """A fault planted in the program's own burst (the family adapter's):
    every step's new parameters thrown away."""
    manifest, cell = tiny
    result = run.run_cell(cell, 7, 0.2, False, manifest=manifest, require_chip=False, accelerator="cpu",
                          fault="state_unchanged")
    assert result["correct"] is False, result["checks"]
    failed = {k for k, row in result["checks"].items() if not row["value"] <= row["limit"]}
    assert "update_gap" in failed, result["checks"]


@pytest.mark.parametrize("cell,chips,config,traffic", [
    ("dv3-XL.learn.dp4", 4, "dv3-XL", "learn.dp4"),
    ("dv3-qwen3next.ep16.learn512", 1, "dv3-qwen3next.ep16", "learn512"),
])
def test_this_prs_cells_resolve_their_files(cell, chips, config, traffic):
    manifest = Manifest()
    workload = manifest.workload(cell)
    assert (workload["chips"], workload["config"], workload["traffic"]) == (chips, config, traffic)
    cfg = manifest.config(workload)
    assert cfg["name"] == config and hasattr(manifest.reference(cfg), "train_step")
    assert "overrides" in manifest.traffic(workload) and "warm_cycles" in manifest.traffic(workload)
    limits = manifest.limits(workload)
    assert {"staging_bad_rows", "mirror_bad_leaves", "wm_loss_gap", "update_gap"} <= set(limits["limits"])
    # the policy loss is compared where a control gives it an upper reading (PERF.md section 2)
    assert "policy_loss_gap" in set(limits["limits"]) | set(limits.get("not_compared", ()))
    assert ("decode_gap" in limits["limits"]) == (chips == 1)
    __import__(cfg["adapter"], fromlist=["Adapter"]).Adapter
    end_to_end = {m["name"] for m in manifest.metrics_for(workload, "end_to_end")}
    assert end_to_end == {"replay_steps_per_s", "setup_s"}
    per_layer = manifest.metrics_for(workload, "per_layer")
    assert all(callable(manifest.reader(m["name"])) for m in per_layer)
    names = {m["name"] for m in per_layer}
    if chips == 4:
        assert "device.collective_ms_per_grad_step.learn" in names and "train.rssm_ms_per_grad_step.learn" in names
    else:
        assert {"train.core_gdn_ms_per_grad_step", "kernel.moe_grouped_roofline_pct", "train.mfu_device_pct.learn512",
                "device.idle_pct.learn", "collect.decode_ms_p50", "train.imagination_ms_per_grad_step.learn",
                "train.unscoped_pct.learn"} <= names
        assert limits["limits"]["dropped_pairs"] == 0
        assert not any(n.startswith("publish.") or "rssm" in n for n in names)


def test_the_catalogs_numbers_are_in_the_configurations_file():
    """Every key of the published config stands in the file under its own name;
    the three that differ are the ones the manifest lists as reduced."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "vocab_size": 151936,
    }
    with open(os.path.join(bench_tiny.BENCH, "configs", "dv3-qwen3next.ep16.json")) as f:
        mine = json.load(f)
    differ = {k for k, v in published.items() if mine[k] != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"}
    entry = next(c for c in Manifest().data["configs"] if c["name"] == "dv3-qwen3next.ep16")
    assert differ <= set(entry["reduced"]) and entry["source"] == mine["source"]
    assert (mine["num_hidden_layers"], mine["num_experts"], mine["vocab_size"]) == (4, 32, 18992)
    assert mine["sizes"]["router_outputs"] == 512


def test_the_roofline_readers_count_the_kernels_own_work(monkeypatch):
    """The device readers on a made-up trace reduction: a share is the least
    time for the kernel's own operations and bytes over its device seconds,
    and says nothing where the trace or the counters are missing."""
    from types import SimpleNamespace

    from benchmarks import dv3_seq_flops, reduce, seq_scopes

    manifest = Manifest()
    config = manifest.config(manifest.workload("dv3-qwen3next.ep16.learn512"))
    sizes = config["sizes"]
    routed = {"held_pairs": 8 * 4 * 5120.0, "experts_hit": 8 * 4 * 32.0, "imagination_pairs": 8 * 31 * 4 * 80.0,
              "imagination_experts_hit": 8 * 31 * 4 * 29.0}
    counts = {"seq_core": {"steps": 8, "max_load": 8 * 2000.0, "episode_ends": 88.0, "imagination_starts": 8 * 128.0,
                           "decode_steps": 8 * 31.0, **routed}}
    run = SimpleNamespace(
        config=config, device_kind="TPU v5 lite", recorder=SimpleNamespace(grad_steps=8),
        marks={"counters_open": {}, "counters_close": counts}, train_device_seconds=lambda: 8 * 0.5, _cache={},
    )
    found = {"kernel/delta_rule": 8 * 0.100, "kernel/ragged_dot": 8 * 0.040, "core/gdn": 8 * 0.25}
    monkeypatch.setattr(seq_scopes, "seconds", lambda run: found)
    peaks = reduce.DEVICE_PEAKS["TPU v5 lite"]
    flops, nbytes = dv3_seq_flops.gdn_scan_work(sizes, 8192.0)
    want = 100.0 * max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]) / 0.100
    assert manifest.reader("kernel.gdn_scan_roofline_pct")(run) == pytest.approx(want) and 0 < want < 100
    flops, nbytes = dv3_seq_flops.moe_grouped_work(sizes, 8, *routed.values())
    want = 100.0 * max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]) / (8 * 0.040)
    assert manifest.reader("kernel.moe_grouped_roofline_pct")(run) == pytest.approx(want) and 0 < want < 100
    # the one-token steps' share of the count: their pairs forward only, and the weights of the experts they hit
    window_only = dv3_seq_flops.moe_grouped_work(sizes, 8, routed["held_pairs"], routed["experts_hit"], 0.0, 0.0)
    expert = 3 * 2048 * 512
    assert flops - window_only[0] == pytest.approx(routed["imagination_pairs"] * 2 * expert)
    assert nbytes - window_only[1] == pytest.approx(
        routed["imagination_pairs"] * 2 * 2048 * 2 + routed["imagination_experts_hit"] * expert * 2)
    assert manifest.reader("train.core_gdn_ms_per_grad_step")(run) == pytest.approx(250.0)
    mfu = manifest.reader("train.mfu_device_pct.learn512")(run)
    assert mfu == pytest.approx(100.0 * dv3_seq_flops.flops_per_grad_step(sizes, held_pairs=4 * 5120.0) / (0.5 * 197e12))
    assert dv3_seq_flops.flops_per_grad_step(sizes, streams=64.0) < dv3_seq_flops.flops_per_grad_step(sizes)
    assert manifest.reader("moe.max_over_mean_load")(run) == pytest.approx(2000.0 / (4 * 5120.0 / (4 * 32)))
    assert manifest.reader("replay.episode_ends_per_window")(run) == pytest.approx(88.0 / (8 * 8))
    # a program that does not count its one-token steps' routing (this PR's first counters): no share, not a guess
    run.marks["counters_close"] = {"seq_core": {"steps": 8, "held_pairs": routed["held_pairs"]}}
    assert manifest.reader("kernel.moe_grouped_roofline_pct")(run) is None
    monkeypatch.setattr(seq_scopes, "seconds", lambda run: None)
    run.marks["counters_close"] = {}
    for name in ("kernel.gdn_scan_roofline_pct", "kernel.moe_grouped_roofline_pct", "train.core_gdn_ms_per_grad_step",
                 "moe.max_over_mean_load", "collect.state_mib_per_env"):
        assert manifest.reader(name)(run) is None


def test_a_grouped_product_takes_the_scope_of_the_operation_before_it_in_its_loop():
    """On the chip a ``ragged-dot`` carries the compiler's own name and the
    ``while`` round it nothing: the loop's other operations say whether the
    pass is the window's or imagination's (names as a v5e trace has them)."""
    from benchmarks import seq_scopes

    window, imagined = "jit(f)/while/body/jvp(dv3/core/moe)/while/body/", "jit(f)/while/body/jvp(dv3/imagination)/dv3/core/moe/while/body/"
    scope_of = {"fusion.1": window + "gather", "fusion.2": window + "scatter-add", "fusion.3": imagined + "sub",
                "fusion.5": "jit(f)/while/body/dv3/heads/dot", "ragged-dot-none.1": "ragged-dot-none",
                "ragged-dot-none.2": "ragged-dot-none", "ragged-dot-none.3": "ragged-dot-none",
                "ragged-dot-metadata.4": "ragged-dot-metadata"}.get
    events = [("while.0", 0.0, 20.0),  # the burst's loop over gradient steps: no scope, as every ``while``
              ("while.1", 1.0, 5.0), ("fusion.1", 1.0, 2.0), ("ragged-dot-none.1", 2.0, 3.0), ("fusion.2", 3.0, 4.0),
              ("fusion.5", 5.0, 6.0),
              ("while.2", 6.0, 12.0), ("while.3", 7.0, 11.0), ("fusion.3", 7.0, 8.0), ("ragged-dot-metadata.4", 8.0, 8.5),
              ("ragged-dot-none.2", 8.5, 9.5),
              ("while.4", 13.0, 15.0), ("ragged-dot-none.3", 13.0, 14.0)]
    named = dict(name.split("\0") for name, _s, _e in seq_scopes.with_scopes(events, lambda op: scope_of(op) or ""))
    assert named["ragged-dot-none.1"] == window + "gather"
    assert named["ragged-dot-none.2"] == named["ragged-dot-metadata.4"] == imagined + "sub"
    assert named["ragged-dot-none.3"] == "ragged-dot-none"  # nothing before it in its loop: as it was
    assert named["fusion.5"].endswith("dv3/heads/dot") and named["while.1"] == ""


def test_the_four_chip_cells_traffic_is_the_learn_mix_number_for_number():
    """``learn.dp4`` exists only because the manifest takes a pair of
    configuration and traffic once: apart from ``why`` it is ``learn``."""
    mixes = []
    for name in ("learn", "learn.dp4"):
        with open(os.path.join(bench_tiny.BENCH, "traffic", name + ".json")) as f:
            mixes.append({k: v for k, v in json.load(f).items() if k != "why"})
    assert mixes[0] == mixes[1]
