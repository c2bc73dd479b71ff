"""The latent-attention sequence-core cell's whole run, less the look for a
chip, at tiny widths on the CPU: ``correct`` on a sound run (the gradient steps
against the reference's losses and gradients, the recorded stretch of acting's
absorbed one-token path against the reference's full forward pass), the
control and every fault coming out as not correct, the counters its per-layer
metrics read, and this PR's manifest entries resolving their files."""

import json
import os
from types import SimpleNamespace

import pytest

import bench_tiny
import bench_tiny_dsv2
from benchmarks import dv3_seq_adapter, run
from benchmarks.manifest import Manifest

CONTROLS = ("fp8", "half_batch", "no_experts")
CELL = "dv3-dsv2lite.ep8.learn512"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tiny cell; its episodes last 10 to 20 steps, so the recorded stretch is cut to fit one."""
    steps, dv3_seq_adapter.STRETCH_STEPS = dv3_seq_adapter.STRETCH_STEPS, 6
    yield bench_tiny_dsv2.write_tiny_benchmark(str(tmp_path_factory.mktemp("benchdsv2")))
    dv3_seq_adapter.STRETCH_STEPS = steps


@pytest.fixture(scope="module")
def traced(tiny):
    manifest, cell = tiny
    return run.run_cell(cell, 2**31 + 7, 0.5, True, manifest=manifest, require_chip=False, accelerator="cpu",
                        controls=CONTROLS)


def test_a_sound_run_is_correct_against_the_reference(traced):
    assert traced["correct"] is True, traced["checks"]
    checks = traced["checks"]
    assert set(checks) == set(bench_tiny_dsv2.TINY_LIMITS)
    assert checks["staging_bad_rows"]["value"] == 0 and checks["dropped_pairs"]["value"] == 0
    # float32 against float32: the program's losses and gradients are the reference's
    assert checks["wm_loss_gap"]["value"] < 2e-4 and checks["grad_gap"]["value"] < 5e-3
    # acting's absorbed one-token path against the un-absorbed full forward pass of the tokens it fed
    assert checks["decode_gap"]["value"] < 1e-3
    window = traced["window"]
    assert window["compiles_in_window"] == 0 and window["cycles"] in (1, 2)
    assert window["grad_steps"] == 2 * window["cycles"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_control_and_each_fault_come_out_as_not_correct(traced, control):
    """The reference in fp8, with half of the batch left out, or with the held
    experts left out, standing in for the program: each fails a limit."""
    readings = traced["controls"][control]
    limits = bench_tiny_dsv2.TINY_LIMITS
    failed = {name for name, value in readings.items() if name in limits and not value <= limits[name]}
    expected = {"fp8": "decode_gap", "half_batch": "wm_loss_gap", "no_experts": "update_gap"}[control]
    assert expected in failed, readings


def test_the_traced_run_reads_the_latent_cores_counters(traced):
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    assert metrics["entry.compiles_in_window.learn"] == 0.0
    assert metrics["moe.max_over_mean_load"] >= 1.0
    assert 0.0 <= metrics["replay.episode_ends_per_window"] <= 32 / 10 + 1
    assert metrics["collect.decode_ms_p50"] > 0
    # three rings of 32 latents of 32 + 8 floats, two counters
    assert metrics["collect.state_mib_per_env"] == pytest.approx((3 * 32 * 40 * 4 + 8) / 2**20)
    # nothing ran on a chip here: the device readers have nothing to read, and say nothing
    for name in bench_tiny_dsv2.READERS:
        if name.startswith(("train.", "kernel.")):
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


def test_this_prs_cell_resolves_its_files():
    manifest = Manifest()
    workload = manifest.workload(CELL)
    assert (workload["chips"], workload["config"], workload["traffic"]) == (1, "dv3-dsv2lite.ep8", "learn512")
    cfg = manifest.config(workload)
    reference = manifest.reference(cfg)
    assert cfg["name"] == "dv3-dsv2lite.ep8" and hasattr(reference, "train_step") and hasattr(reference, "core_forward")
    limits = manifest.limits(workload)
    assert {"staging_bad_rows", "dropped_pairs", "wm_loss_gap", "update_gap", "decode_gap"} <= set(limits["limits"])
    assert limits["limits"]["dropped_pairs"] == 0
    assert "policy_loss_gap" in set(limits["limits"]) | set(limits.get("not_compared", ()))
    module = __import__(cfg["adapter"], fromlist=["Adapter"])
    assert issubclass(module.Adapter, dv3_seq_adapter.Adapter) and module.StopWindow is dv3_seq_adapter.StopWindow
    assert module.compare_with_reference is dv3_seq_adapter.compare_with_reference
    assert {m["name"] for m in manifest.metrics_for(workload, "end_to_end")} == {"replay_steps_per_s", "setup_s"}
    per_layer = manifest.metrics_for(workload, "per_layer")
    assert all(callable(manifest.reader(m["name"])) for m in per_layer)
    names = {m["name"] for m in per_layer}
    assert {"train.core_mla_ms_per_grad_step", "train.core_mlp_ms_per_grad_step", "train.core_moe_ms_per_grad_step",
            "train.core_head_ms_per_grad_step", "kernel.mla_window_roofline_pct", "kernel.mla_decode_roofline_pct",
            "kernel.moe_grouped_roofline_pct", "train.mfu_device_pct.learn512", "collect.state_mib_per_env",
            "device.idle_pct.learn", "train.imagination_ms_per_grad_step.learn"} <= names
    assert not any("gdn" in n or "core_attn" in n or n.startswith("publish.") or "rssm" in n for n in names)
    # the other sequence core's cell reports none of this PR's metrics
    other = {m["name"] for m in manifest.metrics_for(manifest.workload("dv3-qwen3next.ep16.learn512"), "per_layer")}
    assert not any("mla" in n or "core_mlp" in n for n in other)


def test_the_catalogs_numbers_are_in_the_configurations_file():
    """Every key of the published config stands in the file under its own name;
    the three that differ are the ones the manifest lists as reduced."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10944, "kv_lora_rank": 512, "max_position_embeddings": 163840, "model_type": "deepseek_v2",
        "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 2,
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts_per_tok": 6, "num_hidden_layers": 27,
        "num_key_value_heads": 16, "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                                                 "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                                                 "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128, "vocab_size": 102400,
    }
    with open(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_dsv2.CONFIG)) as f:
        mine = json.load(f)
    differ = {k for k, v in published.items() if mine[k] != v}
    assert differ == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    entry = next(c for c in Manifest().data["configs"] if c["name"] == "dv3-dsv2lite.ep8")
    assert differ <= set(entry["reduced"]) == set(mine["reduced"]) and entry["source"] == mine["source"]
    assert (mine["num_hidden_layers"], mine["n_routed_experts"], mine["vocab_size"]) == (6, 8, 12800)
    sizes = mine["sizes"]
    assert sizes["router_outputs"] == 64 and sizes["rope_scaling"] == published["rope_scaling"]
    assert sizes["discrete_size"] + sizes["actions"] == sizes["vocab_size"] and "aux_loss_alpha" in mine["assumed"]
    # the recipe's core block is the file's sizes, key for key (the adapter holds a run to them)
    from sheeprl_tpu.config.engine import compose

    core = compose("config", overrides=["exp=dreamer_v3_dsv2lite_ep8"])["algo"]["world_model"]["core"]
    adapter = __import__(mine["adapter"], fromlist=["CORE_KEYS"])
    assert {k: core[k] for k in adapter.CORE_KEYS} == {k: sizes[k] for k in adapter.CORE_KEYS}
    assert dict(core["rope_scaling"]) == sizes["rope_scaling"] and core["n_routed_experts"] == 64


def test_the_kernel_readers_count_each_kernels_own_work(monkeypatch):
    """The device readers on a made-up trace reduction: a share is the least
    time for the kernel's own operations and bytes over its device seconds,
    and says nothing where the trace, the scopes or the counters are missing."""
    from benchmarks import dv3_dsv2_flops, mla_scopes, reduce, seq_scopes

    manifest = Manifest()
    config = manifest.config(manifest.workload(CELL))
    sizes = config["sizes"]
    per_step = {"held_pairs": 5 * 6144.0, "experts_hit": 5 * 8.0, "imagination_pairs": 31 * 5 * 96.0,
                "imagination_experts_hit": 31 * 5 * 8.0, "attended_pairs": 6 * 8 * 1024 * 300.0,
                "decode_context_tokens": 31 * 6 * 128 * 300.0, "decode_cache_tokens": 31 * 6 * (8 * 700.0 + 128 * 16),
                "imagination_starts": 128.0, "decode_steps": 31.0, "max_load": 1500.0, "episode_ends": 11.0}
    counts = {"seq_core": {"steps": 8, **{k: 8 * v for k, v in per_step.items()}}}
    made_up = SimpleNamespace(
        config=config, device_kind="TPU v5 lite", recorder=SimpleNamespace(grad_steps=8),
        marks={"counters_open": {}, "counters_close": counts}, train_device_seconds=lambda: 8 * 0.4, _cache={},
    )
    monkeypatch.setattr(seq_scopes, "seconds", lambda run: {"kernel/ragged_dot": 8 * 0.060, "core/mla": 8 * 0.12, "core/mlp": 8 * 0.03})
    monkeypatch.setattr(mla_scopes, "seconds", lambda run: {"kernel/mla_scores": 8 * 0.050, "kernel/latent_decode": 8 * 0.020})
    peaks = reduce.DEVICE_PEAKS["TPU v5 lite"]
    least = lambda flops, nbytes: max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    flops, nbytes = dv3_dsv2_flops.mla_window_work(sizes, 8 * per_step["attended_pairs"], 8 * 6 * 8192.0)
    # 2 (192 + 128) operations a head and counted pair, three times for forward and backward
    assert flops == pytest.approx(8 * per_step["attended_pairs"] * 2 * 320 * 16 * 3)
    want = 100.0 * least(flops, nbytes) / (8 * 0.050)
    assert manifest.reader("kernel.mla_window_roofline_pct")(made_up) == pytest.approx(want) and 0 < want < 100
    flops, nbytes = dv3_dsv2_flops.mla_decode_work(
        sizes, 8 * per_step["decode_context_tokens"], 8 * per_step["decode_cache_tokens"], 8 * 128 * 31 * 6.0)
    assert flops == pytest.approx(8 * per_step["decode_context_tokens"] * 2 * 16 * (576 + 512))
    want = 100.0 * least(flops, nbytes) / (8 * 0.020)
    assert manifest.reader("kernel.mla_decode_roofline_pct")(made_up) == pytest.approx(want) and 0 < want < 100
    # masked positions are not work: a program that skipped them could not read over 100 %
    assert dv3_dsv2_flops.mla_decode_work(sizes, 1.0, 1.0, 0.0)[0] == 2 * 16 * 1088
    routed = [8 * per_step[k] for k in ("held_pairs", "experts_hit", "imagination_pairs", "imagination_experts_hit")]
    want = 100.0 * least(*dv3_dsv2_flops.moe_grouped_work(sizes, 8, *routed)) / (8 * 0.060)
    assert manifest.reader("kernel.moe_grouped_roofline_pct")(made_up) == pytest.approx(want) and 0 < want < 100
    assert manifest.reader("train.core_mla_ms_per_grad_step")(made_up) == pytest.approx(120.0)
    assert manifest.reader("train.core_mlp_ms_per_grad_step")(made_up) == pytest.approx(30.0)
    mfu = manifest.reader("train.mfu_device_pct.learn512")(made_up)
    required = dv3_dsv2_flops.flops_per_grad_step(sizes, held_pairs=per_step["held_pairs"], streams=128.0, decode_steps=31.0)
    assert mfu == pytest.approx(100.0 * required / (0.4 * 197e12)) and 15e12 < required < 22e12
    # the window pass's core is most of it: 8,192 tokens through 81 M + 5 x (31 M dense + 0.75 of 8.65 M x 6) parameters, thrice
    core = dv3_dsv2_flops.core_flops_per_token(sizes, 256.0, 0.75)
    assert 3 * 8192 * sum(core.values()) == pytest.approx(13.6e12, rel=0.03)
    # another core's program, or the parent's: no such scope, no such counter, no share
    monkeypatch.setattr(mla_scopes, "seconds", lambda run: None)
    assert manifest.reader("kernel.mla_window_roofline_pct")(made_up) is None
    made_up.marks["counters_close"] = {"seq_core": {"steps": 8, "held_pairs": routed[0]}}
    monkeypatch.setattr(mla_scopes, "seconds", lambda run: {"kernel/mla_scores": 1.0, "kernel/latent_decode": 1.0})
    for name in ("kernel.mla_window_roofline_pct", "kernel.mla_decode_roofline_pct"):
        assert manifest.reader(name)(made_up) is None
    qwen = SimpleNamespace(**{**vars(made_up), "config": manifest.config(manifest.workload("dv3-qwen3next.ep16.learn512")),
                              "marks": {"counters_open": {}, "counters_close": counts}})
    for name in ("kernel.mla_window_roofline_pct", "kernel.mla_decode_roofline_pct"):
        assert manifest.reader(name)(qwen) is None
