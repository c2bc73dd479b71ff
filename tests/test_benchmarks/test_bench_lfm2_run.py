"""The convolution-attention sequence-core cell's whole run, less the look for
a chip, at tiny widths on the CPU: ``correct`` on a sound run (the gradient
steps against the reference's losses and gradients, the selection bias entry
by entry, the recorded stretch of acting's one-token path against the
reference's full forward pass), the control and every fault coming out as not
correct, the counters its per-layer metrics read, and this PR's manifest
entries resolving their files."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
import bench_tiny_lfm2
from benchmarks import dv3_lfm2_adapter, dv3_seq_adapter, run
from benchmarks.manifest import Manifest

CONTROLS = ("fp8", "half_batch", "no_experts", "no_bias")
CELL = "dv3-lfm2.ep4.learn512"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tiny cell; its episodes last 10 to 20 steps, so the recorded stretch is cut to fit one."""
    steps, dv3_seq_adapter.STRETCH_STEPS = dv3_seq_adapter.STRETCH_STEPS, 6
    yield bench_tiny_lfm2.write_tiny_benchmark(str(tmp_path_factory.mktemp("benchlfm2")))
    dv3_seq_adapter.STRETCH_STEPS = steps


@pytest.fixture(scope="module")
def traced(tiny):
    manifest, cell = tiny
    return run.run_cell(cell, 2**31 + 7, 0.5, True, manifest=manifest, require_chip=False, accelerator="cpu",
                        controls=CONTROLS)


def test_a_sound_run_is_correct_against_the_reference(traced):
    assert traced["correct"] is True, traced["checks"]
    checks = traced["checks"]
    assert set(checks) == set(bench_tiny_lfm2.TINY_LIMITS)
    assert checks["staging_bad_rows"]["value"] == 0 and checks["dropped_pairs"]["value"] == 0
    # float32 against float32: the program's losses and gradients are the reference's
    assert checks["wm_loss_gap"]["value"] < 2e-4 and checks["grad_gap"]["value"] < 5e-3
    # acting's one-token path against the full forward pass of the tokens it fed: the median position, and the worst
    assert 0 < checks["decode_gap"]["value"] <= checks["decode_gap_worst"]["value"] < 1e-3
    # the balance step moved every held entry of the 4 x 16 biases as the reference's did
    assert checks["bias_bad_entries"]["value"] == 0 and checks["bias_entries_left_out"]["value"] <= 12
    window = traced["window"]
    assert window["compiles_in_window"] == 0 and window["cycles"] in (1, 2)
    assert window["grad_steps"] == 2 * window["cycles"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_control_and_each_fault_come_out_as_not_correct(traced, control):
    """The reference in fp8, with half of the batch left out, with the held
    experts left out, or choosing its experts without the bias, standing in
    for the program: each fails a limit."""
    readings = traced["controls"][control]
    limits = bench_tiny_lfm2.TINY_LIMITS
    failed = {name for name, value in readings.items() if name in limits and not value <= limits[name]}
    expected = {"fp8": "decode_gap", "half_batch": "wm_loss_gap", "no_experts": "update_gap",
                "no_bias": "bias_entries_left_out"}[control]
    assert expected in failed, readings
    if control == "no_bias":  # another routing altogether: the losses move too
        assert "wm_loss_gap" in failed, readings


def test_the_traced_run_reads_the_cores_counters(traced):
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    assert metrics["entry.compiles_in_window.learn"] == 0.0
    # 128 tokens x 3 of 16 outputs a step: the largest load lies between the mean and every token
    assert 1.0 <= metrics["moe.router_max_over_mean_load"] <= 16 / 3
    assert 0.0 <= metrics["replay.episode_ends_per_window"] <= 32 / 10 + 1
    assert metrics["collect.decode_ms_p50"] > 0
    # five convolution layers' two rows of 64 floats, one ring of 32 keys and values of 2 x 16, two counters
    assert metrics["collect.state_mib_per_env"] == pytest.approx((5 * 2 * 64 * 4 + 2 * 32 * 32 * 4 + 8) / 2**20)
    # nothing ran on a chip here: the device readers have nothing to read, and say nothing
    for name in bench_tiny_lfm2.READERS:
        if name.startswith(("train.", "kernel.")):
            assert name not in metrics


def test_a_state_left_unchanged_in_the_timed_path_comes_out_as_not_correct(tiny):
    """A fault planted in the program's own burst (the family adapter's):
    every step's new parameters thrown away, the moved biases with them."""
    manifest, cell = tiny
    result = run.run_cell(cell, 7, 0.2, False, manifest=manifest, require_chip=False, accelerator="cpu",
                          fault="state_unchanged")
    assert result["correct"] is False, result["checks"]
    failed = {k for k, row in result["checks"].items() if not row["value"] <= row["limit"]}
    assert {"update_gap", "bias_bad_entries"} <= failed, result["checks"]


def test_the_bias_numbers_hold_the_entries_far_from_the_mean_exactly():
    """Two layers of four experts, mean load 10, two steps. The two routings
    disagree on one pair in layer 0 (loads 14/6 against 13/7) and on none in
    layer 1: an entry at 10 or 11 in layer 0 is left out, every other is held
    to the bit, in this step and the next."""
    ref_load = np.array([[13.0, 7.0, 11.0, 9.0], [10.0, 20.0, 5.0, 5.0]], np.float32)
    load = ref_load.copy()
    load[0, :2] = (14.0, 6.0)
    ref_bias = np.float32(0.001) * np.sign(10.0 - ref_load)
    sound = [(ref_load, ref_bias), (ref_load, 2 * ref_bias)]
    assert dv3_lfm2_adapter.bias_numbers(sound, sound) == {"bias_bad_entries": 0, "bias_entries_left_out": 1}  # layer 1's 10
    mine = [(load, ref_bias), (load, 2 * ref_bias)]
    assert dv3_lfm2_adapter.bias_numbers(mine, sound) == {"bias_bad_entries": 0, "bias_entries_left_out": 3}
    off = ref_bias.copy()
    off[0, 2], off[1, 1] = -off[0, 2], 0.0  # one entry that is left out, one that is held
    assert dv3_lfm2_adapter.bias_numbers([(load, off), (load, 2 * ref_bias)], sound)["bias_bad_entries"] == 1
    # a routing far from the reference's leaves every entry out, and that count has a limit of its own
    far = np.array([[40.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 40.0]], np.float32)
    assert dv3_lfm2_adapter.bias_numbers([(far, off)], sound[:1]) == {"bias_bad_entries": 0, "bias_entries_left_out": 8}


class _Forward:
    """A reference whose full forward pass gives ``sound``, or in another
    ``mode`` ``altered``, whatever the tokens: [positions, codes]."""

    def __init__(self, sound, altered=None):
        self.sound, self.altered = sound, altered

    freeze = staticmethod(lambda sizes: ())

    def core_forward(self, flat, tokens, reset, *, sizes, mode="f32", held=True):
        out = self.sound if mode == "f32" and held else self.altered
        return np.concatenate([out, np.zeros((len(tokens) // 2 - len(out), out.shape[1]), np.float32)])


@pytest.mark.parametrize("case", ["one_flip", "every_position", "control"])
def test_the_decode_statistic_is_the_median_position_and_reads_the_worst_beside_it(case):
    """Twelve recorded steps of two envs, env 1 reset at step 2 (ten steps
    since, the longest): its ten action positions are compared. One position
    off by half a per cent of the largest logit — a routing flip — leaves the
    median where rounding left it and shows in the worst; every position off by
    0.9 per cent — a lower precision — moves the median; a control is the
    altered reference against the sound one, not the program's logits."""
    import jax

    rng = np.random.default_rng(3)
    sound = rng.normal(size=(10, 7)).astype(np.float32)
    sound[:, 0] = 4.0  # the largest logit of every position
    mine = sound + np.float32(4e-4) * np.sign(rng.normal(size=sound.shape)).astype(np.float32)
    if case == "one_flip":
        mine[6, 3] += 0.02
    if case == "every_position":
        mine[:, 2] += 0.036
    stretch = []
    for t in range(12):
        logits = np.zeros((2, 7), np.float32)
        if t >= 2:
            logits[1] = mine[t - 2]
        stretch.append({"reset": np.array([t == 5, t == 2], np.float32), "tokens": np.zeros((2, 2), np.int32),
                        "prior_logits": logits})
    config = {"sizes": {"chunk": 4}}
    device = jax.devices()[0]
    if case == "control":
        altered = sound.copy()
        altered[:, 1] -= 0.036
        found, n = dv3_lfm2_adapter.decode_gaps(_Forward(sound, altered), config, stretch, {}, device, mode="fp8")
        assert n == 10 and found["decode_gap"] == pytest.approx(0.009, rel=1e-3) == pytest.approx(found["decode_gap_worst"], rel=1e-3)
        return
    found, n = dv3_lfm2_adapter.decode_gaps(_Forward(sound), config, stretch, {}, device)
    assert n == 10
    if case == "one_flip":
        assert found["decode_gap"] == pytest.approx(1e-4, rel=1e-2) and found["decode_gap_worst"] == pytest.approx(0.0051, rel=1e-2)
    else:
        assert found["decode_gap"] == pytest.approx(0.009, rel=2e-2) == pytest.approx(found["decode_gap_worst"], rel=3e-2)
    assert dv3_lfm2_adapter.decode_gaps(_Forward(sound), config, [dict(row, reset=np.zeros(2, np.float32)) for row in stretch],
                                        {}, device) == ({"decode_gap": None, "decode_gap_worst": None}, 0)


def test_this_prs_cell_resolves_its_files():
    manifest = Manifest()
    workload = manifest.workload(CELL)
    assert (workload["chips"], workload["config"], workload["traffic"]) == (1, "dv3-lfm2.ep4", "learn512")
    assert "1/4" in workload["why"] and "over their share" in workload["why"]
    cfg = manifest.config(workload)
    reference = manifest.reference(cfg)
    assert cfg["name"] == "dv3-lfm2.ep4" and hasattr(reference, "train_step") and hasattr(reference, "core_forward")
    limits = manifest.limits(workload)
    assert {"staging_bad_rows", "dropped_pairs", "wm_loss_gap", "update_gap", "decode_gap", "bias_bad_entries",
            "bias_entries_left_out"} <= set(limits["limits"])
    assert limits["limits"]["dropped_pairs"] == 0 == limits["limits"]["bias_bad_entries"]
    assert "policy_loss_gap" in set(limits["limits"]) | set(limits.get("not_compared", ()))
    assert "decode_gap_worst" in limits["not_compared"]  # one routing flip decides it: read, with its readings in the file
    module = __import__(cfg["adapter"], fromlist=["Adapter"])
    assert issubclass(module.Adapter, dv3_seq_adapter.Adapter) and module.StopWindow is dv3_seq_adapter.StopWindow
    assert set(module.CONTROLS) == set(dv3_seq_adapter.CONTROLS) | {"no_bias"}
    assert {m["name"] for m in manifest.metrics_for(workload, "end_to_end")} == {"replay_steps_per_s", "setup_s"}
    per_layer = manifest.metrics_for(workload, "per_layer")
    assert all(callable(manifest.reader(m["name"])) for m in per_layer)
    names = {m["name"] for m in per_layer}
    assert {"train.core_conv_ms_per_grad_step", "train.core_attn_ms_per_grad_step", "train.core_mlp_ms_per_grad_step",
            "train.core_moe_ms_per_grad_step", "train.core_head_ms_per_grad_step", "kernel.shortconv_roofline_pct",
            "kernel.gqa_window_roofline_pct", "kernel.moe_grouped_roofline_pct", "moe.router_max_over_mean_load",
            "train.mfu_device_pct.learn512", "collect.state_mib_per_env", "device.idle_pct.learn",
            "train.imagination_ms_per_grad_step.learn", "train.unscoped_pct.learn", "device.hbm_peak_gib.learn"} <= names
    # the reader that divides by every layer would read 1.5 x the ratio here: the cell is not on its list
    assert "moe.max_over_mean_load" not in names
    assert not any("gdn" in n or "mla" in n or n.startswith("publish.") or "rssm" in n for n in names)
    # the cell is in every list the latent-attention cell is in, but the latent attention's own and that one
    other = {m["name"] for m in manifest.metrics_for(manifest.workload("dv3-dsv2lite.ep8.learn512"), "per_layer")}
    assert {n for n in other - names} == {"train.core_mla_ms_per_grad_step", "kernel.mla_window_roofline_pct",
                                          "kernel.mla_decode_roofline_pct", "moe.max_over_mean_load"}
    # no other cell reports this PR's metrics
    new = {"train.core_conv_ms_per_grad_step", "kernel.shortconv_roofline_pct", "kernel.gqa_window_roofline_pct",
           "moe.router_max_over_mean_load"}
    assert all(m["workloads"] == [CELL] for m in manifest.data["per_layer"] if m["name"] in new)
    assert [c["name"] for c in manifest.data["configs"]][-1] == "dv3-lfm2.ep4" and manifest.data["workloads"][-1] is workload


def test_the_catalogs_numbers_are_in_the_configurations_file():
    """Every key of the published config stands in the file under its own name;
    the three that differ are the ones the manifest lists as reduced."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                        "conv", "conv", "full_attention", "conv", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    with open(os.path.join(bench_tiny.BENCH, "configs", bench_tiny_lfm2.CONFIG)) as f:
        mine = json.load(f)
    differ = {k for k, v in published.items() if mine[k] != v}
    assert differ == {"num_hidden_layers", "num_experts", "vocab_size"}
    entry = next(c for c in Manifest().data["configs"] if c["name"] == "dv3-lfm2.ep4")
    assert differ <= set(entry["reduced"]) == set(mine["reduced"]) and entry["source"] == mine["source"]
    assert (mine["num_hidden_layers"], mine["num_experts"], mine["vocab_size"]) == (6, 8, 16384)
    sizes = mine["sizes"]
    assert sizes["router_outputs"] == 32 and sizes["layer_types"] == published["layer_types"]
    assert sizes["discrete_size"] + sizes["actions"] == sizes["vocab_size"]
    assert {"balance step", "tie_word_embeddings", "auxiliary loss", "rotary positions", "sizes.cache_len",
            "sizes.batch_size", "fabric.precision"} <= set(mine["assumed"]) and "4 chips" in mine["deployment"]
    # the core block the file's overrides compose is the file's sizes, key for key (the adapter holds a run to them);
    # the recipe alone differs in one assumed number, the balance step's rate: the paper's, for a run of its length
    from sheeprl_tpu.config.engine import compose

    core = compose("config", overrides=mine["overrides"])["algo"]["world_model"]["core"]
    assert {k: core[k] for k in dv3_lfm2_adapter.CORE_KEYS} == {k: sizes[k] for k in dv3_lfm2_adapter.CORE_KEYS}
    recipe = compose("config", overrides=["exp=dreamer_v3_lfm2_ep4"])["algo"]["world_model"]["core"]
    assert {k for k in dv3_lfm2_adapter.CORE_KEYS if recipe[k] != sizes[k]} == {"bias_update_rate"}
    assert (recipe["bias_update_rate"], sizes["bias_update_rate"]) == (0.001, 0.02) and "0.02" in mine["assumed"]["balance step"]
    assert list(core["layer_types"]) == sizes["layer_types"] and core["num_experts"] == 32 and core["held"]["of"] == 4


def test_the_kernel_readers_count_each_kernels_own_work(monkeypatch):
    """The device readers on a made-up trace reduction: a share is the least
    time for the kernel's own operations and bytes over its device seconds,
    and says nothing where the trace, the scopes or the counters are missing."""
    from benchmarks import dv3_lfm2_flops, lfm2_scopes, reduce, seq_scopes

    manifest = Manifest()
    config = manifest.config(manifest.workload(CELL))
    sizes = config["sizes"]
    per_step = {"held_pairs": 4 * 8192.0, "experts_hit": 4 * 8.0, "imagination_pairs": 31 * 4 * 128.0,
                "imagination_experts_hit": 31 * 4 * 8.0, "attended_pairs": 8 * 1024 * 300.0, "router_max_load": 2048.0,
                "imagination_starts": 128.0, "decode_steps": 31.0, "max_load": 1500.0, "episode_ends": 11.0}
    counts = {"seq_core": {"steps": 8, **{k: 8 * v for k, v in per_step.items()}}}
    made_up = SimpleNamespace(
        config=config, device_kind="TPU v5 lite", recorder=SimpleNamespace(grad_steps=8), chips=1,
        marks={"counters_open": {}, "counters_close": counts}, train_device_seconds=lambda: 8 * 0.3, _cache={},
    )
    monkeypatch.setattr(seq_scopes, "seconds", lambda run: {"kernel/ragged_dot": 8 * 0.050, "core/conv": 8 * 0.06, "core/attn": 8 * 0.02})
    monkeypatch.setattr(lfm2_scopes, "seconds", lambda run: {"kernel/gate_conv": 8 * 0.012, "kernel/gqa_scores": 8 * 0.010})
    peaks = reduce.DEVICE_PEAKS["TPU v5 lite"]
    least = lambda flops, nbytes: max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert dv3_lfm2_flops.layers_of(sizes) == {"attn": 1, "conv": 5, "mlp": 2, "moe": 4}
    flops, nbytes = dv3_lfm2_flops.shortconv_work(sizes, 8 * 5 * 8192.0)
    # B, u, C in and the output out, in bf16, and as much again backward: bytes bound it
    assert nbytes == 8 * 5 * 8192 * 2 * 4 * 2048 * 2 and nbytes / peaks["hbm_bytes_per_s"] > flops / peaks["bf16_flops_per_s"]
    want = 100.0 * least(flops, nbytes) / (8 * 0.012)
    assert manifest.reader("kernel.shortconv_roofline_pct")(made_up) == pytest.approx(want) and 0 < want < 100
    flops, nbytes = dv3_lfm2_flops.gqa_window_work(sizes, 8 * per_step["attended_pairs"], 8 * 8192.0)
    # 2 (64 + 64) operations a query head and counted pair, three times for forward and backward
    assert flops == pytest.approx(8 * per_step["attended_pairs"] * 2 * 128 * 32 * 3)
    assert nbytes == 2 * 8 * 8192 * (2 * 32 + 2 * 8) * 64 * 2
    want = 100.0 * least(flops, nbytes) / (8 * 0.010)
    assert manifest.reader("kernel.gqa_window_roofline_pct")(made_up) == pytest.approx(want) and 0 < want < 100
    routed = [8 * per_step[k] for k in ("held_pairs", "experts_hit", "imagination_pairs", "imagination_experts_hit")]
    want = 100.0 * least(*dv3_lfm2_flops.moe_grouped_work(sizes, 8, *routed)) / (8 * 0.050)
    assert manifest.reader("kernel.moe_grouped_roofline_pct")(made_up) == pytest.approx(want) and 0 < want < 100
    assert manifest.reader("train.core_conv_ms_per_grad_step")(made_up) == pytest.approx(60.0)
    assert manifest.reader("train.core_attn_ms_per_grad_step")(made_up) == pytest.approx(20.0)
    # 8,192 tokens x 4 of 32 outputs: a mean load of 1,024
    assert manifest.reader("moe.router_max_over_mean_load")(made_up) == pytest.approx(2.0)
    mfu = manifest.reader("train.mfu_device_pct.learn512")(made_up)
    required = dv3_lfm2_flops.flops_per_grad_step(sizes, held_pairs=per_step["held_pairs"], streams=128.0, decode_steps=31.0)
    assert mfu == pytest.approx(100.0 * required / (0.3 * 197e12)) and 12e12 < required < 20e12
    # the window pass's core is most of it: 8,192 tokens through the six layers' products, thrice
    core = dv3_lfm2_flops.core_flops_per_token(sizes, 256.0, 1.0)
    products = 5 * 16.78e6 + 10.49e6 + 2 * 44.04e6 + 4 * (0.066e6 + 11.01e6)
    assert sum(core.values()) == pytest.approx(2 * products, rel=0.02)
    # another core's program, or the parent's: no such scope, no such counter, no share
    monkeypatch.setattr(lfm2_scopes, "seconds", lambda run: None)
    assert manifest.reader("kernel.shortconv_roofline_pct")(made_up) is None
    assert manifest.reader("kernel.gqa_window_roofline_pct")(made_up) is None
    made_up.marks["counters_close"] = {"seq_core": {"steps": 8, "held_pairs": routed[0]}}
    monkeypatch.setattr(lfm2_scopes, "seconds", lambda run: {"kernel/gate_conv": 1.0, "kernel/gqa_scores": 1.0})
    assert manifest.reader("kernel.gqa_window_roofline_pct")(made_up) is None
    assert manifest.reader("moe.router_max_over_mean_load")(made_up) is None
    dsv2 = SimpleNamespace(**{**vars(made_up), "config": manifest.config(manifest.workload("dv3-dsv2lite.ep8.learn512")),
                              "marks": {"counters_open": {}, "counters_close": counts}})
    for name in ("kernel.shortconv_roofline_pct", "kernel.gqa_window_roofline_pct"):
        assert manifest.reader(name)(dsv2) is None
