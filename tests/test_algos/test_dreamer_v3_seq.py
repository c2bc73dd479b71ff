"""DreamerV3 over a sequence core (``algo.world_model.sequence_model``,
howto/sequence_core.md): the recipe through ``cli.run`` at tiny widths, the
entrypoints that refuse the core, and what acting reads of either core."""

import json

import gymnasium as gym
import jax
import numpy as np
import pytest

from sheeprl_tpu import cli
from sheeprl_tpu.config.engine import compose

TINY_AGENT = [
    "algo.dense_units=16", "algo.horizon=3",
    "algo.world_model.encoder.cnn_channels_multiplier=2", "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.discrete_size=254",
]
#: each sequence core's recipe and its ``core`` block at widths a test can hold (four layers each, or the
#: convolution-attention core's six with its two dense ones; 16 experts, 4 held)
RECIPES = {"qwen3_next": "dreamer_v3_qwen3next_ep16", "deepseek_v2": "dreamer_v3_dsv2lite_ep8",
           "lfm2_moe": "dreamer_v3_lfm2_ep4"}
TINY_CORES = {
    "qwen3_next": dict(
        hidden_size=64, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, num_attention_heads=4, head_dim=32, num_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=32, shared_expert_intermediate_size=32, vocab_size=256, chunk=16, cache_len=32,
    ),
    "deepseek_v2": dict(
        hidden_size=64, num_hidden_layers=4, intermediate_size=96, num_attention_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=32, vocab_size=256, chunk=16, cache_len=32,
    ),
    "lfm2_moe": dict(
        hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=2, num_experts=16,
        num_experts_per_tok=3, moe_intermediate_size=32, vocab_size=256, chunk=16, cache_len=32,
    ),
}
#: acting state of one env, bytes: three delta-rule states of 4 x 16 x 16 floats with their tails and a 32-token
#: cache of 2 x 32 values, or four rings of 32 latents of 32 + 8 floats, or five convolution layers' two rows of 64
#: floats and one 32-token cache of 2 x 2 heads of 16; two counters each
STATE_BYTES = {"qwen3_next": 3 * (4 * 256 + 3 * 128) * 4 + 2 * 32 * 64 * 4 + 8, "deepseek_v2": 4 * 32 * 40 * 4 + 8,
               "lfm2_moe": 5 * 2 * 64 * 4 + 2 * 32 * 32 * 4 + 8}
CORES = sorted(RECIPES)
OBS_SPACE = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})


def tiny_core(core):
    return TINY_AGENT + [f"algo.world_model.core.{k}={v}" for k, v in TINY_CORES[core].items()] + [
        "algo.world_model.core.held.index=0", "algo.world_model.core.held.of=4"]


def recipe_args(tmp_path, extra=(), core="qwen3_next"):
    return [
        f"exp={RECIPES[core]}", "fabric.accelerator=cpu", "fabric.precision=32-true", "metric.log_level=0",
        "env.num_envs=2", "per_rank_batch_size=2", "per_rank_sequence_length=32", "algo.learning_starts=128",
        "algo.train_every=8", "algo.per_rank_gradient_steps=2", "total_steps=176", "buffer.size=4096",
        "buffer.memmap=False", "checkpoint.every=1000000", "checkpoint.save_last=False", "algo.run_test=False",
        "env.capture_video=False", f"root_dir={tmp_path}/logs", "run_name=test", *tiny_core(core), *extra,
    ]


@pytest.mark.parametrize("core", CORES)
def test_the_recipe_trains_through_cli_run_and_counts_what_its_core_did(tmp_path, monkeypatch, core):
    """A few bursts of the recipe at tiny widths: windows of 32 steps over
    episodes of five, so every row holds resets; acting on the device."""
    monkeypatch.chdir(tmp_path)
    summary = tmp_path / "telemetry.json"
    cli.run(recipe_args(tmp_path, [
        "metric.telemetry.enabled=true", f"metric.telemetry.summary_path={summary}",
        f"metric.telemetry.trace_file={tmp_path / 'spans.jsonl'}", "metric.telemetry.learn.enabled=false",
        "metric.telemetry.flight.enabled=false", "metric.telemetry.live_interval_s=0", "metric.telemetry.poll_interval_s=0",
    ], core=core))
    with open(summary) as f:
        told = json.load(f)
    counts = told.get("seq_core") or told["counters"]["seq_core"]
    # seven bursts: the pretrain step and six of two steps
    assert counts["steps"] == 13
    assert counts["dropped_pairs"] == 0 and counts["held_pairs"] > 0
    assert counts["imagination_starts"] == 13 * 2 * 4 and counts["decode_steps"] == 13 * 7
    # 4 held experts a layer, at most 4 expert layers: a pass hits at most 16; a step's 7 one-token steps at most 7 * 16
    assert 0 < counts["experts_hit"] <= 13 * 16 and 0 < counts["imagination_experts_hit"] <= 13 * 7 * 16
    assert 0 < counts["imagination_pairs"] <= 13 * 7 * 4 * (2 * 4) * 3  # 8 streams, 3 experts a token
    assert counts["episode_ends"] > 13 * 2  # more than one a row
    assert counts["state_bytes_per_env"] == STATE_BYTES[core]
    if core == "deepseek_v2":
        # a token attends to its own episode's (five steps: at most ten tokens), in each of four layers
        assert 13 * 2 * 64 * 4 <= counts["attended_pairs"] <= 13 * 2 * 64 * 4 * 10
        # a one-token step reads at least its stream's own ring, and no more positions than it attends to
        assert 13 * 8 * 4 * 7 <= counts["decode_cache_tokens"] <= counts["decode_context_tokens"]
    elif core == "lfm2_moe":
        # one attention layer among the six; the largest load of any of the 16 router outputs in a step of
        # 128 tokens x 3 lies between the mean and every token; the bias moved by 0.001 a step at the most
        assert 13 * 2 * 64 <= counts["attended_pairs"] <= 13 * 2 * 64 * 10 and "decode_context_tokens" not in counts
        assert 13 * 128 * 3 / 16 <= counts["router_max_load"] <= 13 * 128
        assert 0.0 < counts["expert_bias_abs_max"] <= 13 * 0.001 + 1e-6
    else:
        assert "attended_pairs" not in counts and "decode_context_tokens" not in counts
    if core != "lfm2_moe":
        assert "router_max_load" not in counts and "expert_bias_abs_max" not in counts
    spans = [json.loads(line) for line in open(tmp_path / "spans.jsonl")]
    decode = [s for s in spans if s.get("name") == "Time/act_decode_time"]
    assert decode and all(s.get("args", {}).get("parent") == "Time/rollout_time" for s in decode)


@pytest.mark.parametrize("exp,core,why", [
    ("p2e_dv3_exploration", "qwen3_next", "not supported by this entrypoint"),
    ("p2e_dv3_exploration", "deepseek_v2", "not supported by this entrypoint"),
    ("p2e_dv3_exploration", "lfm2_moe", "not supported by this entrypoint"),
    ("dreamer_v3", "no_such_core", "no_such_core"),
])
def test_a_core_the_entrypoint_cannot_train_is_refused_when_the_agent_is_built(exp, core, why):
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

    cfg = compose("config", overrides=[f"exp={exp}", "env=dummy", f"algo.world_model.sequence_model={core}"])
    with pytest.raises(ValueError, match=why) as refused:
        build_agent(cfg, (4,), False, OBS_SPACE, jax.random.PRNGKey(0))
    assert "sequence_model" in str(refused.value)
    if exp == "dreamer_v3":  # the entrypoint that trains them names every core
        assert all(name in str(refused.value) for name in ("gru", "qwen3_next", "deepseek_v2", "lfm2_moe"))


@pytest.mark.parametrize("core", CORES)
@pytest.mark.parametrize("setting,message", [
    (["algo.player_on_host=True"], None),  # on the CPU no mirror is made: the recipe runs either way
    (["env.id=continuous_dummy"], "one discrete action"),
    (["algo.world_model.stochastic_size=2"], "one categorical"),
    (["algo.world_model.core.vocab_size=200"], "no room"),
])
def test_what_the_sequence_core_cannot_take_is_said_at_the_start(setting, message, core):
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

    cfg = compose("config", overrides=[f"exp={RECIPES[core]}", "fabric.precision=32-true", *tiny_core(core), *setting])
    continuous = "env.id=continuous_dummy" in setting
    if message is None:
        world_model, _, _, params = build_agent(cfg, (2,), continuous, OBS_SPACE, jax.random.PRNGKey(0))
        assert world_model.core.experts_held == 4 and "core" in params["world_model"]
        return
    with pytest.raises(ValueError, match=message) as refused:
        build_agent(cfg, (2,), continuous, OBS_SPACE, jax.random.PRNGKey(0))
    if continuous:  # the message names the configured model, whichever it is
        assert f"sequence_model={core}" in str(refused.value)


@pytest.mark.parametrize("core", ["gru", *CORES])
def test_acting_params_select_what_each_cores_acting_reads(core):
    from sheeprl_tpu.algos.dreamer_v3.agent import acting_params, build_agent

    if core == "gru":
        cfg = compose("config", overrides=[
            "exp=dreamer_v3", "env=dummy", "algo.dense_units=8", "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2", "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.transition_model.hidden_size=8", "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4", "cnn_keys.encoder=[rgb]",
        ])
        want = {"encoder", "rssm"}
    else:
        cfg = compose("config", overrides=[f"exp={RECIPES[core]}", "fabric.precision=32-true", *tiny_core(core)])
        want = {"encoder", "posterior", "core"}
    _, _, _, params = build_agent(cfg, (2,), False, OBS_SPACE, jax.random.PRNGKey(0))
    subset = acting_params(params["world_model"])
    assert set(subset) == want
    assert all(subset[k] is params["world_model"][k] for k in want)
    # the decoder and the reward and continue heads are training's alone, for either core
    assert {"cnn_decoder", "reward_model", "continue_model"} <= set(params["world_model"]) - set(subset)
