"""Every shipped experiment recipe must compose (reference recipes run
unchanged per the Hydra-surface parity requirement)."""

import os

import pytest

from sheeprl_tpu.config.engine import compose

_EXP_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "sheeprl_tpu", "configs", "exp"
)
_EXPS = sorted(
    f[: -len(".yaml")]
    for f in os.listdir(_EXP_DIR)
    if f.endswith(".yaml") and f != "default.yaml"
)


def _composed(exp):
    overrides = [f"exp={exp}"]
    if "finetuning" in exp or "fntn" in exp:
        overrides.append("checkpoint.exploration_ckpt_path=/tmp/dummy")
    return compose("config", overrides=overrides)


@pytest.mark.parametrize("exp", _EXPS)
def test_exp_recipe_composes(exp):
    cfg = _composed(exp)
    assert cfg.algo.name
    assert cfg.env.wrapper._target_


@pytest.mark.parametrize("exp", _EXPS)
def test_only_the_dreamer_v3_family_acts_on_the_device_that_trains(exp):
    """The DreamerV3 and P2E-DV3 recipes act on the device that holds the trained
    leaves (no host mirror; PERF.md, PR 35); every other family keeps the global
    default, acting on a CPU mirror, which no chip run has measured against."""
    assert _composed(exp).algo.player_on_host is not exp.startswith(("dreamer_v3", "p2e_dv3"))


def test_headline_recipes_carry_reference_presets():
    cfg = compose("config", overrides=["exp=dreamer_v3_100k_ms_pacman"])
    assert cfg.total_steps == 100000
    assert cfg.algo.world_model.recurrent_model.recurrent_state_size == 512
    assert cfg.env.id == "MsPacmanNoFrameskip-v4"

    cfg = compose("config", overrides=["exp=dreamer_v3_XL_crafter"])
    assert cfg.algo.world_model.recurrent_model.recurrent_state_size == 4096
    assert cfg.algo.world_model.encoder.cnn_channels_multiplier == 96
    assert cfg.mlp_keys.encoder == ["reward"] and cfg.mlp_keys.decoder == []

    cfg = compose("config", overrides=["exp=dreamer_v2_ms_pacman"])
    assert cfg.buffer.type == "episode" and cfg.buffer.prioritize_ends
    assert cfg.algo.world_model.use_continues


def test_doapp_recipes_carry_reference_presets():
    # the four DOA++ DIAMBRA recipes (reference exp/*doapp*.yaml): L-preset
    # model sizes, pixel+vector key sets, and the combo-discrete env setup
    cfg = compose("config", overrides=["exp=dreamer_v3_L_doapp"])
    assert cfg.total_steps == 5_000_000 and cfg.env.num_envs == 8
    assert cfg.algo.world_model.recurrent_model.recurrent_state_size == 2048
    assert cfg.algo.world_model.encoder.cnn_channels_multiplier == 64
    assert cfg.cnn_keys.encoder == ["frame"] and "stage" in cfg.mlp_keys.encoder

    cfg = compose(
        "config", overrides=["exp=dreamer_v3_L_doapp_128px_gray_combo_discrete"]
    )
    assert cfg.env.screen_size == 128 and cfg.env.grayscale
    assert cfg.env.reward_as_observation
    assert "reward" in cfg.mlp_keys.encoder and "reward" not in cfg.mlp_keys.decoder
    assert cfg.per_rank_batch_size == 8

    cfg = compose(
        "config",
        overrides=["exp=p2e_dv3_expl_L_doapp_128px_gray_combo_discrete_15Mexpl_20Mstps"],
    )
    assert cfg.total_steps == 20_000_000 and cfg.env.num_envs == 16
    assert cfg.algo.world_model.encoder.cnn_channels_multiplier == 48
    assert cfg.algo.world_model.recurrent_model.recurrent_state_size == 1024
    assert cfg.algo.learning_starts == 131072 and cfg.algo.train_every == 1
    assert cfg.fabric.precision == "bf16-mixed"

    cfg = compose(
        "config",
        overrides=[
            "exp=p2e_dv3_fntn_L_doapp_64px_gray_combo_discrete_5Mstps",
            "checkpoint.exploration_ckpt_path=/tmp/dummy",
        ],
    )
    assert cfg.total_steps == 5_000_000 and cfg.per_rank_batch_size == 16
    assert cfg.env.screen_size == 64 and cfg.env.grayscale
    assert cfg.algo.world_model.recurrent_model.recurrent_state_size == 1024
