import os

import pytest

from sheeprl_tpu.config import compose, yaml_load
from sheeprl_tpu.config.engine import SEARCH_PATH_ENV_VAR


def test_compose_ppo_defaults():
    cfg = compose(overrides=["exp=ppo"])
    assert cfg.algo.name == "ppo"
    assert cfg.env.id == "CartPole-v1"
    assert cfg.total_steps == 65536
    assert cfg.algo.optimizer.lr == pytest.approx(1e-3)
    assert cfg.buffer.size == cfg.algo.rollout_steps


def test_group_override_beats_exp():
    cfg = compose(overrides=["exp=ppo", "env=dummy"])
    assert cfg.env.id == "discrete_dummy"
    assert cfg.env.wrapper._target_ == "sheeprl_tpu.utils.env.get_dummy_env"


def test_value_override_and_interpolation_tracking():
    cfg = compose(overrides=["exp=ppo", "algo.rollout_steps=8"])
    assert cfg.algo.rollout_steps == 8
    assert cfg.buffer.size == 8  # ${algo.rollout_steps}
    assert cfg.algo.encoder.dense_units == cfg.algo.dense_units


def test_missing_exp_raises():
    with pytest.raises(ValueError, match="exp"):
        compose(overrides=[])


def test_unknown_exp_raises():
    with pytest.raises(FileNotFoundError):
        compose(overrides=["exp=not_an_experiment"])


def test_scientific_notation_floats():
    assert yaml_load("2e-4") == pytest.approx(2e-4)
    assert yaml_load("1e-3") == pytest.approx(1e-3)
    assert yaml_load("1_000_000") == 1_000_000
    assert yaml_load("lr: 1e-4")["lr"] == pytest.approx(1e-4)


def test_add_and_delete_overrides():
    cfg = compose(overrides=["exp=ppo", "+algo.new_knob=3", "~algo.anneal_lr"])
    assert cfg.algo.new_knob == 3
    assert "anneal_lr" not in cfg.algo


def test_search_path_env_var(tmp_path):
    exp_dir = tmp_path / "exp"
    exp_dir.mkdir()
    (exp_dir / "my_exp.yaml").write_text(
        "# @package _global_\n"
        "defaults:\n"
        "  - ppo\n"
        "  - _self_\n"
        "total_steps: 123\n"
    )
    os.environ[SEARCH_PATH_ENV_VAR] = f"file://{tmp_path};pkg://sheeprl_tpu.configs"
    try:
        cfg = compose(overrides=["exp=my_exp"])
        assert cfg.total_steps == 123
        assert cfg.algo.name == "ppo"
    finally:
        del os.environ[SEARCH_PATH_ENV_VAR]


def test_now_resolver_and_run_name():
    cfg = compose(overrides=["exp=ppo", "exp_name=abc", "seed=9"])
    assert cfg.run_name.endswith("_abc_9")


def test_dotdict_round_trip():
    cfg = compose(overrides=["exp=ppo"])
    d = cfg.as_dict()
    assert isinstance(d, dict)
    assert d["algo"]["name"] == "ppo"


# ---------------------------------------------------------------------------
# multirun / sweep grammar (reference CLI surface: hydra 1.3 basic sweeper
# via @hydra.main, /root/reference/sheeprl/cli.py:265-273)
# ---------------------------------------------------------------------------


def test_expand_multirun_cartesian_product():
    from sheeprl_tpu.config.engine import expand_multirun

    jobs = expand_multirun(["exp=ppo,a2c", "optim.lr=1e-3,1e-4", "seed=5"])
    assert len(jobs) == 4
    assert jobs[0] == ["exp=ppo", "optim.lr=1e-3", "seed=5"]
    assert jobs[-1] == ["exp=a2c", "optim.lr=1e-4", "seed=5"]
    # order: later overrides are the fast axis, like hydra's sweeper
    assert jobs[1] == ["exp=ppo", "optim.lr=1e-4", "seed=5"]


def test_expand_multirun_brackets_and_quotes_not_swept():
    from sheeprl_tpu.config.engine import expand_multirun

    jobs = expand_multirun(
        ["cnn_keys.encoder=[rgb,depth]", 'exp_name="a,b"', "algo.mlp_layers=2,3"]
    )
    assert len(jobs) == 2
    assert jobs[0][0] == "cnn_keys.encoder=[rgb,depth]"
    assert jobs[0][1] == 'exp_name="a,b"'
    assert jobs[0][2] == "algo.mlp_layers=2"


def test_multirun_cli_runs_each_job(tmp_path, monkeypatch):
    """-m sweeps one axis end-to-end through the real CLI (dry runs)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu import cli

    cli.run(
        [
            "-m",
            "exp=ppo",
            "seed=5,6",
            "dry_run=True",
            "fabric.accelerator=cpu",
            "env=dummy",
            "env.id=discrete_dummy",
            "env.sync_env=True",
            "env.num_envs=1",
            "env.capture_video=False",
            "cnn_keys.encoder=[rgb]",
            "mlp_keys.encoder=[]",
            "algo.mlp_layers=1",
            "algo.dense_units=8",
            "per_rank_batch_size=2",
            "metric.log_level=0",
            "checkpoint.save_last=False",
            "algo.run_test=False",
        ]
    )
    runs = sorted((tmp_path / "logs" / "runs" / "ppo" / "discrete_dummy").glob("*/version_*"))
    assert len(runs) == 2, runs


def test_resume_reapplies_explicit_overrides(tmp_path):
    """Explicit value overrides on a resume command survive the config swap
    (round-5: `algo.train_every=1e9 metric.log_level=0` were silently dropped
    by the wholesale checkpoint-config restore)."""
    import yaml

    from sheeprl_tpu.cli import resume_from_checkpoint

    stored = compose(overrides=["exp=ppo", "exp_name=orig", "total_steps=5000"])
    log_dir = tmp_path / "run"
    (log_dir / ".hydra").mkdir(parents=True)
    (log_dir / "checkpoint").mkdir()
    (log_dir / ".hydra" / "config.yaml").write_text(yaml.safe_dump(stored.as_dict()))
    ckpt = log_dir / "checkpoint" / "ckpt_100_0"
    ckpt.mkdir()

    overrides = [
        "exp=ppo",
        f"checkpoint.resume_from={ckpt}",
        "algo.update_epochs=99",
        "metric.log_level=0",
    ]
    cfg = compose(overrides=overrides)
    merged = resume_from_checkpoint(cfg, overrides)
    # explicit value overrides win over the checkpointed config
    assert merged.algo.update_epochs == 99
    assert merged.metric.log_level == 0
    # everything else comes from the checkpoint's stored config
    assert merged.total_steps == 5000
    assert merged.algo.name == "ppo"
    # bare-resume keys keep checkpoint values when not overridden
    merged2 = resume_from_checkpoint(
        compose(overrides=["exp=ppo", f"checkpoint.resume_from={ckpt}"]),
        ["exp=ppo", f"checkpoint.resume_from={ckpt}"],
    )
    assert merged2.total_steps == 5000
    assert merged2.algo.update_epochs == stored.algo.update_epochs


def test_resume_accounts_for_every_typed_override(tmp_path):
    """Silently-skipped override classes (group selections, dict-valued keys,
    ~deletions, bare flags) must be reported in the re-apply warning with a
    reason, so every typed token is accounted for as re-applied, rejected, or
    ignored-with-reason."""
    import warnings as _warnings

    import yaml

    from sheeprl_tpu.cli import resume_from_checkpoint

    stored = compose(overrides=["exp=ppo", "total_steps=5000"])
    log_dir = tmp_path / "run"
    (log_dir / ".hydra").mkdir(parents=True)
    (log_dir / "checkpoint").mkdir()
    (log_dir / ".hydra" / "config.yaml").write_text(yaml.safe_dump(stored.as_dict()))
    ckpt = log_dir / "checkpoint" / "ckpt_100_0"
    ckpt.mkdir()

    overrides = [
        "exp=ppo",                      # defaults-list selection
        "env=gym",                      # group selection (dict-valued key)
        "~env.wrapper",                 # deletion
        f"checkpoint.resume_from={ckpt}",
        "algo.update_epochs=7",         # genuine leaf re-apply
    ]
    cfg = compose(overrides=[o for o in overrides if not o.startswith("~")])
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        merged = resume_from_checkpoint(cfg, overrides)
    assert merged.algo.update_epochs == 7
    text = " ".join(str(w.message) for w in caught)
    assert "re-applied: ['algo.update_epochs=7']" in text
    assert "ignored 'exp=ppo'" in text and "compose time" in text
    assert "ignored 'env=gym'" in text and "swap-time semantics" in text
    assert "ignored '~env.wrapper'" in text and "deletions" in text

    # a typo'd key is still REJECTED loudly, not silently invented
    with pytest.raises(ValueError, match="absent from"):
        resume_from_checkpoint(cfg, ["algo.does_not_exist=1"])
