"""Train-burst engine tests (``sheeprl_tpu/train``, howto/train_burst.md).

- ``tau_schedule`` unit coverage: hard-copy cadence (DV2 families), EMA
  cadence with the first-step hard copy (DV3 families), and the pretrain
  catch-up burst at ``learning_starts`` falling out of the same arithmetic;
- fused-vs-per-step **bitwise** e2e parity: the same entrypoint run twice
  under fixed seeds, once with the fused burst (default) and once with
  ``SHEEPRL_TRAIN_NO_FUSE=1`` (n dispatches of one gradient step each) —
  final checkpoints (params, opt state, replay rows) must be identical.
  This works by construction, not by luck: both modes run the SAME compiled
  executable (``burst(state, data, start, count, ...)`` with runtime
  start/count scalars), so there is no two-programs-compiled-differently
  epsilon to tolerate. Covered per-family for DV1 (no target net), DV2
  (hard-copy target cadence + pretrain catch-up burst), and P2E-DV1
  exploration (ensemble optimizer state riding the carry);
- resume-mid-run parity: both modes resumed from the same mid-run
  checkpoint finish bitwise identical.
"""

import glob
import os

import numpy as np
import pytest

from sheeprl_tpu.train import tau_schedule


# -- tau_schedule --------------------------------------------------------------


def test_tau_schedule_hard_copy_cadence():
    """DV2-style hard copy: tau=1.0 exactly on the cadence, 0 elsewhere;
    no first-step special case (the reference copies on g % every == 0,
    which includes g=0 naturally)."""
    taus = tau_schedule(8, 0, 4, tau=1.0, first_hard=False)
    np.testing.assert_array_equal(taus, [1, 0, 0, 0, 1, 0, 0, 0])
    assert taus.dtype == np.float32


def test_tau_schedule_ema_first_hard():
    """DV3-style EMA: soft tau on the cadence, but the run's very first
    gradient step (g=0) hard-copies (tau=1.0) regardless of cadence."""
    taus = tau_schedule(5, 0, 2, tau=0.02, first_hard=True)
    np.testing.assert_allclose(taus, [1.0, 0.0, 0.02, 0.0, 0.02])


def test_tau_schedule_resumes_mid_cadence():
    """A burst starting mid-run picks the cadence up where the counter left
    off — the schedule is a pure function of the global gradient-step index,
    so splitting one burst into two at any point changes nothing."""
    whole = tau_schedule(10, 0, 3, tau=0.5, first_hard=True)
    split = np.concatenate(
        [tau_schedule(4, 0, 3, tau=0.5, first_hard=True),
         tau_schedule(6, 4, 3, tau=0.5, first_hard=True)]
    )
    np.testing.assert_array_equal(whole, split)
    # g=0 hard-copies; g=3, 6, 9 soft-update
    np.testing.assert_allclose(whole[[0, 3, 6, 9]], [1.0, 0.5, 0.5, 0.5])
    assert not whole[[1, 2, 4, 5, 7, 8]].any()


def test_tau_schedule_pretrain_catchup_is_just_large_n():
    """The pretrain catch-up burst at learning_starts is a single call with
    a large n — same arithmetic, no special casing."""
    taus = tau_schedule(12, 0, 5, tau=1.0, first_hard=False)
    np.testing.assert_array_equal(np.nonzero(taus)[0], [0, 5, 10])


# -- fused vs per-step reference: bitwise e2e ----------------------------------


def _burst_args(tmp_path, algo, run_name, extra=()):
    """Tiny-but-real e2e config: total_steps=32 with learning_starts=12 and
    train_every=8 lands the pretrain catch-up burst AND two regular bursts;
    per_rank_gradient_steps=2 makes every regular burst a true multi-step
    scan (n_samples > 1), and pretrain_steps=4 makes the catch-up burst
    longer still."""
    args = [
        f"exp={algo}",
        "dry_run=False",
        "total_steps=32",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.sync_env=True",
        "env.capture_video=False",
        "env.num_envs=2",
        "per_rank_batch_size=2",
        "per_rank_sequence_length=4",
        "algo.horizon=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.per_rank_gradient_steps=2",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.transition_model.hidden_size=8",
        "algo.world_model.representation_model.hidden_size=8",
        "algo.world_model.stochastic_size=4",
        "algo.learning_starts=12",
        "algo.train_every=8",
        "cnn_keys.encoder=[rgb]",
        "buffer.size=16",
        "buffer.memmap=False",
        # bitwise parity needs the synchronous sampling path: the prefetch
        # worker overlaps sampling with collection (data/staging.py) and the
        # two modes would see different interleavings
        "buffer.prefetch=False",
        "buffer.checkpoint=True",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "metric.log_level=0",
        "algo.run_test=False",
        f"root_dir={tmp_path}/logs",
        f"run_name={run_name}",
    ]
    if algo in ("dreamer_v2", "p2e_dv1_exploration"):
        args += ["algo.per_rank_pretrain_steps=4"]
    if algo == "dreamer_v2":
        args += ["algo.world_model.discrete_size=4"]
    return args + list(extra)


def _load_ckpt_arrays(tmp_path, run_name):
    d = sorted(
        glob.glob(f"{tmp_path}/logs/**/{run_name}/**/ckpt_*_0", recursive=True)
    )[-1]
    out = {}
    for f in sorted(glob.glob(os.path.join(d, "*.npz"))):
        z = np.load(f)
        for k in z.files:
            out[(os.path.basename(f), k)] = z[k]
    return out, d


def _assert_bitwise(tmp_path, run_a, run_b, written=8):
    a, _ = _load_ckpt_arrays(tmp_path, run_a)
    b, _ = _load_ckpt_arrays(tmp_path, run_b)
    assert a and a.keys() == b.keys()
    for k in a:
        if a[k].ndim == 0 or a[k].shape[0] < written:
            np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))
        else:
            # replay rows past the write head are np.empty garbage
            np.testing.assert_array_equal(a[k][:written], b[k][:written], err_msg=str(k))


def _run_both_modes(tmp_path, monkeypatch, algo):
    from sheeprl_tpu import cli

    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("SHEEPRL_TRAIN_NO_FUSE", raising=False)
    cli.run(_burst_args(tmp_path, algo, "fused"))
    monkeypatch.setenv("SHEEPRL_TRAIN_NO_FUSE", "1")
    cli.run(_burst_args(tmp_path, algo, "perstep"))
    _assert_bitwise(tmp_path, "fused", "perstep")


def test_dreamer_v1_fused_burst_bitwise_per_step_e2e(tmp_path, monkeypatch):
    """DV1 (no target network, n_scanned=1: only the key array rides the
    scan): the fused burst's final checkpoint equals the per-step loop's."""
    _run_both_modes(tmp_path, monkeypatch, "dreamer_v1")


def test_dreamer_v2_fused_burst_bitwise_per_step_e2e(tmp_path, monkeypatch):
    """DV2 (hard-copy target cadence as a scanned tau array): includes the
    pretrain catch-up burst at learning_starts (n_samples=4), whose target
    copies must land on the same gradient-step indices in both modes."""
    _run_both_modes(tmp_path, monkeypatch, "dreamer_v2")


@pytest.mark.slow
def test_p2e_dv1_exploration_fused_burst_bitwise_per_step_e2e(tmp_path, monkeypatch):
    """P2E-DV1 exploration (ensemble optimizer state riding the burst
    carry): fused equals per-step. Slow-marked: two full e2e runs of the
    heaviest DV1-family entrypoint."""
    _run_both_modes(tmp_path, monkeypatch, "p2e_dv1_exploration")


def test_dreamer_v2_resume_mid_run_fused_bitwise_per_step(tmp_path, monkeypatch):
    """Both modes resumed from the SAME mid-run checkpoint finish bitwise
    identical: the restored update counter drives the host-side schedules
    (tau cadence, key splits) identically whether the remaining bursts are
    fused or dispatched per step."""
    from sheeprl_tpu import cli

    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("SHEEPRL_TRAIN_NO_FUSE", raising=False)
    cli.run(_burst_args(tmp_path, "dreamer_v2", "base", ["total_steps=24"]))
    _, ckpt = _load_ckpt_arrays(tmp_path, "base")
    resume = [f"checkpoint.resume_from={ckpt}", "total_steps=32"]
    cli.run(_burst_args(tmp_path, "dreamer_v2", "rfused", resume))
    monkeypatch.setenv("SHEEPRL_TRAIN_NO_FUSE", "1")
    cli.run(_burst_args(tmp_path, "dreamer_v2", "rperstep", resume))
    _assert_bitwise(tmp_path, "rfused", "rperstep")


# -- the burst's two spans -----------------------------------------------------


@pytest.mark.parametrize("mode", ["fused", "per_step"])
def test_run_train_burst_splits_into_dispatch_and_sync_spans(tmp_path, monkeypatch, mode):
    """Under a tracer the burst emits ``Time/train_dispatch_time`` then
    ``Time/train_sync_time`` (once each, also when the per-step loop
    dispatches n times) and returns bitwise what it returns without one."""
    import json

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.fabric import Fabric
    from sheeprl_tpu.obs.spans import TraceWriter, set_tracer
    from sheeprl_tpu.train import build_train_burst, run_train_burst

    if mode == "fused":
        monkeypatch.delenv("SHEEPRL_TRAIN_NO_FUSE", raising=False)
    else:
        monkeypatch.setenv("SHEEPRL_TRAIN_NO_FUSE", "1")

    def local_step(agent_state, data, key):
        w = agent_state["params"]["w"]
        noise = jax.random.normal(key, w.shape)
        w = w - 0.1 * (w - jnp.mean(data, 0)) + 1e-3 * noise
        return {"params": {"w": w}}, {"Loss/world_model_loss": jnp.sum(w * w), "User/x": jnp.mean(data)}

    program = build_train_burst(local_step, Fabric(devices=1, accelerator="cpu"), n_scanned=1, data_dim=0)
    data = jnp.arange(24, dtype=jnp.float32).reshape(3, 4, 2)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)

    def burst(fetch):
        state = {"params": {"w": jnp.ones(2)}}
        state, metrics = run_train_burst(program, state, data, (keys,), fetch_metrics=fetch)
        return jax.device_get(state), metrics

    plain = [burst(True), burst(False)]
    writer = TraceWriter(str(tmp_path / "t.jsonl"), xla_annotations=True)
    set_tracer(writer)
    try:
        traced = [burst(True), burst(False)]
    finally:
        set_tracer(None)
        writer.close()
    for (state_a, metrics_a), (state_b, metrics_b) in zip(plain, traced):
        np.testing.assert_array_equal(state_a["params"]["w"], state_b["params"]["w"])
        assert (metrics_a is None) == (metrics_b is None)
        for k in metrics_a or ():
            np.testing.assert_array_equal(metrics_a[k], metrics_b[k])
    assert plain[1][1] is None and set(plain[0][1]) == {"Loss/world_model_loss", "User/x"}
    with open(writer.path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    names = [e["name"] for e in events if e["ph"] == "X"]
    assert names == ["Time/train_dispatch_time", "Time/train_sync_time"] * 2
    assert {e["cat"] for e in events if e["ph"] == "X"} == {"train"}
