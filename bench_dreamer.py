"""Dreamer-family training-throughput benchmark on the attached accelerator.

Measures steady-state gradient-steps/sec of the full fused train step
(world model + actor + critic) for any Dreamer generation:

    python bench_dreamer.py                       # DreamerV3, Atari-100K S preset
    python bench_dreamer.py bench.family=dv2      # DreamerV2
    python bench_dreamer.py bench.family=dv1      # DreamerV1
    python bench_dreamer.py fabric.precision=bf16-mixed ...

Prints ONE JSON line like bench.py. The ``vs_baseline`` ratio is only
populated for DV3 at the S/512 preset, against the reference's effective
Atari-100K rate (14 h on a single RTX 3080 ≈ 2 grad-steps/s end-to-end,
`BASELINE.md`); the reference's DV1/DV2 numbers are full-training
wall-clocks on CPU and not comparable to a pure grad-step rate.
"""

from __future__ import annotations

import json
import time

# FLOPs/MFU helpers live in the metric layer (sheeprl_tpu/obs/perf.py) so the
# bench and run telemetry (Perf/mfu, telemetry.json) share one formula
from sheeprl_tpu.obs.perf import cost_flops as _cost_flops, mfu_pct

BASELINE_STEPS_PER_SEC = 100000 / (14 * 3600)  # reference DV3 100K wall-clock


def _family_flops_per_step(family, cfg, world_model, actor, params, T, B, actions_dim):
    """Scan-corrected FLOPs of one Dreamer gradient step (any family).

    XLA's ``cost_analysis`` counts a while-loop *body once* regardless of trip
    count (verified: a 10-iteration matmul scan reports 1 matmul of flops), so
    the raw module number misses ~(T-1) dynamic-scan bodies and ~(H-1)
    imagination bodies. Correction: cost the two scan bodies as standalone
    compiles and add the missing iterations — the dynamic scan is always
    differentiated (fwd+bwd ≈ 3× fwd flops); the imagination rollout is
    gradient-free for the discrete REINFORCE actors (DV2/DV3: log-probs are
    re-evaluated outside the rollout) and differentiated for DV1's
    dynamics-backprop actor (3×). Returns the correction FLOPs to ADD to the
    raw module number.
    """
    if family == "dv1":
        return _dv1_flops_correction(cfg, world_model, actor, params, T, B, actions_dim)
    if family == "dv2":
        return _dv2_flops_correction(cfg, world_model, actor, params, T, B, actions_dim)
    return _dv3_flops_correction(cfg, world_model, actor, params, T, B, actions_dim)


def _embed_dim(world_model, wp, B: int) -> int:
    """Encoder output width via shape-only evaluation (no compile)."""
    import jax
    import jax.numpy as jnp
    import numpy as np  # noqa: F401

    obs = {"rgb": jnp.zeros((B, 3, 64, 64), jnp.float32)}
    shape = jax.eval_shape(
        lambda o: world_model.apply({"params": wp}, o, method=type(world_model).encode),
        obs,
    )
    return int(shape.shape[-1])


def _dv12_flops_correction(
    cfg, world_model, actor, params, T, B, actions_dim,
    stoch_width, has_first, img_grad_factor,
):
    """Shared DV1/DV2 scan-body costing: DV1 passes the continuous
    ``stochastic_size`` and a differentiated (dynamics-backprop, 3x)
    imagination; DV2 passes ``S*D`` discrete width, an ``is_first`` input,
    and a gradient-free (REINFORCE, 1x) imagination."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    wm_cfg = cfg.algo.world_model
    rec = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    act_dim = int(np.sum(actions_dim))
    n_img = T * B
    wp = params["world_model"]
    E = _embed_dim(world_model, wp, B)
    WM = type(world_model)

    def dyn_body(wp, post, recur, action, embed, first, key):
        args = (post, recur, action, embed) + ((first,) if has_first else ()) + (key,)
        return world_model.apply({"params": wp}, *args, method=WM.dynamic_posterior)

    dyn_args = (
        wp, jnp.zeros((B, stoch_width)), jnp.zeros((B, rec)),
        jnp.zeros((B, act_dim)), jnp.zeros((B, E)), jnp.zeros((B, 1)),
        jax.random.PRNGKey(0),
    )

    def img_body(wp, ap, prior, recur, action, key):
        prior, recur = world_model.apply(
            {"params": wp}, prior, recur, action, key, method=WM.imagination
        )
        pre = actor.apply({"params": ap}, jnp.concatenate([prior, recur], -1))
        return prior, recur, pre

    img_args = (
        wp, params["actor"], jnp.zeros((n_img, stoch_width)),
        jnp.zeros((n_img, rec)), jnp.zeros((n_img, act_dim)),
        jax.random.PRNGKey(1),
    )
    f_dyn = _cost_flops(jax.jit(dyn_body).lower(*dyn_args).compile())
    f_img = _cost_flops(jax.jit(img_body).lower(*img_args).compile())
    return (T - 1) * 3.0 * f_dyn + (horizon - 1) * img_grad_factor * f_img


def _dv1_flops_correction(cfg, world_model, actor, params, T, B, actions_dim):
    S = int(cfg.algo.world_model.stochastic_size)
    return _dv12_flops_correction(
        cfg, world_model, actor, params, T, B, actions_dim,
        stoch_width=S, has_first=False, img_grad_factor=3.0,
    )


def _dv2_flops_correction(cfg, world_model, actor, params, T, B, actions_dim):
    wm_cfg = cfg.algo.world_model
    S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
    return _dv12_flops_correction(
        cfg, world_model, actor, params, T, B, actions_dim,
        stoch_width=S * D, has_first=True, img_grad_factor=1.0,
    )


def _dv3_flops_correction(cfg, world_model, actor, params, T, B, actions_dim):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel

    wm_cfg = cfg.algo.world_model
    S, D = int(wm_cfg.stochastic_size), int(wm_cfg.discrete_size)
    rec = int(wm_cfg.recurrent_model.recurrent_state_size)
    hidden = int(wm_cfg.representation_model.hidden_size)
    horizon = int(cfg.algo.horizon)
    act_dim = int(np.sum(actions_dim))
    n_img = T * B
    wp = params["world_model"]

    def dyn_body(wp, post, recur, action, eproj, first, g):
        init_post = world_model.apply(
            {"params": wp}, jnp.zeros((1, rec)), method=WorldModel.initial_posterior
        )
        return world_model.apply(
            {"params": wp}, post, recur, action, eproj, first, init_post, None, g,
            method=WorldModel.dynamic_posterior,
        )

    dyn_args = (
        wp,
        jnp.zeros((B, S * D)), jnp.zeros((B, rec)), jnp.zeros((B, act_dim)),
        jnp.zeros((B, hidden)), jnp.zeros((B, 1)), jnp.zeros((B, S, D)),
    )

    def img_body(wp, ap, prior, recur, action, g):
        prior, recur = world_model.apply(
            {"params": wp}, prior, recur, action, None, g,
            method=WorldModel.imagination,
        )
        pre = actor.apply({"params": ap}, jnp.concatenate([prior, recur], -1))
        return prior, recur, pre

    img_args = (
        wp, params["actor"],
        jnp.zeros((n_img, S * D)), jnp.zeros((n_img, rec)),
        jnp.zeros((n_img, act_dim)), jnp.zeros((n_img, S, D)),
    )

    f_dyn = _cost_flops(jax.jit(dyn_body).lower(*dyn_args).compile())
    f_img = _cost_flops(jax.jit(img_body).lower(*img_args).compile())
    # dynamic scan body runs T times fwd + T times in the reverse-mode scan
    # (bwd ≈ 2x fwd flops); the module already counts each while body once
    extra = (T - 1) * 3.0 * f_dyn + (horizon - 1) * 1.0 * f_img
    return extra

_FAMILIES = {
    "dv1": ("dreamer_v1", "exp=dreamer_v1", False),
    "dv2": ("dreamer_v2", "exp=dreamer_v2_ms_pacman", True),
    "dv3": ("dreamer_v3", "exp=dreamer_v3_100k_ms_pacman", True),
}


def main() -> None:
    import importlib
    import sys

    import gymnasium as gym
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.config.engine import compose
    from sheeprl_tpu.fabric import Fabric

    # same pin as Fabric.launch: uncommitted eager work (init, key math) runs
    # on the host CPU; the train step's inputs are committed to the mesh
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    from sheeprl_tpu.utils.utils import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    overrides = list(sys.argv[1:])
    family = "dv3"
    profile = False
    n = 20
    for ov in list(overrides):
        if ov.startswith("bench.family="):
            family = ov.split("=", 1)[1]
            overrides.remove(ov)
        elif ov.startswith("bench.profile="):
            profile = ov.split("=", 1)[1].lower() in ("1", "true", "yes")
            overrides.remove(ov)
        elif ov.startswith("bench.steps="):
            n = int(ov.split("=", 1)[1])
            overrides.remove(ov)
    if family not in _FAMILIES:
        sys.exit(f"Unknown bench.family={family!r}; choose from {sorted(_FAMILIES)}")
    module_name, exp, has_tau = _FAMILIES[family]

    cfg = compose(
        "config",
        overrides=[
            exp,
            "env=dummy",
            "env.id=discrete_dummy",
            "metric.log_level=0",
            "buffer.checkpoint=False",
            "checkpoint.every=1000000",
            *overrides,  # e.g. fabric.precision=bf16-mixed
        ],
    )
    fabric = Fabric(
        devices=cfg.fabric.get("devices", 1),
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=cfg.fabric.get("precision", "32-true"),
    )
    agent_mod = importlib.import_module(f"sheeprl_tpu.algos.{module_name}.agent")
    algo_mod = importlib.import_module(f"sheeprl_tpu.algos.{module_name}.{module_name}")

    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    # action count follows the benched preset (+bench.actions=17 for Crafter);
    # MsPacman's 9 is the default
    actions_dim = (int(cfg.get("bench", {}).get("actions", 9)),)
    world_model, actor, critic, params = agent_mod.build_agent(
        cfg, actions_dim, False, obs_space, jax.random.PRNGKey(0)
    )
    # every family shares the real training wiring so the bench can't drift
    world_tx, actor_tx, critic_tx, agent_state = algo_mod.build_optimizers_and_state(
        cfg, params
    )
    agent_state = jax.device_put(agent_state, fabric.replicated)
    train_fn = algo_mod.build_train_fn(
        world_model, actor, critic, world_tx, actor_tx, critic_tx,
        cfg, fabric, actions_dim, False,
    )

    T, B = int(cfg.per_rank_sequence_length), int(cfg.per_rank_batch_size)
    rng = np.random.default_rng(0)
    # uint8 pixels: what the real training loop ships (the train step
    # normalizes on device)
    data = {
        "rgb": rng.integers(0, 256, size=(T, B, 3, 64, 64)).astype(np.uint8),
        "actions": np.eye(actions_dim[0], dtype=np.float32)[
            rng.integers(0, actions_dim[0], (T, B))
        ],
        "rewards": rng.normal(size=(T, B, 1)).astype(np.float32),
        "dones": np.zeros((T, B, 1), np.float32),
        "is_first": np.zeros((T, B, 1), np.float32),
    }
    batch = jax.device_put(
        {k: jnp.asarray(v) for k, v in data.items()},
        fabric.sharding(None, fabric.data_axis),
    )

    def step(state, key, tau):
        if has_tau:
            return train_fn(state, batch, key, jnp.float32(tau))
        return train_fn(state, batch, key)

    # compile + warmup; keys prepared outside the timed loop
    keys = [jax.random.PRNGKey(i) for i in range(n + 1)]
    agent_state, metrics = step(agent_state, keys[n], 1.0)
    float(np.asarray(metrics["Loss/world_model_loss"]))

    start = time.perf_counter()
    for i in range(n):
        agent_state, metrics = step(agent_state, keys[i], 0.02 if family == "dv3" else 0.0)
    float(np.asarray(metrics["Loss/world_model_loss"]))  # block
    steps_per_sec = n / (time.perf_counter() - start)

    # with bench.profile=1 also capture an xplane trace and report the
    # device-side per-step time (the 'XLA Modules' line), which the host
    # clock above cannot separate from dispatch
    device_us = None
    if profile:  # CPU too — the parser has a host-plane fallback (obs/prof)
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix=f"bench_{family}_trace_")
        n_prof = min(5, n)  # keys has n+1 entries; bench.steps can be small
        jax.profiler.start_trace(trace_dir)
        for i in range(n_prof):
            agent_state, metrics = step(
                agent_state, keys[i], 0.02 if family == "dv3" else 0.0
            )
        float(np.asarray(metrics["Loss/world_model_loss"]))  # block
        jax.profiler.stop_trace()
        try:
            # the promoted parser (self-contained wire decoding, no tf proto)
            from sheeprl_tpu.obs.prof.xplane import summarize

            device_us = summarize(trace_dir, n_prof)["modules_us_per_step"]
        except Exception as exc:  # unreadable trace — keep the bench alive
            print(f"# profile parse failed: {exc}", file=sys.stderr)

    # FLOPs + MFU (every family): raw XLA module cost_analysis plus the
    # per-family scan-body correction (_family_flops_per_step); %-of-peak
    # uses the profiled device time when available, wall rate otherwise.
    # Peak: the DEVICE_PEAKS bf16 entry of the device the step ran on (no
    # entry, no MFU); 32-true programs are measured against the same bf16
    # peak (disclosed in the line) so numbers stay comparable across
    # precisions.
    from sheeprl_tpu.obs.prof.roofline import detect_peaks

    peak_tflops = detect_peaks(device=fabric.device)["peak_tflops"]
    flops_per_step = mfu = xla_module_flops = None
    try:
        if has_tau:
            lowered = train_fn.lower(agent_state, batch, keys[0], jnp.float32(0.02))
        else:
            lowered = train_fn.lower(agent_state, batch, keys[0])
        xla_module_flops = _cost_flops(lowered.compile())
        extra = _family_flops_per_step(
            family, cfg, world_model, actor, jax.device_get(agent_state["params"]),
            T, B, actions_dim,
        )
        flops_per_step = xla_module_flops + extra
        step_seconds = (
            device_us * 1e-6 if device_us is not None else 1.0 / steps_per_sec
        )
        mfu = mfu_pct(flops_per_step, 1.0, step_seconds, peak_tflops)
    except Exception as exc:  # keep the bench alive
        print(f"# flops analysis failed: {exc}", file=sys.stderr)

    # the Atari-100K wall-clock baseline only compares against DV3's default
    # (S/512) preset it was measured for
    rec_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    vs_baseline = (
        round(steps_per_sec / BASELINE_STEPS_PER_SEC, 2)
        if family == "dv3" and rec_size == 512
        else None
    )
    print(
        json.dumps(
            {
                "metric": f"{module_name}_grad_steps_per_sec",
                "recurrent_state_size": rec_size,
                "actions": int(actions_dim[0]),
                "precision": str(cfg.fabric.get("precision", "32-true")),
                "value": round(steps_per_sec, 2),
                "unit": "steps/s",
                "device_ms_per_step": (
                    round(device_us / 1e3, 2) if device_us is not None else None
                ),
                "flops_per_step": flops_per_step,
                "xla_module_flops": xla_module_flops,
                # mfu basis: the device's bf16 peak; for 32-true programs this
                # is the bf16-relative utilization, not an fp32-peak number
                "mfu_pct": mfu,
                "mfu_peak_tflops_bf16": peak_tflops if mfu is not None else None,
                "platform": fabric.device.platform,
                "device_kind": fabric.device.device_kind,
                "vs_baseline": vs_baseline,
            }
        )
    )


if __name__ == "__main__":
    main()
