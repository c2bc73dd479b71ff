"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py [--devices N]

One process, which is the one that touches JAX, on one TPU chip (``--devices
4`` on a four-chip host). It exits non-zero, before any training, unless
``jax.default_backend() == "tpu"``; every later check raises, and nothing
catches, so a failed phase is a failed script. Phases:

1. **train** — the DreamerV3 Atari-100K recipe itself through
   ``sheeprl_tpu.cli.run`` (``exp=dreamer_v3_100k_ms_pacman``: 512-unit
   recurrent state, 32-multiplier CNN, T=64 x B=16 per device, bf16-mixed) on
   the seeded 64x64x3 pixel dummy env (``ale_py`` is not installed), with
   ``total_steps`` just past ``learning_starts`` so that at least 16 gradient
   steps run. Then, from what the run wrote: the logged world-model loss is
   finite, ``telemetry.json`` names this TPU and counts the gradient steps,
   zero non-finite and stall counters, and every mesh device's
   ``peak_bytes_in_use`` exceeds params + optimizer state (the state lived in
   HBM). A spy on the train-burst dispatch records where the agent state and
   the replay batch were committed (replicated state, batch split over the
   mesh) and where the player acted.
2. **kernels** — both Pallas kernels compiled by Mosaic (no interpreter) at
   the DreamerV2 shape (B=16, T=50, H=600, X=400), forward and custom-VJP
   backward, against ``kernels/reference.py`` under ``KERNEL_TOL``.

Everything the run writes goes under ``chiprun_out/chip_smoke/``. Stdout ends
with two lines: ``chip_smoke: summary: {...}`` (what ran, on which device, cold
compile seconds, compile-cache hits), then the verdict, one JSON object with
exactly these keys: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``, the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import struct
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
MIN_GRADIENT_STEPS = 16
#: kernel vs ``kernels/reference.py`` at float32 (``highest``) matmul precision.
#: forward: max |difference| of GRU states in (-1, 1) — the kernel's MXU dots
#: take f32 operands in one bf16 pass (K = 1152), measured 3e-3..6e-3 on v5e.
#: backward: max |difference| over max |reference|, per gradient leaf, both
#: sides at ``highest`` — the custom VJP is XLA code, so it must agree with
#: reference autodiff to f32 rounding through the 50-step recurrence.
KERNEL_TOL = {"forward": 1.5e-2, "backward": 1e-3}
DV2_SHAPE = {"B": 16, "T": 50, "H": 600, "X": 400}


def require_tpu(n_devices: int) -> list:
    """Print what JAX found, first; exit non-zero unless it is a TPU with at
    least ``n_devices`` chips. Returns every device JAX reports."""
    import jax

    devices = jax.devices()
    first = devices[0]
    print(
        f"chip_smoke: jax {jax.__version__} · backend {jax.default_backend()} · platform "
        f"{first.platform} · device_kind {first.device_kind} · {len(devices)} device(s)",
        flush=True,
    )
    if jax.default_backend() != "tpu":
        sys.exit(
            f"chip_smoke: no TPU — jax.default_backend() is {jax.default_backend()!r}; "
            "this script measures nothing on any other backend."
        )
    if n_devices > len(devices):
        sys.exit(f"chip_smoke: --devices {n_devices} but jax sees {len(devices)} TPU device(s)")
    return devices


def train_overrides(run_dir: str, n_devices: int) -> list:
    """The recipe, unchanged except for what the sandbox forces: the seeded
    pixel dummy env, a run just long enough, outputs under ``run_dir``."""
    learning_starts = 1024  # the recipe's own value; stated to size total_steps
    return [
        "exp=dreamer_v3_100k_ms_pacman",
        "env=dummy",
        "env.id=discrete_dummy",  # env=dummy alone keeps the recipe's Atari id
        "fabric.accelerator=tpu",
        f"fabric.devices={n_devices}",
        "fabric.precision=bf16-mixed",
        f"algo.learning_starts={learning_starts}",
        # one gradient step per update past learning_starts; an update is one
        # policy step per device
        f"total_steps={learning_starts + (MIN_GRADIENT_STEPS + 8) * n_devices}",
        "buffer.size=4096",
        "buffer.checkpoint=False",
        "buffer.memmap=False",
        "algo.run_test=False",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "metric.log_level=1",
        f"metric.log_every={256 * n_devices}",
        "metric.telemetry.enabled=true",
        f"metric.telemetry.summary_path={os.path.join(run_dir, 'telemetry.json')}",
        f"root_dir={run_dir}",
        "run_name=train",
    ]


def spy_on_placement(record: dict) -> None:
    """Record, from the first train burst and the first acting burst, where
    their inputs were committed. Observation only: both calls go through."""
    import jax

    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu.envs.rollout import BurstActor

    run_train_burst = dv3.run_train_burst

    def train_spy(train_fn, agent_state, data_stack, *args, **kwargs):
        if "state_devices" not in record:
            shardings = [leaf.sharding for leaf in jax.tree_util.tree_leaves(agent_state)]
            batch = data_stack["rgb"]  # [n_samples, T, B, C, H, W]
            record.update(
                state_devices=sorted({d.id for s in shardings for d in s.device_set}),
                state_platforms=sorted({d.platform for s in shardings for d in s.device_set}),
                state_replicated=all(s.is_fully_replicated for s in shardings),
                batch_devices=sorted(d.id for d in batch.sharding.device_set),
                batch_shape=list(batch.shape),
                batch_shard_shape=list(batch.sharding.shard_shape(batch.shape)),
            )
        return run_train_burst(train_fn, agent_state, data_stack, *args, **kwargs)

    dv3.run_train_burst = train_spy

    rollout = BurstActor.rollout

    def rollout_spy(self, params, *args, **kwargs):
        out = rollout(self, params, *args, **kwargs)
        record.setdefault("acting_platform", self._device.platform)
        return out

    BurstActor.rollout = rollout_spy


def logged_scalars(log_dir: str, tag: str) -> list:
    """Every ``(step, value)`` the run logged under ``tag``, read back from
    its TensorBoard event file (TFRecord framing + tensorboardX's own proto)."""
    from tensorboardX.proto import event_pb2

    out = []
    for path in sorted(glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))):
        with open(path, "rb") as f:
            while header := f.read(12):
                (length,) = struct.unpack("<Q", header[:8])
                event = event_pb2.Event.FromString(f.read(length))
                f.read(4)  # payload crc
                out += [(event.step, v.simple_value) for v in event.summary.value if v.tag == tag]
    return out


def check(condition: bool, message: str) -> None:
    if not condition:
        raise AssertionError(f"chip_smoke: {message}")


def train_phase(devices: list) -> dict:
    from sheeprl_tpu import cli

    n = len(devices)
    run_dir = os.path.join(OUT_DIR, f"dv3_{n}chip_{os.getpid()}")
    placement: dict = {}
    spy_on_placement(placement)
    t0 = time.perf_counter()
    cli.run(train_overrides(run_dir, n))
    wall = time.perf_counter() - t0

    with open(os.path.join(run_dir, "telemetry.json")) as f:
        tel = json.load(f)
    first = devices[0]
    check(
        (tel["platform"], tel["device_kind"], tel["device_count"])
        == (first.platform, first.device_kind, n),
        f"telemetry.json names {tel['platform']}/{tel['device_kind']}x{tel['device_count']}",
    )
    check(not tel["crashed"], "the run crashed")
    gradient_steps = tel["train_burst_steps"]
    check(gradient_steps >= MIN_GRADIENT_STEPS, f"only {gradient_steps} gradient steps")
    check(tel["nonfinite_metrics"] == 0, f"{tel['nonfinite_metrics']} non-finite metrics")
    check(tel["stalls"] == 0, f"{tel['stalls']} stalls")

    losses = logged_scalars(
        os.path.join(run_dir, "train", "version_0"), "Loss/world_model_loss"
    )
    check(len(losses) > 0, "no Loss/world_model_loss was logged")
    check(all(math.isfinite(v) for _, v in losses), f"non-finite world-model loss: {losses}")

    # params + two Adam moments, as placed (telemetry's measured gauges)
    state_bytes = tel["params_bytes_per_device"] + tel["opt_state_bytes_per_device"]
    check(state_bytes > 150e6, f"agent state is only {state_bytes} bytes: not the ~19 M-param recipe")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    check(
        all(p > state_bytes for p in peaks),
        f"per-device HBM peaks {peaks} do not exceed the {state_bytes}-byte agent state",
    )

    ids = [d.id for d in devices]
    check(placement.get("state_devices") == ids, f"agent state on {placement.get('state_devices')}")
    check(placement["state_platforms"] == ["tpu"], f"agent state on {placement['state_platforms']}")
    check(placement["state_replicated"], "agent state is not replicated over the mesh")
    check(placement["batch_devices"] == ids, f"replay batch on {placement['batch_devices']}")
    # [n_samples, T, B, ...]: T=64, B=16 per device, split over the mesh on B
    check(placement["batch_shape"][1:3] == [64, 16 * n], f"batch is {placement['batch_shape']}")
    check(
        placement["batch_shard_shape"][1:3] == [64, 16],
        f"batch shard is {placement['batch_shard_shape']}",
    )
    return {
        "recipe": "dreamer_v3_100k_ms_pacman (dummy pixels 64x64x3, T=64, B=16/device, bf16-mixed)",
        "gradient_steps": gradient_steps,
        "world_model_loss": [round(v, 4) for _, v in losses],
        "agent_state_bytes": state_bytes,
        "peak_hbm_bytes": peaks,
        "acting_platform": placement.get("acting_platform"),
        "wall_s": round(wall, 1),
        "compile_secs": tel["compile_secs"],
        "compiles": tel["recompiles"],
        "compile_cache_hits": tel["compile_cache_hits"],
    }


def kernel_phase() -> dict:
    """Both Pallas kernels at the DV2 shape, compiled by Mosaic, forward and
    backward, against the reference program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu import kernels
    from sheeprl_tpu.kernels import pallas_tpu, reference
    from sheeprl_tpu.obs import counters as obs_counters

    B, T, H, X = (DV2_SHAPE[k] for k in "BTHX")
    eps = 1e-5  # DV2's GRU LayerNorm epsilon
    rng = np.random.default_rng(0)

    def normal(*shape, scale=1.0):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    h0 = normal(B, H, scale=0.5)
    xs = normal(T, B, X)
    params = (
        normal(H + X, 3 * H, scale=(H + X) ** -0.5),
        normal(3 * H, scale=0.1),
        1.0 + normal(3 * H, scale=0.1),
        normal(3 * H, scale=0.1),
    )
    cot = normal(T, B, H)
    h0, xs, params, cot = jax.device_put((h0, xs, params, cot), jax.devices()[0])

    def ref_cell(h, x, *p):
        return reference.hafner_cell(h, x, *p, eps=eps)

    def ref_seq(h, xs, *p):
        return jax.lax.scan(lambda c, x: (ref_cell(c, x, *p),) * 2, h, xs)[1]

    def pallas_cell(h, x, *p):
        return pallas_tpu.hafner_cell(h, x, *p, hidden_size=H, eps=eps)

    def pallas_seq(h, xs, *p):
        return pallas_tpu.hafner_sequence(h, xs, *p, hidden_size=H, eps=eps)

    def grads(fn, g):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * g), argnums=tuple(range(6))))

    def max_err(got, want, relative):
        return max(
            float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) if relative else 1.0))
            for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))
        )

    counters = obs_counters.Counters()
    obs_counters.install(counters)
    t0 = time.perf_counter()
    report = {}
    cases = (
        ("hafner_cell", pallas_cell, ref_cell, (h0, xs[0], *params), cot[0]),
        ("hafner_sequence", pallas_seq, ref_seq, (h0, xs, *params), cot),
    )
    for name, kernel, ref, args, g in cases:
        compiled = jax.jit(kernel).lower(*args).compile()
        check(
            "tpu_custom_call" in compiled.as_text(),
            f"{name} did not compile to a Mosaic custom call",
        )
        out = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
            got_grads, want_grads = grads(kernel, g)(*args), grads(ref, g)(*args)
        check(out.shape == want.shape, f"{name}: shape {out.shape} vs {want.shape}")
        report[name] = {
            "forward_max_err": max_err(out, want, relative=False),
            "backward_max_err": max_err(got_grads, want_grads, relative=True),
        }
    print("chip_smoke: kernel errors vs reference:", json.dumps(report), flush=True)
    for name, errs in report.items():
        for side in ("forward", "backward"):
            err = errs[f"{side}_max_err"]  # a NaN error fails the comparison too
            check(err <= KERNEL_TOL[side], f"{name} {side} error {err} > {KERNEL_TOL[side]}")

    # the dispatcher the models call: tier "pallas" lowers to the Mosaic
    # kernel on this backend (the padded-XLA twin is for host-CPU programs)
    via_registry = jax.jit(
        lambda h, x, *p: kernels.hafner_gru_cell(h, x, *p, hidden_size=H, eps=eps, tier="pallas")
    )
    args = (h0, xs[0], *params)
    check(
        "tpu_custom_call" in via_registry.lower(*args).compile().as_text(),
        "kernels.hafner_gru_cell(tier='pallas') did not lower to the Mosaic kernel",
    )
    obs_counters.install(None)
    report.update(
        shape=DV2_SHAPE,
        tolerance=KERNEL_TOL,
        wall_s=round(time.perf_counter() - t0, 1),
        compile_secs=round(counters.compile_secs, 2),
        compiles=counters.recompiles,
        compile_cache_hits=counters.compile_cache_hits,
    )
    return report


def verdict_line(all_devices: list) -> str:
    """The last line of stdout of a run in which every phase passed. The
    driver's check accepts exactly these keys; what else there is to say goes
    on the summary line before it."""
    first = all_devices[0]
    device = {"platform": first.platform, "kind": first.device_kind, "count": len(all_devices)}
    return json.dumps({"ok": True, "device": device})


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--devices", type=int, default=1, help="TPU chips to train on (1 or 4)")
    n_devices = parser.parse_args().devices

    sys.path.insert(0, REPO)
    all_devices = require_tpu(n_devices)
    devices = all_devices[:n_devices]
    os.makedirs(OUT_DIR, exist_ok=True)

    train = train_phase(devices)
    print("chip_smoke: train phase passed:", json.dumps(train), flush=True)
    kernel = kernel_phase()
    print("chip_smoke: kernel phase passed:", json.dumps(kernel), flush=True)

    verdict = verdict_line(all_devices)
    summary = {
        "device": json.loads(verdict)["device"],
        "trained_on_devices": len(devices),
        "ran": ["dreamer_v3_100k_ms_pacman train", "pallas hafner_cell+hafner_sequence"],
        "gradient_steps": train["gradient_steps"],
        "peak_hbm_bytes": max(train["peak_hbm_bytes"]),
        "compile_secs": round(train["compile_secs"] + kernel["compile_secs"], 2),
        "compile_cache_hits": train["compile_cache_hits"] + kernel["compile_cache_hits"],
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".jax_cache"),
    }
    sys.stderr.flush()
    print("chip_smoke: summary:", json.dumps(summary), flush=True)
    print(verdict, flush=True)  # nothing follows it on stdout


if __name__ == "__main__":
    main()
