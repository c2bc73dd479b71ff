"""DV3 multi-device scaling study on the virtual CPU mesh.

A virtual CPU mesh is not a multi-chip host (ROADMAP S8 measures that one),
so this study separates what a virtual mesh CAN measure from what it cannot:

- **Program structure** (real): the sharded S-preset train step compiles and
  runs at every mesh size with the batch sharded over ``data``; the host
  batch-assembly path (device-ring ``sample_device``) is timed for real.
- **Collective cost** (static + analytic): the optimized HLO of each
  compiled program is scanned for collective instructions
  (all-reduce / all-gather / reduce-scatter / collective-permute) and their
  output bytes. Projected collective seconds assume v5e ICI at ~45 GB/s per
  link per direction with the standard 2(n-1)/n ring-allreduce factor
  (bytes on the wire ≈ 2x payload for large n).
- **Wall time on the virtual mesh** (caveated): all N virtual devices share
  ONE physical core here, so per-step wall measures total FLOPs + runtime
  overhead, NOT parallel speedup. It is reported to show host-side overhead
  does not grow with mesh size — not as a throughput claim.

Usage:
    python tools/bench_scaling.py                 # meshes 1,2,4,8 via subprocesses
    python tools/bench_scaling.py --single N      # one mesh size, current process

Each single run prints one JSON line; the parent aggregates them to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: v5e ICI, per link per direction (public spec ballpark); used only for the
#: analytic projection, clearly labeled in the output
ICI_GBPS = 45.0
#: single-chip S-preset device step, bf16 (round-5 record, deleted in PR 21;
#: not re-measured on the attached chip) — feeds only the labeled projection
MEASURED_STEP_MS = 13.77

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}


def _collective_bytes(hlo_text: str) -> dict:
    """Collective instruction counts + output payload bytes from optimized HLO.

    Handles TUPLE-typed results: XLA's all-reduce combiner batches many
    gradient tensors into one `(f32[..], bf16[..], ...) all-reduce(...)`
    instruction — every element's bytes count (a first-element-only parse
    undercounted the gradient sync ~60x)."""
    out = {"all-reduce": [0, 0], "all-gather": [0, 0], "reduce-scatter": [0, 0],
           "collective-permute": [0, 0]}
    shape_pat = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        for kind in out:
            marker = f" {kind}("
            idx = line.find(marker)
            if idx < 0:
                continue
            # result type = everything between '=' and the op name
            eq = line.find("=")
            if eq < 0 or eq > idx:
                continue
            result_type = line[eq + 1 : idx]
            size = 0
            for m in shape_pat.finditer(result_type):
                s = _DTYPE_BYTES.get(m.group(1), 4)
                for d in filter(None, m.group(2).split(",")):
                    s *= int(d)
                size += s
            out[kind][0] += 1
            out[kind][1] += size
            break
    return {k: {"count": v[0], "bytes": v[1]} for k, v in out.items()}


def run_single(n_devices: int) -> None:
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config.engine import compose
    from sheeprl_tpu.config.instantiate import instantiate
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
    from sheeprl_tpu.data.device_ring import DeviceRingReplay
    from sheeprl_tpu.fabric import Fabric
    import gymnasium as gym

    devices = jax.devices()
    assert len(devices) >= n_devices and devices[0].platform == "cpu", devices

    # S preset, REAL shapes (B_global=16, T=64, 512 GRU, pixel obs): the same
    # program bench_dreamer times on the chip, batch-sharded over the mesh
    cfg = compose(
        "config",
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            "cnn_keys.encoder=[rgb]",
            "fabric.precision=bf16-mixed",
            "metric.log_level=0",
        ],
    )
    fabric = Fabric(devices=n_devices, accelerator="cpu", precision="bf16-mixed")
    T = int(cfg.per_rank_sequence_length)       # 64
    B_global = int(cfg.per_rank_batch_size)     # 16 — FIXED global batch
    assert B_global % n_devices == 0
    screen = int(cfg.env.screen_size)
    obs_space = gym.spaces.Dict(
        {"rgb": gym.spaces.Box(0, 255, (3, screen, screen), np.uint8)}
    )
    actions_dim = (6,)
    world_model, actor, critic, params = build_agent(
        cfg, actions_dim, False, obs_space, jax.random.PRNGKey(0)
    )
    world_tx = instantiate(
        cfg.algo.world_model.optimizer, max_grad_norm=cfg.algo.world_model.clip_gradients
    )
    actor_tx = instantiate(cfg.algo.actor.optimizer, max_grad_norm=cfg.algo.actor.clip_gradients)
    critic_tx = instantiate(cfg.algo.critic.optimizer, max_grad_norm=cfg.algo.critic.clip_gradients)
    agent_state = jax.device_put(
        {
            "params": params,
            "opt": {
                "world_model": world_tx.init(params["world_model"]),
                "actor": actor_tx.init(params["actor"]),
                "critic": critic_tx.init(params["critic"]),
            },
            "moments": init_moments(),
        },
        fabric.replicated,
    )
    train_fn = build_train_fn(
        world_model, actor, critic, world_tx, actor_tx, critic_tx,
        cfg, fabric, actions_dim, is_continuous=False,
    )

    # device ring with 8 env groups (divides every mesh size), filled enough
    # to sample [1, T, B_global]
    n_envs = 8
    rng = np.random.default_rng(0)
    host_rb = EnvIndependentReplayBuffer(
        T + 8, n_envs, obs_keys=("rgb",), buffer_cls=SequentialReplayBuffer
    )
    ring = DeviceRingReplay(
        host_rb, seed=0, sequence_overlap=T,
        batch_sharding=fabric.sharding(None, None, fabric.data_axis),
    )
    add_t0 = time.perf_counter()
    for _ in range(T + 8):
        ring.add(
            {
                "rgb": rng.integers(0, 255, (1, n_envs, 3, screen, screen)).astype(np.uint8),
                "actions": np.eye(6, dtype=np.float32)[rng.integers(0, 6, (1, n_envs))],
                "rewards": rng.normal(size=(1, n_envs, 1)).astype(np.float32),
                "dones": np.zeros((1, n_envs, 1), np.float32),
                "is_first": np.zeros((1, n_envs, 1), np.float32),
            }
        )
    add_s = time.perf_counter() - add_t0

    # host batch assembly (plan + device-local gather + global array build):
    # warm once, then time 5
    sampled = ring.sample_device(B_global, sequence_length=T, n_samples=1)
    jax.block_until_ready(sampled)
    asm_t0 = time.perf_counter()
    for _ in range(5):
        sampled = ring.sample_device(B_global, sequence_length=T, n_samples=1)
        jax.block_until_ready(sampled)
    assembly_ms = (time.perf_counter() - asm_t0) / 5 * 1e3
    data = jax.tree_util.tree_map(lambda v: v[0], sampled)

    # compiled HLO -> static collective census
    key = jax.random.PRNGKey(1)
    lowered = train_fn.lower(agent_state, data, key, jnp.float32(0.02))
    compiled = lowered.compile()
    coll = _collective_bytes(compiled.as_text())
    ar_bytes = coll["all-reduce"]["bytes"] + coll["reduce-scatter"]["bytes"] + coll["all-gather"]["bytes"]
    # ring all-reduce wire factor 2(n-1)/n; one hop per step at ICI_GBPS
    proj_coll_ms = (
        0.0 if n_devices == 1
        else ar_bytes * 2 * (n_devices - 1) / n_devices / (ICI_GBPS * 1e9) * 1e3
    )
    # projected chip step: measured single-chip step scaled by per-device
    # batch share + projected collective time (compute fully batch-parallel)
    proj_step_ms = MEASURED_STEP_MS / n_devices + proj_coll_ms

    # virtual-mesh wall (1 physical core -> structure check, not speedup);
    # BENCH_SCALING_CENSUS_ONLY=1 skips the minutes-long CPU step timing
    # when only the compile-time collective census is needed
    wall_ms = loss = None
    if os.environ.get("BENCH_SCALING_CENSUS_ONLY") in (None, "", "0"):
        state2 = agent_state
        for i in range(2):  # warmup (donation: keep threading the state through)
            key, k = jax.random.split(key)
            state2, metrics = train_fn(state2, data, k, jnp.float32(0.02))
        jax.block_until_ready(metrics)
        t0 = time.perf_counter()
        steps = 3
        for i in range(steps):
            key, k = jax.random.split(key)
            state2, metrics = train_fn(state2, data, k, jnp.float32(0.02))
            jax.block_until_ready(metrics)
        wall_ms = (time.perf_counter() - t0) / steps * 1e3
        loss = float(np.asarray(metrics["Loss/world_model_loss"]))

    print(json.dumps({
        "n_devices": n_devices,
        "global_batch": B_global,
        "seq_len": T,
        "per_device_batch": B_global // n_devices,
        "virtual_wall_ms_per_step": round(wall_ms, 1) if wall_ms is not None else None,
        "host_assembly_ms": round(assembly_ms, 1),
        "ring_fill_s": round(add_s, 2),
        "collectives": coll,
        "allreduce_payload_mb": round(ar_bytes / 1e6, 2),
        "projected_collective_ms_v5e": round(proj_coll_ms, 3),
        "projected_step_ms_v5e": round(proj_step_ms, 2),
        "projected_scaling_eff_pct": round(
            MEASURED_STEP_MS / (proj_step_ms * n_devices) * 100, 1
        ),
        "world_model_loss": round(loss, 1) if loss is not None else None,
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--single", type=int, default=None)
    ap.add_argument("--meshes", default="1,2,4,8")
    args = ap.parse_args()
    if args.single:
        run_single(args.single)
        return
    for n in [int(x) for x in args.meshes.split(",")]:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            " ".join(
                f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")
            )
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()
        env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--single", str(n)],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=3600,
        )
        line = next(
            (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")), None
        )
        if proc.returncode != 0 or line is None:
            tail = (proc.stderr or proc.stdout).strip().splitlines()[-5:]
            print(json.dumps({"n_devices": n, "error": " | ".join(tail)[-500:]}), flush=True)
        else:
            print(line, flush=True)


if __name__ == "__main__":
    main()
