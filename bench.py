"""Benchmark entry: the full framework-speed matrix vs BASELINE.md.

Prints one JSON line per workload. Contract (round 4's matrix overran the
driver's timeout and lost every line the driver parses): the bench must be
**un-timeout-able**. ROADMAP S0/D8 replace this file with one command that
runs on the chip tool; its parent process stays off jax (one process per
chip: each stage is a subprocess that takes the device and releases it).

- The headline PPO line runs FIRST (it is the cheapest line: ~5 s steady
  per run) and is printed immediately; the full matrix is re-printed at the
  end with the headline LAST, because the driver records a truncated *tail*
  and parses the LAST line.
- A **global wall budget** (env ``BENCH_WALL_BUDGET_S``, default 1080 s)
  gates every stage: each subprocess gets ``timeout=remaining`` and a stage
  whose minimum cost exceeds the remaining budget is SKIPPED with a
  disclosed ``{"skipped": "budget"}`` line instead of blowing the deadline.
- Stage order after the headline: DV3 → DV2 → DV1 device-step lines
  (grad-steps/s + scan-corrected MFU, minutes each) → SAC → optional
  DV1/DV2 e2e micro-runs; SAC and the e2e rows go last because only they
  can overrun their estimates by minutes (one dispatch per env step or per
  burst).

Workloads:

1. PPO CartPole, the reference's own benchmark protocol (`README.md:92-104`
   / `benchmarks/benchmark.py:10-41`): 64 envs x 1024 rollout-collection
   steps (65536 policy steps), test/logging/checkpoints disabled, wall-clock
   around one `python -m sheeprl_tpu` subprocess per run (every stage
   isolates in its own process; the headline keeps its first-measured
   position). Runs with `metric.telemetry` on so the line
   carries `bytes_staged_h2d`/`recompiles` next to the wall-clock.
   Reference baseline: 80.81 s.
2. DreamerV3 S-preset (Atari-100K MsPacman config, bf16) gradient-steps/s
   with the profiled device-ms per step — the north-star workload
   (`BASELINE.md`: 100K policy steps in 14 h on a 3080 ≈ 2 grad-steps/s).
   Run in a subprocess (`bench_dreamer.py`) so a failure there cannot take
   down the headline. `device_ms_per_step` (in-run xplane profile) is the
   DV3 number to read; the wall-clock rate includes dispatch.
3. SAC: the reference's protocol (`/root/reference/benchmarks/
   benchmark_sb3.py:21-29`): LunarLanderContinuous, 4 envs, 1024*64 total
   steps, test/logging/checkpoints disabled. Baseline 318.06 s (v0.5.2,
   4 CPUs, 5 seeds). Gym retired the -v2 env; -v3 is physics-identical.
   Where the default budget cannot fit the full protocol, a DISCLOSED
   1/8-protocol run (8192 steps, baseline scaled 1/8) is measured instead.
4. DreamerV2 / DreamerV1 end-to-end micro-runs. The reference's
   `dreamer_v{1,2}_benchmarks` exp configs are NOT in the snapshot, so the
   rows 2921.38 s / 1148.1 s cannot be step-matched; each line carries the
   exact workload we ran and `vs_baseline` is the raw wall-clock ratio with
   that caveat recorded in `protocol`.
5. Rollout-engine evidence (round 6, howto/rollout_engine.md): a
   `jax_cartpole_rollout_sps` line — jitted-scan collection on the pure-JAX
   CartPole vs the per-step sync Python loop (tools/bench_rollout.py) — and
   a `sac_lunarlander_8192_steps_act_burst16` line with the
   act_dispatches/rollout_bursts counters and the sps delta vs the
   per-step SAC stage.
6. Fused-kernel evidence (ISSUE 13, howto/kernels.md): a
   `hafner_ln_gru_seq_fwd_bwd_sps` line — the fused LayerNorm-GRU sequence
   tiers vs the reference cell scan at the DV2 shape, forward+backward
   (tools/bench_kernels.py; acceptance >= 1.2x on at least one tier).

Wall-clock protocol (round-4 de-noising): repeated lines run one warm-up
(compile/cache fill, disclosed) plus up to 3 measured repeats — trimmed to
what the budget allows — and report the MEDIAN with the full `runs` array
and `spread` = (max-min)/median; the median over steady repeats bounds
run-to-run noise. The minutes-long DV1/DV2 lines are a
single measured run after one warm-up (disclosed in their `protocol`); read
them as order-of-magnitude evidence, not de-noised measurements.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

# Silence XLA's C++ warning spam (e.g. the per-process `cpu_aot_loader`
# persistent-cache notes): each in-process run below would otherwise emit
# ~2.5 KB of stderr that evicts the JSON evidence lines from a truncated
# log tail. Must be set before jax initializes its backends.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

PPO_BASELINE_SECONDS = 80.81  # reference README.md:92-98, PPO 1 device
SAC_BASELINE_SECONDS = 318.06  # reference README.md:106-112, SAC 1 device
DV1_BASELINE_SECONDS = 2921.38  # reference README.md:122-128 (protocol lost)
DV2_BASELINE_SECONDS = 1148.1  # reference README.md:130-136 (protocol lost)

REPO = os.path.dirname(os.path.abspath(__file__))

_START = time.monotonic()
WALL_BUDGET_S = float(os.environ.get("BENCH_WALL_BUDGET_S", "1080"))
#: seconds held back from every stage for the final re-print + process exit
_RESERVE_S = 15.0


def _remaining() -> float:
    return WALL_BUDGET_S - (time.monotonic() - _START) - _RESERVE_S


def _skip_line(metric: str, need_s: float) -> str:
    # On a single-core host the minutes-long device stages are not merely
    # over-budget — they structurally cannot run (one core serves the env
    # loop, the XLA compile, and the dispatch pump at once), so the skip is
    # disclosed as "host-bound" instead of the generic budget marker:
    # bench_compare reads the round as "this host can't measure it", not
    # "the stage regressed to nothing".
    host_bound = (os.cpu_count() or 1) < 2
    return json.dumps(
        {
            "metric": metric,
            "value": None,
            "skipped": "host-bound" if host_bound else "budget",
            "need_s": round(need_s, 1),
            "remaining_s": round(max(_remaining(), 0.0), 1),
            "wall_budget_s": WALL_BUDGET_S,
            "host_cores": os.cpu_count() or 1,
        }
    )


def _dreamer_line(family: str = "dv3", min_stage_s: float = 180.0, extra=()) -> str:
    """Run one Dreamer-family micro-bench (grad-steps/s + device profile +
    scan-corrected MFU, `bench_dreamer.py`) in a subprocess."""
    metric = {"dv1": "dreamer_v1", "dv2": "dreamer_v2", "dv3": "dreamer_v3"}[family] + "_grad_steps_per_sec"
    # needs one cold compile plus the measured burst — below the floor it
    # cannot finish
    if _remaining() < min_stage_s:
        return _skip_line(metric, min_stage_s)
    try:
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "bench_dreamer.py"),
                f"bench.family={family}",
                "fabric.precision=bf16-mixed",
                *extra,
            ],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=max(60.0, _remaining()),
        )
        line = next(
            (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")), None
        )
        if proc.returncode == 0 and line:
            return line
        tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
        return json.dumps(
            {"metric": metric, "value": None, "error": " | ".join(tail)[-400:]}
        )
    except Exception as exc:
        return json.dumps({"metric": metric, "value": None, "error": repr(exc)[:400]})


def _timed_subprocess_run(args, timeout, env=None):
    """One `python -m sheeprl_tpu <overrides>` run; returns wall seconds."""
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    start = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "sheeprl_tpu", *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=min(timeout, max(60.0, _remaining())),
        env=full_env,
    )
    elapsed = time.perf_counter() - start
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout).strip().splitlines()[-4:]
        raise RuntimeError(" | ".join(tail)[-400:])
    return round(elapsed, 2)


def _repeat_line(metric, run_once, baseline, protocol, repeats=3, min_stage_s=60.0):
    """Warm-up + up to `repeats` measured runs -> JSON line (median + spread).

    Budget-aware: skips the whole stage when `min_stage_s` exceeds the
    remaining wall budget, and stops repeating when the next run (estimated
    from the slowest run so far) would not fit. At least one measured run
    happens if the stage starts at all.
    """
    if _remaining() < min_stage_s:
        return _skip_line(metric, min_stage_s)
    try:
        warmup = run_once()
    except Exception as exc:
        return json.dumps({"metric": metric, "value": None, "error": repr(exc)[:400]})
    runs = []
    est = warmup
    truncated = None
    for _ in range(repeats):
        if runs and _remaining() < est * 1.2:
            break
        try:
            runs.append(run_once())
        except Exception as exc:
            # a budget-clamped timeout on a LATER repeat must not throw away the measured runs already in hand
            truncated = repr(exc)[:200]
            break
        est = max(runs)
    if not runs:
        return json.dumps(
            {"metric": metric, "value": None, "warmup_run": warmup, "error": truncated}
        )
    med = statistics.median(runs)
    line = {
        "metric": metric,
        "value": round(med, 2),
        "unit": "s",
        "runs": runs,
        "warmup_run": warmup,
        "spread": round((max(runs) - min(runs)) / med, 3) if len(runs) > 1 else None,
        "vs_baseline": round(baseline / med, 3) if baseline else None,
        "protocol": protocol,
    }
    if truncated:
        line["truncated_by"] = truncated
    return json.dumps(line)


def _phase_tails(tel) -> dict:
    """p50/p95 step-time tails from a telemetry.json's phase percentiles
    (obs/hist.py streaming histograms) — `{train_p50_ms, train_p95_ms,
    env_p95_ms}`, absent keys skipped."""
    out = {}
    pct = tel.get("phase_percentiles") or {}
    for phase, prefix in (
        ("Time/train_time", "train"),
        ("Time/env_interaction_time", "env"),
        # async env pool only: the parent's collective wait for worker
        # results — the *exposed* env latency when stepping overlaps train
        ("Time/env_wait_time", "env_wait"),
        # rollout engine (envs/rollout): one span per collection burst —
        # policy dispatch + env stepping + buffer add; env_p95 above is the
        # pure env.step slice inside it, so rollout_p95 - env-time is the
        # dispatch/bookkeeping residue (the RTT decomposition)
        ("Time/rollout_time", "rollout"),
        # actor–learner plane (sheeprl_tpu/plane): the learner's exposed wait
        # for player trajectory slabs — on a healthy plane this absorbs the
        # env time that used to serialize the train step
        ("Time/plane_wait_time", "plane_wait"),
    ):
        p = pct.get(phase) or {}
        if p.get("p95_ms") is not None:
            if prefix == "train":
                out[f"{prefix}_p50_ms"] = p.get("p50_ms")
            out[f"{prefix}_p95_ms"] = p["p95_ms"]
    # in-run device profile (obs/prof): when a metric.telemetry.profile
    # window landed during the run, the evidence line carries the measured
    # device time + roofline verdict next to the wall-clock —
    # tools/bench_compare.py diffs these unit-directionally across rounds
    for key in ("device_ms_per_step", "mfu_device_pct", "roofline_verdict"):
        if tel.get(key) is not None:
            out[key] = tel[key]
    # distributed observability (obs/dist): host-collective wall time and
    # the data-staleness percentiles — the actor-learner health numbers.
    # The staleness keys keep a legitimate 0.0 (zero lag IS the healthy
    # reading); comms_ms 0 just means no host collectives ran — noise.
    for key in ("sample_age_p95_s", "policy_lag_p95"):
        if tel.get(key) is not None:
            out[key] = tel[key]
    if tel.get("comms_ms"):
        out["comms_ms"] = tel["comms_ms"]
    prof = tel.get("prof") or {}
    if prof.get("comms_ms_per_step") is not None:
        out["comms_ms_per_step"] = prof["comms_ms_per_step"]
    # train-burst engine (sheeprl_tpu/train): dispatched programs per
    # gradient step — 1/n_samples when every burst runs as ONE scanned
    # executable, 1.0 when a per-step loop pays one dispatch per gradient
    # step. Lower-better in bench_compare.
    bursts_steps = tel.get("train_burst_steps")
    if bursts_steps and tel.get("train_dispatches") is not None:
        out["train_dispatches_per_step"] = round(
            tel["train_dispatches"] / bursts_steps, 3
        )
    # learning-health plane (obs/learn): the training-dynamics tails next to
    # the wall-clock — a perf win bought by destabilizing the optimizer
    # (grad_norm_p95 drifting up round over round, warn/critical events
    # appearing) is a regression this matrix must show. learn_warnings keeps
    # a legitimate 0 (zero events IS the healthy reading on an instrumented
    # run); the keys are absent entirely when the learn plane was off.
    for key in ("grad_norm_p95", "update_ratio_p50"):
        if tel.get(key) is not None:
            out[key] = tel[key]
    if tel.get("learn_probe_fetches"):
        out["learn_warnings"] = tel.get("learn_warnings", 0)
        out["learn_criticals"] = tel.get("learn_criticals", 0)
    return out


_QUIET = [
    "env.capture_video=False",
    "checkpoint.every=1000000000",
    "checkpoint.save_last=False",
    "metric.log_level=0",
    "buffer.memmap=False",
    "algo.run_test=False",
]


def _ppo_line() -> str:
    # Subprocess like every other stage (a prior in-process stage could
    # leave backend state that skews the headline, and the parent must stay
    # off jax so each stage's process can take the device). Still the
    # FIRST stage measured, so its position in the matrix is unchanged.
    # metric.telemetry rides along so the headline line carries the new
    # counters (bytes staged h2d, recompiles) next to the wall-clock.
    import tempfile

    tel_path = os.path.join(tempfile.mkdtemp(prefix="bench_ppo_tel_"), "telemetry.json")
    ppo_args = [
        "exp=ppo",
        "env=gym",
        "env.id=CartPole-v1",
        "env.num_envs=64",
        "env.sync_env=True",
        "total_steps=65536",
        "algo.rollout_steps=128",
        "per_rank_batch_size=64",
        "exp_name=bench_ppo",
        "metric.telemetry.enabled=true",
        "metric.telemetry.trace=false",
        f"metric.telemetry.summary_path={tel_path}",
        *_QUIET,
    ]

    line = _repeat_line(
        "ppo_cartpole_65536_steps",
        lambda: _timed_subprocess_run(ppo_args, timeout=600),
        PPO_BASELINE_SECONDS,
        "reference benchmark.py:10-41 (CartPole-v1, 64 envs, 1024*64 steps, "
        "test/log/ckpt off), one subprocess per run like the other stages",
        repeats=3,
        min_stage_s=45.0,
    )
    try:  # fold the last run's telemetry counters into the evidence line
        with open(tel_path) as f:
            tel = json.load(f)
        data = json.loads(line)
        data["telemetry"] = {
            k: tel.get(k)
            for k in (
                "bytes_staged_h2d",
                "h2d_transfers",
                "recompiles",
                "compile_secs",
                "compile_cache_hits",
                "peak_hbm_bytes",
                # checkpoint stall on the step path (ckpt subsystem): the
                # bench protocol runs with checkpoints effectively off, so
                # this stays ~0 — it is here so any future regression that
                # re-introduces step-path checkpoint cost shows in the
                # headline trajectory
                "ckpt_blocked_ms",
                "ckpt_saves",
            )
        }
        # tail latency next to the averages: a regression that only bloats
        # p95 (a periodic stall, a recompile storm) is invisible in the
        # wall-clock median this line is judged on
        data["telemetry"].update(_phase_tails(tel))
        line = json.dumps(data)
    except Exception:
        pass  # a skipped/failed stage has no summary; keep the line as-is
    return line


def _ppo_async_line(sync_line: str) -> str:
    # The same PPO protocol with env.vectorization=async (the shared-memory
    # worker pool, envs/vector/): ONE measured run after warm-up — this line
    # is overlap evidence next to the sync headline, not a de-noised
    # headline itself. Carries env_p95_ms (step span), env_wait_p95_ms (the
    # parent's exposed wait for workers), the pool counters, and sps with
    # the delta vs the sync headline. On trivial CartPole the pool's IPC can
    # honestly LOSE to serial stepping — the deltas are evidence either way;
    # the pool pays off as simulator cost grows (howto/async_envs.md).
    import tempfile

    tel_path = os.path.join(tempfile.mkdtemp(prefix="bench_ppo_async_tel_"), "telemetry.json")
    args = [
        "exp=ppo",
        "env=gym",
        "env.id=CartPole-v1",
        "env.num_envs=64",
        "env.sync_env=null",
        "env.vectorization=async",
        "total_steps=65536",
        "algo.rollout_steps=128",
        "per_rank_batch_size=64",
        "exp_name=bench_ppo_async",
        "metric.telemetry.enabled=true",
        "metric.telemetry.trace=false",
        f"metric.telemetry.summary_path={tel_path}",
        *_QUIET,
    ]
    line = _repeat_line(
        "ppo_cartpole_65536_steps_async_envs",
        lambda: _timed_subprocess_run(args, timeout=600),
        PPO_BASELINE_SECONDS,
        "headline PPO protocol with env.vectorization=async (64 env worker "
        "processes, shared-memory step results); single measured run after "
        "one warm-up — read next to ppo_cartpole_65536_steps for the "
        "sync vs async delta",
        repeats=1,
        min_stage_s=60.0,
    )
    try:
        with open(tel_path) as f:
            tel = json.load(f)
        data = json.loads(line)
        data["telemetry"] = {
            k: tel.get(k)
            for k in (
                "env_steps_async",
                "env_worker_restarts",
                "env_degraded_to_sync",
                "bytes_staged_h2d",
                "recompiles",
            )
        }
        data["telemetry"].update(_phase_tails(tel))
        if data.get("value"):
            data["sps"] = round(65536 / data["value"], 1)
            try:
                sync_median = json.loads(sync_line).get("value")
                if sync_median:
                    data["sps_vs_sync"] = round(sync_median / data["value"], 3)
            except Exception:
                pass
        line = json.dumps(data)
    except Exception:
        pass  # a skipped/failed stage has no summary; keep the line as-is
    return line


def _rollout_jax_line(min_stage_s: float = 60.0) -> str:
    """Tier-a evidence: jitted-scan collection on the pure-JAX CartPole vs
    the per-step sync Python loop (tools/bench_rollout.py, apples-to-apples
    MLP policy + replay add on both sides). ISSUE-6 acceptance: >= 10x."""
    metric = "jax_cartpole_rollout_sps"
    if _remaining() < min_stage_s:
        return _skip_line(metric, min_stage_s)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_rollout.py")],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=max(60.0, _remaining()),
        )
        line = next(
            (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")), None
        )
        if proc.returncode == 0 and line:
            return line
        tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
        return json.dumps(
            {"metric": metric, "value": None, "error": " | ".join(tail)[-400:]}
        )
    except Exception as exc:
        return json.dumps({"metric": metric, "value": None, "error": repr(exc)[:400]})


def _kernels_line(min_stage_s: float = 60.0) -> str:
    """Fused-kernel evidence (ISSUE-13, howto/kernels.md): forward+backward
    of the LayerNorm-GRU sequence at the DV2 shape — the fused tiers vs the
    reference cell under ``lax.scan`` (tools/bench_kernels.py). Acceptance:
    ``speedup_vs_reference`` >= 1.2 on at least one tier; the ``steps/s``
    value is diffed across rounds by tools/bench_compare.py."""
    metric = "hafner_ln_gru_seq_fwd_bwd_sps"
    if _remaining() < min_stage_s:
        return _skip_line(metric, min_stage_s)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_kernels.py")],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=max(60.0, _remaining()),
        )
        line = next(
            (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")), None
        )
        if proc.returncode == 0 and line:
            return line
        tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
        return json.dumps(
            {"metric": metric, "value": None, "error": " | ".join(tail)[-400:]}
        )
    except Exception as exc:
        return json.dumps({"metric": metric, "value": None, "error": repr(exc)[:400]})


def _sac_line() -> str:
    # reference protocol (benchmark_sb3.py:21-29): LunarLanderContinuous,
    # 4 envs, 65536 steps. SAC is one policy+one train dispatch per env step.
    # Full protocol when the budget allows; otherwise a disclosed 1/8-protocol
    # run (8192 steps) whose vs_baseline uses the time-scaled baseline.
    # Runs with the now-universal TPU-first replay path (transition-mode
    # device ring: per-burst batch uploads become index-plan uploads, and
    # the host fallback prefetch overlaps staging with train); telemetry
    # rides along so the line carries bytes_staged_h2d / ring_gathers /
    # prefetch counters as evidence.
    import tempfile

    tel_path = os.path.join(tempfile.mkdtemp(prefix="bench_sac_tel_"), "telemetry.json")

    def build_args(steps):
        return [
            "exp=sac",  # env defaults to LunarLanderContinuous-v3 (exp/sac.yaml)
            "env.num_envs=4",
            "env.sync_env=True",
            f"total_steps={steps}",
            "exp_name=bench_sac",
            "buffer.device_ring=True",
            "metric.telemetry.enabled=true",
            "metric.telemetry.trace=false",
            f"metric.telemetry.summary_path={tel_path}",
            *_QUIET,
        ]

    if _remaining() > 2400:
        line = _repeat_line(
            "sac_lunarlander_65536_steps",
            lambda: _timed_subprocess_run(build_args(65536), timeout=1800),
            SAC_BASELINE_SECONDS,
            "reference benchmark_sb3.py:21-29 (LunarLanderContinuous, 4 envs, "
            "1024*64 steps, test/log/ckpt off, buffer.device_ring=True); -v3 "
            "replaces the retired -v2",
            repeats=3,
            min_stage_s=120.0,
        )
    else:
        line = _repeat_line(
            "sac_lunarlander_8192_steps",
            lambda: _timed_subprocess_run(build_args(8192), timeout=1800),
            SAC_BASELINE_SECONDS / 8.0,
            "1/8 of reference benchmark_sb3.py:21-29 (8192 of 65536 steps, same "
            "4-env LunarLanderContinuous, test/log/ckpt off, "
            "buffer.device_ring=True); vs_baseline uses the baseline time-"
            "scaled by 1/8 — the full protocol exceeds this run's wall budget",
            repeats=1,
            min_stage_s=220.0,
        )
    try:  # fold the last run's staging counters into the evidence line
        with open(tel_path) as f:
            tel = json.load(f)
        data = json.loads(line)
        data["telemetry"] = {
            k: tel.get(k)
            for k in (
                "bytes_staged_h2d",
                "h2d_transfers",
                "ring_gathers",
                "prefetch_hits",
                "prefetch_misses",
                "prefetch_wait_ms",
                "recompiles",
            )
        }
        data["telemetry"].update(_phase_tails(tel))
        line = json.dumps(data)
    except Exception:
        pass  # a skipped/failed stage has no summary; keep the line as-is
    return line


def _sac_burst_line(per_step_line: str) -> str:
    # Tier-b evidence: the same disclosed 1/8 SAC protocol with
    # env.act_burst=16 — one device dispatch per 16 env steps for acting and
    # one train dispatch covering 16 updates' gradient steps, instead of one
    # of each per step. The line carries act_dispatches/rollout_bursts from
    # telemetry (the dispatch amortization, ~total_steps/16 bursts) and the
    # sps delta vs the per-step SAC line; the folded phase tails
    # (rollout_p95 vs env_p95 vs train_p50) decompose where the time went
    # when vs_baseline stays < 1.
    import tempfile

    tel_path = os.path.join(tempfile.mkdtemp(prefix="bench_sac_burst_tel_"), "telemetry.json")
    steps = 8192
    args = [
        "exp=sac",
        "env.num_envs=4",
        "env.sync_env=True",
        "env.act_burst=16",
        f"total_steps={steps}",
        "exp_name=bench_sac_burst",
        "buffer.device_ring=True",
        "metric.telemetry.enabled=true",
        "metric.telemetry.trace=false",
        f"metric.telemetry.summary_path={tel_path}",
        *_QUIET,
    ]
    line = _repeat_line(
        "sac_lunarlander_8192_steps_act_burst16",
        lambda: _timed_subprocess_run(args, timeout=1800),
        SAC_BASELINE_SECONDS / 8.0,
        "1/8 of reference benchmark_sb3.py:21-29 with env.act_burst=16 "
        "(burst acting, envs/rollout: 16 env steps per acting dispatch, one "
        "train burst per 16 updates); single measured run after one warm-up "
        "— read next to the per-step SAC line for the dispatch-amortization "
        "delta",
        repeats=1,
        min_stage_s=200.0,
    )
    try:
        with open(tel_path) as f:
            tel = json.load(f)
        data = json.loads(line)
        data["telemetry"] = {
            k: tel.get(k)
            for k in (
                "act_dispatches",
                "rollout_bursts",
                "ring_gathers",
                "bytes_staged_h2d",
                "recompiles",
            )
        }
        data["telemetry"].update(_phase_tails(tel))
        if data.get("value"):
            data["sps"] = round(steps / data["value"], 1)
            try:
                ps = json.loads(per_step_line)
                ps_steps = int(ps["metric"].split("_")[2])  # sac_lunarlander_<N>_steps
                if ps.get("value"):
                    data["sps_vs_per_step"] = round(
                        data["sps"] / (ps_steps / ps["value"]), 3
                    )
            except Exception:
                pass
        line = json.dumps(data)
    except Exception:
        pass  # a skipped/failed stage has no summary; keep the line as-is
    return line


def _dv2_train_burst_line(min_stage_s: float = 240.0) -> str:
    # Train-burst evidence (sheeprl_tpu/train, howto/train_burst.md): the
    # same tiny-but-real DV2 run twice over the same staged batches — fused
    # (every gradient burst is ONE scanned device program) vs the per-step
    # reference loop (SHEEPRL_TRAIN_NO_FUSE=1: n dispatches of one gradient
    # step each, same compiled executable, so the math is bitwise identical
    # and the delta is pure dispatch overhead). CPU-pinned: the win this
    # line is judged on is the COUNTER (train_dispatches_per_step 1.0 vs
    # ~n), not the CPU wall-clock — local CPU dispatch is cheap, so
    # sps_vs_per_step ~>= 1.0 here.
    import tempfile

    metric = "dv2_train_burst_sps"
    if _remaining() < min_stage_s:
        return _skip_line(metric, min_stage_s)
    steps = 192
    cpu_env = {"JAX_PLATFORMS": "cpu"}

    def build(mode, tel_path):
        return [
            "exp=dreamer_v2",
            "fabric.accelerator=cpu",
            "fabric.devices=1",
            "env=dummy",
            "env.id=discrete_dummy",
            "env.sync_env=True",
            "env.num_envs=1",
            f"total_steps={steps}",
            "per_rank_batch_size=4",
            "per_rank_sequence_length=8",
            "algo.horizon=5",
            "algo.dense_units=16",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=16",
            "algo.world_model.transition_model.hidden_size=16",
            "algo.world_model.representation_model.hidden_size=16",
            "algo.world_model.stochastic_size=4",
            "algo.world_model.discrete_size=4",
            "algo.learning_starts=32",
            "algo.train_every=8",
            "algo.per_rank_gradient_steps=4",
            "algo.per_rank_pretrain_steps=4",
            "cnn_keys.encoder=[rgb]",
            "buffer.size=256",
            f"exp_name=bench_dv2_burst_{mode}",
            "metric.telemetry.enabled=true",
            "metric.telemetry.trace=false",
            f"metric.telemetry.summary_path={tel_path}",
            *_QUIET,
        ]

    fused_tel = os.path.join(tempfile.mkdtemp(prefix="bench_dv2b_f_"), "telemetry.json")
    ps_tel = os.path.join(tempfile.mkdtemp(prefix="bench_dv2b_ps_"), "telemetry.json")
    try:
        # per-step reference first: it is the slower side, and a budget
        # clamp should cost the baseline, not the headline measurement
        ps_s = _timed_subprocess_run(
            build("perstep", ps_tel),
            timeout=900,
            env={**cpu_env, "SHEEPRL_TRAIN_NO_FUSE": "1"},
        )
    except Exception as exc:
        ps_s = None
        ps_err = repr(exc)[:200]
    line = _repeat_line(
        metric,
        lambda: _timed_subprocess_run(build("fused", fused_tel), timeout=900, env=cpu_env),
        # vs_baseline = perstep_s / fused_s: > 1 means the fused burst wins
        ps_s,
        "tiny DV2 recipe (dummy pixel env, 192 steps, 4 grad steps per "
        "burst) run fused vs SHEEPRL_TRAIN_NO_FUSE=1 over the same staged "
        "batches — same compiled executable, so the delta is pure dispatch "
        "count; judged on train_dispatches_per_step (0.25 fused vs 1.0 "
        "per-step), with CPU sps as supporting evidence",
        repeats=1,
        min_stage_s=min_stage_s,
    )
    try:
        data = json.loads(line)
        with open(fused_tel) as f:
            tel = json.load(f)
        data["telemetry"] = {
            k: tel.get(k)
            for k in ("train_bursts", "train_dispatches", "train_burst_steps", "recompiles")
        }
        data["telemetry"].update(_phase_tails(tel))
        if data.get("value"):
            data["sps"] = round(steps / data["value"], 1)
        if ps_s:
            ps_info = {"value": ps_s, "sps": round(steps / ps_s, 1)}
            try:
                with open(ps_tel) as f:
                    ps_t = json.load(f)
                ps_info.update(
                    {
                        k: ps_t.get(k)
                        for k in ("train_bursts", "train_dispatches", "train_burst_steps")
                    }
                )
                ps_info.update(_phase_tails(ps_t))
            except Exception:
                pass
            data["per_step_baseline"] = ps_info
            if data.get("sps"):
                data["sps_vs_per_step"] = round(data["sps"] / ps_info["sps"], 3)
        else:
            data["per_step_baseline"] = {"error": ps_err}
        line = json.dumps(data)
    except Exception:
        pass  # a skipped/failed stage has no summary; keep the line as-is
    return line


def _dreamer_e2e_line(family, baseline, total_steps, min_stage_s, extra=()) -> str:
    args = [
        f"exp={family}",  # defaults to the 64x64-pixel dummy env
        "env.num_envs=1",
        f"total_steps={total_steps}",
        f"exp_name=bench_{family}",
        # the replay path is universal now: pixel bursts gather from the
        # device ring instead of re-crossing the host link every burst
        "buffer.device_ring=True",
        *extra,
        *_QUIET,
    ]
    return _repeat_line(
        f"{family}_e2e_{total_steps}_steps",
        lambda: _timed_subprocess_run(args, timeout=1800),
        baseline,
        f"default {family} S recipe, 64x64 pixel dummy env, {total_steps} "
        "policy steps (prefill + training bursts). SINGLE measured run after "
        "one warm-up (the 3-repeat protocol applies to the SAC/PPO lines; "
        "these runs are minutes long). Reference bench exp configs absent "
        "from snapshot: vs_baseline is the raw wall-clock ratio, NOT "
        "step-matched",
        repeats=1,
        min_stage_s=min_stage_s,
    )


def _sac_plane_line() -> str:
    # Actor–learner plane evidence (sheeprl_tpu/plane, howto/actor_learner.md):
    # the same decoupled SAC protocol twice — thread-local baseline
    # (plane.num_players=0, the historical decoupled topology) and the
    # 2-player+1-learner process plane — and the line reports the plane run
    # with its counters (plane_traj_slabs / plane_policy_version /
    # plane_player_restarts), phase tails (train_p95 beside plane_wait_p95 /
    # env_p95: collection off the train-step critical path), and the sps
    # delta vs the thread baseline. Pinned to CPU devices: the plane is a
    # host-side property (players are CPU processes by design), and 2
    # virtual CPU devices satisfy the decoupled >=2-device contract on any
    # host. SAC is continuous-only, so the env is Pendulum (the CartPole of
    # Box action spaces), not CartPole itself.
    import tempfile

    steps = 4096
    cpu_env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
        ).strip(),
    }

    def build(mode, players, tel_path):
        return [
            "exp=sac_decoupled",
            "fabric.devices=2",
            "fabric.accelerator=cpu",
            f"plane.num_players={players}",
            "env.id=Pendulum-v1",
            "env.num_envs=4",
            f"total_steps={steps}",
            "algo.learning_starts=512",
            "per_rank_batch_size=64",
            f"exp_name=bench_sac_plane_{mode}",
            "metric.telemetry.enabled=true",
            "metric.telemetry.trace=false",
            f"metric.telemetry.summary_path={tel_path}",
            *_QUIET,
        ]

    thread_tel = os.path.join(tempfile.mkdtemp(prefix="bench_plane_thr_"), "telemetry.json")
    plane_tel = os.path.join(tempfile.mkdtemp(prefix="bench_plane_2p_"), "telemetry.json")

    if _remaining() < 300.0:
        return _skip_line("sac_pendulum_plane_2p1l", 300.0)
    try:
        thread_s = _timed_subprocess_run(
            build("thread", 0, thread_tel), timeout=900, env=cpu_env
        )
    except Exception as exc:
        thread_s = None
        thread_err = repr(exc)[:200]
    line = _repeat_line(
        "sac_pendulum_plane_2p1l",
        lambda: _timed_subprocess_run(build("2p1l", 2, plane_tel), timeout=900, env=cpu_env),
        # a failed baseline must not fabricate a ratio: vs_baseline stays
        # null and thread_baseline.error below records why
        thread_s,
        "decoupled SAC, Pendulum-v1, 4 envs, 4096 steps, test/log/ckpt off, "
        "2 player processes + 1 learner (plane.num_players=2) vs the "
        "thread-local decoupled baseline (vs_baseline = thread_s / plane_s, "
        "> 1 means the process plane wins); CPU-pinned 2-device mesh",
        repeats=1,
        min_stage_s=240.0,
    )
    try:
        data = json.loads(line)
        with open(plane_tel) as f:
            tel = json.load(f)
        data["telemetry"] = {
            k: tel.get(k)
            for k in (
                "plane_traj_slabs",
                "plane_policy_version",
                "plane_player_restarts",
                "env_steps_async",
                "recompiles",
            )
        }
        data["telemetry"].update(_phase_tails(tel))
        if data.get("value"):
            data["sps"] = round(steps / data["value"], 1)
        if thread_s:
            thread_info = {"value": thread_s, "sps": round(steps / thread_s, 1)}
            try:
                with open(thread_tel) as f:
                    thread_info.update(_phase_tails(json.load(f)))
            except Exception:
                pass
            data["thread_baseline"] = thread_info
            if data.get("sps"):
                data["sps_vs_thread"] = round(data["sps"] / thread_info["sps"], 3)
        else:
            data["thread_baseline"] = {"error": thread_err}
        line = json.dumps(data)
    except Exception:
        pass  # a skipped/failed stage has no summary; keep the line as-is
    return line


def main() -> None:
    # print every line as soon as it exists (a later crash cannot lose it)
    # AND re-print the full matrix at the end: the driver records a truncated
    # *tail* of this output, so the evidence lines must be the last lines,
    # with the PPO headline last of all.
    lines = []

    def emit(line):
        lines.append(line)
        print(line, flush=True)

    ppo_line = _ppo_line()  # headline: first in, printed again last
    print(ppo_line, flush=True)
    # async-envs evidence line right after the headline it is compared to
    # (env_p95/env_wait_p95 + pool counters + sps delta vs sync)
    emit(_ppo_async_line(ppo_line))
    # rollout-engine tier-a evidence: jitted-scan collection sps vs the sync
    # Python loop (cheap, ~1 min; ISSUE-6 acceptance >= 10x)
    emit(_rollout_jax_line())
    # fused-kernel evidence: LayerNorm-GRU sequence fwd+bwd, fused tiers vs
    # the reference scan at the DV2 shape (cheap, ~1 min; ISSUE-13
    # acceptance >= 1.2x on >= 1 tier)
    emit(_kernels_line())
    # actor–learner plane evidence: 2-player+1-learner decoupled SAC vs the
    # thread-local decoupled baseline (plane counters + plane_wait/train
    # phase tails as the collection-overlap decomposition). Early in the
    # matrix: it is cheap (~3 short CPU runs) and must not be starved by the
    # long SAC stages below.
    emit(_sac_plane_line())
    # train-burst evidence: tiny DV2 fused vs per-step reference over the
    # same staged batches (judged on train_dispatches_per_step, CPU-cheap)
    emit(_dv2_train_burst_line())
    emit(_dreamer_line("dv3", min_stage_s=180.0, extra=("bench.profile=1",)))
    # DV2/DV1 device-step lines (grad-steps/s + scan-corrected MFU vs wall
    # rate; no xplane pass — keeps each under ~3 min warm). Their e2e
    # micro-runs now ride the universal device ring (buffer.device_ring in
    # _dreamer_e2e_line), so bursts gather on device instead of uploading a
    # ~12 MB host batch each; the wall-clock e2e rows only run when a big
    # budget is configured.
    emit(_dreamer_line("dv2", min_stage_s=170.0, extra=("bench.steps=10",)))
    emit(_dreamer_line("dv1", min_stage_s=170.0, extra=("bench.steps=10",)))
    # SAC last: the only stage that can overrun its estimate by minutes
    # (per-step dispatch); anything it loses is only its own line
    sac_line = _sac_line()
    emit(sac_line)
    # burst-acting evidence right after the per-step SAC line it is compared
    # to (act_dispatches/rollout_bursts counters + sps delta + phase tails)
    emit(_sac_burst_line(sac_line))
    # e2e rows fit only a generous budget; their min_stage_s gates emit
    # disclosed skip lines under the default budget
    emit(_dreamer_e2e_line("dreamer_v2", DV2_BASELINE_SECONDS, 2500, min_stage_s=1100.0))
    emit(_dreamer_e2e_line("dreamer_v1", DV1_BASELINE_SECONDS, 6000, min_stage_s=1200.0))

    for line in lines:
        print(line, flush=True)
    print(ppo_line, flush=True)


if __name__ == "__main__":
    main()
