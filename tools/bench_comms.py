#!/usr/bin/env python
"""Measure real 2-process ``jax.distributed`` collectives on this host.

The round-5 scaling study's multi-chip numbers (record deleted in PR 21)
were *analytic* (static HLO census × public ICI specs); nothing had timed
the real 33 MB gradient all-reduce across processes. This tool does: it stands up a genuine 2-process ``jax.distributed``
world on this host (gloo CPU backend — the same software path the reference
exercises in its 2-process CI), then times ``Fabric.all_reduce`` — the
jitted on-the-wire cross-process collective, not a mock — across a sweep of
payload sizes including the exact 33.05 MB gradient payload the DV3 S-preset
census found. Timings run through the instrumented comms spans
(``obs/dist/comms.py``), so the run also demonstrates the distributed
telemetry plane end-to-end: rank 0 writes a ``telemetry.json`` whose
``comms_ms``/``comms`` sections carry the measured collectives and whose
``sources`` section carries rank 1's merged sidecar.

On a CPU host the numbers measure the *software overhead* of the collective
path (serialization, gloo, loopback) — an upper bound on the per-hop latency
term the analytic projection ignores, and the honest "Measured (2-process)"
rows next to that study's projections. On a multi-chip TPU host the
same command times ICI.

Usage::

    python tools/bench_comms.py [--sizes-mb 1,8,33.05] [--repeats 10]
        [--out DIR]            # telemetry + JSON rows land here
    python tools/bench_comms.py --markdown   # print the rows as a markdown table
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the DV3 S-preset gradient all-reduce payload (tools/bench_scaling.py census)
GRADIENT_MB = 33.05
DEFAULT_SIZES_MB = (1.0, 8.0, GRADIENT_MB)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# worker (one per process of the 2-process world)
# ---------------------------------------------------------------------------


def run_worker(process_id: int, port: str, sizes_mb, repeats: int, out_dir: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from sheeprl_tpu.fabric import Fabric, init_distributed
    from sheeprl_tpu.obs.dist.comms import wire_bytes
    from sheeprl_tpu.obs.prof.roofline import detect_link_peaks
    from sheeprl_tpu.obs.telemetry import Telemetry
    from sheeprl_tpu.obs import telemetry as telemetry_mod

    assert init_distributed(f"127.0.0.1:{port}", 2, process_id) is True
    n_proc = jax.process_count()
    assert n_proc == 2, n_proc

    # full run telemetry in both processes: rank 0 owns telemetry.json,
    # rank 1 writes the sidecar the finalize-time aggregator merges
    telemetry = Telemetry(
        {
            "enabled": True,
            "trace": False,
            "poll_interval_s": 0,
            "stall_timeout_s": 0,
            "live_interval_s": 0,
        }
    )
    telemetry.start()
    telemetry_mod._ACTIVE = telemetry
    telemetry.attach_run_dir(out_dir)

    fabric = Fabric(devices="auto", accelerator="cpu")
    link = detect_link_peaks()

    rows = []
    for size_mb in sizes_mb:
        n = max(int(size_mb * 1e6 / 4), 1)
        payload = np.full(n, float(process_id + 1), np.float32)
        expected = float(sum(range(1, n_proc + 1)))
        # warmup: compile + first-touch of the gloo channels
        for _ in range(2):
            out = fabric.all_reduce({"x": payload})
        assert abs(float(out["x"][0]) - expected) < 1e-4, out["x"][0]
        fabric.barrier("warm")
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fabric.all_reduce({"x": payload})
        elapsed = time.perf_counter() - t0
        ms = elapsed / repeats * 1e3
        payload_bytes = payload.nbytes
        wire = wire_bytes("all_reduce", payload_bytes, n_proc)
        rows.append(
            {
                "metric": f"allreduce_2proc_{size_mb:g}mb",
                "value": round(ms, 3),
                "unit": "ms",
                "payload_mb": round(payload_bytes / 1e6, 2),
                "repeats": repeats,
                "achieved_allreduce_gbps": round(wire / (elapsed / repeats) / 1e9, 3),
                "payload_gbps": round(payload_bytes / (elapsed / repeats) / 1e9, 3),
                "link_peak_gbps": link.get("link_gbps"),
                "link_label": link.get("label"),
                "backend": "gloo-cpu-loopback",
                "n_processes": n_proc,
            }
        )
        fabric.barrier(f"size-{size_mb}")

    # one timed all_gather + broadcast so the per-kind breakdown in
    # telemetry.json covers every host-level collective
    fabric.all_gather({"g": np.ones(1024, np.float32)})
    fabric.broadcast({"b": np.ones(1024, np.float32)})

    fabric.barrier("pre-finalize")
    if process_id != 0:
        # rank 1's finalize writes sidecar_rank1.json; rank 0 waits (barrier
        # below) so its merge sees the sidecar on disk
        telemetry_mod.finalize_telemetry(print_summary=False)
        fabric.barrier("post-sidecar")
    else:
        fabric.barrier("post-sidecar")
        summary = telemetry_mod.finalize_telemetry(print_summary=False)
        assert summary["comms_ms"] > 0, "instrumented collectives recorded nothing"
        for row in rows:
            print(json.dumps(row), flush=True)
        print(
            json.dumps(
                {
                    "telemetry_json": os.path.join(out_dir, "telemetry.json"),
                    "comms_ms": summary["comms_ms"],
                    "comms_ops": summary["comms_ops"],
                    "sources": sorted(summary.get("sources", {})),
                }
            ),
            flush=True,
        )
    print(f"WORKER{process_id} PASS", flush=True)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def spawn_world(sizes_mb, repeats: int, out_dir: str, timeout_s: float = 600.0):
    """Spawn the 2-process world; returns (rows, telemetry_summary_line)."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one virtual device per process -> a 2-device world mesh across the
    # 2-process boundary (the collective must cross processes, not lanes)
    env["XLA_FLAGS"] = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p and p != REPO]
    )
    os.makedirs(out_dir, exist_ok=True)
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--worker",
                str(pid),
                "--port",
                str(port),
                "--sizes-mb",
                ",".join(str(s) for s in sizes_mb),
                "--repeats",
                str(repeats),
                "--out",
                out_dir,
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=REPO,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout_s)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0 or f"WORKER{pid} PASS" not in out:
            raise RuntimeError(f"comms worker {pid} failed:\n{out[-3000:]}")
    rows, tail = [], None
    for line in outs[0].splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        doc = json.loads(line)
        if "metric" in doc:
            rows.append(doc)
        elif "telemetry_json" in doc:
            tail = doc
    return rows, tail


def to_markdown(rows) -> str:
    lines = [
        "| payload MB | measured ms/op | payload GB/s | wire GB/s | repeats |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['payload_mb']} | {r['value']} | {r['payload_gbps']} | "
            f"{r['achieved_allreduce_gbps']} | {r['repeats']} |"
        )
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--port", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--sizes-mb", default=",".join(str(s) for s in DEFAULT_SIZES_MB))
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(REPO, "logs", "bench_comms"))
    ap.add_argument("--markdown", action="store_true", help="print the rows as a markdown table")
    args = ap.parse_args()
    sizes = [float(s) for s in str(args.sizes_mb).split(",") if s]

    if args.worker is not None:
        run_worker(args.worker, args.port, sizes, args.repeats, args.out)
        return 0

    rows, tail = spawn_world(sizes, args.repeats, args.out)
    for row in rows:
        print(json.dumps(row))
    if tail:
        print(json.dumps(tail))
    if args.markdown:
        print()
        print(to_markdown(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
