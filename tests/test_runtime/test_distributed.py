"""Real 2-process ``jax.distributed`` exercise.

The reference proves its distributed path with a 2-process Gloo run in CI
(reference tests/test_algos/test_algos.py:16-52). Here two subprocesses with
2 virtual CPU devices each form a 4-device world mesh via
``init_distributed`` and run the previously-dead multi-host branches of
``Fabric`` for real: a cross-process jitted reduction, ``all_gather``,
``broadcast``, and ``barrier`` (see ``distributed_worker.py``).
"""

import os
import socket
import subprocess
import sys

_WORKER = os.path.join(os.path.dirname(__file__), "distributed_worker.py")
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_world_collectives():
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # workers set their own device count
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(pid), str(port)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=_REPO,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert f"WORKER{pid} PASS" in out
