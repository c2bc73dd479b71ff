"""``chip_smoke.py`` measures nothing without a TPU: on any other backend it
exits non-zero, before any training, and prints no result line."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_chip_smoke_exits_nonzero_naming_the_missing_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    # it said what jax found, and nothing that could be read as a result
    assert "backend cpu" in out.stdout
    assert '"ok"' not in out.stdout


def test_verdict_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any other key (PR 21 learned it)."""
    import jax

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    devices = jax.devices()
    verdict = json.loads(chip_smoke.verdict_line(devices))
    assert verdict == {
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }
    assert "\n" not in chip_smoke.verdict_line(devices)
