"""Placement of the persistent compilation cache
(``utils.enable_persistent_compilation_cache``): ``JAX_COMPILATION_CACHE_DIR``
is honoured and nothing sets a directory in code; without it the cache lives
at one fixed path inside the checkout, the same from any process."""

import os
import subprocess
import sys

import jax

from sheeprl_tpu.utils.utils import enable_persistent_compilation_cache

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_env_var_is_honoured_and_no_directory_is_set_in_code(monkeypatch):
    # conftest placed the suite's cache through the environment
    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == placed
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: (updates.append(name), real_update(name, value))
    )
    enable_persistent_compilation_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == placed


def test_unset_env_var_gives_the_same_in_checkout_path_from_any_process(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    script = (
        "import jax; from sheeprl_tpu.utils.utils import enable_persistent_compilation_cache as e;"
        " e(); print(jax.config.jax_compilation_cache_dir)"
    )
    seen = []
    # two processes that differ in everything the old default depended on
    for cwd, home in ((tmp_path, "/nonexistent-home"), (REPO, str(tmp_path))):
        out = subprocess.run(
            [sys.executable, "-c", script],
            env={**env, "HOME": home},
            cwd=str(cwd),
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        seen.append(out.stdout.strip().splitlines()[-1])
    assert seen == [os.path.join(REPO, ".jax_cache")] * 2
