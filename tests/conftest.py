"""Test harness setup.

Multi-device testing strategy (reference used 2-process Gloo via Fabric,
tests/test_algos.py:16-52): here we run JAX on the host CPU platform with 8
virtual devices so mesh/sharding code paths execute exactly as they would on
an 8-chip TPU slice, without TPU hardware.
"""

import os

# Must be set before jax is imported anywhere: tests run on the
# 8-virtual-device CPU mesh whatever accelerator the machine has. Force (not
# setdefault), so a TPU host still runs the suite on the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent compilation cache: the e2e algo tests jit several programs each;
# caching compilations to disk makes repeated suite runs fast. Placed through
# the environment, outside the checkout: jax reads the variable itself, and
# in-process `cli.run` calls (enable_persistent_compilation_cache) then set no
# directory of their own, so the whole suite shares this one cache.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_pytest_cache")

import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_observability_globals():
    """Restore the class-level disable flags the CLI flips (cli.py:136-139);
    without this an algo test run with ``metric.log_level=0`` leaks
    ``disabled=True`` into later aggregator/timer unit tests."""
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    agg_disabled, timer_disabled = MetricAggregator.disabled, timer.disabled
    yield
    MetricAggregator.disabled = agg_disabled
    timer.disabled = timer_disabled
    timer.reset()


@pytest.fixture(autouse=True)
def _preserve_environ():
    """Snapshot/restore os.environ around every test (reference
    tests/conftest.py:20-61 asserts no env-var leaks)."""
    before = dict(os.environ)
    yield
    after = dict(os.environ)
    for k in after.keys() - before.keys():
        del os.environ[k]
    for k, v in before.items():
        if os.environ.get(k) != v:
            os.environ[k] = v
