"""Test harness configuration.

Mirrors the reference's decoupling of unit tests from live services
(``COVALENT_PLUGIN_LOAD=false``, ``tests.yml:87-89``) and adds the CPU
simulated-mesh tier from SURVEY §4.2c: an 8-device virtual CPU mesh via
``--xla_force_host_platform_device_count`` so all pjit/shard_map fan-out
logic is tested without TPUs.  Environment must be set before jax first
initializes its backends, hence module level, before any test imports jax.
"""

import asyncio
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The test tier always runs on the virtual CPU mesh; jax reads the variable
# at import, so it is set first.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# Isolate the config system from any real user config file.
os.environ.setdefault("COVALENT_TPU_CONFIG", "/tmp/covalent-tpu-test-config.toml")

import pytest

#: jax-heavy modules (interpret-mode Pallas kernels, model forwards,
#: virtual-mesh shard_map) — minutes each on one core.  The fast tier
#: (``pytest -m "not slow"``) is the executor/transport/workflow/config
#: stack, mirroring the reference's seconds-fast mocked unit tier
#: (reference tests/ssh_test.py); CI runs both tiers.
SLOW_MODULES = {
    "test_attention",
    "test_attention_sinks",
    "test_continuous",
    "test_distributed_pod",
    "test_beam",
    "test_decode",
    "test_kv_cache_quant",
    "test_lora",
    "test_models",
    "test_moe",
    "test_parallel",
    "test_pipeline",
    "test_quant",
    "test_ring_attention",
    "test_serving_sharded",
    "test_sliding_window",
    "test_speculative",
}


def pytest_collection_modifyitems(items):
    for item in items:
        module = item.module.__name__.rsplit(".", 1)[-1]
        if module in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_compile_state():
    """Clear jax's in-process caches after each test module.

    A full-suite run accumulates hundreds of compiled executables in one
    process; at ~85% through, XLA:CPU's compiler segfaulted inside
    backend_compile (reproduced twice at the same test, while the same
    test passes in isolation and in whole-file runs).  Per-module
    clearing bounds the growth; cross-module cache reuse is ~nil anyway
    (modules compile their own model/kernel shapes)."""
    yield
    jax.clear_caches()


@pytest.fixture()
def run_async():
    """Drive a coroutine to completion (no pytest-asyncio in this image)."""

    def runner(coro):
        return asyncio.run(coro)

    return runner


@pytest.fixture()
def tmp_config(tmp_path, monkeypatch):
    """Point the config system at a fresh file and reset its cache."""
    from covalent_tpu_plugin.utils import config as config_mod

    path = tmp_path / "config.toml"
    monkeypatch.setenv("COVALENT_TPU_CONFIG", str(path))
    config_mod._reset_cache_for_tests()
    yield path
    config_mod._reset_cache_for_tests()
