"""Test harness (modeled on the reference's root conftest.py + pytest.ini).

Runs the suite on CPU with 8 virtual XLA devices so every multi-device /
mesh test exercises real sharding + collectives without a TPU pod — the
multi-process trick the reference used for dist kvstore tests
(tests/nightly/dist_sync_kvstore.py via tools/launch.py), done the
jax-native way.

Must set env BEFORE jax is imported anywhere.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The tests run on the CPU whatever the machine holds: the package sets
# no platform itself, so this is where that choice is made — through
# jax.config, before any backend is initialized.
import jax

jax.config.update("jax_platforms", "cpu")
# Oracle tightness: suite comparisons against NumPy run at exact fp32.
# The package itself no longer pins this process-wide (the TPU-idiomatic
# default is one-pass MXU matmul; see docs/precision.md) — tests opt in.
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as onp
import pytest


@pytest.fixture(autouse=True)
def _seed_rng(request):
    """Per-test deterministic seeding with the seed printed on failure
    (reference conftest.py behavior)."""
    seed = onp.random.randint(0, 2 ** 31)
    marker = request.node.get_closest_marker("seed")
    if marker is not None and marker.args:
        seed = marker.args[0]
    onp.random.seed(seed)
    try:
        from mxnet_tpu.numpy import random as mxrandom

        mxrandom.seed(seed)
    except Exception:
        pass
    yield
    # pytest shows captured stdout only on failure — record the seed there
    print(f"[test seed: {seed}]")


@pytest.fixture
def lockwatch_armed(monkeypatch):
    """Opt-in runtime lock-order witness (the C001 property checked
    against a real execution): arms ``analysis.lockwatch`` through its
    env knob for the drill, yields the module, and asserts on teardown
    that no lock-order cycle was observed."""
    from mxnet_tpu.analysis import lockwatch

    monkeypatch.setenv(lockwatch.ENV_KNOB, "1")
    assert lockwatch.install_if_env()
    lockwatch.reset()
    try:
        yield lockwatch
        lockwatch.assert_acyclic()
    finally:
        lockwatch.uninstall()
        lockwatch.reset()


def pytest_configure(config):
    config.addinivalue_line("markers", "seed(n): fix the RNG seed for a test")
    config.addinivalue_line("markers", "serial: run test serially")
    config.addinivalue_line("markers", "integration: end-to-end test")
    # chaos tests inject faults through mxnet_tpu.resilience.chaos; they
    # are fast and hermetic (scoped rules / subprocess kills), so they
    # run in tier-1 — the marker exists for `-m chaos` selection
    config.addinivalue_line("markers", "chaos: fault-injection test")
