"""Test env: force JAX onto CPU with 8 virtual devices so multi-chip sharding
paths (tensor/data/sequence parallel) are exercised without TPU hardware —
the gap the reference left (it has no automated distributed tests, SURVEY.md §4).

Why forced, today: the tests run in a sandbox with no accelerator but WITH
the TPU's library installed, and their sharding cases count on exactly
eight devices. ``JAX_PLATFORMS`` is the one way this repository chooses a
backend; it is set in the environment (before anything imports jax), so
the server and CLI children the tests start inherit it. The only tests
that load the TPU's library are the described-topology compiles in
``tests/test_chip_compile.py``, from inside their own fixture.

The persistent compile cache is switched off for the whole run: the CLI
points it at ``<checkout>/.jax_cache`` (runtime/device.py), and a test run
must not fill the checkout — the chip tool copies the tree as it stands.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection chaos tests (CI smoke job: -m faults)")
