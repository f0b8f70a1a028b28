"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip sharding tests run against
``--xla_force_host_platform_device_count=8`` on the CPU backend, as
SURVEY.md §4 prescribes; the chip is reached through ``chip_smoke.py`` and
``bench.py`` only.

The platform is pinned both ways (the env var before JAX is imported, and
the jax config after), so the suite stays on the CPU even on a host with a
TPU attached, where JAX would otherwise claim the chip.  The one test file
that targets the chip, ``test_tpu_compile.py``, compiles for a *described*
v5e topology and never runs on it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps excluded from the tier-1 run "
        "(`-m 'not slow'`); ci.sh runs them in their own stage")
