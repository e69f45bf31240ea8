"""Test config: force an 8-device CPU mesh BEFORE jax initializes.

Mirrors the reference's strategy of testing distributed behavior without a
cluster (reference test_dist_base.py localhost multi-process): here we use
XLA's host-platform device multiplication, so every sharding/collective test
runs on any machine. The chip is reached separately and only through
chip_smoke.py / bench.py (one process per chip).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from paddle_tpu.testing import cpu_mesh_env  # noqa: E402,F401  (re-export for tests)


def pytest_configure(config):
    # the tier-1 command (ROADMAP.md) deselects with -m 'not slow': the
    # marker is for compile-heavy tests that cannot fit tier-1's hard
    # wall-clock budget; the unfiltered suite still runs them
    config.addinivalue_line(
        "markers", "slow: compile-heavy; excluded from the tier-1 budget")


os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# NOTE: no persistent XLA compilation cache here — A/B measurement showed
# it cannot speed the CPU-mesh suite (XLA CPU compiles are ~0.2 s, under
# any sane min-compile-time threshold; jax tracing dominates wall time),
# and multi-process LRU eviction can emit warnings that would break the
# suite's zero-warnings contract. TPU entry points enable it
# (paddle_tpu/compile_cache.py).

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test a fresh default program + scope (like the reference's
    new Program() per unit test)."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import program as prog_mod
    from paddle_tpu.framework import scope as scope_mod
    from paddle_tpu.framework import unique_name

    old_main, old_startup = prog_mod._main_program, prog_mod._startup_program
    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    scope_mod._reset_global_scope()
    unique_name.switch()
    np.random.seed(0)
    yield
    prog_mod._main_program, prog_mod._startup_program = old_main, old_startup
