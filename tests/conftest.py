"""Test harness: the XLA CPU backend with 8 virtual devices, so the
multi-chip sharding paths are exercised without TPU hardware (the
reference's fake_cpu_device / gloo-backend strategy, SURVEY.md §4).

The persistent compile cache (paddle_tpu/_bootstrap.py) is switched off
here: the suite compiles thousands of tiny CPU programs once each, and
writing them under the checkout buys nothing.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"  # inherited by children

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _reset_fleet_per_module():
    """Isolate test modules from each other's fleet topology: a module
    that never calls fleet.init must see single-device behavior even if
    a previously-run module initialized a hybrid mesh (the reference gets
    this isolation for free from per-test subprocesses)."""
    from paddle_tpu.distributed import fleet as _fleet

    _fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)
    yield
