"""Test config: force an 8-device virtual CPU platform.

Mirrors the reference's strategy of testing distributed logic without real
accelerators (SURVEY.md §4: fake/Gloo backends, multi-process single host) —
here a single-process 8-device CPU mesh exercises the same SPMD code paths the
TPU mesh uses.

The platform is forced at config level: the suite must never take the chip,
whatever JAX_PLATFORMS the caller exported.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# fixture PROJECTS are parse-only inputs for the staticcheck tests, never
# test modules — keep pytest out of them (a fixture file named test_*.py,
# like the chaos-site-coverage known-answer matrix, would otherwise
# basename-collide with the real tests/test_no_hang.py at collection)
collect_ignore_glob = ["fixtures/*", "staticcheck_proj/*"]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running battery (tier-1 excludes these via -m 'not slow')")


REFERENCE_TREE = "/root/reference/python/paddle"


@pytest.fixture
def reference_tree():
    """For a test that reads the reference's own sources (the API-parity
    families): skipped, by name, on a machine that does not hold them."""
    if not os.path.isdir(REFERENCE_TREE):
        pytest.skip(f"the reference tree {REFERENCE_TREE} is not on this "
                    f"machine; nothing to compare the API against")
    return REFERENCE_TREE


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as P
    P.seed(2024)
    np.random.seed(2024)
    yield
