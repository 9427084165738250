"""Test configuration: force a hermetic 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding/collective paths are
validated on a virtual CPU mesh (mirrors how the reference tests multi-node
logic in one process with mock messengers — SURVEY.md §4 tier 2).

Tests never touch an accelerator: JAX_PLATFORMS=cpu is set and the live
jax config pinned to it before any backend initialisation.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from ceph_tpu.utils.jaxenv import force_cpu  # noqa: E402

force_cpu(device_count=8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: large-object / long-running integration tests")
