"""The benchmark's own tests run on the CPU at tiny sizes; nothing here
is a measurement."""

import copy
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def tiny(cell: dict) -> dict:
    """The cell at a size a test run can hold: 64 KiB objects at most,
    48 of them, four callers."""
    cell = copy.deepcopy(cell)
    t = cell["traffic"]
    t["object_bytes"] = min(t["object_bytes"], 65536)
    t["objects"] = min(t["objects"], 48)
    t["payload"]["pool"] = min(t["payload"]["pool"], 53)
    t["verify"]["objects"] = 8
    t["inflight"] = min(t["inflight"], 4)
    return cell


@pytest.fixture(scope="session")
def bench():
    from benchmark import cells
    return cells.manifest()
