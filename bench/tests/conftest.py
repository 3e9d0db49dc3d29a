"""Benchmark tests run on the CPU, from the repository root:
``python -m pytest bench/tests``. The environment is set before anything
imports jax."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


import dataclasses  # noqa: E402

import pytest  # noqa: E402


def shrink(cell, n: int):
    """``cell`` at ``n`` requests a stream; its capacity follows the
    request count (``cells.dc_capacity``), so admission binds as it does at
    full size."""
    scale = n / cell.traffic["requests"]
    traffic = dict(cell.traffic, requests=n)
    if "max_batch" in traffic:  # peak hours still take two drafts
        traffic["max_batch"] = max(16, int(traffic["max_batch"] * scale))
    return dataclasses.replace(cell, traffic=traffic)


@pytest.fixture
def small_cell():
    from harness import cells

    return lambda name, n=4000: shrink(cells.load(name), n)
