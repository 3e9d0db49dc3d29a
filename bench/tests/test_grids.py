"""The deployments the benchmark builds from its configuration files are
the ones the program documents: its fully connected 4-region grid, its
TPU v5e fleet and h2o-danube-1.8b's cost row."""

import numpy as np
import pytest

from harness import cells, grids, program, reference
from repro.configs import get_config
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.core.infrastructure import tpu_fleet


def _config(name):
    return cells.load(name).config


@pytest.mark.parametrize("cell,build", [
    ("dense4.place",
     lambda: CarbonGrid.fully_connected(DEFAULT_REGIONS, latency_penalty=1.05)),
])
def test_grid_tables_match_the_program(cell, build):
    ours = cells.load(cell).grid()
    theirs = build()
    for f in ("ci_hourly", "ci_mobile", "ci_core", "pue", "latency_penalty",
              "rtt_s"):
        np.testing.assert_allclose(ours[f], np.asarray(getattr(theirs, f)),
                                   rtol=1e-6, err_msg=f)
    np.testing.assert_array_equal(ours["adjacency"],
                                  np.asarray(theirs.adjacency))
    assert theirs.nbr_idx is None
    np.testing.assert_allclose(grids.component_table(ours),
                               np.asarray(theirs.table), rtol=1e-6)


@pytest.mark.parametrize("cell", ["dense4.place", "dense4.serve"])
def test_fleet_and_cost_row_match_the_program(cell):
    cfg = _config(cell)
    assert program.fleet_of(cfg) == tpu_fleet()
    assert (reference.n_active_params(cfg["model"])
            == get_config(cfg["model"]["name"]).active_param_count())
