"""Grid kind ``regions_horizon``: the ``regions`` grid over a horizon of
``days`` days. Each day repeats day one's hourly CI and PUE, as
``CarbonGrid.repeat`` tiles them; battery and core CI, adjacency, latency
penalties and round trips are the one-day grid's. Hours index the horizon
absolutely, so hour 24 + h is day two's hour h."""

from __future__ import annotations

from pathlib import Path

import numpy as np

from harness import cells

regions = cells.module(Path(__file__).parent / "regions.py")


def build(spec: dict, source_ci) -> dict:
    g = regions.build(spec, source_ci)
    days = int(spec["days"])
    for k in ("ci_hourly", "pue"):
        g[k] = np.tile(g[k], (1, days))
    return g
