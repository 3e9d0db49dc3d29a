"""Grid kind ``regions``: a fully connected regional grid. Each region's CI
comes from its generation profile (``harness.grids.PROFILES``), battery CI
is the uniform (all-day) charging average, core CI the daily mean, and
every remote hop carries one latency penalty and one round trip."""

from __future__ import annotations

import numpy as np

from harness.grids import dense_tables, profile_ci


def build(spec: dict, source_ci) -> dict:
    ci = np.stack([profile_ci(p, source_ci) for p in spec["regions"]])
    mean = ci.mean(axis=1)
    out = dense_tables(ci, mean, mean, float(spec["pue"]))
    r = len(ci)
    pen = np.full((r, r), spec["latency_penalty"], np.float32)
    np.fill_diagonal(pen, 1.0)
    rtt = np.full((r, r), spec["rtt_s"], np.float32)
    np.fill_diagonal(rtt, 0.0)
    out.update(adjacency=np.ones((r, r), bool), latency_penalty=pen,
               rtt_s=rtt)
    return out
