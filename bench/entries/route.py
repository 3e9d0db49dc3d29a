"""Entry ``route``: the one-shot day plan,
``FleetRouter.route_stream_with_state``, one call per day.

Every call copies its decisions back to the host, as a caller of the
router would; those host arrays are what the check compares with the
reference once the window has closed. The execution hour is the policy's
where its state carries one (``exec_hour``), else the arrival hour.
"""

from __future__ import annotations

import numpy as np

from harness import cells
from harness.program import build_router, request_batch


class Entry:
    """One-shot day plan: ``FleetRouter.route_stream_with_state``."""

    @staticmethod
    def caps(cell, n_regions: int) -> np.ndarray:
        """(R, 3) caps the policy is built with: mobile uncapped, each DC
        tier its hourly capacity (``cells.dc_capacity``)."""
        caps = np.full((n_regions, 3), np.inf)
        caps[:, cell.config["capacity"]["dc_tiers"]] = cells.dc_capacity(
            cell.config, cell.traffic["requests"], n_regions)
        return caps

    def __init__(self, cell, g, caps, streams, spans):
        self.fr = build_router(cell, g, caps)
        self.spans = spans
        self.inputs = [(request_batch(s), np.asarray(s.region, np.int32),
                        np.asarray(s.t_hours),
                        np.floor(s.t_hours).astype(np.int64) % 24)
                       for s in streams]
        self.step_s: list[float] = []
        self.drafts: list[int] = []
        #: each call's ``admit_rounds`` counter, as the device scalar
        self.admit_rounds: list = []

    def once(self, k: int) -> tuple[int, dict]:
        batch, region, t_hours, hour = self.inputs[k]
        with self.spans("route_call"):
            res, state = self.fr.route_stream_with_state(batch, region,
                                                         t_hours)
            with self.spans("copy_back"):
                out = dict(target=np.asarray(res.target),
                           exec_region=np.asarray(res.exec_region),
                           shed=np.asarray(state.shed),
                           carbon_g=np.asarray(res.carbon_g))
                exec_hour = getattr(state, "exec_hour", None)
                if exec_hour is not None:
                    out["exec_hour"] = np.asarray(exec_hour)
        out.setdefault("exec_hour", hour)
        self.admit_rounds.append(state.admit_rounds)
        return len(region), out
