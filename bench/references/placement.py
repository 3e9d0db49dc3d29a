"""Reference of policy kind ``placement``: ``harness.reference.Problem``,
the plain numpy model of capped cross-region placement, under the
admission limits the cell's entry point applies. Imports nothing of the
program."""

from __future__ import annotations

import numpy as np

from harness import cells, reference


def effective_caps(cell, caps) -> np.ndarray:
    """(R, 3) float64 per-window admission limits: the policy's caps, times
    the live worker slots in the online loop (entry ``serve``)."""
    caps = np.asarray(caps, np.float32).astype(np.float64)
    if cell.traffic["entry"] != "serve":
        return caps
    c = cell.config["capacity"]
    per_worker = cells.slots_per_worker(cell.config,
                                        cell.traffic["requests"], len(caps))
    slots = np.zeros_like(caps)
    slots[:, c["dc_tiers"]] = np.float32(c["pool"]["workers"] * per_worker)
    slots[:, 0] = np.inf
    return caps * slots


def problem(cell, g: dict, caps, stream, precision: str = "highest"):
    """The plain reference of one stream: the day plan, or with entry
    ``serve`` the online loop's drafts of at most ``max_batch`` rows."""
    serve = cell.traffic["entry"] == "serve"
    return reference.Problem(
        stream, cell.config, g, effective_caps(cell, caps),
        reference.n_active_params(cell.config["model"]), precision,
        serve_batch=int(cell.traffic["max_batch"]) if serve else None)
