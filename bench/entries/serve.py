"""Entry ``serve``: the online loop, ``repro.serve.queue.serve_stream``, over
a day at ``step_h``-hour steps, admission gated by a fresh ``WorkerPool``
per day.

Every day copies its decisions back to the host (the loop's own commits);
those host arrays are what the check compares with the reference once the
window has closed.
"""

from __future__ import annotations

import time

import numpy as np

from repro.serve import WorkerPool, serve_stream
from repro.serve.queue import BatchFormer

from harness import cells
from harness.program import build_router, request_batch


class StepClock:
    """Serve-step boundaries from the loop's per-step refit hook: handed to
    ``serve_stream`` as its refitter, ``step()`` runs once at the end of
    every step and never refits. A step lasts from the end of the one
    before (or the call's start) through draft, route and commit."""

    n_refits = 0

    def __init__(self, spans, n_steps: int):
        self.spans, self.n_steps = spans, n_steps
        self.times: list[float] = []
        self._span = None

    def _open(self, name: str) -> None:
        if self.spans.on:
            self._span = self.spans(name)
            self._span.__enter__()

    def _close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def start(self) -> None:
        self._t = time.perf_counter()
        self._open("serve_step")

    def observe(self, fr, fb, targets, committed) -> None:
        pass

    def step(self, fr):
        now = time.perf_counter()
        self.times.append(now - self._t)
        self._t = now
        self._close()
        # the loop settles carbon after its last step
        self._open("serve_step" if len(self.times) < self.n_steps
                   else "serve_settle")
        return fr, False

    def stop(self) -> None:
        self._close()




class Entry:
    """The online loop: ``serve_stream`` over a day at ``step_h``-hour
    steps, admission gated by a fresh ``WorkerPool`` per day."""

    @staticmethod
    def caps(cell, n_regions: int) -> np.ndarray:
        """(R, 3) unit caps: live worker slots scale them."""
        return np.ones((n_regions, 3))

    def __init__(self, cell, g, caps, streams, spans):
        cfg, traffic = cell.config, cell.traffic
        self.fr = build_router(cell, g, caps)
        self.spans = spans
        self.pool_spec = dict(
            cfg["capacity"]["pool"], tiers=cfg["capacity"]["dc_tiers"],
            slots_per_worker=cells.slots_per_worker(
                cfg, int(traffic["requests"]), g["ci_hourly"].shape[0]))
        self.step_h = int(traffic["step_h"])
        self.max_batch = int(traffic["max_batch"])
        self.inputs = [(request_batch(s), np.asarray(s.region, np.int32),
                        np.asarray(s.t_hours)) for s in streams]
        self.step_s: list[float] = []
        self.drafts: list[int] = []
        #: each day's ``admit_rounds``: one count per draft
        self.admit_rounds: list = []

    def _pool(self) -> WorkerPool:
        p = self.pool_spec
        pool = WorkerPool(self.fr.grid.n_regions,
                          slots_per_worker=p["slots_per_worker"],
                          launch_delay_steps=p["launch_delay_steps"])
        for r in range(self.fr.grid.n_regions):
            for tier in p["tiers"]:
                pool.launch(r, tier, n=p["workers"])
        return pool

    def once(self, k: int) -> tuple[int, dict]:
        batch, region, t_hours = self.inputs[k]
        clock = StepClock(self.spans, 24 // self.step_h)
        with self.spans("serve_call"):
            clock.start()
            res = serve_stream(
                self.fr, batch, region, t_hours, step_h=self.step_h,
                pool=self._pool(), refitter=clock,
                former=BatchFormer(max_batch=self.max_batch))
            clock.stop()
        self.step_s.extend(clock.times)
        self.drafts.extend(s.n_batches for s in res.steps)
        self.admit_rounds.append(res.admit_rounds)
        out = dict(target=res.target, exec_region=res.exec_region,
                   exec_hour=res.exec_hour, shed=res.shed,
                   carbon_g=res.carbon_g)
        return len(region), out
