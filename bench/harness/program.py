"""The system under test: the program's router for a cell, built from the
configuration's data through the program's public constructors, and the
entry points the measured window drives.

Every call copies its decisions back to the host, as a caller of the
router would; those host arrays are what the check compares with the
reference once the window has closed.
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.carbon_intensity import CarbonGrid
from repro.core.infrastructure import (
    ComputeSpec,
    Fleet,
    NetworkSpec,
    pack_infra,
)
from repro.serve import (
    FleetRouter,
    OraclePolicy,
    PlacementPolicy,
    WorkerPool,
    serve_stream,
)
from repro.serve.queue import BatchFormer
from repro.serve.router import RequestBatch

from harness import cells

_TIER_KEYS = ("name", "eff_flops", "eff_mem_bw", "p_comp", "p_comm",
              "p_idle", "ecf_lca_g", "lifetime_s", "pue")
_NET_KEYS = ("name", "bandwidth_bps", "base_latency_s", "p_active",
             "n_user", "ecf_lca_g", "lifetime_s")


def fleet_of(cfg: dict) -> Fleet:
    f = cfg["fleet"]
    tier = lambda k: ComputeSpec(**{x: f[k][x] for x in _TIER_KEYS})
    net = lambda k: NetworkSpec(**{x: f[k][x] for x in _NET_KEYS})
    return Fleet(mobile=tier("mobile"), edge_dc=tier("edge_dc"),
                 hyper_dc=tier("hyper_dc"), edge_net=net("edge_net"),
                 core_net=net("core_net"), n_user_edge=f["n_user_edge"],
                 n_user_dc=f["n_user_dc"], n_batch_dc=f["n_batch_dc"])


def carbon_grid(g: dict) -> CarbonGrid:
    return CarbonGrid(
        ci_hourly=jnp.asarray(g["ci_hourly"]),
        ci_mobile=jnp.asarray(g["ci_mobile"]),
        ci_core=jnp.asarray(g["ci_core"]),
        pue=jnp.asarray(g["pue"]),
        adjacency=jnp.asarray(g["adjacency"]),
        latency_penalty=jnp.asarray(g["latency_penalty"]),
        rtt_s=jnp.asarray(g["rtt_s"]))


def request_batch(stream) -> RequestBatch:
    return RequestBatch(prompt_tokens=stream.prompt_tokens,
                        max_new_tokens=stream.max_new_tokens,
                        latency_budget_s=stream.latency_budget_s,
                        bytes_per_token=stream.bytes_per_token,
                        available=stream.available)


def build_router(cfg: dict, g: dict, caps: np.ndarray) -> FleetRouter:
    fleet = fleet_of(cfg)
    inner = OraclePolicy(pack_infra(fleet, cfg["embodied_model"]))
    return FleetRouter(get_config(cfg["model"]["name"]), fleet=fleet,
                       embodied_model=cfg["embodied_model"],
                       grid=carbon_grid(g), policy=PlacementPolicy(inner, caps))


class Spans:
    """Benchmark spans on the profiler's clock (``TraceAnnotation``), or
    nothing when the run is not traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"bench.{name}")


class StepClock:
    """Serve-step boundaries from the loop's per-step refit hook: handed to
    ``serve_stream`` as its refitter, ``step()`` runs once at the end of
    every step and never refits. A step lasts from the end of the one
    before (or the call's start) through draft, route and commit."""

    n_refits = 0

    def __init__(self, spans: Spans, n_steps: int):
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


class RouteEntry:
    """One-shot day plan: ``FleetRouter.route_stream_with_state`` on one
    device."""

    def __init__(self, cfg, traffic, g, caps, streams, spans):
        self.fr = build_router(cfg, g, caps)
        self.spans = spans
        self.inputs = [(request_batch(s), np.asarray(s.region, np.int32),
                        np.asarray(s.t_hours),
                        np.floor(s.t_hours).astype(np.int64) % 24)
                       for s in streams]
        self.step_s: list[float] = []
        self.drafts: list[int] = []

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
        out["exec_hour"] = hour
        return len(region), out


class ServeEntry:
    """The online loop: ``repro.serve.queue.serve_stream`` over a day at
    ``step_h``-hour steps, admission gated by a fresh ``WorkerPool`` per
    day."""

    def __init__(self, cfg, traffic, g, caps, streams, spans):
        self.fr = build_router(cfg, g, caps)
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
        out = dict(target=res.target, exec_region=res.exec_region,
                   exec_hour=res.exec_hour, shed=res.shed,
                   carbon_g=res.carbon_g)
        return len(region), out


ENTRIES = {"route": RouteEntry, "serve": ServeEntry}


def devices_used(n: int) -> list:
    return jax.devices()[:n]


def memory_peak_bytes(n: int) -> int | None:
    """Peak bytes in use on the fullest of the cell's chips."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices_used(n)]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
