"""The system under test: the program's router for a cell, built from the
configuration's data through the program's public constructors, with the
policy that ``bench/policies/<config["policy_kind"]>.py`` builds and, on
more than one chip, a mesh over the cell's chips. The entry points the
measured window drives are ``bench/entries/<traffic["entry"]>.py``.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.carbon_intensity import CarbonGrid
from repro.core.infrastructure import ComputeSpec, Fleet, NetworkSpec
from repro.serve import FleetRouter, data_mesh
from repro.serve.router import RequestBatch

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
                        available=stream.available,
                        slack_hours=stream.slack_hours)


def build_router(cell, g: dict, caps: np.ndarray) -> FleetRouter:
    """The program's router for ``cell``: its policy from
    ``bench/policies/<policy_kind>.py`` built with ``caps``, and on more than
    one chip a 1-D mesh over the cell's chips."""
    cfg = cell.config
    fleet = fleet_of(cfg)
    return FleetRouter(get_config(cfg["model"]["name"]), fleet=fleet,
                       embodied_model=cfg["embodied_model"],
                       grid=carbon_grid(g),
                       policy=cell.policy().build(cfg, fleet, g, caps),
                       mesh=data_mesh(cell.chips) if cell.chips > 1 else None)


class Spans:
    """Benchmark spans on the profiler's clock (``TraceAnnotation``), or
    nothing when the run is not traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"bench.{name}")


def devices_used(n: int) -> list:
    return jax.devices()[:n]


def memory_peak_bytes(n: int) -> int | None:
    """Peak bytes in use on the fullest of the cell's chips."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices_used(n)]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
