"""Smoke run of the carbon-aware router on a TPU: the main routing and
serving paths once, through the entry points a user calls, at the stream
sizes the repo's policy sections use.

Phases (one chip, the default):

  place_1m       ``FleetRouter.route_stream_with_state`` with a capped
                 cross-region ``PlacementPolicy`` on the fully connected
                 4-region grid, 1M requests.
  temporal_200k  the same entry point with a capped joint-deferral
                 ``TemporalPolicy(max_defer_h=12)``, 200k deferrable requests.
  serve_1m       ``serve_stream`` over the 1M diurnal stream, admission gated
                 by live ``WorkerPool`` slots.
  sparse_128     a capped ``PlacementPolicy`` on the 128-site k-NN grid
                 (``CarbonGrid.from_sites(128, 8)``), 1M requests.

Each phase runs twice on the default device (the chip: a cold call that
compiles, then a warm call), and once more through the same router built
on the host CPU in the same process — the reference. A phase passes only if
``target``, ``exec_region``, ``exec_hour`` and ``shed`` agree on every row
and total and routed carbon agree within ``RTOL``.

``--chips 4`` instead runs ``place_1m`` and ``sparse_128`` through
``FleetRouter(mesh=data_mesh(4))`` against the single-device program on
chip 0: decisions bit-identical, routed carbon within ``RTOL``.

The per-phase lines are smoke output, not benchmark numbers. The last line
is one JSON object naming the device. The script exits nonzero, and prints
no such line, when JAX finds no TPU or any phase fails.

Run:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path
from typing import Callable

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.carbon_intensity import (  # noqa: E402
    DEFAULT_REGIONS,
    CarbonGrid,
)
from repro.serve import (  # noqa: E402
    FleetRouter,
    OraclePolicy,
    PlacementPolicy,
    TemporalPolicy,
    WorkerPool,
    data_mesh,
    enable_compile_cache,
    serve_stream,
)
from repro.serve.distributed import shard_stream  # noqa: E402
from repro.serve.streams import (  # noqa: E402
    deferrable_stream,
    diurnal_stream,
    multi_region_stream,
)

ARCH = "h2o-danube-1.8b"
#: carbon agreement bound between the chip and its reference
RTOL = 1e-5
#: per-row decision fields compared between the chip and its reference
FIELDS = ("target", "exec_region", "exec_hour", "shed")


@dataclasses.dataclass(frozen=True)
class Outcome:
    """Host copy of one routing call's decisions and carbon totals."""

    target: np.ndarray
    exec_region: np.ndarray
    exec_hour: np.ndarray
    shed: np.ndarray
    total_g: float
    routed_g: float
    platform: str  # where the router's grid tables live


@dataclasses.dataclass(frozen=True)
class Phase:
    """One smoke phase: a seeded stream, a router builder (the router lands
    on the current default device) and the entry-point call."""

    name: str
    n: int
    stream: tuple
    build: Callable[..., FleetRouter]
    run: Callable[[FleetRouter, tuple], Outcome]


def _platform(fr: FleetRouter) -> str:
    return next(iter(fr.grid.ci_hourly.devices())).platform


def _route(fr: FleetRouter, stream: tuple) -> Outcome:
    batch, region, t_hours = stream
    res, state = fr.route_stream_with_state(batch, region, t_hours)
    # copy to the host now: a later donated call may recycle these buffers
    exec_hour = getattr(state, "exec_hour", None)
    arrival = (np.floor(t_hours) % fr.grid.horizon_h).astype(np.int32)
    return Outcome(
        target=np.array(res.target), exec_region=np.array(res.exec_region),
        exec_hour=arrival if exec_hour is None else np.array(exec_hour),
        shed=np.array(state.shed), total_g=float(res.total_carbon_g),
        routed_g=float(res.routed_carbon_g), platform=_platform(fr))


def _serve(fr: FleetRouter, stream: tuple) -> Outcome:
    batch, region, t_hours = stream
    pool = WorkerPool(fr.grid.n_regions, slots_per_worker=30_000.0,
                      launch_delay_steps=0)
    for r in range(fr.grid.n_regions):
        for tier in (1, 2):
            pool.launch(r, tier, n=2)
    res = serve_stream(fr, batch, region, t_hours, pool=pool)
    return Outcome(
        target=res.target, exec_region=res.exec_region,
        exec_hour=res.exec_hour, shed=res.shed,
        total_g=res.total_carbon_g, routed_g=res.routed_carbon_g,
        platform=_platform(fr))


def _capped_router(grid: CarbonGrid, caps: np.ndarray, temporal=False,
                   mesh=None) -> FleetRouter:
    cfg = get_config(ARCH)
    inner = OraclePolicy(FleetRouter(cfg).infra)
    policy = (TemporalPolicy(inner, caps, max_defer_h=12) if temporal
              else PlacementPolicy(inner, caps))
    return FleetRouter(cfg, grid=grid, policy=policy, mesh=mesh)


def _dc_caps(n_regions: int, per_cell: float) -> np.ndarray:
    """Both DC tiers capped at ``per_cell`` requests per window; mobile
    unbounded."""
    caps = np.full((n_regions, 3), np.inf)
    caps[:, 1] = caps[:, 2] = max(1.0, per_cell)
    return caps


def _grid4() -> CarbonGrid:
    """The fully connected 4-region grid with a 1.05 remote penalty."""
    return CarbonGrid.fully_connected(DEFAULT_REGIONS, latency_penalty=1.05)


def place_phase(n: int = 1_000_000) -> Phase:
    """Capped cross-region placement, caps 0.4·n/(R·24) per cell."""
    r = len(DEFAULT_REGIONS)

    def build(mesh=None):
        return _capped_router(_grid4(), _dc_caps(r, 0.4 * n / (r * 24)),
                              mesh=mesh)

    return Phase("place_1m", n, multi_region_stream(n, r), build, _route)


def sparse_phase(n: int = 1_000_000) -> Phase:
    """Capped placement on the 128-site K=8 sparse grid."""
    sites = 128

    def build(mesh=None):
        grid = CarbonGrid.from_sites(sites, 8, seed=0)
        return _capped_router(grid, _dc_caps(sites, 0.4 * n / (sites * 24)),
                              mesh=mesh)

    return Phase("sparse_128", n, multi_region_stream(n, sites, seed=1),
                 build, _route)


def temporal_phase(n: int = 200_000) -> Phase:
    """Capped joint (region, tier, hour) deferral, caps 0.6·n/(R·24)."""
    r = len(DEFAULT_REGIONS)

    def build(mesh=None):
        return _capped_router(_grid4(), _dc_caps(r, 0.6 * n / (r * 24)),
                              temporal=True, mesh=mesh)

    return Phase("temporal_200k", n, deferrable_stream(n, r), build, _route)


def serve_phase(n: int = 1_000_000) -> Phase:
    """The continuous-batching serve loop, unit caps scaled by live
    ``WorkerPool`` slots."""
    r = len(DEFAULT_REGIONS)

    def build(mesh=None):
        return _capped_router(_grid4(), np.ones((r, 3)), mesh=mesh)

    return Phase("serve_1m", n, diurnal_stream(n, r), build, _serve)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from jax's own
    monitoring events (the listener stays registered for the process)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def timed(self, fn, *args):
        """``(result, wall_s, compile_s, cache_hits)`` of one call."""
        c0, h0 = self.seconds, self.cache_hits
        t0 = time.perf_counter()
        out = fn(*args)
        return (out, time.perf_counter() - t0, self.seconds - c0,
                self.cache_hits - h0)


def _differ(got: Outcome, ref: Outcome) -> np.ndarray:
    """Per-row mask: any decision field differs."""
    differ = np.zeros(len(ref.target), bool)
    for f in FIELDS:
        differ |= np.asarray(getattr(got, f)) != np.asarray(getattr(ref, f))
    return differ


def compare(got: Outcome, ref: Outcome) -> tuple[int, float]:
    """(rows where any decision field differs, worst relative gap of the
    total and routed carbon)."""
    rel = max(abs(got.total_g - ref.total_g) / max(abs(ref.total_g), 1e-30),
              abs(got.routed_g - ref.routed_g)
              / max(abs(ref.routed_g), 1e-30))
    return int(_differ(got, ref).sum()), float(rel)


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _describe_diff(got: Outcome, ref: Outcome, limit: int = 5) -> str:
    """The first differing rows, field by field (for the error stream)."""
    rows = np.nonzero(_differ(got, ref))[0][:limit]
    return "; ".join(
        f"row {i}: " + " ".join(
            f"{f}={getattr(got, f)[i]}/{getattr(ref, f)[i]}" for f in FIELDS)
        for i in rows)


def run_phase(phase: Phase, clock: CompileClock) -> dict:
    """Cold and warm calls on the default device, then the CPU reference;
    returns the phase's record (``ok`` says whether it passed)."""
    fr = phase.build()
    got, cold_s, compile_s, hits = clock.timed(phase.run, fr, phase.stream)
    got, warm_s, _, _ = clock.timed(phase.run, fr, phase.stream)
    peak = _peak_bytes()
    with jax.default_device(jax.devices("cpu")[0]):
        ref_fr = phase.build()
        ref, ref_s, _, _ = clock.timed(phase.run, ref_fr, phase.stream)
    rows_differ, rel = compare(got, ref)
    on_device = got.platform == jax.devices()[0].platform
    if rows_differ:
        print(f"{phase.name}: {_describe_diff(got, ref)}", file=sys.stderr)
    return dict(
        phase=phase.name, n=phase.n, cold_s=cold_s, warm_s=warm_s,
        compile_s=compile_s, cache_hits=hits, ref_s=ref_s,
        rows_differ=rows_differ, carbon_rel_err=rel,
        peak_bytes_in_use=peak, device=got.platform,
        reference=ref.platform,
        ok=bool(rows_differ == 0 and rel <= RTOL and on_device
                and ref.platform == "cpu"))


def check_sharded_inputs(fr: FleetRouter, phase: Phase, mesh) -> int:
    """Shards per row-input of the sharded program, read off the arrays
    ``shard_stream`` hands it; raises unless every per-row input is split
    (not replicated) over every device of ``mesh``."""
    batch, region, t_hours = phase.stream
    hour = (np.floor(t_hours) % fr.grid.horizon_h).astype(np.int32)
    rows, _ = shard_stream(fr, batch, np.asarray(region, np.int32), hour,
                           mesh)
    want = set(mesh.devices.flat)
    for leaf in jax.tree.leaves(rows):
        shards = leaf.addressable_shards
        if (leaf.sharding.is_fully_replicated
                or {s.device for s in shards} != want
                or sum(s.data.shape[0] for s in shards) != leaf.shape[0]):
            raise AssertionError(
                f"{phase.name}: a per-row input is not split over the "
                f"{len(want)} mesh devices ({leaf.sharding})")
    return len(want)


def run_sharded(phase: Phase, clock: CompileClock, n_devices: int) -> dict:
    """The phase through ``FleetRouter(mesh=data_mesh(n_devices))`` vs the
    single-device program on chip 0."""
    mesh = data_mesh(n_devices)
    fr_mesh = phase.build(mesh=mesh)
    shards = check_sharded_inputs(fr_mesh, phase, mesh)
    got, cold_s, compile_s, hits = clock.timed(phase.run, fr_mesh,
                                               phase.stream)
    got, warm_s, _, _ = clock.timed(phase.run, fr_mesh, phase.stream)
    ref, ref_s, _, _ = clock.timed(phase.run, phase.build(), phase.stream)
    rows_differ, _ = compare(got, ref)
    rel = abs(got.routed_g - ref.routed_g) / max(abs(ref.routed_g), 1e-30)
    if rows_differ:
        print(f"{phase.name}: {_describe_diff(got, ref)}", file=sys.stderr)
    return dict(
        phase=phase.name + f"_mesh{n_devices}", n=phase.n, shards=shards,
        cold_s=cold_s, warm_s=warm_s, compile_s=compile_s, cache_hits=hits,
        single_device_s=ref_s, rows_differ=rows_differ,
        routed_rel_err=rel, peak_bytes_in_use=_peak_bytes(),
        ok=bool(rows_differ == 0 and rel <= RTOL))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded place_1m and sparse_128 phases "
                         "on a 4-chip mesh against chip 0, and nothing else")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    clock = CompileClock()
    print(f"# smoke output, not benchmark numbers; compile cache {cache}",
          flush=True)

    if args.chips == 1:
        phases = (place_phase, temporal_phase, serve_phase, sparse_phase)
        run = lambda phase: run_phase(phase, clock)
    else:
        phases = (place_phase, sparse_phase)
        run = lambda phase: run_sharded(phase, clock, args.chips)
    ok = True
    for make in phases:
        try:
            rec = run(make())
        except Exception:  # report, run the other phases, then fail
            traceback.print_exc()
            rec = dict(phase=make.__name__, ok=False)
        ok &= rec["ok"]
        print("smoke " + " ".join(f"{k}={v!r}" for k, v in rec.items()),
              flush=True)
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
