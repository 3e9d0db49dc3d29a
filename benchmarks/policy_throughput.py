"""Policy-layer throughput + carbon head-to-head on the diurnal fleet stream.

Routes the same 1M-request diurnal trace (the `examples/serving_router.py`
stream) under every kind of ``RoutingPolicy`` — Table-1 oracle (carbon +
latency/energy baseline variants), fitted learned schedulers (regression /
classification inference in pure JAX), and both capacity formulations: the
PR-2 ``lax.scan`` CapacityLimiter and the segment-rank ``PlacementPolicy``
(identical decisions, pinned head-to-head for the >=5x speedup criterion) —
and reports each policy's req/s, total gCO2, carbon saved vs. the
latency-optimal baseline, and QoS/shed rates.

A second section routes the *multi-region* diurnal stream (staggered peak
hours, skewed load shares) through the placement layer: uncapped oracle vs.
tier-only spill vs. cross-region spill on a fully-connected ``CarbonGrid``,
pinning the gCO2 reduction from making region a placement axis — and the
full PR-3 program (per-region Table-1 sweeps + fixed-round admission,
``factorized=False``) vs. the factorized einsum evaluator + skip-full
admission, head-to-head twice. The *uncapped* pair makes identical
decisions (admission never binds; this speedup is the ISSUE-4 >=3x
placement-path acceptance criterion); the *capped* pair additionally
swaps the admission algorithm, so decisions may differ where capacity
binds (near-identical aggregates in practice — see the shed/carbon
columns) and its speedup is the end-to-end program comparison.

A third section routes ``deferrable_stream`` (deadline-tagged batch-class
slice) through the temporal deferral engine: immediate (PR-3 cross-region
spill) vs. defer-only (identity adjacency) vs. joint spatio-temporal
placement, pinning the gCO2 reduction from making the HOUR a placement
axis. Runs at min(n, 200k): candidate scores are (N, S+1, R, 3).

A fourth section is the ISSUE-5 multi-day + learned-factorized pin. At
full n the cross-region placement path runs learned-vs-oracle head-to-head
on the factorized einsum engines (the ~2x-of-oracle learned-throughput
acceptance: a CI-linear classification scheduler collapses to one probed
einsum, the piecewise regression scheduler re-featurizes per candidate
region). At min(n, 200k) the joint deferral engines route the 2-day
``deferrable_stream_multiday`` against a matching 2-day rolling
``CarbonGrid`` (the horizon tail is non-wrapping — windows past the last
hour are refused, so no guard-day padding): oracle vs. learned joint
(region, tier, hour) scheduling, plus a repeated-diurnal vs. day-scaled
(cleaner day two, via ``scaled_days``) grid pair showing midnight-crossing
deferral chasing tomorrow's greener hours — capacity charged to day-two
cells, not aliased into day one's.

A fifth section is the ISSUE-6 forecast-native pin: the grid carries a
rolling CI forecast with realistic error (``sigma_h * sqrt(lead)``);
policies decide on the forecast, carbon is charged at the actuals.
Immediate cross-region routing vs. one-shot error-blind deferral vs. the
rolling risk-aware re-planner (``route_stream_rolling`` + the
``EmissionsLedger``). ASSERTS the forecast-aware re-planner routes less
gCO2 than immediate routing — `benchmarks.run` turns an assertion into a
failing CI job.

A sixth section is the ISSUE-7 continuous-batching queue pin. At full n
the raw serve loop (``repro.serve.queue.serve_stream``: EDF batch
formation, live ``WorkerPool`` slots through the cap_scale seam, per-step
commits) drains the diurnal stream — the >= 0.3M req/s acceptance. At
min(n, 30k) the online-refit gap trio routes the multiday joint-deferral
stream through the SAME queue loop: the static offline-fitted
classification policy vs. the ``OnlineRefitter`` hot-swap loop vs. the
oracle, reporting req/s + routed gCO2 + the fraction of the
static-vs-oracle gap the refit closes. ASSERTS refit routes no dirtier
than static — the `--smoke` CI gate.

A seventh section is the ISSUE-8 device-scaling pin. The capped
cross-region placement stream (the reconciliation-heavy admission mode)
runs through the ``shard_map`` sharded routing path on 1/2/4/.../D-device
meshes (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` CPU fakes
in CI) against the single-device program. Decisions are bit-identical at
every device count — hard-asserted here: routed gCO2 through the sharded
path must be EXACT across counts and match the single-device program to
f32 round-off — and the per-count speedup is reported; the >=3x-at-8
acceptance asserts only where it can hold (the full 10M stream on >= 8
devices with >= 8 physical cores). ``enable_compile_cache`` is wired
first, so CI's cached cache directory turns every rerun into a warm
start.

An eighth section is the mesoscale provisioning pin. A 128-site K=8
sparse carbon grid (``CarbonGrid.from_sites``) routes the skewed
multi-region stream through the gathered O(N·K) candidate formulation:
(a) a dense 4-region grid round-tripped through
``with_sparse_neighbors()`` must route bit-identically (hard parity
gate, runs in ``--smoke``); (b) the gathered scorer vs. the dense
O(N·R) scorer head-to-head — the >=3x acceptance asserts at n >= 1M;
(c) ``repro.serve.provision`` sizes per-(site, tier, hour) fleets
against the stream's demand forecast, charging each server-hour its
amortized embodied + idle operational carbon: provisioned-vs-static-
overprovision-vs-oracle total-carbon rows, ASSERTING the provisioned
plan carries less total gCO2 at equal-or-lower shed rate (the
``--smoke`` CI gate), plus an end-to-end ``serve_stream(plan=...)``
row where the plan drives ``WorkerPool`` launch/drain through the
cap_scale seam; (d) a ``grid_event_stream`` site-outage row — the dead
site's DC load must spill strictly along its sparse neighbor list; and
(e) when >= 4 devices are visible (CI exports
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) the 128-site
sparse stream re-routes through the ``shard_map`` path, bit-identical
to the single-device program.

Run:  PYTHONPATH=src python -m benchmarks.policy_throughput [--n 1000000]
      [--devices 8]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow
from repro.configs import get_config
from repro.core import (
    CarbonGrid,
    build_scenarios,
    carbon_model,
    explore,
    paper_fleet,
)
from repro.core.design_space import ScenarioAxes
from repro.core.schedulers import (
    ClassificationScheduler,
    RegressionScheduler,
    build_dataset,
)
from repro.core.workloads import ALL_PAPER_WORKLOADS
from repro.serve import (
    CapacityLimiter,
    EmissionsLedger,
    FleetRouter,
    LearnedPolicy,
    OnlineRefitter,
    OraclePolicy,
    PlacementPolicy,
    TemporalPolicy,
    WorkerPool,
    data_mesh,
    demand_from_arrivals,
    enable_compile_cache,
    oracle_plan,
    provision_greedy,
    serve_stream,
    static_overprovision_plan,
)
from repro.serve.streams import (
    deferrable_stream,
    deferrable_stream_multiday,
    diurnal_stream,
    forecast_scenario,
    grid_event_stream,
    multi_region_stream,
)

ARCH = "h2o-danube-1.8b"


def fit_dataset():
    """Small offline design-space dataset for the learned policies."""
    axes = ScenarioAxes(hours=tuple(range(0, 24, 4)))
    table = build_scenarios(paper_fleet(), axes)
    res = explore(ALL_PAPER_WORKLOADS, table)
    return build_dataset(ALL_PAPER_WORKLOADS, res, table).split()[0]


def _time_stream(fr, batch, region, t_hours, reps, mesh=None):
    """(mean_s, best_s, result) over ``reps`` timed calls after a warm-up.

    Best-of-reps is reported alongside the mean everywhere: on shared CI
    runners the mean soaks up scheduler noise while the best approximates
    the machine's actual capability — a regression that moves BOTH is
    real."""
    res = fr.route_stream(batch, region, t_hours, mesh=mesh)  # compile+warm
    jax.block_until_ready(res.target)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res = fr.route_stream(batch, region, t_hours, mesh=mesh)
        jax.block_until_ready(res.target)
        times.append(time.perf_counter() - t0)
    return sum(times) / reps, min(times), res


def run(n: int = 1_000_000, reps: int = 3,
        devices: int | None = None) -> list[BenchRow]:
    cfg = get_config(ARCH)
    base = FleetRouter(cfg)
    infra = base.infra
    n_regions = len(base.regions)
    batch, region, t_hours = diurnal_stream(n, n_regions)

    train = fit_dataset()
    caps = np.full((n_regions, 3), np.inf)
    caps[:, 1] = max(1.0, 0.5 * n / (n_regions * 24))  # bind the edge tier

    policies = [
        ("oracle_carbon", None),  # FleetRouter default — the reference
        ("oracle_latency", OraclePolicy(infra, metric="latency")),
        ("oracle_energy", OraclePolicy(infra, metric="energy")),
        ("learned_regression", LearnedPolicy.fit(RegressionScheduler(),
                                                 train)),
        ("learned_classification", LearnedPolicy.fit(
            ClassificationScheduler(), train)),
        # the same caps through both capacity formulations: PR-2 lax.scan
        # reference vs. the segment-rank placement layer (identical
        # decisions; the speedup between these two rows is the ISSUE-3
        # >=5x acceptance criterion)
        ("capped_oracle_scan", CapacityLimiter(OraclePolicy(infra), caps)),
        ("capped_oracle_segrank", PlacementPolicy(OraclePolicy(infra),
                                                  caps)),
    ]

    rows = []
    baseline_g = None
    capped_us = {}
    for name, policy in policies:
        fr = base if policy is None else FleetRouter(cfg, policy=policy)
        dt, dt_best, res = _time_stream(fr, batch, region, t_hours, reps)
        us = dt / n * 1e6
        if baseline_g is None:
            baseline_g = float(res.latency_opt_carbon_g)
        if name.startswith("capped_oracle"):
            capped_us[name] = us
        extra = ""
        if name == "capped_oracle_segrank":
            extra = (f" speedup_vs_scan="
                     f"{capped_us['capped_oracle_scan'] / us:.2f}x")
        rows.append(BenchRow(
            f"policy_{name}", us,
            f"req/s={1e6 / us:.0f} best_req_s={n / dt_best:.0f} "
            f"carbon_g={float(res.total_carbon_g):.4g} "
            f"saved_vs_latency_g={baseline_g - float(res.total_carbon_g):.4g} "
            f"qos_rate={float(res.qos_violation_rate):.4f} "
            f"shed={int(res.shed_count)}{extra}"))

    rows += placement_rows(cfg, infra, n=n, reps=reps)
    rows += temporal_rows(cfg, infra, n=min(n, 200_000), reps=reps)
    rows += multiday_rows(cfg, infra, train, n=n, reps=reps)
    rows += forecast_rows(cfg, infra, n=min(n, 50_000), reps=reps)
    rows += queue_rows(cfg, infra, train, n=n, reps=reps)
    rows += device_rows(cfg, infra, n=n, reps=reps, devices=devices)
    rows += mesoscale_rows(cfg, infra, n=n, reps=reps)
    return rows


def device_rows(cfg, infra, n: int, reps: int = 1,
                devices: int | None = None) -> list[BenchRow]:
    """ISSUE-8 device-scaling pin: the capped cross-region placement
    stream (the reconciliation-heavy admission mode) through the
    ``shard_map`` sharded routing path on 1/2/4/.../D-device meshes vs the
    single-device program.

    Hard parity gates at EVERY count: decisions bit-identical, routed
    gCO2 EXACT across device counts (the sharded path aggregates
    host-side from bit-identical per-row arrays) and equal to the
    single-device program to f32 round-off. The >=3x-at-8-devices
    acceptance asserts only where it can hold: the full 10M-request
    stream on >= 8 devices backed by >= 8 physical cores (fake CPU
    devices share cores, so speedup on a small host measures nothing).
    """
    enable_compile_cache()
    avail = len(jax.devices())
    want = avail if devices is None else devices
    if want > avail:
        return [BenchRow(
            "devices_unavailable", 0.0,
            f"requested {want} devices but only {avail} present — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={want}")]
    d_list = [d for d in (1, 2, 4, 8, 16, 32, 64) if d <= want]

    base = FleetRouter(cfg)
    n_regions = len(base.regions)
    batch, region, t_hours = multi_region_stream(n, n_regions)
    caps = np.full((n_regions, 3), np.inf)
    per_cell = max(1.0, 0.4 * n / (n_regions * 24))
    caps[:, 1] = caps[:, 2] = per_cell  # binding: reconciliation is live
    xgrid = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05)
    fr = FleetRouter(cfg, grid=xgrid,
                     policy=PlacementPolicy(OraclePolicy(infra), caps))

    dt, dt_best, ref = _time_stream(fr, batch, region, t_hours, reps)
    # snapshot NOW: with the persistent cache warm the donated-buffer
    # programs recycle retained results' memory on the next route call, so
    # a lazy np.asarray view read after later calls sees scribbled data
    ref_tgt = np.array(ref.target)
    ref_routed = float(ref.routed_carbon_g)
    rows = [BenchRow(
        "devices_single_program", dt / n * 1e6,
        f"req/s={n / dt:.0f} best_req_s={n / dt_best:.0f} "
        f"routed_g={ref_routed:.6g} "
        f"shed={int(ref.shed_count)}")]

    tgt1 = routed1 = us1 = None
    speedup = 1.0
    for d in d_list:
        mesh = data_mesh(d)
        dt, dt_best, res = _time_stream(fr, batch, region, t_hours, reps,
                                        mesh=mesh)
        us = dt / n * 1e6
        routed = float(res.routed_carbon_g)
        tgt = np.array(res.target)  # copy before the next route call
        if tgt1 is None:
            tgt1, routed1, us1 = tgt, routed, us
        # the headline invariant: sharding is not allowed to change a
        # single decision or move the routed total by one bit
        assert np.array_equal(tgt, tgt1), \
            f"sharded decisions diverged at {d} devices"
        assert routed == routed1, (
            f"sharded routed gCO2 not bit-stable across device counts: "
            f"{routed!r} at {d} devices vs {routed1!r} at {d_list[0]}")
        np.testing.assert_allclose(
            routed, ref_routed, rtol=1e-5,
            err_msg=f"sharded routed gCO2 != single-device at {d} devices")
        assert np.array_equal(tgt, ref_tgt), \
            f"sharded decisions != single-device program at {d} devices"
        speedup = us1 / us
        rows.append(BenchRow(
            f"devices_shard_{d}", us,
            f"req/s={n / dt:.0f} best_req_s={n / dt_best:.0f} "
            f"routed_g={routed:.6g} shed={int(res.shed_count)} "
            f"speedup_vs_1dev={speedup:.2f}x"))

    # the ISSUE-8 acceptance: >=3x at 8 devices on the full 10M stream —
    # gated on real parallel hardware (fake devices time-slicing one core
    # can only show parity, not speedup)
    if n >= 10_000_000 and max(d_list) >= 8 and (os.cpu_count() or 1) >= 8:
        assert speedup >= 3.0, (
            f"sharded routing at {max(d_list)} devices reached only "
            f"{speedup:.2f}x over 1 device (>=3x required at n={n})")
    return rows


def placement_rows(cfg, infra, n: int, reps: int = 1) -> list[BenchRow]:
    """Multi-region skewed stream: uncapped vs tier-spill vs cross-region
    spill (legacy sweep AND factorized einsum evaluator) — the README
    results table + the >=3x factorization speedup pin."""
    base = FleetRouter(cfg)
    n_regions = len(base.regions)
    batch, region, t_hours = multi_region_stream(n, n_regions)
    caps = np.full((n_regions, 3), np.inf)
    per_cell = max(1.0, 0.4 * n / (n_regions * 24))
    caps[:, 1] = per_cell  # bind both DC tiers: the busy region overflows
    caps[:, 2] = per_cell  # (0.8x mean demand fleet-wide, uneven per region)
    xgrid = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05)
    free = np.full((n_regions, 3), np.inf)
    configs = [
        ("placement_uncapped", base),
        ("placement_tier_spill", FleetRouter(cfg, policy=PlacementPolicy(
            OraclePolicy(infra), caps))),
        # the PR-3 per-region Table-1 sweep program vs. the ISSUE-4
        # factorized einsum + skip-full admission, twice: under the PR-3
        # overload caps (carbon/shed continuity; admission contention
        # dominates), and uncapped — the pure placement-scoring path whose
        # speedup is the >=3x ISSUE-4 acceptance criterion
        ("placement_xregion_sweep", FleetRouter(
            cfg, grid=xgrid,
            policy=PlacementPolicy(OraclePolicy(infra), caps,
                                   factorized=False))),
        ("placement_xregion_einsum", FleetRouter(
            cfg, grid=xgrid,
            policy=PlacementPolicy(OraclePolicy(infra), caps))),
        ("placement_xregion_sweep_uncapped", FleetRouter(
            cfg, grid=xgrid,
            policy=PlacementPolicy(OraclePolicy(infra), free,
                                   factorized=False))),
        ("placement_xregion_einsum_uncapped", FleetRouter(
            cfg, grid=xgrid,
            policy=PlacementPolicy(OraclePolicy(infra), free))),
    ]
    rows = []
    sweep_us = {}
    for name, fr in configs:
        dt, dt_best, res = _time_stream(fr, batch, region, t_hours, reps)
        us = dt / n * 1e6
        if name.endswith("sweep") or name.endswith("sweep_uncapped"):
            sweep_us[name.replace("sweep", "einsum")] = us
        extra = ""
        if name in sweep_us:
            extra = f" speedup_vs_sweep={sweep_us[name] / us:.2f}x"
        rows.append(BenchRow(
            name, us,
            f"req/s={1e6 / us:.0f} best_req_s={n / dt_best:.0f} "
            f"carbon_g={float(res.total_carbon_g):.4g} "
            f"routed_g={float(res.routed_carbon_g):.4g} "
            f"shed={int(res.shed_count)} "
            f"spilled={int(res.spilled_count)}{extra}"))
    return rows


def temporal_rows(cfg, infra, n: int, reps: int = 1) -> list[BenchRow]:
    """Deadline-tagged stream: immediate (PR-3 cross-region spill) vs
    defer-only vs joint spatio-temporal deferral — the README temporal
    results table."""
    base = FleetRouter(cfg)
    n_regions = len(base.regions)
    batch, region, t_hours = deferrable_stream(n, n_regions)
    caps = np.full((n_regions, 3), np.inf)
    per_cell = max(1.0, 0.6 * n / (n_regions * 24))
    caps[:, 1] = per_cell  # moderate DC pressure: evening peaks overflow,
    caps[:, 2] = per_cell  # later windows have headroom
    xgrid = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05)
    configs = [
        ("temporal_immediate", FleetRouter(
            cfg, grid=xgrid,
            policy=PlacementPolicy(OraclePolicy(infra), caps))),
        ("temporal_defer_only", FleetRouter(cfg, policy=TemporalPolicy(
            OraclePolicy(infra), caps, max_defer_h=12))),
        ("temporal_joint", FleetRouter(
            cfg, grid=xgrid,
            policy=TemporalPolicy(OraclePolicy(infra), caps,
                                  max_defer_h=12))),
    ]
    rows = []
    immediate_g = None
    for name, fr in configs:
        dt, dt_best, res = _time_stream(fr, batch, region, t_hours, reps)
        us = dt / n * 1e6
        if immediate_g is None:
            immediate_g = float(res.routed_carbon_g)
        rows.append(BenchRow(
            name, us,
            f"req/s={1e6 / us:.0f} best_req_s={n / dt_best:.0f} "
            f"routed_g={float(res.routed_carbon_g):.4g} "
            f"saved_vs_immediate_g="
            f"{immediate_g - float(res.routed_carbon_g):.4g} "
            f"shed={int(res.shed_count)} "
            f"spilled={int(res.spilled_count)} "
            f"deferred={int(res.deferred_count)} "
            f"mean_defer_h={float(res.mean_defer_hours):.2f}"))
    return rows


def multiday_rows(cfg, infra, train, n: int, reps: int = 1
                  ) -> list[BenchRow]:
    """Rolling multi-day horizon + learned policies on factorized engines.

    Full-n placement half: learned-vs-oracle cross-region einsum scoring
    (uncapped, multi-day stream/grid) — the learned-throughput-within-~2x
    pin. Reduced-n temporal half: learned-vs-oracle joint deferral under
    binding caps, and the repeated-diurnal vs cleaner-day-two grids.
    """
    base = FleetRouter(cfg)
    n_regions = len(base.regions)
    n_t = min(n, 200_000)
    batch, region, t_hours = deferrable_stream_multiday(n, n_regions,
                                                        n_days=2)
    # a 2-day grid matches the 2-day stream: the horizon tail is
    # non-wrapping, so the last arrivals' 16h windows past hour 47 are
    # refused rather than aliased — no guard-day padding
    grid2 = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05,
                                       n_days=2)
    # day two 15% cleaner: the multi-day CI trajectory midnight-crossing
    # deferral should chase
    grid2c = grid2.scaled_days((1.0, 0.85))
    learned_lin = LearnedPolicy.fit(ClassificationScheduler(), train)
    learned_gen = LearnedPolicy.fit(RegressionScheduler(), train)
    free = np.full((n_regions, 3), np.inf)
    caps = np.full((n_regions, 3), np.inf)
    per_cell = max(1.0, 0.6 * n_t / (n_regions * 48))
    caps[:, 1] = caps[:, 2] = per_cell

    rows = []
    # --- full-n: learned vs oracle on the cross-region einsum path -------
    place = [
        ("multiday_place_oracle", OraclePolicy(infra)),
        ("multiday_place_learned_classification", learned_lin),
        ("multiday_place_learned_regression", learned_gen),
    ]
    oracle_us = None
    for name, inner in place:
        fr = FleetRouter(cfg, grid=grid2,
                         policy=PlacementPolicy(inner, free))
        dt, dt_best, res = _time_stream(fr, batch, region, t_hours, reps)
        us = dt / n * 1e6
        if oracle_us is None:
            oracle_us = us
        rows.append(BenchRow(
            name, us,
            f"req/s={1e6 / us:.0f} best_req_s={n / dt_best:.0f} "
            f"routed_g={float(res.routed_carbon_g):.4g} "
            f"spilled={int(res.spilled_count)} "
            f"vs_oracle={us / oracle_us:.2f}x"))

    # --- reduced-n: joint deferral across midnight, learned vs oracle ----
    bt, rt_, tt = (batch, region, t_hours) if n == n_t else \
        deferrable_stream_multiday(n_t, n_regions, n_days=2)
    temporal = [
        ("multiday_joint_oracle", grid2, OraclePolicy(infra)),
        ("multiday_joint_learned_classification", grid2, learned_lin),
        ("multiday_joint_learned_regression", grid2, learned_gen),
        ("multiday_joint_oracle_cleaner_day2", grid2c, OraclePolicy(infra)),
    ]
    oracle_us = oracle_g = None
    for name, grid, inner in temporal:
        fr = FleetRouter(cfg, grid=grid,
                         policy=TemporalPolicy(inner, caps, max_defer_h=16))
        dt, dt_best, res = _time_stream(fr, bt, rt_, tt, reps)
        us = dt / n_t * 1e6
        if oracle_us is None:
            oracle_us, oracle_g = us, float(res.routed_carbon_g)
        rows.append(BenchRow(
            name, us,
            f"req/s={1e6 / us:.0f} best_req_s={n_t / dt_best:.0f} "
            f"routed_g={float(res.routed_carbon_g):.4g} "
            f"saved_vs_oracle_g={oracle_g - float(res.routed_carbon_g):.4g} "
            f"shed={int(res.shed_count)} "
            f"deferred={int(res.deferred_count)} "
            f"mean_defer_h={float(res.mean_defer_hours):.2f} "
            f"vs_oracle={us / oracle_us:.2f}x"))
    return rows


def forecast_rows(cfg, infra, n: int, reps: int = 1) -> list[BenchRow]:
    """Forecast-native scheduling under realistic forecast error: immediate
    cross-region routing vs. one-shot error-blind deferral vs. the rolling
    risk-aware re-planner, all charged at ACTUAL CI. Asserts the
    forecast-aware re-planner beats immediate routing — run via
    ``benchmarks.run`` (and its ``--smoke`` CI job) this is a hard gate."""
    base = FleetRouter(cfg)
    batch, region, t_hours, grid = forecast_scenario(
        n, base.regions, sigma_h=0.03, seed=0)
    n_regions = len(base.regions)
    free = np.full((n_regions, 3), np.inf)
    immediate = FleetRouter(cfg, grid=grid, policy=PlacementPolicy(
        OraclePolicy(infra), free))
    blind = FleetRouter(cfg, grid=grid, policy=TemporalPolicy(
        OraclePolicy(infra), free, max_defer_h=12))
    aware = FleetRouter(cfg, grid=grid, policy=TemporalPolicy(
        OraclePolicy(infra), free, max_defer_h=12, risk_lambda=1.0))

    rows = []
    dt, dt_best, res_im = _time_stream(immediate, batch, region, t_hours,
                                       reps)
    g_im = float(res_im.routed_carbon_g)
    rows.append(BenchRow(
        "forecast_immediate", dt / n * 1e6,
        f"req/s={n / dt:.0f} best_req_s={n / dt_best:.0f} "
        f"routed_g={g_im:.4g} sigma_h=0.03"))

    dt, dt_best, res_bl = _time_stream(blind, batch, region, t_hours, reps)
    g_bl = float(res_bl.routed_carbon_g)
    rows.append(BenchRow(
        "forecast_oneshot_blind", dt / n * 1e6,
        f"req/s={n / dt:.0f} best_req_s={n / dt_best:.0f} "
        f"routed_g={g_bl:.4g} "
        f"saved_vs_immediate_g={g_im - g_bl:.4g} "
        f"deferred={int(res_bl.deferred_count)}"))

    roll = aware.route_stream_rolling(batch, region, t_hours, step_h=6,
                                      ledger=EmissionsLedger())  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        roll = aware.route_stream_rolling(batch, region, t_hours, step_h=6,
                                          ledger=EmissionsLedger())
    dt = (time.perf_counter() - t0) / reps
    g_rl = roll.routed_carbon_g
    rows.append(BenchRow(
        "forecast_rolling_risk_aware", dt / n * 1e6,
        f"req/s={n / dt:.0f} routed_g={g_rl:.4g} "
        f"saved_vs_immediate_g={g_im - g_rl:.4g} "
        f"saved_vs_oneshot_g={g_bl - g_rl:.4g} "
        f"deferred={roll.deferred_count} steps={len(roll.steps)}"))

    # the ISSUE-6 CI gate: forecast-aware deferral must beat routing
    # everything immediately on the realistic-error stream
    assert g_rl < g_im, (
        f"forecast-aware rolling deferral ({g_rl:.4g} g) failed to beat "
        f"immediate routing ({g_im:.4g} g) at sigma_h=0.03")
    return rows


def queue_rows(cfg, infra, train, n: int, reps: int = 1) -> list[BenchRow]:
    """ISSUE-7 continuous-batching queue: serve-loop throughput at full n
    (the >= 0.3M req/s acceptance) + the online-refit gap trio on the
    multiday joint-deferral stream at min(n, 30k). ASSERTS refit routes no
    dirtier than the static offline-fitted classification policy through
    the same queue loop — ``benchmarks.run --smoke`` turns the assertion
    into a failing CI job."""
    base = FleetRouter(cfg)
    n_regions = len(base.regions)

    # --- full-n: raw serve-loop throughput through live worker slots -----
    batch, region, t_hours = diurnal_stream(n, n_regions)
    xgrid = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05)
    unit = np.ones((n_regions, 3))  # pool slots ARE the caps (cap_scale)
    fr = FleetRouter(cfg, grid=xgrid,
                     policy=PlacementPolicy(OraclePolicy(infra), unit))

    def mk_pool():
        pool = WorkerPool(n_regions, slots_per_worker=30_000.0,
                          launch_delay_steps=0)
        for r in range(n_regions):
            for tier in (1, 2):
                pool.launch(r, tier, n=2)
        return pool

    res = serve_stream(fr, batch, region, t_hours, pool=mk_pool())  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        res = serve_stream(fr, batch, region, t_hours, pool=mk_pool())
    dt = (time.perf_counter() - t0) / reps
    rows = [BenchRow(
        "queue_throughput", dt / n * 1e6,
        f"req/s={n / dt:.0f} routed_g={float(res.routed_carbon_g):.4g} "
        f"shed={res.shed_count} steps={len(res.steps)} "
        f"batches={sum(s.n_batches for s in res.steps)}")]

    # --- reduced-n: static-learned vs online-refit vs oracle -------------
    n_q = min(n, 30_000)
    bq, rq, tq = deferrable_stream_multiday(n_q, n_regions, n_days=2)
    grid2 = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05,
                                       n_days=2)
    caps = np.full((n_regions, 3), np.inf)
    caps[:, 1] = caps[:, 2] = max(1.0, 0.6 * n_q / (n_regions * 48))
    static = LearnedPolicy.fit(ClassificationScheduler(carbon_head=False),
                               train, infra=infra)

    def q_serve(inner, refitter=None):
        frq = FleetRouter(cfg, grid=grid2, policy=TemporalPolicy(
            inner, caps, max_defer_h=16))
        t0 = time.perf_counter()
        resq = serve_stream(frq, bq, rq, tq, step_h=2, refitter=refitter)
        return time.perf_counter() - t0, resq

    mk_refitter = lambda: OnlineRefitter(
        min_observations=max(256, n_q // 12),
        refit_every=max(512, n_q // 6))
    configs = [
        ("queue_static_learned", lambda: q_serve(static)),
        ("queue_online_refit", lambda: q_serve(static, mk_refitter())),
        ("queue_oracle", lambda: q_serve(OraclePolicy(infra))),
    ]
    g = {}
    for name, fn in configs:
        fn()  # compile + warm (fresh refitter per run: cold replay state)
        dt, resq = fn()
        g[name] = float(resq.routed_carbon_g)
        extra = ""
        if name == "queue_online_refit":
            extra = f" refits={resq.refits}"
        elif name == "queue_oracle":
            gap = g["queue_static_learned"] - g[name]
            closed = (g["queue_static_learned"]
                      - g["queue_online_refit"]) / max(gap, 1e-9)
            extra = f" refit_gap_closed={closed:.1%}"
        rows.append(BenchRow(
            name, dt / n_q * 1e6,
            f"req/s={n_q / dt:.0f} routed_g={g[name]:.4g} "
            f"shed={resq.shed_count}{extra}"))

    # the ISSUE-7 CI gate: learning from the live stream must not route
    # dirtier than the static offline fit it started from
    assert g["queue_online_refit"] <= g["queue_static_learned"] * 1.001, (
        f"online refit ({g['queue_online_refit']:.4g} g) routed dirtier "
        f"than the static learned policy "
        f"({g['queue_static_learned']:.4g} g)")
    return rows


def mesoscale_rows(cfg, infra, n: int, reps: int = 1) -> list[BenchRow]:
    """Mesoscale provisioning pin: sparse-vs-dense parity, the O(N·K)
    scorer speedup, provision-vs-static-vs-oracle total carbon, the
    site-outage spill, and the sharded 128-site path. The parity and
    provisioning asserts run at every n — ``benchmarks.run --smoke``
    turns them into failing CI jobs; the >=3x scorer acceptance asserts
    at n >= 1M."""
    base = FleetRouter(cfg)

    # --- (a) dense round-trip parity: bit-identical routing ---------------
    n_p = min(n, 5_000)
    n_regions = len(base.regions)
    caps = np.full((n_regions, 3), np.inf)
    caps[:, 1] = caps[:, 2] = max(1.0, 0.4 * n_p / (n_regions * 24))
    dense_g = CarbonGrid.fully_connected(base.regions, latency_penalty=1.05)
    sparse_g = dense_g.with_sparse_neighbors()
    bp, rp, tp = deferrable_stream(n_p, n_regions, seed=0)
    rows = []
    for label, pol_cls in (("placement", PlacementPolicy),
                           ("temporal", TemporalPolicy)):
        fr_d = FleetRouter(cfg, grid=dense_g,
                           policy=pol_cls(OraclePolicy(infra), caps))
        fr_s = FleetRouter(cfg, grid=sparse_g,
                           policy=pol_cls(OraclePolicy(infra), caps))
        _, dt_d, rd = _time_stream(fr_d, bp, rp, tp, reps)
        # copy before the sparse router runs: the donated-buffer programs
        # may recycle this result's memory on the next route call
        tgt_d, g_d = np.array(rd.target), float(rd.total_carbon_g)
        _, dt_s, rs = _time_stream(fr_s, bp, rp, tp, reps)
        assert np.array_equal(tgt_d, np.asarray(rs.target)), \
            f"sparse round-trip diverged from the dense {label} program"
        assert g_d == float(rs.total_carbon_g), (
            f"sparse round-trip moved {label} total gCO2: "
            f"{float(rs.total_carbon_g)!r} vs {g_d!r}")
        rows.append(BenchRow(
            f"mesoscale_parity_{label}", dt_s / n_p * 1e6,
            f"req/s={n_p / dt_s:.0f} dense_req_s={n_p / dt_d:.0f} "
            f"carbon_g={float(rs.total_carbon_g):.4g} bit_identical=True"))

    # --- (b) gathered O(N·K) vs dense O(N·R) scorer at R=128, K=8 ---------
    r, k = 128, 8
    gs = CarbonGrid.from_sites(r, k, seed=0)
    gd = dataclasses.replace(gs, nbr_idx=None, nbr_rtt_s=None)
    free128 = jnp.asarray(np.full((r, 3), np.inf))
    pol_s = PlacementPolicy(OraclePolicy(infra), free128)
    pol_s.bind_grid(gs)
    pol_d = PlacementPolicy(OraclePolicy(infra), free128)
    pol_d.bind_grid(gd)
    batch, region, t_hours = multi_region_stream(n, r, seed=1)
    fr128 = FleetRouter(cfg, grid=gd)
    w = batch.workload(cfg)
    home = jnp.asarray(region)
    hr = jnp.asarray(np.floor(t_hours).astype(np.int32) % 24)
    env0 = fr128.env_at(0, 0)
    ci = jnp.asarray(gs.table)[home, hr]
    avail = jnp.asarray(np.asarray(batch.available))
    factors = carbon_model.energy_factors_batch(
        w, infra, env0.interference, env0.net_slowdown)

    @jax.jit
    def dense_scores(factors, w, avail, home, hr, ci):
        env = dataclasses.replace(env0, ci=ci)
        return pol_d.pair_scores_from_factors(factors, w, env, avail,
                                              home, hr)

    @jax.jit
    def sparse_scores(factors, w, avail, home, hr, ci):
        env = dataclasses.replace(env0, ci=ci)
        return pol_s.sparse_pair_scores_from_factors(
            factors, w, env, avail, home, hr)

    def best_of(f):
        jax.block_until_ready(f(factors, w, avail, home, hr, ci))  # warm
        t = np.inf
        for _ in range(max(reps, 2)):
            t0 = time.perf_counter()
            jax.block_until_ready(f(factors, w, avail, home, hr, ci))
            t = min(t, time.perf_counter() - t0)
        return t

    td, ts = best_of(dense_scores), best_of(sparse_scores)
    speedup = td / ts
    rows.append(BenchRow(
        "mesoscale_scorer_sparse", ts / n * 1e6,
        f"req/s={n / ts:.0f} dense_req_s={n / td:.0f} R={r} K={k} "
        f"speedup_vs_dense={speedup:.2f}x"))
    # the ISSUE-9 acceptance: O(N·K) >= 3x over O(N·R) on the 1M batch —
    # tiny batches are dispatch-bound, so the gate binds only at full n
    if n >= 1_000_000:
        assert speedup >= 3.0, (
            f"gathered scorer reached only {speedup:.2f}x over the dense "
            f"scorer at R={r}, K={k}, n={n} (>=3x required)")

    # --- (c) joint capacity provisioning on the 128-site grid -------------
    n_v = min(n, 20_000)
    bv, rv, tv = (batch, region, t_hours) if n == n_v else \
        multi_region_stream(n_v, r, seed=1)
    fleet = paper_fleet()
    demand = demand_from_arrivals(rv, tv, 24, r)
    prov = provision_greedy(demand, gs, fleet)
    slo = provision_greedy(demand, gs, fleet, slo_shed=0.02,
                           name="slo_0.02")
    stat = static_overprovision_plan(demand, gs, fleet)
    orac = oracle_plan(demand, gs, fleet)
    for plan in (prov, slo, stat, orac):
        rows.append(BenchRow(
            f"mesoscale_plan_{plan.name}", 0.0,
            f"server_h={plan.server_hours} "
            f"total_g={plan.total_carbon_g:.6g} "
            f"operational_g={plan.operational_g:.4g} "
            f"embodied_g={plan.embodied_g:.4g} "
            f"forecast_shed={plan.shed_rate:.4f}"))
    # the ISSUE-9 CI gate: demand-shaped provisioning must beat static
    # over-provisioning on total (operational + amortized embodied) carbon
    # at equal-or-lower shed rate
    assert prov.total_carbon_g < stat.total_carbon_g, (
        f"provisioned plan ({prov.total_carbon_g:.6g} g) failed to beat "
        f"static over-provisioning ({stat.total_carbon_g:.6g} g)")
    assert prov.shed_rate <= stat.shed_rate + 1e-12, (
        f"provisioned shed {prov.shed_rate:.4f} exceeds static "
        f"{stat.shed_rate:.4f}")
    assert slo.total_carbon_g <= orac.total_carbon_g

    # end-to-end: the plan drives WorkerPool launch/drain inside the serve
    # loop; admission sees provisioned slots through the cap_scale seam
    unit = np.ones((r, 3))
    fr_serve = FleetRouter(cfg, grid=gs, policy=PlacementPolicy(
        OraclePolicy(infra), jnp.asarray(unit)))
    t0 = time.perf_counter()
    res = serve_stream(fr_serve, bv, rv, tv, plan=prov)
    dt = time.perf_counter() - t0
    rows.append(BenchRow(
        "mesoscale_serve_provisioned", dt / n_v * 1e6,
        f"req/s={n_v / dt:.0f} routed_g={float(res.routed_carbon_g):.4g} "
        f"standing_g={prov.total_carbon_g:.6g} shed={res.shed_count} "
        f"steps={len(res.steps)}"))

    # --- (d) site outage: dead site's DC load spills along neighbors ------
    bo, ro, to, g_ev, outage = grid_event_stream(
        n_v, gs, seed=3, outage_site=5, outage_window=(0, 24))
    fr_ev = FleetRouter(cfg, grid=g_ev, policy=PlacementPolicy(
        OraclePolicy(infra), jnp.asarray(np.full((r, 3), np.inf))))
    scale = np.ones((r, 3), np.float32)
    scale[5, 1:] = 0.0  # the outage mask, capacity-side
    hour_np = (np.floor(to) % fr_ev._horizon_h).astype(np.int32)
    res_ev, _ = fr_ev._route_arrays(bo, np.asarray(ro, np.int32), hour_np,
                                    cap_scale=jnp.asarray(scale))
    exec_r = np.asarray(res_ev.exec_region)
    tgt = np.asarray(res_ev.target)
    on_dead = ((exec_r == 5) & (tgt > 0)).sum()
    assert on_dead == 0, \
        f"{on_dead} requests executed on the outaged site's DC tiers"
    spilled = int(((np.asarray(ro) == 5) & (exec_r != 5) & (tgt > 0)).sum())
    rows.append(BenchRow(
        "mesoscale_outage_spill", 0.0,
        f"outage_hours={int(np.asarray(outage).sum(axis=1).max())} "
        f"spilled_from_site5={spilled} "
        f"routed_g={float(res_ev.routed_carbon_g):.4g} "
        f"shed={int(res_ev.shed_count)}"))

    # --- (e) the 128-site sparse stream through the sharded path ----------
    if len(jax.devices()) >= 4:
        enable_compile_cache()
        caps128 = np.full((r, 3), np.inf)
        caps128[:, 1] = caps128[:, 2] = max(1.0, 0.4 * n_v / (r * 24))
        fr_sh = FleetRouter(cfg, grid=gs, policy=PlacementPolicy(
            OraclePolicy(infra), caps128))
        _, dt1, ref = _time_stream(fr_sh, bv, rv, tv, reps)
        ref_tgt = np.array(ref.target)  # copy before the sharded call
        _, dt4, shd = _time_stream(fr_sh, bv, rv, tv, reps,
                                   mesh=data_mesh(4))
        assert np.array_equal(np.asarray(shd.target), ref_tgt), \
            "sharded 128-site sparse routing diverged from single-device"
        rows.append(BenchRow(
            "mesoscale_shard_4dev", dt4 / n_v * 1e6,
            f"req/s={n_v / dt4:.0f} single_req_s={n_v / dt1:.0f} "
            f"routed_g={float(shd.routed_carbon_g):.6g} "
            f"shed={int(shd.shed_count)} bit_identical=True"))
    else:
        rows.append(BenchRow(
            "mesoscale_shard_unavailable", 0.0,
            f"needs >= 4 devices, {len(jax.devices())} present — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--devices", type=int, default=None,
                    help="device-scaling section mesh size (default: all "
                         "local devices; use XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N for "
                         "fake CPU devices)")
    args = ap.parse_args()
    rows = run(args.n, args.reps, devices=args.devices)
    print("name,us_per_call,derived")
    for row in rows:
        print(row.csv())


if __name__ == "__main__":
    main()
