"""GreenScaleRouter — carbon-aware execution-target selection (paper Table 1
applied to LM serving), from one request to a fleet-scale stream.

Each inference request becomes a GreenScale workload descriptor (FLOPs from
the request's prefill+decode token counts and the model's active params;
payload bytes from the token counts), and the Table-1 carbon model picks the
carbon-optimal tier among {on-device NPU, edge-DC slice, hyperscale pod}
subject to the request's latency constraint — under the *current* carbon
intensities and runtime variance, which is exactly the paper's contribution
(time/location-varying CI shifts the optimum).

Two granularities:

  * ``GreenScaleRouter`` — one environment. ``route`` decides a single
    request; ``route_batch`` vmaps the same scalar core over a stacked
    request batch in ONE jitted call (no Python loop).
  * ``FleetRouter``      — many regions, each with its own hourly CI trace
    (CASPER/CarbonEdge-style aggregate routing): a request stream tagged
    with (region, arrival time) is routed against per-request CI rows
    gathered from a (region, hour) table, and the result aggregates
    per-region/per-tier assignment counts plus gCO2 saved vs. the latency-
    and energy-optimal baselines.

Both routers accept ``policy=`` (see ``repro.serve.policy``): the decision-
maker — Table-1 oracle, fitted scheduler, capacity-capped wrapper — is a
pluggable ``RoutingPolicy`` running inside the same jitted stream call; the
default is the carbon oracle and reproduces the pre-policy results exactly.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import carbon_model
from repro.core.carbon_intensity import (
    DEFAULT_REGIONS,
    CarbonGrid,
    RegionSpec,
    site_regions,
)
from repro.core.carbon_model import Environment, RouteOutputs
from repro.core.constants import N_TARGETS
from repro.core.infrastructure import Fleet, pack_infra, tpu_fleet
from repro.core.workloads import Workload, batch_workloads
from repro.serve.policy import OraclePolicy, RoutingPolicy

# The routing/settle jits donate their per-stream buffers; donation is
# deliberately partial (f32 workload columns cannot alias the int32/bool
# outputs), so silence jax's per-shape advisory about the leftover leaves.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request."""

    prompt_tokens: int
    max_new_tokens: int
    latency_budget_s: float = 2.0
    bytes_per_token: float = 4.0
    #: which tiers can hold this model at all (e.g. 72B never fits on-device)
    available: tuple[bool, bool, bool] = (True, True, True)
    #: deferral allowance (hours past arrival the request may still start;
    #: 0 = interactive, must run on arrival). Only temporal policies
    #: (``repro.serve.temporal``) consume it.
    slack_hours: float = 0.0


@dataclasses.dataclass(frozen=True)
class RouteDecision:
    target: int  # Target enum value
    carbon_g: float
    latency_s: float
    feasible: bool
    per_target_carbon: tuple[float, float, float]


@dataclasses.dataclass(frozen=True)
class RequestBatch:
    """Columnar request batch: (N,) float64 columns + (N, 3) availability.

    The columnar form is what lets a million requests become ONE stacked
    Workload pytree (``batch_workloads``) instead of a million Python
    objects; ``from_requests`` converts the object form when convenience
    beats throughput.
    """

    prompt_tokens: np.ndarray
    max_new_tokens: np.ndarray
    latency_budget_s: np.ndarray
    bytes_per_token: np.ndarray
    available: np.ndarray  # (N, 3) bool
    #: (N,) deferral allowance in hours (None = all-interactive, slack 0) —
    #: the deadline tag temporal policies schedule against: a request may
    #: execute in any hour of [arrival, arrival + slack].
    slack_hours: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.prompt_tokens)

    @classmethod
    def from_requests(cls, reqs: list[Request]) -> "RequestBatch":
        n = len(reqs)
        col = lambda attr: np.fromiter(
            (getattr(r, attr) for r in reqs), np.float64, n)
        # reshape keeps the (0, 3) availability shape on an empty list —
        # np.array([]) alone collapses to (0,) and breaks downstream stacking
        return cls(
            prompt_tokens=col("prompt_tokens"),
            max_new_tokens=col("max_new_tokens"),
            latency_budget_s=col("latency_budget_s"),
            bytes_per_token=col("bytes_per_token"),
            available=np.array([r.available for r in reqs],
                               bool).reshape(n, 3),
            slack_hours=col("slack_hours"),
        )

    @property
    def slack_h(self) -> np.ndarray:
        """(N,) int32 whole-hour slack (zeros when untagged)."""
        if self.slack_hours is None:
            return np.zeros(len(self), np.int32)
        return np.floor(np.asarray(self.slack_hours)).astype(np.int32)

    def workload(self, cfg: ModelConfig) -> Workload:
        """Stacked GreenScale descriptors — elementwise identical to
        ``request_workload`` on each row (the parity tests pin this)."""
        n_active = cfg.active_param_count()
        total_tokens = self.prompt_tokens + self.max_new_tokens
        return batch_workloads(
            flops=2.0 * n_active * total_tokens,
            mem_bytes=2.0 * n_active * np.maximum(self.max_new_tokens, 1),
            data_in=self.bytes_per_token * self.prompt_tokens,
            data_out=self.bytes_per_token * self.max_new_tokens,
            latency_req=self.latency_budget_s,
        )

    @property
    def avail(self) -> jax.Array:
        return jnp.asarray(self.available)


def request_workload(cfg: ModelConfig, req: Request) -> Workload:
    """GreenScale descriptor for one LM request.

    FLOPs: 2·N_active per token (forward only), prefill + decode tokens.
    mem_bytes: decode re-reads the active params every generated token
    (the memory-bound side of decode).
    """
    n_active = cfg.active_param_count()
    total_tokens = req.prompt_tokens + req.max_new_tokens
    return Workload.make(
        flops=2.0 * n_active * total_tokens,
        mem_bytes=2.0 * n_active * max(req.max_new_tokens, 1),
        data_in=req.bytes_per_token * req.prompt_tokens,
        data_out=req.bytes_per_token * req.max_new_tokens,
        latency_req=req.latency_budget_s,
    )


def _decisions_from_outputs(out: RouteOutputs) -> list[RouteDecision]:
    """Unpack batched RouteOutputs into per-request RouteDecision objects."""
    target = np.asarray(out.target)
    cf = np.asarray(out.total_cf)
    lat = np.asarray(out.latency)
    ok = np.asarray(out.ok)
    idx = np.arange(len(target))
    carbon = cf[idx, target]
    latency = lat[idx, target]
    feas = ok[idx, target]
    return [
        RouteDecision(target=int(t), carbon_g=float(c), latency_s=float(l),
                      feasible=bool(f), per_target_carbon=tuple(map(float, row)))
        for t, c, l, f, row in zip(target, carbon, latency, feas, cf)
    ]


@dataclasses.dataclass
class GreenScaleRouter:
    """Carbon-aware tier selection for a serving fleet (one environment).

    ``policy`` plugs any ``repro.serve.policy.RoutingPolicy`` into the
    decision; the default (None) is the Table-1 carbon oracle on the exact
    pre-policy code path, so existing results are reproduced bit-for-bit.
    """

    cfg: ModelConfig
    fleet: Fleet = dataclasses.field(default_factory=tpu_fleet)
    embodied_model: str = "act"
    policy: RoutingPolicy | None = None

    def __post_init__(self):
        self._infra = pack_infra(self.fleet, self.embodied_model)
        infra = self._infra

        @jax.jit
        def _route_one(w: Workload, env: Environment, avail: jax.Array):
            return carbon_model.route_one(w, infra, env, avail)

        @jax.jit
        def _route_many(w: Workload, env: Environment, avail: jax.Array):
            return carbon_model.route_many(w, infra, env, avail)

        self._route_one = _route_one
        self._route_many = _route_many

    @property
    def infra(self):
        """Packed ``InfraParams`` of this router's fleet — the public handle
        for building policies: ``OraclePolicy(router.infra, ...)``."""
        return self._infra

    def route(self, req: Request, env: Environment) -> RouteDecision:
        w = request_workload(self.cfg, req)
        out = self._route_one(w, env, jnp.asarray(req.available))
        t = int(out.target)
        return RouteDecision(
            target=t,
            carbon_g=float(out.total_cf[t]),
            latency_s=float(out.latency[t]),
            feasible=bool(out.ok[t]),
            per_target_carbon=tuple(float(x) for x in np.asarray(out.total_cf)),
        )

    def route_batch(self, reqs: list[Request], env: Environment
                    ) -> list[RouteDecision]:
        """All requests in one jitted vmap (no per-request Python loop)."""
        if not reqs:  # avoid jitting a zero-length program for nothing
            return []
        out = self.route_batch_arrays(RequestBatch.from_requests(reqs), env)
        return _decisions_from_outputs(out)

    def route_batch_arrays(self, batch: RequestBatch, env: Environment,
                           hour: float | np.ndarray | None = None
                           ) -> RouteOutputs:
        """Array-in/array-out batched routing — the fleet-scale hot path.

        With a custom ``policy`` the Table-1 evaluation still supplies the
        per-tier carbon/latency/feasibility columns (the accounting), and
        ``target`` is replaced by the policy's decisions. ``hour`` (scalar
        or (N,)) is forwarded to the policy for time-aware features — a
        ``LearnedPolicy`` fitted with hour-of-day harmonics treats a batch
        without it as arriving at midnight.
        """
        w = batch.workload(self.cfg)
        out = self._route_many(w, env, batch.avail)
        if self.policy is None:
            return out
        n = len(batch)
        env_b = Environment(ci=jnp.broadcast_to(env.ci, (n,) + env.ci.shape),
                            interference=env.interference,
                            net_slowdown=env.net_slowdown)
        if hour is not None:
            hour = jnp.broadcast_to(jnp.asarray(hour, jnp.float32), (n,))
        slack = (None if batch.slack_hours is None
                 else jnp.asarray(batch.slack_h))
        targets, _ = self.policy.decide(
            w, env_b, batch.avail, self.policy.initial_state(1, n),
            hour=hour, outputs=out, slack=slack)
        return dataclasses.replace(out, target=jnp.asarray(targets,
                                                           jnp.int32))


# ---------------------------------------------------------------------------
# Fleet-level routing: many regions, hourly CI traces, aggregate savings
# ---------------------------------------------------------------------------


_admit_windows_warned = False


def _warn_admit_windows() -> None:
    """Warn ONCE per process that bucketed admission is deprecated."""
    global _admit_windows_warned
    if not _admit_windows_warned:
        _admit_windows_warned = True
        warnings.warn(
            "hourly-bucketed admit_windows is deprecated: requests arrive "
            "continuously, not in hour buckets. Serve the stream through "
            "repro.serve.queue.serve_stream and pass its QueueServeResult "
            "as queue= (or call repro.serve.queue.admit_batches directly) "
            "for per-step continuous-batching admission.",
            DeprecationWarning, stacklevel=3)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FleetRouteResult:
    """Aggregate result of routing a request stream across the fleet.

    The three reference aggregates put any policy's outcome in context on
    the *same* stream: ``oracle_carbon_g`` is the carbon-optimal Table-1
    pick under each request's HOME region (0 regret for the default policy;
    a cross-region placement policy can legitimately beat it),
    ``latency_opt_carbon_g`` / ``energy_opt_carbon_g`` the paper's baseline
    objectives.

    ``exec_region`` is where each request actually executes — equal to the
    home region except for cross-region placements (``PlacementPolicy``
    spill), whose carbon is accounted under the executing region's CI.
    """

    target: jax.Array  # (N,) int32 chosen tier per request
    carbon_g: jax.Array  # (N,) gCO2 of the chosen tier (executing region CI)
    feasible: jax.Array  # (N,) bool — chosen tier meets the QoS constraint
    counts: jax.Array  # (R, 3) int32 capacity-counted assignments per
    #                    *executed* (region, tier); shed requests excluded
    total_carbon_g: jax.Array  # () sum of carbon_g — shed requests count at
    #                    their nominal placement (they must run eventually)
    routed_carbon_g: jax.Array  # () sum of carbon_g over NON-shed requests
    #                    only — compare capped configs with different shed
    #                    rates on this, not on total_carbon_g
    latency_opt_carbon_g: jax.Array  # () same stream, latency-optimal picks
    energy_opt_carbon_g: jax.Array  # () same stream, energy-optimal picks
    oracle_carbon_g: jax.Array  # () same stream, carbon-optimal picks
    infeasible_count: jax.Array  # () int32 picks violating their QoS budget
    shed_count: jax.Array  # () int32 capacity-shed requests (0 w/o caps)
    exec_region: jax.Array  # (N,) int32 executing region (= home w/o spill)
    spilled_count: jax.Array  # () int32 requests executed off-home (0 w/o
    #                           cross-region placement)
    deferred_count: jax.Array  # () int32 non-shed requests executed after
    #                            their arrival hour (0 w/o temporal policy)
    mean_defer_hours: jax.Array  # () float32 mean defer of the deferred
    #                              requests (0 when none deferred)

    @property
    def saved_vs_latency_g(self) -> jax.Array:
        return self.latency_opt_carbon_g - self.total_carbon_g

    @property
    def saved_vs_energy_g(self) -> jax.Array:
        return self.energy_opt_carbon_g - self.total_carbon_g

    @property
    def extra_vs_oracle_g(self) -> jax.Array:
        """Carbon regret of this policy vs. the Table-1 carbon oracle."""
        return self.total_carbon_g - self.oracle_carbon_g

    @property
    def qos_violation_rate(self) -> jax.Array:
        return self.infeasible_count / self.target.shape[0]

    @property
    def shed_rate(self) -> jax.Array:
        return self.shed_count / self.target.shape[0]

    @property
    def spill_rate(self) -> jax.Array:
        """Fraction of the stream executed outside its home region."""
        return self.spilled_count / self.target.shape[0]

    @property
    def defer_rate(self) -> jax.Array:
        """Fraction of the stream executed after its arrival hour."""
        return self.deferred_count / self.target.shape[0]


@dataclasses.dataclass
class FleetRouter:
    """Route a (region, time)-tagged request stream against regional grids.

    The fleet's geo-temporal carbon state lives in ONE ``CarbonGrid`` pytree
    (``self.grid``): per-region (24, 5) component-CI tables — device CI from
    the charging behaviour (a battery buffers the grid, so it is flat across
    the day), edge network/DC CI from the hourly trace, core CI from the
    trace mean, hyperscale CI from the hourly trace, all PUE-scaled on the
    DC components — plus the inter-region adjacency / latency-penalty
    matrices placement policies spill along. Routing gathers each request's
    CI row by (region, hour-of-day) — the trace "plays" as the stream's
    timestamps advance — and vmaps the scalar Table-1 core once over the
    whole stream.

    Pass ``grid=`` to control spill topology / PUE (e.g.
    ``CarbonGrid.fully_connected(regions)``); the default is
    ``CarbonGrid.from_regions(regions)`` — identity adjacency, PUE 1 — which
    reproduces the pre-grid router bit-for-bit. A policy with a
    ``bind_grid`` hook (``PlacementPolicy``) that was built without an
    explicit grid adopts the router's at construction.
    """

    cfg: ModelConfig
    fleet: Fleet = dataclasses.field(default_factory=tpu_fleet)
    embodied_model: str = "act"
    regions: tuple[RegionSpec, ...] = DEFAULT_REGIONS
    interference: tuple[float, float, float] = (1.0, 1.0, 1.0)
    net_slowdown: tuple[float, float] = (1.0, 1.0)
    #: decision-maker for the stream; None = Table-1 carbon oracle. Any
    #: ``repro.serve.policy.RoutingPolicy`` (learned, capacity-capped,
    #: placement, ...) plugs in here and routes inside the same jitted call.
    policy: RoutingPolicy | None = None
    #: unified carbon-grid abstraction; None = built from ``regions`` with
    #: identity adjacency (no cross-region spill) and PUE 1.
    grid: CarbonGrid | None = None
    #: 1-D device mesh to shard the routing hot path over
    #: (``repro.serve.distributed``); None = the single-device program.
    #: With a mesh attached every stream — ``route_stream``, the rolling
    #: re-planner, ``serve_stream`` — rides the sharded path, with
    #: decisions bit-identical to the single-device program.
    mesh: object | None = None

    def __post_init__(self):
        self._infra = pack_infra(self.fleet, self.embodied_model)
        self._interference = jnp.asarray(self.interference, jnp.float32)
        self._net_slowdown = jnp.asarray(self.net_slowdown, jnp.float32)

        if self.grid is None:
            self.grid = CarbonGrid.from_regions(self.regions)
        elif self.grid.n_regions != len(self.regions):
            if (self.regions is DEFAULT_REGIONS
                    and self.grid.n_regions > len(DEFAULT_REGIONS)):
                # mesoscale grids (CarbonGrid.from_sites) carry their own
                # site count; synthesize matching site specs rather than
                # forcing callers to hand-build O(100) RegionSpecs
                self.regions = site_regions(self.grid.n_regions)
            else:
                raise ValueError(
                    f"grid covers {self.grid.n_regions} regions, "
                    f"router has {len(self.regions)}")
        self._ci_table = self.grid.table  # (R, H, 5) actuals — the charge
        # forecast view the policies decide on; the SAME buffer as
        # ``_ci_table`` when no forecast is attached (the split is inert)
        self._ci_fc = self.grid.table_forecast
        # arrival times index the grid's rolling horizon by ABSOLUTE hour
        # (wrapping only at the horizon end), so a multi-day grid gives day
        # two its own CI rows and capacity cells; a single-day grid keeps
        # the historical hour-of-day (% 24) behaviour bit-for-bit.
        self._horizon_h = int(self._ci_table.shape[1])

        if self.policy is None:
            self.policy = OraclePolicy(self._infra)
        bind = getattr(self.policy, "bind_grid", None)
        if bind is not None:
            bind(self.grid)
        policy = self.policy
        infra = self._infra
        n_regions = len(self.regions)
        interference = self._interference
        net_slowdown = self._net_slowdown
        # Factorized hot path: policies that score candidate (region, hour)
        # placements via the einsum evaluator (cross-region PlacementPolicy,
        # TemporalPolicy) get ONE Table-1 evaluation per batch — factors feed
        # the routing outputs, the policy's candidate scores, AND the
        # executed-placement accounting (no out_exec re-evaluation). The
        # default path keeps the sweep program bit-for-bit.
        use_factors = bool(getattr(self.policy, "wants_factors", False))
        rtt_s = self.grid.rtt_s
        # Forecast/actual split (host-static): with a forecast attached the
        # policy DECIDES on the forecast view while routed carbon is CHARGED
        # at actuals; without one, ``ci_fc`` is the very same buffer as
        # ``ci_table`` and the whole split compiles away — the historical
        # program, bit-for-bit.
        split = self.grid.ci_forecast is not None

        # Donate the per-stream buffers (workload columns, region/hour,
        # order/inv_order, slack): every caller rebuilds them from host
        # arrays per call, so XLA may reuse their device memory for outputs
        # instead of copying. The CI tables live on the router across calls,
        # ``cap_scale`` is shared by all drafts of a serve step, and
        # ``used0`` may be caller-owned — none of those are donated.
        @partial(jax.jit, donate_argnums=(0, 2, 3, 7, 8, 9))
        def _fleet_route(w: Workload, avail: jax.Array, region: jax.Array,
                         hour: jax.Array, ci_table: jax.Array,
                         ci_fc: jax.Array, state,
                         order: jax.Array, inv_order: jax.Array,
                         slack: jax.Array, cap_scale, used0
                         ) -> tuple[FleetRouteResult, object]:
            env = Environment(ci=ci_fc[region, hour],  # (N, 5) forecast view
                              interference=interference,
                              net_slowdown=net_slowdown)
            # Table-1 evaluation supplies the carbon/QoS accounting and the
            # three reference objectives; the policy makes the decision
            # (oracle-family policies reuse ``out`` via the outputs hint, so
            # the default path is the pre-policy program, bit-for-bit).
            if use_factors:
                factors = carbon_model.energy_factors_batch(
                    w, infra, interference, net_slowdown)
                out = carbon_model.route_many_from_factors(
                    factors, w, env.ci, avail)
            else:
                factors = None
                out = carbon_model.route_many_envs(w, infra, env, avail)
            # settle-at-actuals hook: what a (N,) target vector COSTS on the
            # actual table at the arrival (region, hour). QoS feasibility is
            # CI-free, so only carbon re-prices under the split.
            if not split:
                take_act = lambda t: jnp.take_along_axis(
                    out.total_cf, t[:, None], axis=1)[:, 0]
            elif factors is not None:
                cf_act = carbon_model.total_cf_from_factors(
                    factors, ci_table[region, hour])
                take_act = lambda t: jnp.take_along_axis(
                    cf_act, t[:, None], axis=1)[:, 0]
            else:
                out_act = carbon_model.route_many_envs(
                    w, infra,
                    Environment(ci=ci_table[region, hour],
                                interference=interference,
                                net_slowdown=net_slowdown), avail)
                take_act = lambda t: jnp.take_along_axis(
                    out_act.total_cf, t[:, None], axis=1)[:, 0]
            targets, new_state = policy.decide(
                w, env, avail, state, region=region, hour=hour, outputs=out,
                order=order, inv_order=inv_order, slack=slack,
                factors=factors, fc_table=ci_fc, cap_scale=cap_scale,
                used0=used0)
            # executed-placement accounting and the call's aggregates
            with jax.named_scope("account"):
                shed = getattr(new_state, "shed", None)
                exec_region = getattr(new_state, "exec_region", None)
                exec_hour = getattr(new_state, "exec_hour", None)
                take = lambda o, t: jnp.take_along_axis(
                    o.total_cf, t[:, None], axis=1)[:, 0]
                take2 = lambda a, t: jnp.take_along_axis(
                    a, t[:, None], axis=1)[:, 0]
                if exec_region is None and exec_hour is None:
                    # no cross-region / deferred placement: execute on arrival,
                    # charged at the arrival cell's ACTUAL CI
                    exec_region = region
                    spilled = jnp.zeros((), jnp.int32)
                    carbon = take_act(targets)
                    feas = take2(out.ok, targets)
                elif factors is not None:
                    # executed-placement accounting on the factorized evaluator:
                    # CI rows gathered at the EXECUTING (region, hour) — home
                    # [mobile, edge_net] components stay billed in the home
                    # region at the execution hour (the device draws energy when
                    # the work actually runs), the WAN hop enters the QoS check
                    # — and the precomputed factors turn them into carbon with
                    # one einsum instead of the out_exec Table-1 re-evaluation.
                    er = region if exec_region is None else exec_region
                    eh = hour if exec_hour is None else exec_hour
                    exec_region = er
                    ci_exec = jnp.concatenate(
                        [ci_table[region, eh][:, :2],
                         ci_table[er, eh][:, 2:]], axis=1)
                    cf_exec = carbon_model.total_cf_from_factors(factors, ci_exec)
                    ok_exec = carbon_model.qos_feasible_from_factors(
                        factors, w, rtt_s[region, er]) & avail
                    carbon = take2(cf_exec, targets)
                    feas = take2(ok_exec, targets)
                    moved = er != region
                    if shed is not None:
                        moved = moved & ~shed
                    spilled = moved.sum().astype(jnp.int32)
                else:
                    # legacy sweep path (non-factorizable inner policies):
                    # carbon/QoS accounting under the EXECUTING region's CI for
                    # rows that moved; unmoved rows keep the home-region values
                    # bit-for-bit (adjacency == I parity with tier-only spill).
                    # Only the infrastructure relocates: the device and access
                    # network still draw energy in the HOME region, so the
                    # executing env mixes home [mobile, edge_net] CI with the
                    # executing region's [edge_dc, core_net, hyper_dc] — the
                    # same mixing PlacementPolicy.pair_scores decides with.
                    # Home components come from the ACTUAL table (== env.ci
                    # without a forecast — the historical values bit-for-bit).
                    ci_exec = jnp.concatenate(
                        [ci_table[region, hour][:, :2],
                         ci_table[exec_region, hour][:, 2:]],
                        axis=1)
                    env_exec = Environment(ci=ci_exec,
                                           interference=interference,
                                           net_slowdown=net_slowdown)
                    out_exec = carbon_model.route_many_envs(w, infra, env_exec,
                                                            avail)
                    moved = exec_region != region
                    if shed is not None:
                        moved = moved & ~shed
                    spilled = moved.sum().astype(jnp.int32)
                    carbon = jnp.where(moved, take(out_exec, targets),
                                       take_act(targets))
                    feas = jnp.where(moved, take2(out_exec.ok, targets),
                                     take2(out.ok, targets))
                # (region, tier) assignment counts as a one-hot reduction over
                # the flattened pair index — a dense sum, not an N-wide scatter
                pair = exec_region * N_TARGETS + targets
                one_hot = jax.nn.one_hot(pair, n_regions * N_TARGETS,
                                         dtype=jnp.int32)
                if shed is not None:
                    one_hot = one_hot * (~shed)[:, None].astype(jnp.int32)
                counts = one_hot.sum(axis=0).reshape(n_regions, N_TARGETS)
                defer = getattr(new_state, "defer_hours", None)
                if defer is None:
                    deferred = jnp.zeros((), jnp.int32)
                    mean_defer = jnp.zeros((), jnp.float32)
                else:
                    dmask = defer > 0
                    if shed is not None:
                        dmask = dmask & ~shed
                    deferred = dmask.sum().astype(jnp.int32)
                    mean_defer = ((defer * dmask).sum()
                                  / jnp.maximum(deferred, 1)).astype(jnp.float32)
                return FleetRouteResult(
                    target=targets,
                    carbon_g=carbon,
                    feasible=feas,
                    counts=counts,
                    total_carbon_g=carbon.sum(),
                    routed_carbon_g=(carbon.sum() if shed is None
                                     else (carbon * ~shed).sum()),
                    # reference baselines decide on the forecast view too (they
                    # are schedulers, not oracles-with-hindsight), but are
                    # charged at actuals like everything else
                    latency_opt_carbon_g=take_act(out.target_latency).sum(),
                    energy_opt_carbon_g=take_act(out.target_energy).sum(),
                    oracle_carbon_g=take_act(out.target).sum(),
                    infeasible_count=(~feas).sum().astype(jnp.int32),
                    shed_count=(jnp.zeros((), jnp.int32) if shed is None
                                else shed.sum().astype(jnp.int32)),
                    exec_region=exec_region,
                    spilled_count=spilled,
                    deferred_count=deferred,
                    mean_defer_hours=mean_defer,
                ), new_state

        self._fleet_route = _fleet_route

    @property
    def infra(self):
        """Packed ``InfraParams`` of this router's fleet — the public handle
        for building policies: ``OraclePolicy(router.infra, ...)``."""
        return self._infra

    def env_at(self, region: int, hour: int) -> Environment:
        """The exact Environment a request in ``region`` at ``hour`` sees
        (the scalar-parity hook: GreenScaleRouter.route against this env
        must reproduce the fleet decision). ``hour`` is an absolute horizon
        hour, wrapped modulo the grid's horizon (== the historical % 24 on
        a single-day grid). Indexes the cached ``CarbonGrid`` table —
        ``grid.table`` is recomputed per access."""
        return Environment(ci=self._ci_table[region, hour % self._horizon_h],
                           interference=self._interference,
                           net_slowdown=self._net_slowdown)

    def route_stream(self, batch: RequestBatch, region: np.ndarray,
                     t_hours: np.ndarray, *, mesh=None) -> FleetRouteResult:
        """Route a request stream. ``region`` (N,) int region indices,
        ``t_hours`` (N,) arrival times in absolute hours since the horizon
        start (wrapped modulo the grid horizon — 24 on the default
        single-day grid, ``n_days * 24`` on a rolling multi-day one).
        ``mesh`` shards this call across a 1-D device mesh (overriding the
        router's own ``mesh`` field); decisions are bit-identical either
        way."""
        return self.route_stream_with_state(batch, region, t_hours,
                                            mesh=mesh)[0]

    def route_stream_with_state(
            self, batch: RequestBatch, region: np.ndarray,
            t_hours: np.ndarray, *, mesh=None
    ) -> tuple[FleetRouteResult, object]:
        """``route_stream`` + the policy's final state (e.g. the
        ``PlacementState`` counters/shed mask of a ``PlacementPolicy``)."""
        with TraceAnnotation("gs.route.prepare"):
            hour_np = (np.floor(np.asarray(t_hours))
                       % self._horizon_h).astype(np.int32)
            region_np = np.asarray(region).astype(np.int32)
        return self._route_arrays(batch, region_np, hour_np, mesh=mesh)

    def _route_arrays(self, batch: RequestBatch, region_np: np.ndarray,
                      hour_np: np.ndarray, *, ci_fc: jax.Array | None = None,
                      cap_scale: jax.Array | None = None,
                      used0: jax.Array | None = None,
                      slack_np: np.ndarray | None = None,
                      mesh=None) -> tuple[FleetRouteResult, object]:
        """One jitted ``_fleet_route`` call on prepared int32 arrays — the
        seam the rolling re-planner drives with per-step forecast tables
        (``ci_fc``, defaulting to the grid's own forecast view), budget-
        ledger capacity multipliers, pre-committed cell counts, and
        re-anchored slack. Computes the host-side stream-order hint exactly
        as ``route_stream_with_state`` always did.

        With a mesh (the ``mesh=`` argument, defaulting to the router's
        ``mesh`` field) the call delegates to the device-sharded program
        (``repro.serve.distributed``) — which is why every caller of this
        seam (``serve_stream``, the rolling re-planner) rides the sharded
        path automatically.

        Host spans on the profiler's clock: ``gs.route.prepare`` (order
        sort, inverse, uploads, initial state) and ``gs.route.dispatch``
        (enqueue of the jitted call; a long one is a compile or a hidden
        sync)."""
        mesh = self.mesh if mesh is None else mesh
        if mesh is not None and len(batch) > 0:
            from repro.serve import distributed
            return distributed.route_arrays_sharded(
                self, batch, region_np, hour_np, mesh, ci_fc=ci_fc,
                cap_scale=cap_scale, used0=used0, slack_np=slack_np)
        with TraceAnnotation("gs.route.prepare"):
            args = self._route_args(batch, region_np, hour_np, ci_fc=ci_fc,
                                    cap_scale=cap_scale, used0=used0,
                                    slack_np=slack_np)
        with TraceAnnotation("gs.route.dispatch"):
            return self._fleet_route(*args)

    def _route_args(self, batch: RequestBatch, region_np: np.ndarray,
                    hour_np: np.ndarray, *, ci_fc: jax.Array | None = None,
                    cap_scale: jax.Array | None = None,
                    used0: jax.Array | None = None,
                    slack_np: np.ndarray | None = None) -> tuple:
        """The single-device ``_fleet_route`` arguments for a prepared
        stream (what ``_route_arrays`` calls it with; their shapes are what
        an ahead-of-time compile for a described device needs)."""
        # stream-order hint: stable radix sort by arrival window — or by
        # (window, home region) when the policy wants finer segments
        # (tier-only PlacementPolicy) — on the host; only computed for
        # policies that declare a ``stream_order_key`` (the default path
        # must not pay an O(N log N) sort it never consumes). The window
        # key honours the policy's own window count so the sort stays
        # segment-contiguous for n_windows != 24 too.
        order_key = getattr(self.policy, "stream_order_key", None)
        if order_key is None:
            order = inv_order = None
        else:
            n_win = getattr(self.policy, "n_windows", None) or self._horizon_h
            win_np = hour_np % n_win
            key = (win_np * len(self.regions) + region_np
                   if order_key == "window_region" else win_np)
            order_np = np.argsort(key, kind="stable").astype(np.int32)
            inv_np = np.empty_like(order_np)
            inv_np[order_np] = np.arange(len(order_np), dtype=np.int32)
            order, inv_order = jnp.asarray(order_np), jnp.asarray(inv_np)
        region = jnp.asarray(region_np)
        hour = jnp.asarray(hour_np)
        slack = jnp.asarray(batch.slack_h if slack_np is None else
                            np.asarray(slack_np, np.int32))
        state = self.policy.initial_state(len(self.regions), len(batch))
        return (batch.workload(self.cfg), batch.avail, region, hour,
                self._ci_table, self._ci_fc if ci_fc is None else ci_fc,
                state, order, inv_order, slack, cap_scale, used0)

    def route_stream_rolling(self, batch: RequestBatch, region: np.ndarray,
                             t_hours: np.ndarray, *, step_h: int = 6,
                             ledger=None):
        """Rolling re-planned routing: plan the stream in ``step_h``-hour
        steps, holding deferred work in a carry-over queue that is
        re-scored each step as ``CarbonGrid.roll`` advances the forecast
        (revealed hours become actuals), with an optional
        ``EmissionsLedger`` conserving capacity ahead of predicted clean
        windows. Requires a ``TemporalPolicy``; returns a
        ``repro.serve.forecast.RollingRouteResult``. One-shot equivalence:
        with a perfect forecast (``forecast_sigma_h == 0``) every plan
        step sees the same table, so decisions match the one-shot
        ``route_stream`` on the same commit schedule."""
        from repro.serve import forecast as _forecast
        return _forecast.route_stream_rolling(
            self, batch, region, t_hours, step_h=step_h, ledger=ledger)

    def admit_windows(self, res: FleetRouteResult, t_hours: np.ndarray,
                      engine, n_windows: int = 24, *,
                      queue=None) -> list[np.ndarray]:
        """Serving side of the windowed loop: per hourly window, the stream
        indices ``engine`` admits (``ServeEngine.admit`` over the routed
        targets, sliced by arrival hour). The same windows the policy's
        ``lax.scan`` walks while deciding — route once, then each tier-pinned
        engine drains its slice window by window.

        With ``queue=`` (a ``repro.serve.queue.QueueServeResult`` from
        ``serve_stream``) the call delegates to the continuous-batching
        path — ``queue.admit_batches`` — returning one index array per
        SERVE STEP instead of per hourly bucket (``res`` / ``t_hours`` are
        ignored: the queue result already carries its own commitments and
        timing). The bucketed path is deprecated in favour of it; without
        ``queue`` the historical behaviour is kept bit-for-bit, behind a
        once-per-process ``DeprecationWarning``."""
        if queue is not None:
            from repro.serve.queue import admit_batches
            return admit_batches(queue, engine)
        _warn_admit_windows()
        hour = np.floor(np.asarray(t_hours)).astype(np.int64) % n_windows
        mask = np.asarray(engine.admit(res.target))
        return [np.nonzero(mask & (hour == h))[0] for h in range(n_windows)]
