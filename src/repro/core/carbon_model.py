"""GreenScale carbon emission model — faithful implementation of paper Table 1.

For every execution target (Mobile / Edge DC / Hyperscale DC) the model
produces the operational and embodied carbon footprint of every involved
infrastructure component (mobile device, edge network base station, edge DC,
core-router path, hyperscale DC), plus the end-to-end latency used for the
QoS-feasibility check.

The whole model is a pure function of three array pytrees —

    evaluate(workload: Workload, infra: InfraParams, env: Environment)

— so the ~200K-point design space of the paper (§5) is explored with a single
``jax.vmap`` (see repro.core.design_space).

Unit discipline: time s, power W, energy J, carbon g, CI g/kWh.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.constants import (
    J_PER_KWH,
    N_COMPONENTS,
    Component,
    Target,
)
from repro.core.infrastructure import InfraParams
from repro.core.workloads import Workload


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Environment:
    """Scenario-dependent state: carbon intensities + runtime variance.

    ``ci``            (5,) gCO2/kWh per Component (paper: CI_M/CI_E/CI_R/CI_H;
                      edge network and edge DC share CI_E).
    ``interference``  (3,) computation-slowdown multiplier per compute tier
                      (co-located workloads, paper §5.3).
    ``net_slowdown``  (2,) communication-slowdown multiplier per network
                      (weak signal / congestion, paper §5.3).
    """

    ci: jax.Array
    interference: jax.Array
    net_slowdown: jax.Array

    @staticmethod
    def make(ci_mobile, ci_edge, ci_core, ci_hyper,
             interference=(1.0, 1.0, 1.0), net_slowdown=(1.0, 1.0)) -> "Environment":
        ci = jnp.stack([
            jnp.asarray(ci_mobile, jnp.float32),
            jnp.asarray(ci_edge, jnp.float32),
            jnp.asarray(ci_edge, jnp.float32),
            jnp.asarray(ci_core, jnp.float32),
            jnp.asarray(ci_hyper, jnp.float32),
        ])
        return Environment(
            ci=ci,
            interference=jnp.asarray(interference, jnp.float32),
            net_slowdown=jnp.asarray(net_slowdown, jnp.float32),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CFBreakdown:
    """Model output: per-(target, component) carbon + per-target latency."""

    op_cf: jax.Array  # (3, 5) grams CO2e, operational
    emb_cf: jax.Array  # (3, 5) grams CO2e, embodied (amortized)
    latency: jax.Array  # (3,) seconds end-to-end
    t_comp: jax.Array  # (3,) computation time on each tier
    t_comm: jax.Array  # (2,) [edge, core] network times

    @property
    def total_cf(self) -> jax.Array:  # (3,)
        return self.op_cf.sum(-1) + self.emb_cf.sum(-1)

    @property
    def op_total(self) -> jax.Array:  # (3,)
        return self.op_cf.sum(-1)

    @property
    def emb_total(self) -> jax.Array:  # (3,)
        return self.emb_cf.sum(-1)


def _cf(energy_j: jax.Array, ci: jax.Array) -> jax.Array:
    """Operational CF in grams from energy (J) and carbon intensity (g/kWh)."""
    return energy_j / J_PER_KWH * ci


def compute_times(w: Workload, infra: InfraParams, env: Environment) -> jax.Array:
    """T_comp per tier: roofline max of compute- and memory-bound times.

    Tier 0 (client device) honours the per-network delegate efficiency
    (``w.mobile_eff_scale``): the paper measured real devices where e.g.
    ResNet-50 runs quantized on the DSP while small float nets use the GPU.
    """
    eff0 = infra.eff_flops[0] * w.mobile_eff_scale
    eff = jnp.concatenate([eff0[None], infra.eff_flops[1:]])
    t = jnp.maximum(w.flops / eff, w.mem_bytes / infra.eff_mem_bw)
    return t * env.interference


def comm_times(w: Workload, infra: InfraParams, env: Environment) -> jax.Array:
    """[T_comm_E, T_comm_R]: per-request transfer + base latency, degraded."""
    payload = w.data_in + w.data_out
    t = payload / infra.net_bw + infra.net_lat
    return t * env.net_slowdown


def evaluate(w: Workload, infra: InfraParams, env: Environment) -> CFBreakdown:
    """Table 1, all three execution targets at once."""
    t_comp = compute_times(w, infra, env)  # (3,)
    t_comm = comm_times(w, infra, env)  # (2,)

    t_m = t_comp[Target.MOBILE]
    t_e = t_comp[Target.EDGE_DC]
    t_h = t_comp[Target.HYPERSCALE_DC]
    t_ce = t_comm[0]  # edge network
    t_cr = t_comm[1]  # core network

    # Streaming extension (paper §5.1: cloud gaming "needs to keep
    # transmitting the captured frames to Mobile"): for continuous workloads
    # the radio, base station and core path stay active for the full frame
    # interval, so the *energy* accounting uses max(transfer, frame) time.
    # Latency/feasibility still use the raw transfer times.
    frame = jnp.where(w.fps_req > 0, 1.0 / jnp.maximum(w.fps_req, 1e-6), 0.0)
    is_stream = w.continuous > 0
    t_ce_e = jnp.where(is_stream, jnp.maximum(t_ce, frame), t_ce)
    t_cr_e = jnp.where(is_stream, jnp.maximum(t_cr, frame), t_cr)

    ci = env.ci
    p_comp = infra.p_comp
    p_idle = infra.p_idle

    M, EN, ED, CN, HD = (Component.MOBILE, Component.EDGE_NETWORK,
                         Component.EDGE_DC, Component.CORE_NETWORK,
                         Component.HYPERSCALE_DC)
    zero = jnp.zeros((), jnp.float32)  # Table 1 '-': component not involved

    # The table is built whole, one stack per target row in component order:
    # under vmap a per-entry ``.at[t, c].set`` write becomes one
    # dynamic-update-slice over the whole batched (N, 3, 5) array.

    # ---- Target: Mobile Device (Table 1, first block) ------------------------
    op_mob = jnp.stack([
        _cf(t_m * p_comp[0], ci[M]),
        zero,
        _cf(t_m * p_idle[1] / infra.n_user_edge, ci[ED]),
        zero,
        _cf(t_m * p_idle[2] / infra.n_user_dc, ci[HD]),
    ])
    emb_mob = jnp.stack([
        infra.ecf_g[0] * t_m / infra.lifetime_s[0],
        zero,
        infra.ecf_g[1] / infra.n_user_edge * t_m / infra.lifetime_s[1],
        zero,
        infra.ecf_g[2] / infra.n_user_dc * t_m / infra.lifetime_s[2],
    ])

    # ---- Target: Edge DC (Table 1, second block) ------------------------------
    op_edc = jnp.stack([
        _cf(t_ce_e * infra.p_comm_mobile + t_e * p_idle[0], ci[M]),
        _cf(t_ce_e * infra.net_p[0] / infra.net_n_user[0], ci[EN]),
        _cf(t_e * p_comp[1] / infra.n_user_edge, ci[ED]),
        zero,
        _cf((t_ce + t_e) * p_idle[2] / infra.n_user_dc, ci[HD]),
    ])
    emb_edc = jnp.stack([
        infra.ecf_g[0] * (t_ce + t_e) / infra.lifetime_s[0],
        infra.net_ecf_g[0] / infra.net_n_user[0] * t_ce / infra.net_lifetime_s[0],
        infra.ecf_g[1] / infra.n_user_edge * t_e / infra.lifetime_s[1],
        zero,
        infra.ecf_g[2] / infra.n_user_dc * (t_ce + t_e) / infra.lifetime_s[2],
    ])

    # ---- Target: Hyperscale DC (Table 1, third block) -------------------------
    op_hyp = jnp.stack([
        _cf(t_ce_e * infra.p_comm_mobile + (t_cr + t_h) * p_idle[0], ci[M]),
        _cf(t_ce_e * infra.net_p[0] / infra.net_n_user[0], ci[EN]),
        _cf((t_ce + t_cr + t_h) * p_idle[1] / infra.n_user_edge, ci[ED]),
        _cf(t_cr_e * infra.net_p[1] / infra.net_n_user[1], ci[CN]),
        _cf(t_h * p_comp[2] / infra.n_batch_dc, ci[HD]),
    ])
    emb_hyp = jnp.stack([
        infra.ecf_g[0] * (t_ce + t_cr + t_h) / infra.lifetime_s[0],
        infra.net_ecf_g[0] / infra.net_n_user[0] * t_ce / infra.net_lifetime_s[0],
        infra.ecf_g[1] / infra.n_user_edge * (t_ce + t_cr + t_h)
        / infra.lifetime_s[1],
        infra.net_ecf_g[1] / infra.net_n_user[1] * t_cr / infra.net_lifetime_s[1],
        infra.ecf_g[2] / infra.n_batch_dc * t_h / infra.lifetime_s[2],
    ])

    op = jnp.stack([op_mob, op_edc, op_hyp])
    emb = jnp.stack([emb_mob, emb_edc, emb_hyp])

    latency = jnp.stack([t_m, t_ce + t_e, t_ce + t_cr + t_h])
    return CFBreakdown(op_cf=op, emb_cf=emb, latency=latency,
                       t_comp=t_comp, t_comm=t_comm)


def stream_feasible(t_comm: jax.Array, w: Workload) -> jax.Array:
    """(3,) bool — fps-sustain half of the QoS check: per-frame transfer must
    fit in the frame interval on every network hop the target uses. True for
    non-streaming workloads (CI-free, so factorized evaluators reuse it)."""
    frame_time = jnp.where(w.fps_req > 0, 1.0 / jnp.maximum(w.fps_req, 1e-6),
                           jnp.inf)
    stream_ok = jnp.stack([
        jnp.asarray(True),
        t_comm[0] <= frame_time,
        (t_comm[0] <= frame_time) & (t_comm[1] <= frame_time),
    ])
    return jnp.where(w.continuous > 0, stream_ok, True)


def qos_feasible(latency: jax.Array, t_comm: jax.Array, w: Workload,
                 extra_latency: jax.Array | float = 0.0) -> jax.Array:
    """(3,) bool QoS check from its CI-free ingredients. ``extra_latency``
    adds a WAN hop (CarbonGrid.rtt_s) on top of the Table-1 end-to-end
    latency — a remote placement candidate is infeasible when the hop blows
    the budget; 0.0 reproduces ``feasible`` exactly."""
    ok = latency + extra_latency <= w.latency_req
    return ok & stream_feasible(t_comm, w)


def feasible(b: CFBreakdown, w: Workload) -> jax.Array:
    """(3,) bool — does each target satisfy the QoS latency constraint?"""
    return qos_feasible(b.latency, b.t_comm, w)


def pick_target(score: jax.Array, ok: jax.Array, fallback: jax.Array,
                avail: jax.Array | None = None) -> jax.Array:
    """argmin(score) over feasible+available targets.

    When *no* available target meets the QoS constraint, the paper still
    reports an optimum (e.g. Fig 10(c): every target misses under unstable
    networks, Mobile is picked on carbon) — fall back to argmin(fallback)
    over available targets.

    Degenerate all-False ``avail`` (the request can run nowhere) resolves to
    ``Target.MOBILE`` (index 0): every masked score is +inf and
    ``jnp.argmin`` over a constant array returns the first index. This is
    pinned behaviour (tests/test_carbon_model.py) — the request falls back to
    the user's own device, the only tier that always physically exists.
    """
    if avail is None:
        avail = jnp.ones_like(ok)
    ok = ok & avail
    any_ok = jnp.any(ok)
    return jnp.where(any_ok,
                     jnp.argmin(jnp.where(ok, score, jnp.inf)),
                     jnp.argmin(jnp.where(avail, fallback, jnp.inf)))


def optimal_target(b: CFBreakdown, w: Workload, metric: str = "carbon",
                   avail: jax.Array | None = None) -> jax.Array:
    """argmin over feasible targets of the chosen metric (paper Fig 5 stars)."""
    if metric == "carbon":
        score = b.total_cf
    elif metric == "latency":
        score = b.latency
    else:  # the energy metric needs infra/env: use optimal_targets_all_metrics
        raise ValueError(metric)
    return pick_target(score, feasible(b, w), b.total_cf, avail)


def evaluate_energy(w: Workload, infra: InfraParams, env: Environment) -> jax.Array:
    """(3,) operational energy (J) per target — the paper's Fig 5(b) axis.

    Same accounting as evaluate() with CI := 1 for every component, times
    J_PER_KWH to undo the unit conversion.
    """
    unit_env = Environment(ci=jnp.ones_like(env.ci),
                           interference=env.interference,
                           net_slowdown=env.net_slowdown)
    b = evaluate(w, infra, unit_env)
    return b.op_cf.sum(-1) * J_PER_KWH


# ---------------------------------------------------------------------------
# Batched entry points (fleet-scale routing: one vmap instead of a Python
# loop over requests — see repro.serve.router)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RouteOutputs:
    """Routing result for one request (leading batch axis under vmap).

    ``target`` is the carbon-optimal feasible pick; ``target_latency`` /
    ``target_energy`` are the latency- and energy-optimal baseline picks the
    paper compares against (Fig 5), evaluated under the same feasibility set.
    """

    target: jax.Array  # () int32
    target_latency: jax.Array  # () int32
    target_energy: jax.Array  # () int32
    total_cf: jax.Array  # (3,) gCO2 per execution target
    latency: jax.Array  # (3,) s per execution target
    ok: jax.Array  # (3,) bool, feasible & available


def route_one(w: Workload, infra: InfraParams, env: Environment,
              avail: jax.Array) -> RouteOutputs:
    """Single-request routing core — the scalar unit every batched router
    vmaps, so batched and per-request decisions agree by construction."""
    b = evaluate(w, infra, env)
    ok = feasible(b, w) & avail
    energy = evaluate_energy(w, infra, env)
    return RouteOutputs(
        target=pick_target(b.total_cf, ok, b.total_cf, avail),
        target_latency=pick_target(b.latency, ok, b.total_cf, avail),
        target_energy=pick_target(energy, ok, b.total_cf, avail),
        total_cf=b.total_cf,
        latency=b.latency,
        ok=ok,
    )


#: (N,)-batched requests against ONE environment (single-region batch).
route_many = jax.vmap(route_one, in_axes=(0, None, None, 0))

#: (N,)-batched requests, each against ITS OWN environment (fleet routing:
#: per-request region/hour CI rows; interference/net_slowdown stay shared).
route_many_envs = jax.vmap(
    route_one,
    in_axes=(0, None, Environment(ci=0, interference=None, net_slowdown=None),
             0))

#: Table-1 model over a stacked Workload (leading axis) in one environment.
evaluate_batch = jax.vmap(evaluate, in_axes=(0, None, None))

#: QoS feasibility over stacked breakdowns/workloads (matches evaluate_batch).
feasible_batch = jax.vmap(feasible, in_axes=(0, 0))


# ---------------------------------------------------------------------------
# Factorized evaluator: operational carbon is LINEAR in carbon intensity
# (op_cf[t, c] = op_unit[t, c] * ci[c]), embodied carbon / latency / QoS
# feasibility are CI-free — so ONE Table-1 evaluation at unit CI yields the
# score of every candidate (region, hour) placement as an einsum against a
# ``CarbonGrid`` CI table instead of one full sweep per candidate region
# (the ROADMAP factorization; geo-temporal policies build on this).
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EnergyFactors:
    """CI-independent factorization of Table 1 for one request (leading batch
    axis under vmap — see ``energy_factors_batch``).

    ``op_unit``  (3, 5) grams per (g/kWh): operational CF at unit CI, i.e.
                 component energy / J_PER_KWH. ``op_unit @ ci`` reproduces
                 ``evaluate(...).op_cf.sum(-1)`` for any CI row to fp32
                 tolerance (pinned in tests/test_carbon_model.py).
    ``emb_cf``   (3, 5) grams, embodied (CI-free).
    ``latency``  (3,) s end-to-end; ``t_comm`` (2,) network times — together
                 with the workload these reproduce the QoS check, optionally
                 with a WAN-hop ``extra_latency`` for remote candidates.
    """

    op_unit: jax.Array
    emb_cf: jax.Array
    latency: jax.Array
    t_comm: jax.Array

    @property
    def emb_total(self) -> jax.Array:  # (3,)
        return self.emb_cf.sum(-1)

    @property
    def energy_j(self) -> jax.Array:
        """(3,) operational energy per target — ``evaluate_energy`` without
        the extra sweep (op_unit already is energy / J_PER_KWH)."""
        return self.op_unit.sum(-1) * J_PER_KWH


def energy_factors(w: Workload, infra: InfraParams, interference: jax.Array,
                   net_slowdown: jax.Array) -> EnergyFactors:
    """One Table-1 evaluation at unit CI: everything CI-dependent downstream
    is an einsum against ``op_unit``. Interference / net_slowdown (the
    runtime-variance state) shape the times exactly as in ``evaluate``."""
    unit_env = Environment(
        ci=jnp.ones((N_COMPONENTS,), jnp.float32),
        interference=jnp.asarray(interference, jnp.float32),
        net_slowdown=jnp.asarray(net_slowdown, jnp.float32))
    b = evaluate(w, infra, unit_env)
    return EnergyFactors(op_unit=b.op_cf, emb_cf=b.emb_cf,
                         latency=b.latency, t_comm=b.t_comm)


#: (N,)-batched factorization — ONE evaluation for the whole stream; every
#: (region, tier, hour) candidate score downstream is einsum + mask. Its
#: device ops carry the ``factors`` scope.
energy_factors_batch = jax.named_scope("factors")(
    jax.vmap(energy_factors, in_axes=(0, None, None, None)))


def total_cf_from_factors(f: EnergyFactors, ci: jax.Array) -> jax.Array:
    """(N, 3) total CF rows under per-request CI rows ``ci`` (N, 5) — the
    einsum replacing a full ``evaluate`` sweep per candidate region/hour.
    Full f32 precision: a TPU multiplies f32 operands in bf16 passes by
    default, which would shift scores (and tie-breaks) off the CPU's."""
    return (jnp.einsum("ntc,nc->nt", f.op_unit, ci,
                       precision=jax.lax.Precision.HIGHEST)
            + f.emb_cf.sum(-1))


def charged_ci(ci_table: jax.Array, home: jax.Array, exec_region: jax.Array,
               exec_hour: jax.Array) -> jax.Array:
    """(N, 5) CI rows a committed request is charged at, gathered from an
    (R, H, 5) table at its execution hour: the device and access network
    [mobile, edge_net] draw energy in its HOME region, the infrastructure
    [edge_dc, core_net, hyper_dc] in the region it executes in."""
    return jnp.concatenate([ci_table[home, exec_hour][:, :2],
                            ci_table[exec_region, exec_hour][:, 2:]], axis=1)


# --- Forecast-error risk on the factorized scorer ------------------------------
#
# Operational carbon is LINEAR in CI, so scoring a candidate on expected
# carbon plus a forecast-error penalty reduces to inflating its FORECAST CI
# components before the einsum: score = E[cf] + lambda * std[cf] when the
# relative error std of the grid-driven components at lead L hours is
# sigma_h * sqrt(L) (see ``CarbonGrid.forecast_sigma_h``). Only the
# grid-trace-driven components carry forecast risk — the device battery and
# the core path are flat knowns.

#: risk mask over the 5-component CI row [mobile, edge_net, edge_dc,
#: core_net, hyper_dc]: the grid-trace-driven components.
_HOME_CI_RISK = jnp.asarray([0.0, 1.0, 1.0, 0.0, 1.0], jnp.float32)
#: risk mask over the relocating [edge_dc, core_net, hyper_dc] columns.
_DC_CI_RISK = jnp.asarray([1.0, 0.0, 1.0], jnp.float32)


def forecast_risk_scale(lead_h: jax.Array | float, sigma_h: float,
                        risk_lambda: float) -> jax.Array:
    """Risk-inflation multiplier ``1 + lambda * sigma_h * sqrt(lead)`` on
    forecast-driven CI — the mean-plus-lambda-std score of a candidate at
    ``lead_h`` hours ahead, in multiplier form. 1.0 at lead 0 (and
    everywhere when ``risk_lambda`` or ``sigma_h`` is 0): an error-blind
    scorer, bit-for-bit."""
    lead = jnp.maximum(jnp.asarray(lead_h, jnp.float32), 0.0)
    return 1.0 + risk_lambda * sigma_h * jnp.sqrt(lead)


def inflate_ci_risk(home_ci: jax.Array, cand_ci_dc: jax.Array,
                    scale: jax.Array | float
                    ) -> tuple[jax.Array, jax.Array]:
    """Apply a ``forecast_risk_scale`` multiplier to the forecast-driven
    components of a split candidate CI — ``home_ci`` (..., 5) rows and
    ``cand_ci_dc`` (..., 3) relocating columns — leaving the known
    device-battery and core-path components untouched. Because the scorer
    is linear in CI, this prices the risk term into ANY factorized inner
    policy (oracle einsums, learned re-featurization) without touching its
    scoring code."""
    s = jnp.asarray(scale, jnp.float32)
    home = home_ci * (1.0 + (s - 1.0) * _HOME_CI_RISK)
    dc = cand_ci_dc * (1.0 + (s - 1.0) * _DC_CI_RISK)
    return home, dc


def qos_feasible_from_factors(f: EnergyFactors, w: Workload,
                              extra_latency: jax.Array | float = 0.0
                              ) -> jax.Array:
    """(N, 3) QoS feasibility from batched factors (+ optional WAN hop)."""
    extra = jnp.broadcast_to(jnp.asarray(extra_latency, jnp.float32),
                             (w.flops.shape[0],))
    return jax.vmap(qos_feasible)(f.latency, f.t_comm, w, extra[:, None])


def pair_qos_feasible_from_factors(f: EnergyFactors, w: Workload,
                                   extra_latency: jax.Array) -> jax.Array:
    """(R, N, 3) QoS feasibility of every candidate-region placement under
    per-candidate WAN hops ``extra_latency`` (R, N) — the ONE definition of
    hop-adjusted feasibility shared by the oracle's factorized pair scorer
    and the learned policies' hop gate, so their refusal semantics can
    never diverge. Availability is the caller's to mask."""
    lat = f.latency[None] + jnp.asarray(extra_latency,
                                        jnp.float32)[:, :, None]
    return ((lat <= w.latency_req[None, :, None])
            & stream_feasible_batch(f.t_comm, w)[None])


#: (N, 3) fps-sustain feasibility over batched factors (CI- and hop-free).
stream_feasible_batch = jax.vmap(stream_feasible)


@jax.named_scope("factors")
def route_many_from_factors(f: EnergyFactors, w: Workload, ci: jax.Array,
                            avail: jax.Array) -> RouteOutputs:
    """``route_many_envs`` semantics rebuilt from precomputed factors + the
    per-request home CI rows — no Table-1 re-evaluation. Scores agree with
    the sweep to fp32 tolerance; pick/fallback semantics are identical
    (``pick_target`` is shared)."""
    total_cf = total_cf_from_factors(f, ci)
    ok = qos_feasible_from_factors(f, w) & avail
    energy = f.energy_j
    pick = jax.vmap(pick_target)
    return RouteOutputs(
        target=pick(total_cf, ok, total_cf, avail),
        target_latency=pick(f.latency, ok, total_cf, avail),
        target_energy=pick(energy, ok, total_cf, avail),
        total_cf=total_cf,
        latency=f.latency,
        ok=ok,
    )


def optimal_targets_all_metrics(
    w: Workload, infra: InfraParams, env: Environment,
    avail: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """Carbon/energy/latency-optimal targets, feasibility-aware (Fig 5 stars).

    ``avail`` masks the targets a workload can run on at all — e.g. games
    compare the on-device build against the cloud-gaming service (paper §4.1),
    so Edge DC is not in their design space.

    Thin wrapper over ``route_one`` (the single source of pick/fallback
    semantics); XLA CSE dedupes the repeated evaluate under jit.
    """
    b = evaluate(w, infra, env)
    ok = feasible(b, w)
    out = route_one(w, infra, env,
                    jnp.ones_like(ok) if avail is None else avail)
    return {
        "carbon": out.target,
        "energy": out.target_energy,
        "latency": out.target_latency,
        "breakdown": b,
        "feasible": ok,
    }
