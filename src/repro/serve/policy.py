"""Pluggable routing policies: ONE decision interface from the Table-1 oracle
to learned schedulers to capacity-capped fleet simulation.

The paper's core claim is that *how you decide* changes the carbon outcome
(oracle Table-1 search vs. learned predictors, §5.4/Fig 14). This module is
the seam that lets every decision-maker route the same fleet-scale stream:

  * ``OraclePolicy``    — exhaustive Table-1 evaluation per request (the
    paper's explorer), with ``metric="carbon"/"latency"/"energy"`` variants
    so the baselines are ordinary policies instead of special cases.
  * ``LearnedPolicy``   — pure-JAX *inference* of a fitted scheduler from
    ``repro.core.schedulers`` (Regression / Classification / BO / RL).
    Fitting stays offline on the design-space dataset; the fitted model then
    routes a million-request stream inside one jitted call.
  * ``CapacityLimiter`` — composable wrapper enforcing per-(region, tier)
    request caps per hourly window (CASPER-style load caps), spilling each
    over-cap request to its next-best *feasible* tier via a ``lax.scan`` over
    windows.

Protocol (all methods jit-compatible over stacked batches; ``env.ci`` is
per-request ``(N, 5)`` — the fleet form — while ``interference`` /
``net_slowdown`` stay shared):

  ``scores(w, env, avail, hour=None) -> (N, 3)``
      per-tier preference scores, lower is better; +inf marks tiers the
      policy would never pick (infeasible and/or unavailable). ``argmin``
      over a row IS the policy's decision for that request, which is what
      lets wrappers like ``CapacityLimiter`` re-rank and spill.
  ``decide(w, env, avail, state, *, region=None, hour=None, outputs=None,
      order=None, inv_order=None, slack=None, factors=None, fc_table=None,
      cap_scale=None, used0=None)
      -> (targets, new_state)``
      the decision entry point. ``state`` is a policy-owned pytree threaded
      through the call (capacity counters, ...); stateless policies pass it
      through. ``outputs`` is an optional precomputed
      ``carbon_model.RouteOutputs`` hint: the fleet router already evaluates
      Table 1 for carbon accounting, and oracle-family policies reuse it so
      the default path stays bit-identical to routing without the policy
      layer (and XLA sees a single evaluation). ``order`` / ``inv_order``
      are an optional stream-order hint and its inverse — the indices that
      stably sort the stream by arrival window (or by (window, region) when
      the policy sets ``stream_order_key = "window_region"``), precomputed
      on the host by the fleet router (a numpy radix sort) so windowed
      policies (``PlacementPolicy``) skip an O(N log N) device sort;
      policies that don't window ignore them. ``slack`` is the per-request
      deferral allowance in hours ((N,) int32; None = nothing may defer) —
      only temporal policies consume it. ``factors`` is an optional
      precomputed ``carbon_model.EnergyFactors`` batch (the router computes
      it once for policies that set ``wants_factors = True``) from which
      CI-linear policies score every candidate (region, tier, hour) as an
      einsum instead of one Table-1 sweep per candidate region. ``fc_table``
      is an optional traced (R, H, 5) FORECAST component table — what
      forecast-native policies score candidate hours on, while routed carbon
      is charged at actuals; None means score on the grid's own forecast
      view (which IS the actual table when no forecast is attached).
      ``cap_scale`` ((R,) or (R, 3) float32) and ``used0`` (flat
      pre-consumed window cell counts) are runtime-capacity inputs consumed
      by capacity-aware placement/temporal policies: a per-region
      emissions-budget multiplier (the rolling re-planner) or a live
      per-(region, tier) worker-slot matrix (the continuous-batching serve
      loop), and cells already committed by earlier planning/serving
      steps. Policies that don't implement them ignore (or refuse) them.
  ``initial_state(n_regions, n_requests) -> pytree``
      the state to thread into the first ``decide``.

Factorized scoring hooks (optional — what lets a policy ride the einsum
placement/temporal engines; ``OraclePolicy`` and ``LearnedPolicy`` expose
both):

  ``scores_from_factors(factors, w, ci, avail, extra_latency=0.0, *,
      hour=None, interference=None, net_slowdown=None) -> (N, 3)``
      ``scores`` under arbitrary per-request CI rows. ``extra_latency`` is
      a remote candidate's WAN hop; the keyword-only tail is the non-CI
      scoring context — the EXECUTION hour (an absolute horizon hour, which
      for deferred candidates differs from arrival) plus the shared
      variance state — that feature-based policies fold into their inputs
      and CI-only policies ignore.
  ``pair_scores_from_factors(factors, w, home_ci, cand_ci_dc, avail,
      extra_latency=None, *, hour=None, interference=None,
      net_slowdown=None) -> (R, N, 3)``
      the vectorized form over candidate regions: ``home_ci`` (N, 5) anchors
      the non-relocating [mobile, edge_net] components, ``cand_ci_dc``
      (R, N, 3) each candidate's relocating columns, ``extra_latency``
      (R, N) the per-candidate hop. The leading candidate axis is
      shape-generic: sparse mesoscale grids pass gathered per-row neighbor
      lists ((C, N, 3) with C = K+1 candidates) instead of all R regions —
      each row is arithmetically identical to the matching dense row.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import carbon_model
from repro.core.carbon_model import Environment, RouteOutputs
from repro.core.constants import N_TARGETS
from repro.core.infrastructure import InfraParams
from repro.core.schedulers import SchedulerDataset
from repro.core.workloads import Workload


def scores_with_reuse(inner: "RoutingPolicy", w: Workload, env: Environment,
                      avail: jax.Array, hour: jax.Array | None,
                      outputs: RouteOutputs | None) -> jax.Array:
    """``inner.scores`` — or its reconstruction from a precomputed
    ``RouteOutputs`` when the inner policy offers ``scores_from_outputs``
    (the router already evaluated Table 1 under this very env). The ONE
    reuse seam shared by every capacity wrapper, so the scan and
    segment-rank formulations can never diverge on their score source."""
    if outputs is not None:
        reuse = getattr(inner, "scores_from_outputs", None)
        if reuse is not None:
            s = reuse(outputs, avail)
            if s is not None:
                return s
    return inner.scores(w, env, avail, hour=hour)


class RoutingPolicy(abc.ABC):
    """Base class: a policy is ``scores`` + (optionally stateful) ``decide``.

    The default ``decide`` is the stateless argmin over ``scores`` — exactly
    ``carbon_model.pick_target`` semantics when the scores use the same
    +inf encoding (see ``OraclePolicy.scores``).
    """

    # NOTE: deliberately not annotated — dataclass subclasses would inherit
    # an annotated class attribute as a defaulted field.
    name = "policy"

    def initial_state(self, n_regions: int, n_requests: int) -> Any:
        """Fresh threaded decision state for a stream of ``n_requests``
        over ``n_regions`` regions; stateless policies return ``()``."""
        return ()

    @abc.abstractmethod
    def scores(self, w: Workload, env: Environment, avail: jax.Array, *,
               hour: jax.Array | None = None) -> jax.Array:
        """(N, 3) per-tier scores, lower is better, +inf = never pick.

        Units are policy-defined — only the ORDERING is contracted (the
        oracle's carbon metric scores in gCO2/request, latency in seconds,
        energy in joules; learned scores are unitless model outputs).
        ``hour`` is the absolute grid-horizon hour of each request."""

    def decide(self, w: Workload, env: Environment, avail: jax.Array,
               state: Any, *, region: jax.Array | None = None,
               hour: jax.Array | None = None,
               outputs: RouteOutputs | None = None,
               order: jax.Array | None = None,
               inv_order: jax.Array | None = None,
               slack: jax.Array | None = None,
               factors: Any | None = None,
               fc_table: jax.Array | None = None,
               cap_scale: jax.Array | None = None,
               used0: jax.Array | None = None,
               axis_name: str | None = None
               ) -> tuple[jax.Array, Any]:
        # ``axis_name`` names the mesh axis when the stream is sharded
        # (repro.serve.distributed); a per-row argmin needs no cross-device
        # reconciliation, so the default decide simply ignores it.
        s = self.scores(w, env, avail, hour=hour)
        return jnp.argmin(s, axis=-1).astype(jnp.int32), state


# ---------------------------------------------------------------------------
# Oracle (Table-1 search) — carbon objective + latency/energy baselines
# ---------------------------------------------------------------------------


def _oracle_scores_one(w: Workload, infra: InfraParams, env: Environment,
                       avail: jax.Array, metric: str) -> jax.Array:
    """(3,) score row whose argmin reproduces ``carbon_model.pick_target``:
    feasible tiers carry the metric, infeasible tiers +inf; when nothing is
    feasible the row degrades to the carbon fallback over available tiers."""
    b = carbon_model.evaluate(w, infra, env)
    ok = carbon_model.feasible(b, w) & avail
    if metric == "carbon":
        score = b.total_cf
    elif metric == "latency":
        score = b.latency
    elif metric == "energy":
        score = carbon_model.evaluate_energy(w, infra, env)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return jnp.where(jnp.any(ok),
                     jnp.where(ok, score, jnp.inf),
                     jnp.where(avail, b.total_cf, jnp.inf))


@dataclasses.dataclass
class OraclePolicy(RoutingPolicy):
    """Exhaustive Table-1 evaluation per request (paper's explorer).

    ``metric`` selects the objective: ``"carbon"`` is GreenScale,
    ``"latency"``/``"energy"`` are the paper's Fig-5/6 baselines — as
    policies they route head-to-head on the same stream instead of living as
    special-cased aggregate columns inside the fleet router.

    Score units per metric: carbon = gCO2/request (operational at the
    env's CI plus amortized embodied), latency = seconds, energy =
    joules. ``decide`` reproduces ``carbon_model.route_many_envs``'s
    per-metric targets bit-for-bit (the scalar-router parity anchor).
    """

    infra: InfraParams
    metric: str = "carbon"

    def __post_init__(self):
        if self.metric not in ("carbon", "latency", "energy"):
            raise ValueError(f"unknown metric {self.metric!r}")
        self.name = f"oracle-{self.metric}"
        infra, metric = self.infra, self.metric
        self._scores_many = jax.vmap(
            lambda w, env, avail: _oracle_scores_one(w, infra, env, avail,
                                                     metric),
            in_axes=(0, Environment(ci=0, interference=None,
                                    net_slowdown=None), 0))

    def scores(self, w, env, avail, *, hour=None):
        """(N, 3) metric scores (gCO2 / s / J per request) via one vmapped
        Table-1 evaluation; Table-1 scores are hour-free (CI is in env)."""
        return self._scores_many(w, env, avail)

    def scores_from_outputs(self, out: RouteOutputs,
                            avail: jax.Array) -> jax.Array | None:
        """``scores`` reconstructed from a precomputed ``RouteOutputs`` of
        the same (w, env, avail) — wrappers (``PlacementPolicy``) reuse the
        router's Table-1 evaluation instead of re-evaluating. ``None`` for
        the energy metric (RouteOutputs carries no per-tier energy)."""
        if self.metric == "energy":
            return None
        score = out.total_cf if self.metric == "carbon" else out.latency
        return jnp.where(jnp.any(out.ok, axis=-1, keepdims=True),
                         jnp.where(out.ok, score, jnp.inf),
                         jnp.where(avail, out.total_cf, jnp.inf))

    def scores_from_factors(self, factors, w: Workload, ci: jax.Array,
                            avail: jax.Array,
                            extra_latency: jax.Array | float = 0.0, *,
                            hour: jax.Array | None = None,
                            interference: jax.Array | None = None,
                            net_slowdown: jax.Array | None = None
                            ) -> jax.Array:
        """``scores`` under arbitrary per-request CI rows ``ci`` (N, 5),
        rebuilt from a precomputed ``carbon_model.EnergyFactors`` batch — the
        einsum path placement/temporal policies use to score every candidate
        (region, hour) without a Table-1 sweep per candidate. Supports all
        three metrics (unlike ``scores_from_outputs``). ``extra_latency``
        ((N,) or scalar, seconds) is the WAN hop of a remote candidate: it
        tightens the QoS feasibility mask and adds to the latency-metric
        score; 0.0 reproduces the home-region scores to fp32 tolerance.

        Fallback semantics: a request with no feasible tier even WITHOUT
        the hop keeps the legacy degenerate fallback (carbon over available
        tiers — it must run somewhere, the hop changes nothing). But a
        candidate that is infeasible purely BECAUSE of the hop is refused
        outright (all +inf): a tight-budget request never trades its QoS
        constraint for a greener remote region.

        The ``hour`` / ``interference`` / ``net_slowdown`` kwargs are the
        factorized-hook protocol's non-CI scoring context (feature-based
        policies need them); Table-1 scores depend on CI alone — the
        variance state already shaped ``factors`` — so they are ignored
        here."""
        total_cf = carbon_model.total_cf_from_factors(factors, ci)
        ok_base = carbon_model.qos_feasible_from_factors(factors, w) & avail
        ok = carbon_model.qos_feasible_from_factors(
            factors, w, extra_latency) & avail
        extra = jnp.asarray(extra_latency, jnp.float32)
        if self.metric == "carbon":
            score = total_cf
        elif self.metric == "latency":
            score = factors.latency + jnp.broadcast_to(
                extra.reshape(-1, 1) if extra.ndim else extra,
                factors.latency.shape)
        else:  # energy — CI- and hop-free
            score = factors.energy_j
        return jnp.where(
            jnp.any(ok, axis=-1, keepdims=True),
            jnp.where(ok, score, jnp.inf),
            jnp.where(jnp.any(ok_base, axis=-1, keepdims=True),
                      jnp.inf,
                      jnp.where(avail, total_cf, jnp.inf)))

    def pair_scores_from_factors(self, factors, w: Workload,
                                 home_ci: jax.Array, cand_ci_dc: jax.Array,
                                 avail: jax.Array,
                                 extra_latency: jax.Array | None = None, *,
                                 hour: jax.Array | None = None,
                                 interference: jax.Array | None = None,
                                 net_slowdown: jax.Array | None = None
                                 ) -> jax.Array:
        """(R, N, 3) ``scores_from_factors`` vectorized over candidate
        regions — the placement/temporal hot path. ``home_ci`` (N, 5) bills
        the [mobile, edge_net] components at the home region;
        ``cand_ci_dc`` (R, N, 3) holds ONLY the relocating
        [edge_dc, core_net, hyper_dc] CI components of each candidate
        (callers gather just those three table columns). One einsum pair +
        ONE QoS evaluation replace R per-region score calls (and, with
        ``extra_latency=None`` — no WAN hop anywhere — the hop-gating
        collapses away statically). Fallback semantics per candidate match
        ``scores_from_factors``."""
        # full f32 precision (TPU default is bf16 passes): candidate
        # scores must order the same on every backend
        hp = jnp.einsum("ntc,nc->nt", factors.op_unit[..., :2],
                        home_ci[..., :2],
                        precision=jax.lax.Precision.HIGHEST)  # (N, 3)
        cp = jnp.einsum("ntc,rnc->rnt", factors.op_unit[..., 2:],
                        cand_ci_dc,
                        precision=jax.lax.Precision.HIGHEST)  # (R, N, 3)
        total_cf = hp[None] + cp + factors.emb_cf.sum(-1)[None]
        ok_base = carbon_model.qos_feasible_from_factors(factors, w) & avail
        any_base = jnp.any(ok_base, axis=-1, keepdims=True)  # (N, 1)
        if extra_latency is None:
            ok = ok_base[None]
            lat = factors.latency[None]
        else:
            extra = jnp.asarray(extra_latency, jnp.float32)  # (R, N)
            lat = factors.latency[None] + extra[:, :, None]
            ok = (carbon_model.pair_qos_feasible_from_factors(
                factors, w, extra) & avail[None])
        if self.metric == "carbon":
            score = total_cf
        elif self.metric == "latency":
            score = jnp.broadcast_to(lat, total_cf.shape)
        else:  # energy — CI- and hop-free
            score = jnp.broadcast_to(factors.energy_j[None], total_cf.shape)
        return jnp.where(
            jnp.any(ok, axis=-1, keepdims=True),
            jnp.where(ok, score, jnp.inf),
            jnp.where(any_base[None], jnp.inf,
                      jnp.where(avail[None], total_cf, jnp.inf)))

    def decide(self, w, env, avail, state, *, region=None, hour=None,
               outputs=None, order=None, inv_order=None, slack=None,
               factors=None, fc_table=None, cap_scale=None, used0=None,
               axis_name=None):
        """(N,) int32 targets straight from the Table-1 search — reuses the
        router's precomputed ``RouteOutputs`` when given, and is bit-
        identical to ``carbon_model.route_many_envs`` either way."""
        out = outputs if outputs is not None else \
            carbon_model.route_many_envs(w, self.infra, env, avail)
        t = {"carbon": out.target, "latency": out.target_latency,
             "energy": out.target_energy}[self.metric]
        return t, state


# ---------------------------------------------------------------------------
# Learned policies: offline-fitted schedulers routing live streams
# ---------------------------------------------------------------------------


def _gate_hop_broken(s: jax.Array, factors, w: Workload,
                     extra_latency) -> jax.Array:
    """+inf for candidates whose WAN hop breaks an otherwise-feasible tier.

    Learned scores carry no explicit QoS model (parity with the sweep
    path), but a remote candidate must not trade a request's latency
    budget for a greener score: where ``extra_latency`` flips a tier from
    QoS-feasible to infeasible, that candidate is refused outright — the
    same refusal the oracle's factorized scorer applies. Tiers infeasible
    even WITHOUT the hop keep their learned score (capacity was never the
    hop's fault, and the sweep path never gated them either). No-hop calls
    (``None`` / literal 0) skip the gate statically. ``s`` is (N, 3) with
    scalar/(N,) ``extra_latency``, or (R, N, 3) with (R, N); availability
    must already be masked into ``s`` by the caller."""
    if extra_latency is None or (
            not isinstance(extra_latency, jax.Array)
            and np.ndim(extra_latency) == 0
            and float(extra_latency) == 0.0):
        return s
    ok_base = carbon_model.qos_feasible_from_factors(factors, w)  # (N, 3)
    if s.ndim == 3:  # (R, N, 3) candidate scores, (R, N) hops
        ok_hop = carbon_model.pair_qos_feasible_from_factors(
            factors, w, extra_latency)
        return jnp.where(ok_base[None] & ~ok_hop, jnp.inf, s)
    ok_hop = carbon_model.qos_feasible_from_factors(factors, w,
                                                    extra_latency)
    return jnp.where(ok_base & ~ok_hop, jnp.inf, s)


#: feature-column indices of the 5 CI components (after the 6 workload
#: columns); the last 3 of them — [edge_dc, core_net, hyper_dc] — are the
#: components that relocate with a cross-region placement.
_CI_COLS = slice(6, 11)
_CI_DC_COLS = slice(8, 11)


def feature_rows(w: Workload, ci: jax.Array,
                 interference: jax.Array | None = None,
                 net_slowdown: jax.Array | None = None,
                 hour: jax.Array | None = None,
                 emb_lca: bool = False) -> jax.Array:
    """(N, 19) raw (un-standardized) feature rows from explicit CI rows.

    Mirrors ``schedulers.build_dataset`` column-for-column — workload
    descriptor, scenario CI/variance, hour-of-day harmonics, embodied-model
    flag — so a model fitted on the offline design space reads the same
    inputs when routing online. ``ci`` is (5,) shared or (N, 5) per-request
    — the seam that lets factorized policies re-featurize arbitrary
    candidate (region, hour) CI rows without an Environment in hand.
    ``hour`` may be any absolute horizon hour; the harmonics wrap daily.
    """
    n = w.flops.shape[0]
    f_w = jnp.stack([
        jnp.log10(w.flops + 1.0),
        jnp.log10(w.mem_bytes + 1.0),
        jnp.log10(w.data_in + 1.0),
        jnp.log10(w.data_out + 1.0),
        jnp.log10(w.latency_req + 1e-6),
        w.continuous,
    ], axis=-1)
    bcast = lambda a, k: jnp.broadcast_to(
        jnp.asarray(a, jnp.float32).reshape(-1, k), (n, k))
    if interference is None:
        interference = jnp.ones((3,), jnp.float32)
    if net_slowdown is None:
        net_slowdown = jnp.ones((2,), jnp.float32)
    h = (jnp.zeros((n,), jnp.float32) if hour is None
         else jnp.asarray(hour, jnp.float32))
    ang = 2.0 * jnp.pi * h / 24.0
    return jnp.concatenate([
        f_w,
        bcast(ci, 5) / 100.0,
        bcast(interference, 3),
        bcast(net_slowdown, 2),
        jnp.sin(ang)[:, None],
        jnp.cos(ang)[:, None],
        jnp.full((n, 1), 1.0 if emb_lca else 0.0, jnp.float32),
    ], axis=-1)


def policy_features(w: Workload, env: Environment,
                    hour: jax.Array | None = None,
                    emb_lca: bool = False) -> jax.Array:
    """``feature_rows`` of a live stream's Environment (the sweep path)."""
    return feature_rows(w, env.ci, env.interference, env.net_slowdown,
                        hour, emb_lca)


@dataclasses.dataclass
class LearnedPolicy(RoutingPolicy):
    """A fitted scheduler routing live streams in pure JAX.

    Built via ``LearnedPolicy.fit(scheduler, train)``: the scheduler's
    ``fit_params`` runs offline (numpy / host loops allowed), and its static
    ``jax_scores(params, X)`` becomes the jitted per-request scorer. The
    training dataset's feature standardization statistics travel along so
    live feature rows land in the same input distribution.

    Fitted schedulers also expose the factorized scoring hooks
    (``scores_from_factors`` / ``pair_scores_from_factors``), so a
    ``LearnedPolicy`` plugs into the einsum placement / temporal engines
    exactly like the Table-1 oracle: a candidate (region, hour) placement
    is scored by re-featurizing its CI row (and execution hour) — no
    Table-1 sweep anywhere. For CI-linear schedulers (``ci_linear`` on the
    scheduler class, e.g. classification) the candidate axis collapses to
    ONE einsum against probed per-CI-column sensitivities (``ci_sens``);
    non-linear scorers (RBF-GP, quadratic RL features) re-run inference
    per candidate region, still at one feature build per candidate.
    ``infra`` is optional and only needed to self-compute an
    ``EnergyFactors`` batch outside a ``FleetRouter`` (which precomputes
    factors for ``wants_factors`` wrappers).
    """

    params: Any
    score_fn: Callable[[Any, jax.Array], jax.Array]
    feat_mean: jax.Array
    feat_std: jax.Array
    emb_lca: bool = False
    name: str = "learned"
    infra: Any = None
    #: (F, 3) score sensitivity to each standardized feature column, probed
    #: at fit time for CI-linear schedulers; None = generic per-candidate
    #: inference in the pair hook.
    ci_sens: jax.Array | None = None

    @classmethod
    def fit(cls, scheduler, train: SchedulerDataset,
            emb_lca: bool = False, infra: Any = None) -> "LearnedPolicy":
        """Fit ``scheduler`` offline on ``train`` and wrap the fitted
        scorer as a policy. The dataset's feature statistics (and its CI
        normalization, gCO2/kWh over 100) travel along, so live streams
        are featurized exactly as the training rows were; CI-linear
        schedulers additionally get their ``ci_sens`` sensitivities probed
        here for the one-einsum candidate path."""
        if train.feat_mean is None or train.feat_std is None:
            raise ValueError(
                "dataset has no feature statistics — rebuild it with "
                "schedulers.build_dataset (feat_mean/feat_std are required "
                "to featurize live streams)")
        params = jax.tree.map(jnp.asarray, scheduler.fit_params(train))
        ci_sens = None
        if getattr(scheduler, "ci_linear", False):
            # probe the (affine) scorer's per-feature sensitivities once:
            # score(X) = score(0) + X @ sens for a CI-linear scheduler, so
            # candidate CI deltas become one einsum at decision time
            n_feat = int(np.asarray(train.feat_mean).shape[0])
            probes = jnp.concatenate(
                [jnp.zeros((1, n_feat), jnp.float32),
                 jnp.eye(n_feat, dtype=jnp.float32)])
            s = type(scheduler).jax_scores(params, probes)
            ci_sens = s[1:] - s[:1]
        return cls(name=f"learned-{scheduler.name}", params=params,
                   score_fn=type(scheduler).jax_scores,
                   feat_mean=jnp.asarray(train.feat_mean, jnp.float32),
                   feat_std=jnp.asarray(train.feat_std, jnp.float32),
                   emb_lca=emb_lca, infra=infra, ci_sens=ci_sens)

    def _score_rows(self, w, ci, interference, net_slowdown, hour
                    ) -> jax.Array:
        """(N, 3) raw scheduler scores under explicit CI rows + context."""
        X = feature_rows(w, ci, interference, net_slowdown, hour,
                         self.emb_lca)
        X = (X - self.feat_mean) / self.feat_std
        return self.score_fn(self.params, X)

    def scores(self, w, env, avail, *, hour=None):
        return jnp.where(
            avail,
            self._score_rows(w, env.ci, env.interference, env.net_slowdown,
                             hour),
            jnp.inf)

    def scores_from_factors(self, factors, w: Workload, ci: jax.Array,
                            avail: jax.Array,
                            extra_latency: jax.Array | float = 0.0, *,
                            hour: jax.Array | None = None,
                            interference: jax.Array | None = None,
                            net_slowdown: jax.Array | None = None
                            ) -> jax.Array:
        """``scores`` under arbitrary per-request CI rows — the factorized
        placement/temporal hook. With no WAN hop this IS the sweep path
        (same features, same scorer — parity-tested); ``factors`` only
        enters through the hop gate: a candidate whose ``extra_latency``
        breaks an otherwise-QoS-feasible tier is refused outright (+inf),
        matching the oracle's refusal semantics — the learned score itself
        stays feasibility-free, exactly like the sweep path."""
        s = jnp.where(
            avail,
            self._score_rows(w, ci, interference, net_slowdown, hour),
            jnp.inf)
        return _gate_hop_broken(s, factors, w, extra_latency)

    def pair_scores_from_factors(self, factors, w: Workload,
                                 home_ci: jax.Array, cand_ci_dc: jax.Array,
                                 avail: jax.Array,
                                 extra_latency: jax.Array | None = None, *,
                                 hour: jax.Array | None = None,
                                 interference: jax.Array | None = None,
                                 net_slowdown: jax.Array | None = None
                                 ) -> jax.Array:
        """(R, N, 3) ``scores_from_factors`` over candidate regions.
        ``home_ci`` (N, 5) anchors the non-relocating [mobile, edge_net]
        components; ``cand_ci_dc`` (R, N, 3) carries each candidate's
        relocating CI columns. CI-linear schedulers score the home row
        once and add ``delta_ci @ ci_sens`` (one einsum — the learned
        analogue of the oracle's ``op_unit`` einsum); others re-run
        inference per candidate region."""
        if self.ci_sens is not None:
            s0 = self._score_rows(w, home_ci, interference, net_slowdown,
                                  hour)  # (N, 3)
            # features carry ci/100 standardized by feat_std: a candidate
            # differs from home only in the relocating CI columns
            scale = 1.0 / (100.0 * self.feat_std[_CI_DC_COLS])  # (3,)
            delta = (cand_ci_dc - home_ci[None, :, 2:]) * scale  # (R, N, 3)
            s = s0[None] + jnp.einsum("rnc,ct->rnt", delta,
                                      self.ci_sens[_CI_DC_COLS],
                                      precision=jax.lax.Precision.HIGHEST)
        else:
            def one_region(ci_dc):
                ci_mixed = jnp.concatenate([home_ci[:, :2], ci_dc], axis=1)
                return self._score_rows(w, ci_mixed, interference,
                                        net_slowdown, hour)

            s = jax.vmap(one_region)(cand_ci_dc)  # (R, N, 3)
        s = jnp.where(avail[None], s, jnp.inf)
        return _gate_hop_broken(s, factors, w, extra_latency)


# ---------------------------------------------------------------------------
# Capacity-capped routing (CASPER-style per-tier load caps)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CapacityState:
    """Threaded state of a ``CapacityLimiter`` decision.

    ``counts``  (R, 3) int32 — capacity-*admitted* assignments so far: shed
                requests and unroutable requests (no finite-score tier at
                all, e.g. all-False availability) are excluded, because
                neither consumed any cap budget.
    ``shed``    (N,) bool — *routable* requests for which every finite-score
                tier was at cap in their window. They still receive a
                nominal target (the inner policy's top pick) because the
                request must execute *somewhere* — shedding models QoS
                degradation / deferral, and the fleet aggregates report it —
                but they never consume cap. Unroutable requests are NOT shed
                (capacity was never the problem); they take the same
                degenerate fallback the uncapped router gives them, so
                generous caps remain an exact no-op.
    """

    counts: jax.Array
    shed: jax.Array


@dataclasses.dataclass
class CapacityLimiter(RoutingPolicy):
    """Wrap any policy with per-(region, tier) request caps per hourly window.

    This is the PR-2 ``lax.scan``-over-windows formulation, kept as the
    semantics reference: ``repro.serve.placement.PlacementPolicy`` with
    ``adjacency == I`` reproduces it bit-for-bit via segment-rank admission
    (one sort per spill round instead of 24 one-hot cumsums) and extends the
    spill axis across regions — prefer it on hot paths; both are pinned
    head-to-head in ``benchmarks/policy_throughput.py``.

    Each window (default: the 24 hours of the diurnal trace) gets a fresh
    budget of ``caps[r, t]`` requests per (region, tier); ``jnp.inf`` means
    uncapped (the natural setting for ``Target.MOBILE`` — the user's own
    device is not a shared resource). Requests are admitted greedily in
    stream order against the inner policy's preference ranking: a request
    whose best tier is full spills to its next-best tier with a finite score
    (i.e. still feasible+available under the inner policy), and a routable
    request whose every finite-score tier is at cap is shed (see
    ``CapacityState``; requests with no finite-score tier at all bypass
    capacity accounting entirely and keep the uncapped fallback).

    The per-window assignment is vectorized — within a spill round, each
    request's in-window rank among competitors for the same (region, tier)
    column comes from a masked cumulative sum, so a window costs O(N·R·3)
    instead of a million-step sequential scan — and windows are folded with
    ``lax.scan`` carrying the cumulative counts.
    """

    inner: RoutingPolicy
    caps: Any  # array-like (R, 3); jnp.inf = uncapped
    n_windows: int = 24

    def __post_init__(self):
        self._caps = jnp.asarray(self.caps, jnp.float32)
        if self._caps.ndim != 2 or self._caps.shape[1] != N_TARGETS:
            raise ValueError(f"caps must be (n_regions, {N_TARGETS}), got "
                             f"{self._caps.shape}")
        self.name = f"capped-{self.inner.name}"

    def initial_state(self, n_regions: int, n_requests: int) -> CapacityState:
        """Zeroed admission counts (requests per (region, tier)) and an
        all-False shed mask, validated against the cap matrix's regions."""
        if self._caps.shape[0] != n_regions:
            raise ValueError(f"caps cover {self._caps.shape[0]} regions, "
                             f"fleet has {n_regions}")
        return CapacityState(
            counts=jnp.zeros((n_regions, N_TARGETS), jnp.int32),
            shed=jnp.zeros((n_requests,), bool))

    def scores(self, w, env, avail, *, hour=None):
        """The inner policy's scores, untouched — capacity only reorders
        ADMISSION, never preference (same units as the inner policy)."""
        return self.inner.scores(w, env, avail, hour=hour)

    def decide(self, w, env, avail, state, *, region=None, hour=None,
               outputs=None, order=None, inv_order=None, slack=None,
               factors=None, fc_table=None, cap_scale=None, used0=None,
               axis_name=None):
        """(N,) int32 targets under greedy per-window cap admission (see
        the class docstring); generous caps are an exact no-op vs the
        inner policy, and ``PlacementPolicy`` with identity adjacency
        reproduces these decisions bit-for-bit."""
        if axis_name is not None:
            raise NotImplementedError(
                "CapacityLimiter's lax.scan admission walks windows "
                "sequentially per device and cannot reconcile caps across "
                "a sharded stream — use PlacementPolicy (identity "
                "adjacency reproduces CapacityLimiter bit-for-bit) on the "
                "sharded path")
        n = w.flops.shape[0]
        n_cols = self._caps.size
        region = (jnp.zeros((n,), jnp.int32) if region is None
                  else jnp.asarray(region, jnp.int32))
        win = (jnp.zeros((n,), jnp.int32) if hour is None
               else jnp.asarray(hour, jnp.int32) % self.n_windows)
        scores = scores_with_reuse(self.inner, w, env, avail, hour, outputs)
        pref = jnp.argsort(scores, axis=1).astype(jnp.int32)  # best-first
        valid = jnp.isfinite(jnp.take_along_axis(scores, pref, axis=1))
        caps_flat = self._caps.reshape(-1)

        def window(counts, h):
            in_win = win == h
            target = jnp.zeros((n,), jnp.int32)
            placed = jnp.zeros((n,), bool)
            win_counts = jnp.zeros((n_cols,), jnp.float32)
            for k in range(N_TARGETS):  # spill rounds: 1st..3rd choice
                choice = pref[:, k]
                want = in_win & ~placed & valid[:, k]
                col = region * N_TARGETS + choice
                oh = jax.nn.one_hot(col, n_cols,
                                    dtype=jnp.float32) * want[:, None]
                # 1-based arrival rank among this round's competitors for
                # the same (region, tier) column
                rank = jnp.take_along_axis(jnp.cumsum(oh, axis=0),
                                           col[:, None], axis=1)[:, 0]
                fits = want & (win_counts[col] + rank <= caps_flat[col])
                target = jnp.where(fits, choice, target)
                win_counts = win_counts + (oh * fits[:, None]).sum(axis=0)
                placed = placed | fits
            # only *routable* leftovers are capacity-shed; a request with no
            # finite-score tier at all (all-False availability) was never a
            # capacity decision — it takes the uncapped degenerate fallback
            shed_w = in_win & ~placed & valid[:, 0]
            target = jnp.where(in_win & ~placed, pref[:, 0], target)
            counts = counts + win_counts.reshape(
                self._caps.shape).astype(jnp.int32)
            return counts, (jnp.where(in_win, target, 0), shed_w)

        counts, (t_steps, shed_steps) = jax.lax.scan(
            window, state.counts, jnp.arange(self.n_windows))
        # each request sits in exactly one window, so the sum selects it
        targets = t_steps.sum(axis=0).astype(jnp.int32)
        return targets, CapacityState(counts=counts,
                                      shed=shed_steps.any(axis=0))
