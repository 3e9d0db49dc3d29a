"""Geo-temporal placement: joint (region, tier) decisions under capacity.

GreenScale's core claim is that carbon-optimal scheduling is a joint *when
and where* decision. ``CapacityLimiter`` (PR 2) only answers "where" as
tier-within-one-region: hyperscale overflow spills to a worse local tier
even when a neighbouring region is greener. This module makes region a
first-class placement axis:

  * ``PlacementPolicy`` scores every ``(region, tier)`` pair jointly —
    the inner policy's score under each *candidate* region's CI (gathered
    from the fleet's ``CarbonGrid``), times the grid's inter-region
    latency penalty, masked by its adjacency — and admits requests
    greedily against per-(region, tier) hourly-window caps, spilling each
    over-cap request to its next-feasible pair ordered by effective
    carbon. ``adjacency == I`` is tier-only spill and reproduces the
    PR-2 ``CapacityLimiter`` decisions bit-for-bit (parity-tested).
  * Admission uses a *segment-rank* formulation instead of the 24-window
    ``lax.scan`` + per-window one-hot cumsum: the stream is sorted by
    arrival window ONCE (a cheap host-side radix sort the fleet router
    passes in as the ``order`` hint), window boundaries come from one
    ``jnp.searchsorted``, and each spill round computes every request's
    within-(window, pair) arrival rank with a single segmented cumulative
    count — admitted iff ``used[cell] + rank < cap[pair]``. One pass over
    the stream per round replaces 24 × rounds passes, and per-cell
    admission totals fall out of the same prefix sums, so the loop has no
    scatters at all. This is the ROADMAP's segment-rank follow-up to the
    ~13µs/request CapacityLimiter scan cost.

Semantics (identical to ``CapacityLimiter``, with pairs for tiers): each
(window, region, tier) cell has a fresh budget of ``caps[r, t]`` requests;
priority is (spill round, stream order); a routable request whose every
finite-score pair is at cap is shed — it keeps a nominal placement (its
first-choice pair) but consumes no cap; a request with no finite-score
pair at all (e.g. all-False availability) bypasses capacity accounting and
takes the uncapped degenerate fallback on its *home* region.

Two admission programs share the segment-rank core: tier-only mode keeps
the PR-2-parity 3-round preference march (bit-for-bit CapacityLimiter
decisions), while cross-region mode runs *skip-full best-open attempts*
under a ``lax.while_loop`` — each round every unplaced request targets its
best candidate whose cell still has budget via a masked argmin (no
(N, pairs) argsort), a rejected request's cell is provably full afterwards,
and the loop ends only when every unplaced routable request is out of open
cells — exhaustive shed semantics at a fraction of the fixed-round cost
(pinned >=3x placement-path speedup in ``benchmarks/policy_throughput.py``
together with the factorized evaluator below).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import carbon_model
from repro.core.carbon_intensity import CarbonGrid
from repro.core.carbon_model import EnergyFactors, Environment
from repro.core.constants import N_TARGETS
from repro.serve.policy import RoutingPolicy, scores_with_reuse


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlacementState:
    """Threaded state of a ``PlacementPolicy`` decision.

    ``counts``      (R, 3) int32 — capacity-admitted assignments per
                    *executed* (region, tier) pair; shed and unroutable
                    requests are excluded (neither consumed cap budget).
    ``shed``        (N,) bool — routable requests whose every finite-score
                    pair was at cap in their window (see module docstring).
    ``exec_region`` (N,) int32 — the region each request executes in; differs
                    from the home region exactly for cross-region placements
                    (shed requests execute nowhere and report home). The
                    fleet router accounts carbon under THIS region's CI.
                    ``None`` when the grid's adjacency is the identity —
                    execution is always at home, and the sentinel lets the
                    router skip the executed-region re-evaluation entirely.
    ``shed_pair``   (R, 3) int32 — per-pair shed accounting: shed requests
                    keyed by their first-choice (region, tier) pair, i.e.
                    where the demand that could not be placed wanted to run.
    ``admit_rounds`` () int32 — admission rounds run, summed over decisions
                    like ``counts``: the skip-full ``while_loop``'s final
                    trip count, or the static round count of the unrolled
                    programs. Equal on every device of a sharded call (the
                    loop condition is a global any).
    """

    counts: jax.Array
    shed: jax.Array
    exec_region: jax.Array | None
    shed_pair: jax.Array
    admit_rounds: jax.Array


def windowed_segment_ranks(choice: jax.Array, active: jax.Array,
                           cell: jax.Array, starts: jax.Array,
                           ends: jax.Array, n_pairs: int
                           ) -> tuple[jax.Array, jax.Array]:
    """Segment-rank core of one spill round, on a stream ALREADY stably
    sorted by admission segment (ties keep stream order). A segment is an
    arrival window — or a (window, home region) cell in tier-only mode,
    where a request's candidates never leave its home.

    ``choice`` is the in-segment column (width ``n_pairs``), ``cell =
    segment * n_pairs + choice`` the flat capacity cell, and ``starts`` /
    ``ends`` the segment boundary indices in the sorted stream (one
    ``searchsorted``, hoisted out of the round loop). Returns ``(rank,
    totals)``: ``rank[i]`` is the 0-based arrival rank of active row i
    among active rows sharing its cell, and ``totals`` the per-cell active
    count over all cells. One segmented cumulative count over the round's
    (N, n_pairs) one-hot replaces the per-window scan: a row's rank is its
    exclusive prefix count minus the count at its segment's start, and
    per-cell totals fall out of the same prefix sums — no scatters
    anywhere. The prefix counts accumulate per pair COLUMN across the
    whole stream in int32, so ranks stay exact up to 2**31 active rows
    per column per round.
    """
    act_i = active.astype(jnp.int32)
    oh = jax.nn.one_hot(choice, n_pairs, dtype=jnp.int32) * act_i[:, None]
    cs = jnp.cumsum(oh, axis=0)  # inclusive prefix counts, (N, n_pairs)
    prefix = lambda idx: jnp.where(  # cs rows *before* each index, (W, P)
        (idx > 0)[:, None], cs[jnp.maximum(idx - 1, 0)], 0)
    base = prefix(starts).reshape(-1)  # flat (n_windows * n_pairs,)
    # inclusive count at own row minus own contribution minus window base
    own = jnp.take_along_axis(cs, choice[:, None], axis=1)[:, 0]
    rank = own - act_i - base[cell]
    totals = prefix(ends).reshape(-1) - base
    return rank, totals


def device_prefix_ranks(rank: jax.Array, totals: jax.Array, cell: jax.Array,
                        axis_name: str | None
                        ) -> tuple[jax.Array, jax.Array]:
    """Lift one round's local ``(rank, totals)`` to their GLOBAL values when
    the sorted stream is sharded contiguously over a mesh axis.

    Contiguous sharding of the segment-sorted stream means every row on an
    earlier device (by ``lax.axis_index``) precedes every local row in
    stream order, so a row's global within-cell rank is its local rank plus
    the earlier devices' active count for its cell: one ``all_gather`` of
    the per-cell totals to (n_devices, n_cells), an exclusive cumsum over
    the device axis, and a per-row gather. Global per-cell totals are the
    device sum of the same gather (== psum). All int32 counting arithmetic
    — the reconciliation is exact, which is what makes sharded admission
    bit-identical to the single-device program. ``axis_name=None`` is the
    single-device identity. The cross-device step carries the device
    scope ``reconcile``; at one device there is no such step."""
    if axis_name is None:
        return rank, totals
    with jax.named_scope("reconcile"):
        all_totals = jax.lax.all_gather(totals, axis_name)  # (D, n_cells)
        prior = (jnp.cumsum(all_totals, axis=0)
                 [jax.lax.axis_index(axis_name)]
                 - totals)  # exclusive prefix over earlier devices
        return rank + prior[cell], all_totals.sum(axis=0)


def _global_any(pred: jax.Array, axis_name: str | None) -> jax.Array:
    """``pred.any()`` across the mesh axis (identity when unsharded) — the
    sharded admission loops must keep spinning while ANY device still has
    an open-celled contender, or devices would exit the collective loop at
    different trip counts and deadlock. Scope ``reconcile``, as in
    ``device_prefix_ranks``."""
    if axis_name is None:
        return pred
    with jax.named_scope("reconcile"):
        return jax.lax.psum(pred.astype(jnp.int32), axis_name) > 0


@dataclasses.dataclass
class PlacementPolicy(RoutingPolicy):
    """Wrap any policy with joint (region, tier) placement under per-pair
    hourly-window caps and cross-region spill.

    ``caps`` is (R, 3) requests per (region, tier) per window (``jnp.inf`` =
    uncapped). ``grid`` supplies the candidate regions' CI tables and the
    adjacency / latency-penalty matrices; leave it ``None`` to have
    ``FleetRouter`` bind its own grid at construction (the common case — a
    policy must place against the same grid the router routes against).

    The effective score of pair (r', t) for a request homed in r is
    ``inner.scores`` evaluated under region r' CI at the request's hour,
    scaled by ``grid.latency_penalty[r, r']``, or +inf where
    ``grid.adjacency[r, r']`` is False. The penalty is applied sign-aware
    (``s * pen`` for s >= 0, ``s / pen`` otherwise) so it disfavours remote
    execution for negative scores too — learned policies (classification
    logits, log-carbon regressions) produce those; positive scores (the
    oracle family) keep the historical ``s * pen`` bit-for-bit.

    With identity adjacency the policy statically reduces to tier-only
    spill: one home-region scoring (reusing the router's Table-1 evaluation
    via ``scores_from_outputs`` when the inner policy offers it), 3 spill
    rounds, and no executed-region accounting — the segment-rank hot path
    benchmarked against the PR-2 scan in ``benchmarks/policy_throughput.py``.
    """

    inner: RoutingPolicy
    caps: Any  # array-like (R, 3); jnp.inf = uncapped
    grid: CarbonGrid | None = None
    #: capacity windows over the grid's rolling horizon. None (default)
    #: resolves to the horizon length when the grid binds — one window per
    #: ABSOLUTE hour, so a multi-day grid gives day two fresh budgets
    #: (24 on the single-day grid: the historical behaviour, bit-for-bit).
    #: An explicit count must divide the horizon.
    n_windows: int | None = None
    #: score candidate regions via the factorized einsum evaluator when the
    #: inner policy supports it (``scores_from_factors``) — one Table-1
    #: evaluation per batch instead of one sweep per candidate region.
    #: False forces the legacy per-region sweep (the PR-3 program), kept as
    #: the numerics reference and the benchmark baseline.
    factorized: bool = True

    def __post_init__(self):
        self._caps = jnp.asarray(self.caps, jnp.float32)
        if self._caps.ndim != 2 or self._caps.shape[1] != N_TARGETS:
            raise ValueError(f"caps must be (n_regions, {N_TARGETS}), got "
                             f"{self._caps.shape}")
        self.name = f"placed-{self.inner.name}"
        self._factorizable = (self.factorized
                              and hasattr(self.inner, "scores_from_factors"))
        # remember whether the window count is horizon-derived: binding
        # re-resolves it from the bound grid every time, so a resolved
        # value can never be carried stale onto a different-horizon grid
        # (an explicitly configured count is honoured — and validated —
        # as given)
        self._auto_windows = self.n_windows is None
        if self.grid is not None:
            self._check_grid(self.grid)

    def _check_grid(self, grid: CarbonGrid) -> None:
        if grid.n_regions != self._caps.shape[0]:
            raise ValueError(f"caps cover {self._caps.shape[0]} regions, "
                             f"grid has {grid.n_regions}")
        self._horizon_h = grid.horizon_h
        if self._auto_windows:
            # one capacity window per absolute horizon hour: day-two
            # arrivals (and deferrals crossing midnight) charge day-two
            # cells instead of aliasing modulo 24 into day one's budgets
            self.n_windows = self._horizon_h
        if self._horizon_h % self.n_windows != 0:
            raise ValueError(
                f"n_windows must divide the grid horizon "
                f"({self._horizon_h} h) so every capacity window covers a "
                f"whole number of hours, got {self.n_windows}")
        adjacency = np.asarray(grid.adjacency)
        # Legacy-path spill rounds: a request has at most (adjacent regions
        # x feasible tiers) finite pairs, so rounds beyond that never admit.
        self._n_rounds = int(adjacency.sum(axis=1).max()) * N_TARGETS
        # Identity adjacency = tier-only spill: score ONE region per request
        # (its home), run exactly CapacityLimiter's 3 rounds, and tell the
        # router execution never leaves home (exec_region=None), so the hot
        # path pays no cross-region cost it doesn't use.
        self._diag_only = bool((adjacency == np.eye(len(adjacency),
                                                    dtype=bool)).all())
        # Tier-only requests compete only within their own (window, home)
        # segment, so a finer host-side sort lets the round loop run
        # width-3 segmented counts instead of width-(R*3); within a
        # segment all competitors share a home, so stream-order priority
        # (and CapacityLimiter parity) is unchanged. Cross-region cells
        # mix homes — there the sort must stay window-only to keep
        # stream-order priority among competitors from different homes.
        self.stream_order_key = ("window_region" if self._diag_only
                                 else "window")
        self._has_rtt = bool(np.asarray(grid.rtt_s).any())
        # Sparse neighbor-list grids (``CarbonGrid.from_sites`` /
        # ``with_sparse_neighbors``): precompute each home's candidate list
        # [home] + neighbors in ASCENDING region order — local argmin
        # tie-breaking over the gathered (C = K+1) columns then matches the
        # dense program's region-major column order exactly, which is what
        # makes the sparse path bit-identical on an embedded dense grid.
        # Pad slots alias the home region (a safe gather) and are masked
        # invalid. Scoring walks these C columns (O(N·K)); admission maps
        # each local column back to its GLOBAL (region, tier) pair, so the
        # segment-rank machinery (and the sharded reconciliation) runs
        # unchanged on global cells.
        self._sparse = (grid.nbr_idx is not None) and not self._diag_only
        if self._sparse:
            if not self._factorizable:
                raise ValueError(
                    "sparse neighbor-list grids route through the "
                    "factorized einsum scorer — the inner policy offers no "
                    "scores_from_factors (or factorized=False)")
            r = grid.n_regions
            nbr = np.asarray(grid.nbr_idx)
            if nbr.ndim != 2 or nbr.shape[0] != r:
                raise ValueError(f"nbr_idx must be ({r}, K), got {nbr.shape}")
            cand = np.concatenate(
                [np.arange(r, dtype=np.int64)[:, None],
                 np.where(nbr >= 0, nbr.astype(np.int64), r)], axis=1)
            cand.sort(axis=1)  # ascending; pads (value r) land at the end
            valid = cand < r
            rows = np.arange(r)[:, None]
            cand_idx = np.where(valid, cand, rows)
            adj_sparse = np.zeros((r, r), bool)
            adj_sparse[np.repeat(np.arange(r), cand_idx.shape[1]),
                       cand_idx.reshape(-1)] = True
            if not np.array_equal(adj_sparse, adjacency):
                raise ValueError(
                    "grid.nbr_idx disagrees with the dense adjacency — the "
                    "sparse neighbor lists must enumerate exactly the "
                    "off-diagonal True entries of each adjacency row")
            self._cand_idx = jnp.asarray(cand_idx.astype(np.int32))
            self._cand_ok = jnp.asarray(valid)
            self._cand_pen = jnp.asarray(np.asarray(
                grid.latency_penalty)[rows, cand_idx].astype(np.float32))
            self._cand_rtt = jnp.asarray(np.asarray(
                grid.rtt_s)[rows, cand_idx].astype(np.float32))
            # first occurrence of the home id is the genuine home slot
            # (pad aliases sort after every real candidate)
            self._cand_home_slot = jnp.asarray(np.argmax(
                cand_idx == rows, axis=1).astype(np.int32))
            tiers = np.arange(N_TARGETS, dtype=np.int64)
            self._cand_pair = jnp.asarray(
                (cand_idx[:, :, None] * N_TARGETS + tiers).reshape(
                    r, -1).astype(np.int32))
        # The legacy per-region sweep scores through ``inner.scores``, which
        # has no seam for the WAN-hop latency — only the factorized path
        # models rtt_s in the QoS check.
        if not self._diag_only and not self._factorizable and self._has_rtt:
            raise ValueError(
                "grid has a non-zero rtt_s but the inner policy offers no "
                "scores_from_factors (or factorized=False) — the WAN-hop "
                "QoS check needs the factorized evaluator")

    @property
    def wants_factors(self) -> bool:
        """Ask the fleet router for a precomputed ``EnergyFactors`` batch.
        Tier-only (identity-adjacency) placement never needs it — it reuses
        the router's own Table-1 evaluation via the ``outputs`` hint."""
        return self._factorizable and not getattr(self, "_diag_only", True)

    def bind_grid(self, grid: CarbonGrid) -> None:
        """Adopt the fleet's grid — or, when one was set explicitly, verify
        it matches: the policy must place against the same grid the router
        accounts under, or carbon/feasibility silently diverge."""
        if self.grid is None:
            self._check_grid(grid)
            self.grid = grid
            return
        self._check_grid(self.grid)
        if self.grid is grid:
            return
        for field in ("ci_hourly", "ci_mobile", "ci_core", "pue",
                      "adjacency", "latency_penalty", "rtt_s",
                      "ci_forecast", "forecast_sigma_h",
                      "nbr_idx", "nbr_rtt_s"):
            a, b = getattr(self.grid, field), getattr(grid, field)
            same = ((a is None) == (b is None)) and (
                a is None or np.array_equal(np.asarray(a), np.asarray(b)))
            if not same:
                raise ValueError(
                    f"policy grid disagrees with the router's grid on "
                    f"{field!r} — pass the same CarbonGrid to both (or "
                    f"leave the policy's grid unset to adopt the "
                    f"router's)")

    def initial_state(self, n_regions: int, n_requests: int) -> PlacementState:
        """Fresh ``PlacementState`` (zero admitted counts / nothing shed);
        requires a bound grid — admission windows span its horizon."""
        if self._caps.shape[0] != n_regions:
            raise ValueError(f"caps cover {self._caps.shape[0]} regions, "
                             f"fleet has {n_regions}")
        if self.grid is None:
            raise ValueError(
                "PlacementPolicy has no CarbonGrid — pass grid= at "
                "construction or route via a FleetRouter (which binds its "
                "own grid)")
        return PlacementState(
            counts=jnp.zeros((n_regions, N_TARGETS), jnp.int32),
            shed=jnp.zeros((n_requests,), bool),
            exec_region=(None if self._diag_only
                         else jnp.zeros((n_requests,), jnp.int32)),
            shed_pair=jnp.zeros((n_regions, N_TARGETS), jnp.int32),
            admit_rounds=jnp.zeros((), jnp.int32))

    def scores(self, w, env, avail, *, hour=None):
        """The inner policy's home-region scores (same units); placement
        preference lives in ``pair_scores`` / the factorized variants."""
        return self.inner.scores(w, env, avail, hour=hour)

    @jax.named_scope("score")
    def pair_scores(self, w, env, avail, home: jax.Array,
                    hour: jax.Array) -> jax.Array:
        """(N, R, 3) effective scores of every (region, tier) pair: the inner
        score under the candidate region's CI at the request's hour, times
        the home->candidate latency penalty, +inf where not adjacent.

        Only the infrastructure components relocate with the placement: the
        user's device and access-network energy is drawn in the HOME region
        no matter where the request executes, so a candidate's CI row mixes
        home [mobile, edge_net] with the candidate's [edge_dc, core_net,
        hyper_dc]. For the same reason the on-device tier exists only at
        home — remote (region', MOBILE) pairs are structurally +inf.

        Candidates are scored on the grid's FORECAST view
        (``table_forecast`` — the actual table when no forecast is
        attached): the policy plans on what a scheduler could know."""
        table = self.grid.table_forecast  # (R, H, 5)
        ci_all = table[:, hour % table.shape[1], :]  # (R, N, 5)
        home_ci = env.ci  # (N, 5) — the env the router routes/accounts under
        interference, net_slowdown = env.interference, env.net_slowdown

        def one_region(ci_rows):
            ci_mixed = jnp.concatenate([home_ci[:, :2], ci_rows[:, 2:]],
                                       axis=1)
            env_r = Environment(ci=ci_mixed, interference=interference,
                                net_slowdown=net_slowdown)
            return self.inner.scores(w, env_r, avail, hour=hour)

        s = jnp.moveaxis(jax.vmap(one_region)(ci_all), 0, 1)  # (N, R, 3)
        return self._mask_pairs(s, home)

    def _mask_pairs(self, s: jax.Array, home: jax.Array) -> jax.Array:
        """Apply the placement structure to raw (N, R, 3) candidate scores:
        home->candidate latency penalty, +inf where not adjacent, and the
        structural exclusion of remote (region', MOBILE) pairs (the phone
        only exists at home). The penalty (>= 1 off-diagonal) must move a
        score AWAY from being picked whatever its sign, so negative scores
        (learned logits / log-carbon) divide instead of multiply; the
        non-negative branch is the historical ``s * pen``, bit-for-bit."""
        pen = self.grid.latency_penalty[home][:, :, None]  # (N, R, 1)
        adj = self.grid.adjacency[home]  # (N, R)
        n_regions = self._caps.shape[0]
        remote = jnp.arange(n_regions)[None, :] != home[:, None]  # (N, R)
        mobile = (jnp.arange(N_TARGETS) == 0)[None, None, :]
        allowed = adj[:, :, None] & ~(remote[:, :, None] & mobile)
        penalized = jnp.where(s >= 0.0, s * pen, s / pen)
        return jnp.where(allowed, penalized, jnp.inf)

    @jax.named_scope("score")
    def pair_scores_from_factors(self, factors: EnergyFactors, w, env, avail,
                                 home: jax.Array, hour: jax.Array,
                                 fc_table: jax.Array | None = None
                                 ) -> jax.Array:
        """``pair_scores`` on the factorized evaluator: the inner policy's
        einsum scorer under every candidate region's CI row (mixed with the
        home [mobile, edge_net] components, exactly like the sweep) — no
        Table-1 re-evaluation per region — plus the WAN-hop
        ``grid.rtt_s[home, r']`` in each candidate's QoS latency check
        (skipped statically when the grid has no rtt_s anywhere).

        ``fc_table`` is an optional traced (R, H, 5) forecast component
        table (the rolling re-planner passes the current roll); None falls
        back to the grid's own ``table_forecast``, which is the actual
        table when no forecast is attached — the historical behaviour."""
        table = self.grid.table_forecast if fc_table is None else fc_table
        ci_dc = table[..., 2:][:, hour % table.shape[1], :]  # (R, N, 3)
        home_ci = env.ci  # (N, 5)
        extra = None if not self._has_rtt else self.grid.rtt_s.T[:, home]
        s = self._inner_pair_scores(factors, w, home_ci, ci_dc, avail,
                                    extra, hour=hour,
                                    interference=env.interference,
                                    net_slowdown=env.net_slowdown)
        return self._mask_pairs(jnp.moveaxis(s, 0, 1), home)

    def _inner_pair_scores(self, factors, w, home_ci, cand_ci_dc, avail,
                           extra, *, hour=None, interference=None,
                           net_slowdown=None) -> jax.Array:
        """(R, N, 3) candidate scores via the inner policy's vectorized
        ``pair_scores_from_factors`` when it has one, else a vmap of its
        per-region ``scores_from_factors``. ``cand_ci_dc`` carries only the
        relocating [edge_dc, core_net, hyper_dc] CI components; ``hour`` /
        ``interference`` / ``net_slowdown`` are the non-CI scoring context
        feature-based policies (``LearnedPolicy``) need — the execution
        hour here, not the arrival hour, so deferred candidates are scored
        with the features of the hour they would actually run in."""
        vectorized = getattr(self.inner, "pair_scores_from_factors", None)
        if vectorized is not None:
            return vectorized(factors, w, home_ci, cand_ci_dc, avail,
                              extra_latency=extra, hour=hour,
                              interference=interference,
                              net_slowdown=net_slowdown)

        def one_region(ci_rows, ex):
            ci_mixed = jnp.concatenate([home_ci[:, :2], ci_rows], axis=1)
            return self.inner.scores_from_factors(
                factors, w, ci_mixed, avail, extra_latency=ex, hour=hour,
                interference=interference, net_slowdown=net_slowdown)

        if extra is None:
            extra = jnp.zeros((cand_ci_dc.shape[0], home_ci.shape[0]),
                              jnp.float32)
        return jax.vmap(one_region)(cand_ci_dc, extra)

    @jax.named_scope("score")
    def sparse_pair_scores_from_factors(self, factors, w, env, avail,
                                        home: jax.Array, hour: jax.Array,
                                        fc_table: jax.Array | None = None
                                        ) -> jax.Array:
        """``pair_scores_from_factors`` on the gathered neighbor lists:
        (N, C, 3) scores over each request's C = K+1 candidate sites
        (``_cand_idx[home]`` — home plus sparse neighbors, ascending)
        instead of all R regions, so scoring cost is O(N·K). Per candidate
        row the einsum is arithmetic-identical to the dense program's row
        for that region — the parity the sparse tests pin bit-for-bit."""
        table = self.grid.table_forecast if fc_table is None else fc_table
        h = table.shape[1]
        cand_r = self._cand_idx[home]  # (N, C)
        ci_dc = table[..., 2:][cand_r, (hour % h)[:, None]]  # (N, C, 3)
        ci_dc = jnp.moveaxis(ci_dc, 0, 1)  # (C, N, 3)
        extra = None if not self._has_rtt else self._cand_rtt[home].T
        s = self._inner_pair_scores(factors, w, env.ci, ci_dc, avail,
                                    extra, hour=hour,
                                    interference=env.interference,
                                    net_slowdown=env.net_slowdown)
        return self._mask_sparse(jnp.moveaxis(s, 0, 1), home, cand_r)

    def _mask_sparse(self, s: jax.Array, home: jax.Array,
                     cand_r: jax.Array) -> jax.Array:
        """``_mask_pairs`` on the gathered candidate axis: the same
        sign-aware latency penalty, +inf at pad slots (``_cand_ok`` False)
        and at remote (site', MOBILE) columns — identical float values to
        the dense mask at each candidate's global column."""
        pen = self._cand_pen[home][:, :, None]  # (N, C, 1)
        ok = self._cand_ok[home]  # (N, C)
        mobile = (jnp.arange(N_TARGETS) == 0)[None, None, :]
        remote = cand_r != home[:, None]  # (N, C)
        allowed = ok[:, :, None] & ~(remote[:, :, None] & mobile)
        penalized = jnp.where(s >= 0.0, s * pen, s / pen)
        return jnp.where(allowed, penalized, jnp.inf)

    def _use_factors(self, factors) -> bool:
        """Can this decide() call run the factorized program? Needs an
        inner-policy einsum scorer plus either router-provided factors or
        an ``inner.infra`` to compute them from (a ``LearnedPolicy``
        carries the attribute but may hold None — fit with ``infra=`` to
        enable self-computed factors outside a FleetRouter)."""
        return self._factorizable and (
            factors is not None
            or getattr(self.inner, "infra", None) is not None)

    def _cross_scores_factorized(self, factors, w, env, avail, home, hr,
                                 fc_table=None):
        """(N, R, 3) candidate-pair scores on the einsum evaluator,
        computing factors here if the router didn't pass them."""
        if factors is None:
            factors = carbon_model.energy_factors_batch(
                w, self.inner.infra, env.interference, env.net_slowdown)
        return self.pair_scores_from_factors(factors, w, env, avail,
                                             home, hr, fc_table=fc_table)

    def _to_stream_order(self, n, win, home, order, inv_order):
        """Resolve the host-provided stream-order hint (or fall back to an
        in-jit argsort) and its inverse permutation."""
        n_regions = self._caps.shape[0]
        if order is None:  # no host-provided hint (e.g. GreenScaleRouter)
            order = jnp.argsort(
                win * n_regions + home if self._diag_only else win)
            inv_order = None
        else:
            order = jnp.asarray(order, jnp.int32)
        if inv_order is None:
            # inverse permutation via scatter-set: ~4x cheaper than argsort
            inv = jnp.zeros((n,), jnp.int32).at[order].set(
                jnp.arange(n, dtype=jnp.int32))
        else:
            inv = jnp.asarray(inv_order, jnp.int32)
        return order, inv

    def _caps_runtime(self, cap_scale) -> jax.Array:
        """(R, 3) effective caps: the configured caps scaled by ``cap_scale``
        — a per-region (R,) multiplier (the rolling re-planner's emissions
        budget) or a full (R, 3) per-(region, tier) matrix (the
        ``WorkerPool`` live-slot seam: caps of 1.0 turn the scale into the
        live slot count itself). ``None`` = the configured caps,
        bit-for-bit. The ndim branch is host-static, so both shapes share
        one compiled program per shape."""
        if cap_scale is None:
            return self._caps
        cs = jnp.asarray(cap_scale, jnp.float32)
        return self._caps * (cs[:, None] if cs.ndim == 1 else cs)

    def decide(self, w, env, avail, state, *, region=None, hour=None,
               outputs=None, order=None, inv_order=None, slack=None,
               factors=None, fc_table=None, cap_scale=None, used0=None,
               axis_name=None):
        """(N,) int32 tier targets + ``PlacementState`` under segment-rank
        (region, tier) admission. Parity anchors: identity adjacency
        reproduces ``CapacityLimiter`` decisions bit-for-bit; sharded
        streams (``axis_name``) reconcile to the single-device program
        bit-identically; ``cap_scale=None`` uses the configured caps
        (requests per cell per window) unchanged."""
        n = w.flops.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        if n == 0:
            return jnp.zeros((0,), jnp.int32), state
        home = (jnp.zeros((n,), jnp.int32) if region is None
                else jnp.asarray(region, jnp.int32))
        hr = (jnp.zeros((n,), jnp.int32) if hour is None
              else jnp.asarray(hour, jnp.int32))
        win = hr % self.n_windows
        order, inv = self._to_stream_order(n, win, home, order, inv_order)

        caps_rt = self._caps_runtime(cap_scale)
        if self._diag_only:
            # Tier-only spill: the home region is the only candidate. The
            # diagonal latency penalty scales a request's whole row by one
            # positive factor, which never reorders it — skip the multiply
            # so the scores stay bit-identical to CapacityLimiter's.
            with jax.named_scope("score"):
                s = scores_with_reuse(self.inner, w, env, avail, hour,
                                      outputs)  # (N, 3)
            return self._decide_diag(s, win, home, order, inv, state,
                                     caps_rt, used0, axis_name)
        if getattr(self, "_sparse", False):
            # gathered O(N·K) scoring; admission on global (region, tier)
            # cells via the per-column pair map
            if not self._use_factors(factors):
                raise ValueError(
                    "sparse neighbor-list grids need EnergyFactors — route "
                    "via a FleetRouter (which precomputes them) or give "
                    "the inner policy an infra")
            if factors is None:
                factors = carbon_model.energy_factors_batch(
                    w, self.inner.infra, env.interference, env.net_slowdown)
            s = self.sparse_pair_scores_from_factors(
                factors, w, env, avail, home, hr,
                fc_table=fc_table).reshape(n, -1)
            return self._decide_cross(s, win, home, order, inv, state,
                                      caps_rt, used0, axis_name,
                                      cand_pair=self._cand_pair)
        if self._use_factors(factors):
            s = self._cross_scores_factorized(
                factors, w, env, avail, home, hr,
                fc_table=fc_table).reshape(n, n_pairs)
            return self._decide_cross(s, win, home, order, inv, state,
                                      caps_rt, used0, axis_name)
        # non-factorizable inner policy: the verbatim PR-3 program (one
        # Table-1 sweep per candidate region, fixed-round admission). The
        # sweep has no rtt_s seam, so a WAN-hop grid must not silently
        # degrade here — a factorizable-but-factorless inner (a
        # LearnedPolicy fit without infra, outside a FleetRouter) would
        # otherwise place hop-broken remotes the gate exists to refuse.
        if self._has_rtt:
            raise ValueError(
                "grid has a non-zero rtt_s but no EnergyFactors are "
                "available for the WAN-hop QoS gate — route via a "
                "FleetRouter (which precomputes factors) or give the "
                "inner policy an infra (LearnedPolicy.fit(..., infra=))")
        s = self.pair_scores(w, env, avail, home, hr).reshape(n, n_pairs)
        return self._decide_cross_legacy(s, win, home, order, inv, state,
                                         caps_rt, used0, axis_name)

    @jax.named_scope("admit")
    def _decide_diag(self, s, win, home, order, inv, state,
                     caps_rt=None, used0=None, axis_name=None):
        """Tier-only admission: the PR-2/PR-3 segment-rank program,
        unchanged — 3 unrolled spill rounds marching each request down its
        preference list, bit-for-bit CapacityLimiter parity. ``caps_rt``
        (None = the configured caps) and ``used0`` (None = fresh cells) are
        the runtime-capacity seams of the serving loop. ``axis_name`` names
        the mesh axis the sorted stream is sharded over (None = unsharded):
        each round's local ranks/totals are lifted to global values by
        ``device_prefix_ranks`` before the capacity comparison, so the
        replicated ``used`` ledger advances identically on every device."""
        n = s.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        if caps_rt is None:
            caps_rt = self._caps
        # Admission segments: (window, home) cells of width 3 — all of a
        # request's candidate cells live in its own segment. The flat cell
        # id is win * n_pairs + home * 3 + tier, so ``used`` / ``caps``
        # indexing matches the cross-region mode.
        win_s, home_s, s_s = win[order], home[order], s[order]
        # Best-first preference; stable argsort breaks score ties by tier
        # index, matching CapacityLimiter's tier order.
        pref_s = jnp.argsort(s_s, axis=1).astype(jnp.int32)
        valid_s = jnp.isfinite(jnp.take_along_axis(s_s, pref_s, axis=1))
        width = N_TARGETS
        seg_s = win_s * n_regions + home_s
        n_segments = self.n_windows * n_regions
        col_base_s = home_s * N_TARGETS  # pref_s columns are tiers
        starts = jnp.searchsorted(seg_s, jnp.arange(n_segments))
        ends = jnp.concatenate([starts[1:], jnp.array([n])])
        caps_flat = caps_rt.reshape(-1)
        caps_cell = jnp.tile(caps_flat, self.n_windows)

        used_init = (jnp.zeros((self.n_windows * n_pairs,), jnp.float32)
                     if used0 is None
                     else jnp.asarray(used0, jnp.float32).reshape(-1))
        used = used_init
        placed = jnp.zeros((n,), bool)
        exec_pair = jnp.zeros((n,), jnp.int32)
        for k in range(N_TARGETS):
            choice = pref_s[:, k]
            active = valid_s[:, k] & ~placed
            col = col_base_s + choice  # flat (region, tier) pair
            cell = seg_s * width + choice  # == win * n_pairs + col
            rank, totals = windowed_segment_ranks(
                choice, active, cell, starts, ends, width)
            rank, totals = device_prefix_ranks(rank, totals, cell, axis_name)
            # 1-based rank vs <= cap, exactly CapacityLimiter's comparison —
            # fractional caps admit floor(cap) either way
            fits = active & (used[cell] + rank + 1.0 <= caps_flat[col])
            exec_pair = jnp.where(fits, col, exec_pair)
            placed = placed | fits
            # ranks are contiguous per cell, so the admitted count is just
            # min(remaining integral budget, contenders) — no scatter
            # needed; the floor keeps ``used`` integral under fractional
            # caps (matching the per-request admissions above)
            used = used + jnp.minimum(
                jnp.maximum(jnp.floor(caps_cell - used), 0.0), totals)

        # Only *routable* leftovers are capacity-shed; their nominal
        # placement is the first-choice pair. A request with no finite-score
        # pair at all was never a capacity decision — it takes the uncapped
        # degenerate fallback on its HOME region (argmin of an all-inf row
        # is MOBILE, matching the uncapped router).
        shed_s = valid_s[:, 0] & ~placed
        first_col_s = col_base_s + pref_s[:, 0]  # first-choice flat pair
        fb_pair = jnp.where(valid_s[:, 0], first_col_s,
                            col_base_s + jnp.argmin(
                                s_s, axis=1).astype(jnp.int32))
        exec_pair = jnp.where(placed, exec_pair, fb_pair)

        shed = shed_s[inv]
        targets = (exec_pair % N_TARGETS).astype(jnp.int32)[inv]
        # ``used`` advanced by GLOBAL totals, so counts are already the
        # fleet-wide ledger (replicated when sharded); shed is per-row and
        # the shed_pair histogram needs the cross-device sum
        counts = (used - used_init).reshape(
            self.n_windows, n_regions, N_TARGETS).sum(axis=0)
        shed_pair = (jax.nn.one_hot(first_col_s, n_pairs, dtype=jnp.int32)
                     * shed_s[:, None]).sum(axis=0).reshape(
            n_regions, N_TARGETS)
        if axis_name is not None:
            shed_pair = jax.lax.psum(shed_pair, axis_name)
        return targets, PlacementState(
            counts=state.counts + counts.astype(jnp.int32),
            shed=shed,
            # tier-only spill never leaves home: the None sentinel lets the
            # router skip the executed-region accounting entirely
            exec_region=None,
            shed_pair=state.shed_pair + shed_pair,
            admit_rounds=state.admit_rounds + N_TARGETS)

    @jax.named_scope("admit")
    def _decide_cross(self, s, win, home, order, inv, state,
                      caps_rt=None, used0=None, axis_name=None,
                      cand_pair=None):
        """Cross-region admission: skip-full best-open attempts under a
        ``lax.while_loop``. Each round every unplaced request targets its
        best candidate whose cell still has budget (a masked argmin — no
        (N, pairs) argsort anywhere) and competes by stream order. A
        rejected request's cell is provably full afterwards (the round
        admits exactly the remaining budget), so every round retires at
        least one cell per rejected request and the loop terminates with
        the exact shed semantics — a routable request is shed iff every
        finite-score cell is at cap — without a fixed round count. Priority
        is (attempt round, stream order within the window). ``caps_rt`` /
        ``used0`` are the runtime-capacity seams (None = configured caps,
        fresh cells).

        ``cand_pair`` is the sparse-grid seam: an (R, C·3) int32 map from
        each home's LOCAL score column to its GLOBAL (region, tier) pair.
        ``s`` then has C·3 gathered columns per row, but ranks, the
        capacity ledger, and the open-cell test all run on global cells —
        the admission machinery (and its sharded reconciliation) is
        untouched. Local columns are in ascending global-pair order, so
        argmin tie-breaking matches the dense program. None = dense: the
        column index IS the pair."""
        n = s.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        if caps_rt is None:
            caps_rt = self._caps
        win_s, home_s, s_s = win[order], home[order], s[order]
        finite_s = jnp.isfinite(s_s)  # (N, width)
        routable = finite_s.any(axis=1)
        # ties break by column index (region-major, tier-minor), matching
        # the stable-argsort preference of the tier-only mode
        col_pair_s = None if cand_pair is None else cand_pair[home_s]
        to_pair = (lambda col: col if col_pair_s is None
                   else jnp.take_along_axis(
                       col_pair_s, col[:, None], axis=1)[:, 0])
        first_col = to_pair(jnp.argmin(s_s, axis=1).astype(jnp.int32))
        home_row_s = None
        if col_pair_s is not None:
            c = s_s.shape[1] // N_TARGETS
            home_row_s = jnp.take_along_axis(
                s_s.reshape(n, c, N_TARGETS),
                self._cand_home_slot[home_s][:, None, None],
                axis=1)[:, 0]
        seg_s = win_s
        starts = jnp.searchsorted(seg_s, jnp.arange(self.n_windows))
        ends = jnp.concatenate([starts[1:], jnp.array([n])])
        caps_flat = caps_rt.reshape(-1)
        caps_cell = jnp.tile(caps_flat, self.n_windows)
        limit = self.n_windows * n_pairs + 1  # closable cells + 1

        def open_mask(used, placed):
            """(N, width) — open-celled finite candidates of unplaced rows.
            Its any() is the loop condition: empty means every unplaced
            routable row is out of open cells, i.e. shed."""
            open_flat = jnp.floor(caps_cell - used) >= 1.0
            if col_pair_s is None:
                open_s = open_flat.reshape(self.n_windows, n_pairs)[win_s]
            else:
                open_s = open_flat[win_s[:, None] * n_pairs + col_pair_s]
            return open_s & finite_s & ~placed[:, None]

        # the loop condition must agree across devices (the body runs
        # collectives), so the continue flag is computed IN the body with a
        # psum-any and carried — a device with no local contenders keeps
        # spinning while any other still has one
        def cond(carry):
            go, _, _, _, _, k = carry
            return go & (k < limit)

        def body(carry):
            _, mask, used, placed, exec_pair, k = carry
            active = mask.any(axis=1)
            choice = to_pair(jnp.argmin(jnp.where(mask, s_s, jnp.inf),
                                        axis=1).astype(jnp.int32))
            cell = seg_s * n_pairs + choice
            rank, totals = windowed_segment_ranks(
                choice, active, cell, starts, ends, n_pairs)
            rank, totals = device_prefix_ranks(rank, totals, cell, axis_name)
            fits = active & (used[cell] + rank + 1.0 <= caps_flat[choice])
            exec_pair = jnp.where(fits, choice, exec_pair)
            placed = placed | fits
            used = used + jnp.minimum(
                jnp.maximum(jnp.floor(caps_cell - used), 0.0), totals)
            # rejected rows lost their target cell (now full); the carried
            # next-round mask either re-aims them or retires them
            mask = open_mask(used, placed)
            return (_global_any(mask.any(), axis_name), mask, used, placed,
                    exec_pair, k + 1)

        used_init = (jnp.zeros((self.n_windows * n_pairs,), jnp.float32)
                     if used0 is None
                     else jnp.asarray(used0, jnp.float32).reshape(-1))
        placed0 = jnp.zeros((n,), bool)
        mask0 = open_mask(used_init, placed0)
        _, _, used, placed, exec_pair, rounds = jax.lax.while_loop(
            cond, body,
            (_global_any(mask0.any(), axis_name), mask0, used_init, placed0,
             jnp.zeros((n,), jnp.int32), jnp.zeros((), jnp.int32)))
        return self._finalize_cross(s_s, home_s, routable, first_col,
                                    placed, exec_pair, used, inv, state,
                                    rounds, used_init, axis_name,
                                    home_row_s=home_row_s)

    @jax.named_scope("admit")
    def _finalize_cross(self, s_s, home_s, routable, first_col, placed,
                        exec_pair, used, inv, state, rounds, used_init=None,
                        axis_name=None, home_row_s=None):
        """Shared shed/fallback + back-to-stream-order tail of both
        cross-region admission programs (``rounds``: the admission rounds
        the program ran). Only *routable* leftovers are
        capacity-shed; their nominal placement is the first-choice pair. A
        request with no finite-score pair at all was never a capacity
        decision — it takes the uncapped degenerate fallback on its HOME
        region (argmin of an all-inf row is MOBILE, matching the uncapped
        router). ``home_row_s`` carries the pre-gathered (N, 3) home-tier
        scores when ``s_s``'s columns are a sparse candidate list (the
        home column index is then per-row); None = dense columns."""
        n = s_s.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        shed_s = routable & ~placed
        if home_row_s is None:
            home_row_s = jnp.take_along_axis(
                s_s.reshape(n, n_regions, N_TARGETS),
                home_s[:, None, None], axis=1)[:, 0]  # (N, 3)
        fb_pair = jnp.where(routable, first_col,
                            home_s * N_TARGETS + jnp.argmin(
                                home_row_s, axis=1).astype(jnp.int32))
        exec_pair = jnp.where(placed, exec_pair, fb_pair)

        # --- back to stream order + aggregates ----------------------------
        shed = shed_s[inv]
        # a shed request executes nowhere — report its HOME region (its
        # nominal target tier keeps the first-choice pair's tier)
        exec_region = jnp.where(shed_s, home_s,
                                exec_pair // N_TARGETS)[inv]
        targets = (exec_pair % N_TARGETS).astype(jnp.int32)[inv]
        if used_init is not None:
            used = used - used_init
        counts = used.reshape(
            self.n_windows, n_regions, N_TARGETS).sum(axis=0)
        shed_pair = (jax.nn.one_hot(first_col, n_pairs, dtype=jnp.int32)
                     * shed_s[:, None]).sum(axis=0).reshape(
            n_regions, N_TARGETS)
        if axis_name is not None:
            shed_pair = jax.lax.psum(shed_pair, axis_name)
        return targets, PlacementState(
            counts=state.counts + counts.astype(jnp.int32),
            shed=shed,
            exec_region=exec_region,
            shed_pair=state.shed_pair + shed_pair,
            admit_rounds=state.admit_rounds + rounds)

    @jax.named_scope("admit")
    def _decide_cross_legacy(self, s, win, home, order, inv, state,
                             caps_rt=None, used0=None, axis_name=None):
        """The PR-3 cross-region admission, kept verbatim for inner
        policies without a factorized scorer (and as the benchmark's
        baseline program): best-first preference via a stable (N, pairs)
        argsort, then ``adjacency degree x 3`` fixed spill rounds marching
        each request down its preference list. Priority (spill round,
        stream order); same shed/fallback semantics as ``_decide_cross``."""
        n = s.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        if caps_rt is None:
            caps_rt = self._caps
        win_s, home_s, s_s = win[order], home[order], s[order]
        pref_s = jnp.argsort(s_s, axis=1).astype(jnp.int32)
        valid_s = jnp.isfinite(jnp.take_along_axis(s_s, pref_s, axis=1))
        seg_s = win_s
        starts = jnp.searchsorted(seg_s, jnp.arange(self.n_windows))
        ends = jnp.concatenate([starts[1:], jnp.array([n])])
        caps_flat = caps_rt.reshape(-1)
        caps_cell = jnp.tile(caps_flat, self.n_windows)

        used_init = (jnp.zeros((self.n_windows * n_pairs,), jnp.float32)
                     if used0 is None
                     else jnp.asarray(used0, jnp.float32).reshape(-1))
        used = used_init
        placed = jnp.zeros((n,), bool)
        exec_pair = jnp.zeros((n,), jnp.int32)
        n_rounds = min(self._n_rounds, n_pairs)
        for k in range(n_rounds):
            choice = pref_s[:, k]
            active = valid_s[:, k] & ~placed
            cell = seg_s * n_pairs + choice
            rank, totals = windowed_segment_ranks(
                choice, active, cell, starts, ends, n_pairs)
            rank, totals = device_prefix_ranks(rank, totals, cell, axis_name)
            fits = active & (used[cell] + rank + 1.0 <= caps_flat[choice])
            exec_pair = jnp.where(fits, choice, exec_pair)
            placed = placed | fits
            used = used + jnp.minimum(
                jnp.maximum(jnp.floor(caps_cell - used), 0.0), totals)

        return self._finalize_cross(s_s, home_s, valid_s[:, 0], pref_s[:, 0],
                                    placed, exec_pair, used, inv, state,
                                    jnp.int32(n_rounds), used_init,
                                    axis_name)
