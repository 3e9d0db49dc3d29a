"""Temporal deferral: time-shifted (region, tier, hour) placement.

GreenScale's claim is that carbon-optimal scheduling exploits *when* as well
as *where* energy is clean. ``PlacementPolicy`` (PR 3) answers "where" —
every request still executes in its arrival hour. This module adds the other
axis (CASPER's deferral, CarbonEdge's joint spatio-temporal decision): a
deadline-tagged request may execute in ANY hour from arrival to
``arrival + slack``, scored by that hour's CI from the fleet's
``CarbonGrid``, so delay-tolerant batch-class work rides the solar dip
instead of the evening gas peak.

  * ``TemporalPolicy`` scores every ``(defer d, region r', tier t)``
    candidate — the inner policy's factorized einsum score under region r''s
    CI at hour ``arrival + d`` (home device/access-network components billed
    at the home region, same hour), times the grid's latency penalty, with
    the WAN-hop ``rtt_s`` in the QoS check — and admits greedily against
    per-(region, tier, hour) caps. Preference is best-first over the joint
    candidate list, so a request spills first in time (a greener feasible
    hour at home outranks a penalized remote pair), then in space
    (adjacency), and is shed only when every candidate cell within its
    deadline is full.
  * Admission reuses the segment-rank machinery: the stream stays sorted by
    arrival window, the per-round choice column gains the candidate-hour
    dimension (width ``(S+1) x pairs``), and cross-window contention — a
    deferred request competes in a LATER window's cell — is resolved by a
    per-round prior-count matrix: each arrival window's per-(defer, pair)
    totals are shifted onto their execution cells and prefix-summed over
    arrival windows, so a row's global rank is its within-window rank plus
    the earlier-window contenders of its cell. Priority is (spill round,
    arrival window, stream order); no scatters anywhere.
  * Scoring runs on the factorized evaluator (``carbon_model.EnergyFactors``)
    exclusively: one Table-1 evaluation per batch, every candidate hour an
    einsum against ``CarbonGrid.table``. The inner policy must expose
    ``scores_from_factors`` — the Table-1 oracle family does, and so do
    fitted ``LearnedPolicy`` schedulers (their features are CI rows plus
    CI-free workload context, so candidate (region, hour) placements are
    re-featurized — an einsum for CI-linear models — instead of re-swept).
  * The time axis is the grid's rolling multi-day horizon: candidate hours
    and capacity windows index ABSOLUTE hours, so deferral across midnight
    is scored at day two's CI and charged to day two's budgets — a
    repeated-diurnal multi-day grid reproduces the single-day decisions
    whenever no deadline window crosses midnight (parity-tested), and
    differs exactly where the old modulo-24 wrap aliased day two into
    day one.

Zero slack degenerates to ``PlacementPolicy`` exactly: only ``d = 0``
candidates are finite, the prior-count matrix is empty, and the decisions
reproduce the PR-3 placement bit-for-bit (parity-tested).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import carbon_model
from repro.core.constants import HOURS_PER_DAY, N_TARGETS
from repro.serve.placement import (
    PlacementPolicy,
    _global_any,
    device_prefix_ranks,
    windowed_segment_ranks,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TemporalState:
    """Threaded state of a ``TemporalPolicy`` decision.

    ``counts``      (R, 3) int32 — capacity-admitted assignments per executed
                    (region, tier) pair, summed over execution windows.
    ``shed``        (N,) bool — routable requests whose every candidate
                    (defer, region, tier) cell within their deadline was
                    full.
    ``exec_region`` (N,) int32 — executing region (home for shed rows).
    ``shed_pair``   (R, 3) int32 — shed demand keyed by first-choice pair.
    ``exec_hour``   (N,) int32 — absolute hour of the grid's horizon the
                    request executes in (arrival hour + ``defer_hours``;
                    == the arrival hour for undeferred, shed, and
                    unroutable rows; past 23 on a multi-day grid). The
                    fleet router accounts carbon under THIS hour's CI.
    ``defer_hours`` (N,) int32 — hours deferred past arrival; always within
                    ``[0, slack]`` (property-tested).
    ``admit_rounds`` () int32 — admission rounds run (the ``while_loop``'s
                    final trip count), summed over decisions like
                    ``counts``.
    """

    counts: jax.Array
    shed: jax.Array
    exec_region: jax.Array
    shed_pair: jax.Array
    exec_hour: jax.Array
    defer_hours: jax.Array
    admit_rounds: jax.Array


@dataclasses.dataclass
class TemporalPolicy(PlacementPolicy):
    """Joint (region, tier, hour) placement under per-cell caps.

    Extends ``PlacementPolicy`` (same caps/grid validation, same spill
    topology) with the deferral axis: requests carry a per-request ``slack``
    (hours past arrival they may still execute, clipped to
    ``max_defer_h``) and every candidate hour is scored at that hour's CI.

    ``max_defer_h`` is the static deferral horizon (bounds the candidate
    enumeration; must be < ``n_windows`` so distinct defers land in distinct
    windows). On a multi-day grid the windows span the grid's rolling
    horizon (one per absolute hour by default), so a deferral window that
    crosses midnight is scored at DAY TWO's CI and admitted against day
    two's capacity cells — no modulo-24 aliasing into day one's spent
    budgets, and ``max_defer_h`` may exceed the hours left in the arrival
    day. The horizon tail is NON-WRAPPING: candidate hours past the
    grid's last hour are refused (masked +inf) instead of aliasing to
    hour 0, so a tail arrival whose deadline extends past the horizon
    simply has fewer candidates — it executes earlier or is shed, never
    wrapped into hour 0's CI and budgets, and no guard-day padding is
    needed (that convention is retired). Candidate hours are scored on
    the grid's FORECAST view (``table_forecast``; the actual table when
    no forecast is attached), optionally with a ``risk_lambda`` penalty
    that inflates forecast-driven CI components by ``1 + risk_lambda *
    forecast_sigma_h * sqrt(defer)`` — a mean-plus-lambda-std score that
    shrinks the preference for far-out (noisier) candidate hours;
    ``risk_lambda = 0`` (or a forecast-free grid) scores bit-identically
    to the error-blind engine (parity-tested).
    Admission runs skip-full best-open attempts under a
    ``lax.while_loop`` (same machinery as the cross-region
    ``PlacementPolicy``): exhaustive — a routable request is shed iff every
    candidate cell within its deadline is at cap.
    """

    max_defer_h: int = 12
    #: forecast-error risk aversion: weight of the per-defer
    #: ``sigma * sqrt(d)`` CI inflation in candidate scores (0 = blind).
    risk_lambda: float = 0.0

    def __post_init__(self):
        super().__post_init__()
        self.name = f"temporal-{self.inner.name}"
        if not self._factorizable:
            raise ValueError(
                "TemporalPolicy scores candidate hours via the factorized "
                "evaluator — the inner policy must expose "
                "scores_from_factors (OraclePolicy and LearnedPolicy do) "
                "and factorized must stay True")
        if self.n_windows is not None:
            self._check_windows(self.n_windows)

    def _check_windows(self, n_windows: int) -> None:
        """Window-count checks that don't need the grid: an explicit count
        is validated eagerly at construction, the horizon-derived default
        when the grid binds."""
        if (HOURS_PER_DAY % n_windows != 0
                and n_windows % HOURS_PER_DAY != 0):
            raise ValueError(
                f"n_windows must divide {HOURS_PER_DAY} (sub-daily "
                f"windows) or be a multiple of it (multi-day horizons) so "
                f"deferred hours map consistently onto capacity windows, "
                f"got {n_windows}")
        if not 0 <= self.max_defer_h < n_windows:
            raise ValueError(
                f"max_defer_h must be in [0, n_windows), got "
                f"{self.max_defer_h} with n_windows={n_windows}")

    def _check_grid(self, grid) -> None:
        super()._check_grid(grid)  # resolves a None n_windows -> horizon
        self._check_windows(self.n_windows)

    @property
    def wants_factors(self) -> bool:
        """Temporal scoring always needs the factorized evaluator — even
        tier-only deferral re-scores every candidate hour."""
        return True

    def initial_state(self, n_regions: int, n_requests: int) -> TemporalState:
        """Fresh ``TemporalState``: the placement fields plus zeroed
        ``exec_hour`` / ``defer_hours`` (absolute horizon hours)."""
        base = super().initial_state(n_regions, n_requests)
        return TemporalState(
            counts=base.counts,
            shed=base.shed,
            # deferral moves the execution HOUR even at home, so the router
            # always needs the executed-accounting path (no None sentinel)
            exec_region=jnp.zeros((n_requests,), jnp.int32),
            shed_pair=base.shed_pair,
            exec_hour=jnp.zeros((n_requests,), jnp.int32),
            defer_hours=jnp.zeros((n_requests,), jnp.int32),
            admit_rounds=base.admit_rounds)

    @jax.named_scope("score")
    def candidate_scores(self, factors, w, env, avail, home: jax.Array,
                         hr: jax.Array,
                         fc_table: jax.Array | None = None) -> jax.Array:
        """Scores of every (defer[, region], tier) candidate: the inner
        policy's factorized score under the candidate region's CI at hour
        ``arrival + d`` — home [mobile, edge_net] components at the HOME
        region's CI of that same hour (the device draws energy when the
        work actually runs) — masked/penalized like ``pair_scores``.
        (S+1, N, R, 3) with cross-region spill; (S+1, N, 3) in tier-only
        mode, where home is the only candidate and the adjacency/penalty/
        remote-mobile masks are no-ops, so only the home row is scored.
        Candidate hours index the GRID HORIZON absolutely: on a multi-day
        grid a midnight-crossing defer reads day two's CI rows, and hours
        past the horizon's last hour are clamped to it here — ``decide``
        masks those candidates out entirely (the non-wrapping tail), so
        the clamp only keeps gathers in bounds. CI rows come from the
        grid's FORECAST view (``fc_table`` when the rolling re-planner
        passes one, else ``table_forecast``), risk-inflated per defer
        when ``risk_lambda`` and the grid's ``forecast_sigma_h`` are both
        non-zero. ``env`` supplies the non-CI scoring context
        (interference / net_slowdown) feature-based inner policies need;
        each candidate is scored with its own execution hour."""
        table = (self.grid.table_forecast if fc_table is None
                 else fc_table)  # (R, H, 5)
        table_dc = table[..., 2:]  # relocating [edge_dc, core_net, hyper_dc]
        sparse = getattr(self, "_sparse", False)
        cand_r = self._cand_idx[home] if sparse else None  # (N, C)
        extra = (None if not self._has_rtt else
                 (self._cand_rtt[home].T if sparse
                  else self.grid.rtt_s.T[:, home]))
        ctx = dict(interference=env.interference,
                   net_slowdown=env.net_slowdown)
        sigma = float(self.grid.forecast_sigma_h)
        lam = float(self.risk_lambda)
        risky = sigma > 0.0 and lam != 0.0  # host-static: zero-risk path
        # compiles the historical program
        S = self.max_defer_h

        def scores_at(he_d, rscale):  # (N,) absolute exec hour, () risk
            home_ci = table[home, he_d]  # (N, 5)
            if self._diag_only:
                ci_dc = table_dc[home, he_d][None]  # (1, N, 3): home only
                if risky:
                    home_ci, ci_dc = carbon_model.inflate_ci_risk(
                        home_ci, ci_dc, rscale)
                return self._inner_pair_scores(factors, w, home_ci, ci_dc,
                                               avail, None, hour=he_d,
                                               **ctx)[0]  # (N, 3)
            if sparse:
                # gathered candidate sites only: O(N·K) per defer
                ci_dc = jnp.moveaxis(
                    table_dc[cand_r, he_d[:, None]], 0, 1)  # (C, N, 3)
                if risky:
                    home_ci, ci_dc = carbon_model.inflate_ci_risk(
                        home_ci, ci_dc, rscale)
                s = self._inner_pair_scores(factors, w, home_ci, ci_dc,
                                            avail, extra, hour=he_d, **ctx)
                return self._mask_sparse(jnp.moveaxis(s, 0, 1), home,
                                         cand_r)  # (N, C, 3)
            ci_dc = table_dc[:, he_d, :]  # (R, N, 3)
            if risky:
                home_ci, ci_dc = carbon_model.inflate_ci_risk(
                    home_ci, ci_dc, rscale)
            s = self._inner_pair_scores(factors, w, home_ci, ci_dc, avail,
                                        extra, hour=he_d, **ctx)  # (R, N, 3)
            return self._mask_pairs(jnp.moveaxis(s, 0, 1), home)

        he = jnp.clip(
            hr[None, :] + jnp.arange(S + 1, dtype=hr.dtype)[:, None],
            0, self._horizon_h - 1)  # (S+1, N)
        rscales = carbon_model.forecast_risk_scale(
            jnp.arange(S + 1, dtype=jnp.float32), sigma, lam)  # (S+1,)
        return jax.vmap(scores_at)(he, rscales)

    def decide(self, w, env, avail, state, *, region=None, hour=None,
               outputs=None, order=None, inv_order=None, slack=None,
               factors=None, fc_table=None, cap_scale=None, used0=None,
               axis_name=None):
        """(N,) int32 tier targets + ``TemporalState`` under joint
        (defer, region, tier) admission. ``slack`` is per-request hours of
        deadline headroom (clipped to ``max_defer_h``); all-zero slack
        reproduces ``PlacementPolicy.decide`` bit-for-bit, and
        ``risk_lambda = 0`` (or a forecast-free grid) scores candidates
        bit-identically to the error-blind engine."""
        n = w.flops.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        if n == 0:
            return jnp.zeros((0,), jnp.int32), state
        home = (jnp.zeros((n,), jnp.int32) if region is None
                else jnp.asarray(region, jnp.int32))
        hr = (jnp.zeros((n,), jnp.int32) if hour is None
              else jnp.asarray(hour, jnp.int32))
        W, S = self.n_windows, self.max_defer_h
        win = hr % W
        slack_w = (jnp.zeros((n,), jnp.int32) if slack is None
                   else jnp.clip(jnp.asarray(slack, jnp.int32), 0, S))
        if factors is None:
            infra = getattr(self.inner, "infra", None)
            if infra is None:
                raise ValueError(
                    "TemporalPolicy needs an EnergyFactors batch: route "
                    "via a FleetRouter (which precomputes factors for "
                    "wants_factors policies) or give the inner policy an "
                    "infra (LearnedPolicy.fit(..., infra=...))")
            factors = carbon_model.energy_factors_batch(
                w, infra, env.interference, env.net_slowdown)

        # --- candidate scores over (defer[, region], tier) ----------------
        s_all = self.candidate_scores(factors, w, env, avail, home, hr,
                                      fc_table=fc_table)
        # a candidate must sit within the request's slack AND inside the
        # grid horizon — the non-wrapping tail: hours past H-1 are refused,
        # never aliased to hour 0 (d = 0 is always in-horizon, so this can
        # never by itself make a routable request unroutable)
        d_ok = ((jnp.arange(S + 1)[:, None] <= slack_w[None, :])
                & ((hr[None, :] + jnp.arange(S + 1, dtype=hr.dtype)[:, None])
                   < self._horizon_h))  # (S+1, N)
        sparse = getattr(self, "_sparse", False)
        if self._diag_only:
            # home is the only candidate region ((S+1, N, 3) scores): the
            # width-(S+1)*3 home columns keep the admission one-hots narrow
            sub_p = N_TARGETS
            s_all = jnp.where(d_ok[:, :, None], s_all, jnp.inf)
        else:
            # sparse grids enumerate only the gathered (home + neighbors)
            # candidate columns — width (S+1)*C*3 instead of (S+1)*R*3
            sub_p = self._cand_pair.shape[1] if sparse else n_pairs
            s_all = jnp.where(d_ok[:, :, None, None], s_all, jnp.inf)
        s = jnp.moveaxis(s_all, 0, 1).reshape(n, (S + 1) * sub_p)
        return self._admit(s, sub_p, win, home, hr, order, inv_order, state,
                           cap_scale, used0, axis_name)

    @jax.named_scope("admit")
    def _admit(self, s, sub_p, win, home, hr, order, inv_order, state,
               cap_scale, used0, axis_name):
        """Joint (defer, region, tier) admission of the (N, (S+1)·sub_p)
        candidate scores ``s``: skip-full best-open attempts under a
        ``lax.while_loop``, then the shed/fallback tail."""
        n = s.shape[0]
        n_regions, n_pairs = self._caps.shape[0], self._caps.size
        W, S = self.n_windows, self.max_defer_h
        sparse = getattr(self, "_sparse", False)
        width = (S + 1) * sub_p

        # --- to segment-sorted stream order -------------------------------
        # Same segments as PlacementPolicy — (window, home) cells in
        # tier-only mode, windows otherwise; deferred candidates live in
        # LATER windows' cells, handled by the prior-count matrix below.
        order, inv = self._to_stream_order(n, win, home, order, inv_order)
        win_s, home_s, hr_s, s_s = win[order], home[order], hr[order], s[order]
        # per-row local-column -> GLOBAL pair map (sparse grids only)
        cand_pair_s = self._cand_pair[home_s] if sparse else None
        finite_s = jnp.isfinite(s_s)  # (N, width)
        routable = finite_s.any(axis=1)
        # first choice over the joint candidate list; ties break by column
        # index — earlier execution first, then region-major, tier-minor
        col0 = jnp.argmin(s_s, axis=1).astype(jnp.int32)
        if self._diag_only:
            seg_s = win_s * n_regions + home_s
            n_segments = W * n_regions
        else:
            seg_s = win_s
            n_segments = W
        starts = jnp.searchsorted(seg_s, jnp.arange(n_segments))
        ends = jnp.concatenate([starts[1:], jnp.array([n])])
        # cap_scale is the rolling re-planner's per-region emissions-budget
        # multiplier ((R,): conserve ahead of predicted clean windows, spend
        # ahead of dirty ones) or the serving loop's live per-(region, tier)
        # worker-slot matrix ((R, 3)); None = the configured caps,
        # bit-for-bit
        caps_rt = self._caps_runtime(cap_scale)
        caps_flat = caps_rt.reshape(-1)
        caps_cell = jnp.tile(caps_flat, W)
        limit = W * n_pairs + 1  # closable cells + 1

        # Prior-count plumbing: d_map[s, e] is the defer a request arriving
        # in window s needs to execute in window e; valid_map masks defers
        # beyond the horizon. Requires S < W (validated) so the map is
        # injective per arrival window.
        s_idx = jnp.arange(W)
        d_map = (s_idx[None, :] - s_idx[:, None]) % W  # [arrival, exec]
        valid_map = d_map <= S

        def open_mask(used, placed):
            """(N, width) — open-celled finite candidates of unplaced rows:
            does each row's (defer, pair) column point at a cell with
            remaining budget? Built per (arrival window, defer) from the
            tiny (W, pairs) open-cell table, then gathered per row — never
            an (N,)-wide scatter. Its any() is the loop condition: empty
            means every unplaced routable row is out of open cells within
            its deadline, i.e. shed."""
            open_w = (jnp.floor(caps_cell - used) >= 1.0).reshape(W, n_pairs)
            shifted_w = open_w[(s_idx[:, None] + jnp.arange(S + 1)[None, :])
                               % W]  # (W, S+1, pairs): arrival -> exec cell
            if self._diag_only:
                look = shifted_w.reshape(W, S + 1, n_regions, N_TARGETS)
                rows = look[win_s, :, home_s, :].reshape(n, width)
            elif sparse:
                # gather only each row's candidate columns per defer
                rows = shifted_w[win_s[:, None, None],
                                 jnp.arange(S + 1)[None, :, None],
                                 cand_pair_s[:, None, :]].reshape(n, width)
            else:
                rows = shifted_w[win_s].reshape(n, width)
            return rows & finite_s & ~placed[:, None]

        # collectives run in the body, so the continue flag is a carried
        # psum-any: every device spins until NO device has an open-celled
        # contender left (see PlacementPolicy._decide_cross)
        def cond(carry):
            go, _, _, _, _, _, k = carry
            return go & (k < limit)

        def body(carry):
            _, mask, used, placed, exec_pair, exec_d, k = carry
            active = mask.any(axis=1)
            choice = jnp.argmin(jnp.where(mask, s_s, jnp.inf),
                                axis=1).astype(jnp.int32)
            d = choice // sub_p
            sub = choice % sub_p
            if self._diag_only:
                pair = home_s * N_TARGETS + sub
                local_cell = seg_s * width + choice
                rank_w, totals = windowed_segment_ranks(
                    choice, active, local_cell, starts, ends, width)
            else:
                # rank on the dense-equivalent (defer, GLOBAL pair) column:
                # within one arrival window the same exec cell implies the
                # same defer, so (d, pair) keys exec cells exactly — sparse
                # local columns alias into the dense program's ranks/totals
                # and the prior-count matrix below runs unchanged
                pair = (sub if cand_pair_s is None else jnp.take_along_axis(
                    cand_pair_s, sub[:, None], axis=1)[:, 0])
                rank_col = d * n_pairs + pair
                rank_width = (S + 1) * n_pairs
                local_cell = seg_s * rank_width + rank_col
                rank_w, totals = windowed_segment_ranks(
                    rank_col, active, local_cell, starts, ends, rank_width)
            # sharded streams: lift the within-arrival-window ranks/totals
            # to global BEFORE the prior-count shift, so the cross-window
            # contention matrix below is built from fleet-wide totals and
            # the replicated ``used`` ledger advances identically everywhere
            rank_w, totals = device_prefix_ranks(rank_w, totals, local_cell,
                                                 axis_name)
            e = (win_s + d) % W
            cell = e * n_pairs + pair
            # shift each arrival window's per-(defer, column) totals onto
            # their execution cells, prefix-sum over arrival windows: a
            # row's global rank = its within-window rank + every earlier
            # window's contenders for the same cell
            if self._diag_only:
                t4 = totals.reshape(W, n_regions, S + 1, N_TARGETS)
                t4 = t4.transpose(0, 2, 1, 3)  # (W, S+1, R, 3)
                shifted = (t4[s_idx[:, None], d_map, :, :]
                           * valid_map[:, :, None, None])  # [s, e, r, t]
                prior = jnp.cumsum(shifted, axis=0) - shifted
                prior_i = prior.reshape(W, W * n_pairs)[win_s, cell]
            else:
                t3 = totals.reshape(W, S + 1, n_pairs)
                shifted = (t3[s_idx[:, None], d_map, :]
                           * valid_map[:, :, None])  # [s, e, pair]
                prior = jnp.cumsum(shifted, axis=0) - shifted
                prior_i = prior.reshape(W, W * n_pairs)[seg_s, cell]
            totals_cell = shifted.sum(axis=0).reshape(-1)  # (W * n_pairs,)
            rank = rank_w + prior_i
            fits = active & (used[cell] + rank + 1.0 <= caps_flat[pair])
            exec_pair = jnp.where(fits, pair, exec_pair)
            exec_d = jnp.where(fits, d, exec_d)
            placed = placed | fits
            used = used + jnp.minimum(
                jnp.maximum(jnp.floor(caps_cell - used), 0.0), totals_cell)
            # rejected rows lost their target cell (now full); the carried
            # next-round mask either re-aims them or retires them
            mask = open_mask(used, placed)
            return (_global_any(mask.any(), axis_name), mask, used, placed,
                    exec_pair, exec_d, k + 1)

        # used0 seeds the cell ledger with capacity already committed by
        # earlier rolling-planner steps (None = fresh, the one-shot path)
        used_init = (jnp.zeros((W * n_pairs,), jnp.float32) if used0 is None
                     else jnp.asarray(used0, jnp.float32).reshape(-1))
        placed0 = jnp.zeros((n,), bool)
        mask0 = open_mask(used_init, placed0)
        _, _, used, placed, exec_pair, exec_d, rounds = jax.lax.while_loop(
            cond, body,
            (_global_any(mask0.any(), axis_name), mask0, used_init, placed0,
             jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), jnp.int32),
             jnp.zeros((), jnp.int32)))

        # --- shed / unroutable fallback (PlacementPolicy semantics) -------
        shed_s = routable & ~placed
        if self._diag_only:
            pair0 = home_s * N_TARGETS + col0 % sub_p
            home_row_s = s_s.reshape(n, S + 1, N_TARGETS)[:, 0]
        elif sparse:
            pair0 = jnp.take_along_axis(
                cand_pair_s, (col0 % sub_p)[:, None], axis=1)[:, 0]
            home_row_s = jnp.take_along_axis(
                s_s.reshape(n, S + 1, sub_p // N_TARGETS, N_TARGETS)[:, 0],
                self._cand_home_slot[home_s][:, None, None], axis=1)[:, 0]
        else:
            pair0 = col0 % sub_p
            home_row_s = jnp.take_along_axis(
                s_s.reshape(n, S + 1, n_regions, N_TARGETS)[:, 0],
                home_s[:, None, None], axis=1)[:, 0]
        fb_pair = jnp.where(
            routable, pair0,
            home_s * N_TARGETS + jnp.argmin(
                home_row_s, axis=1).astype(jnp.int32))
        exec_pair = jnp.where(placed, exec_pair, fb_pair)
        exec_d = jnp.where(placed, exec_d, 0)

        # --- back to stream order + aggregates ----------------------------
        shed = shed_s[inv]
        exec_region = jnp.where(shed_s, home_s, exec_pair // N_TARGETS)[inv]
        targets = (exec_pair % N_TARGETS).astype(jnp.int32)[inv]
        defer = exec_d.astype(jnp.int32)[inv]
        # non-wrapping tail: admitted candidates always satisfy
        # hr + d < horizon (masked above), so no modulo here — fallback
        # rows have d = 0 and stay at their (in-horizon) arrival hour
        exec_hour = (hr_s + exec_d).astype(jnp.int32)[inv]
        counts = (used - used_init).reshape(
            W, n_regions, N_TARGETS).sum(axis=0)
        shed_pair = (jax.nn.one_hot(pair0, n_pairs, dtype=jnp.int32)
                     * shed_s[:, None]).sum(axis=0).reshape(
            n_regions, N_TARGETS)
        if axis_name is not None:
            shed_pair = jax.lax.psum(shed_pair, axis_name)
        return targets, TemporalState(
            counts=state.counts + counts.astype(jnp.int32),
            shed=shed,
            exec_region=exec_region,
            shed_pair=state.shed_pair + shed_pair,
            exec_hour=exec_hour,
            defer_hours=defer,
            admit_rounds=state.admit_rounds + rounds)
