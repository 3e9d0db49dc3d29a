"""Temporal deferral engine tests: zero-slack bit-for-bit parity with the
PR-3 placement layer, the joint spatio-temporal carbon win (ISSUE-4
acceptance), deadline/capacity conservation (property-based), the
single-evaluation regression probe for the factorized hot path, and the
WAN-hop (rtt_s) QoS satellite."""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import carbon_model
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.core.schedulers import ClassificationScheduler
from repro.serve import (
    FleetRouter,
    LearnedPolicy,
    OraclePolicy,
    PlacementPolicy,
    RequestBatch,
    TemporalPolicy,
)
from repro.serve.streams import (
    deferrable_stream,
    deferrable_stream_multiday,
    multi_region_stream,
)

ARCH = "h2o-danube-1.8b"
N_REGIONS = len(DEFAULT_REGIONS)


@functools.lru_cache(maxsize=None)
def _train_dataset():
    """Small offline design-space dataset for fitting learned policies."""
    from repro.core import build_scenarios, explore, paper_fleet
    from repro.core.design_space import ScenarioAxes
    from repro.core.schedulers import build_dataset
    from repro.core.workloads import ALL_PAPER_WORKLOADS

    axes = ScenarioAxes(hours=tuple(range(0, 24, 6)))
    table = build_scenarios(paper_fleet(), axes)
    res = explore(ALL_PAPER_WORKLOADS, table)
    return build_dataset(ALL_PAPER_WORKLOADS, res, table).split()[0]


def _learned_policy(sched_cls=ClassificationScheduler, **kw):
    return LearnedPolicy.fit(sched_cls(), _train_dataset(), **kw)


def _stream(n: int, seed: int = 0, n_regions: int = N_REGIONS,
            max_slack: int = 6):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(16, 4096, n).astype(np.float64)
    new = rng.integers(8, 512, n).astype(np.float64)
    avail = np.ones((n, 3), bool)
    avail[:, 0] = prompt < 2048
    batch = RequestBatch(
        prompt_tokens=prompt, max_new_tokens=new,
        latency_budget_s=rng.choice([0.5, 2.0, 10.0], n),
        bytes_per_token=np.full(n, 4.0), available=avail,
        slack_hours=rng.integers(0, max_slack + 1, n).astype(np.float64))
    return batch, rng.integers(0, n_regions, n), rng.uniform(0.0, 48.0, n)


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


@pytest.fixture(scope="module")
def base(cfg):
    return FleetRouter(cfg)


@pytest.fixture(scope="module")
def xgrid():
    return CarbonGrid.fully_connected(DEFAULT_REGIONS, latency_penalty=1.05)


@pytest.fixture(scope="module")
def xgrid2():
    """Two repeated-diurnal days: the horizon tail is non-wrapping, so a
    stream whose deadline windows cross midnight needs the grid to carry
    the next day's hours (same CI, fresh capacity cells) — the explicit
    replacement for the retired wrap-into-hour-0 aliasing."""
    return CarbonGrid.fully_connected(DEFAULT_REGIONS, latency_penalty=1.05,
                                      n_days=2)


class TestValidation:
    def test_rejects_non_factorizable_inner(self, base):
        caps = np.full((N_REGIONS, 3), np.inf)
        with pytest.raises(ValueError, match="factoriz"):
            TemporalPolicy(OraclePolicy(base.infra), caps, factorized=False)

    def test_rejects_bad_window_count(self, base):
        caps = np.full((N_REGIONS, 3), np.inf)
        with pytest.raises(ValueError, match="n_windows"):
            TemporalPolicy(OraclePolicy(base.infra), caps, n_windows=7)

    def test_rejects_horizon_beyond_windows(self, base):
        caps = np.full((N_REGIONS, 3), np.inf)
        with pytest.raises(ValueError, match="max_defer_h"):
            TemporalPolicy(OraclePolicy(base.infra), caps, n_windows=12,
                           max_defer_h=12)

    def test_learned_inner_rides_the_factorized_engine(self, base):
        """ISSUE-5: LearnedPolicy exposes the factorized hooks, so it is a
        legal TemporalPolicy inner (the PR-4 rejection is retired)."""
        assert hasattr(LearnedPolicy, "scores_from_factors")
        assert hasattr(LearnedPolicy, "pair_scores_from_factors")
        caps = np.full((N_REGIONS, 3), np.inf)
        pol = TemporalPolicy(_learned_policy(), caps, max_defer_h=4)
        assert pol.wants_factors

    def test_windows_default_to_grid_horizon(self, base, xgrid):
        caps = np.full((N_REGIONS, 3), np.inf)
        pol = TemporalPolicy(OraclePolicy(base.infra), caps, max_defer_h=4)
        assert pol.n_windows is None
        pol.bind_grid(xgrid)
        assert pol.n_windows == 24
        pol2 = TemporalPolicy(OraclePolicy(base.infra), caps, max_defer_h=30)
        pol2.bind_grid(CarbonGrid.fully_connected(DEFAULT_REGIONS, n_days=2))
        assert pol2.n_windows == 48  # > 24h deferral is legal on 2 days

    def test_rejects_defer_beyond_resolved_horizon(self, base, xgrid):
        caps = np.full((N_REGIONS, 3), np.inf)
        pol = TemporalPolicy(OraclePolicy(base.infra), caps, max_defer_h=30)
        with pytest.raises(ValueError, match="max_defer_h"):
            pol.bind_grid(xgrid)  # 30h deferral needs > 1 day of windows


class TestZeroSlackParity:
    """ISSUE-4 acceptance: a TemporalPolicy given no slack IS the PR-3
    PlacementPolicy — decisions, shed, counts, executing regions, and
    (both running the factorized accounting) carbon, bit-for-bit."""

    def test_bit_for_bit_on_multi_region_stream(self, cfg, base, xgrid):
        n = 4000
        batch, region, t_hours = multi_region_stream(n, N_REGIONS, seed=0)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = max(1.0, 0.25 * n / (N_REGIONS * 24))
        place = FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps))
        temp = FleetRouter(cfg, grid=xgrid, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=6))
        rp, sp = place.route_stream_with_state(batch, region, t_hours)
        rt, st_ = temp.route_stream_with_state(batch, region, t_hours)
        np.testing.assert_array_equal(np.asarray(rp.target),
                                      np.asarray(rt.target))
        np.testing.assert_array_equal(np.asarray(sp.shed),
                                      np.asarray(st_.shed))
        np.testing.assert_array_equal(np.asarray(rp.counts),
                                      np.asarray(rt.counts))
        np.testing.assert_array_equal(np.asarray(rp.exec_region),
                                      np.asarray(rt.exec_region))
        np.testing.assert_array_equal(np.asarray(rp.carbon_g),
                                      np.asarray(rt.carbon_g))
        assert int(rp.shed_count) == int(rt.shed_count) > 0
        assert int(rt.deferred_count) == 0
        assert float(rt.mean_defer_hours) == 0.0
        assert (np.asarray(st_.defer_hours) == 0).all()
        hour = np.floor(t_hours).astype(int) % 24
        np.testing.assert_array_equal(np.asarray(st_.exec_hour), hour)

    def test_zero_slack_huge_caps_match_uncapped_oracle(self, cfg, base):
        """Caps larger than the stream + zero slack: the temporal engine is
        a no-op wrapper (identity-adjacency parity with the base router)."""
        n = 1200
        batch, region, t_hours = multi_region_stream(n, N_REGIONS, seed=2)
        caps = np.full((N_REGIONS, 3), float(n + 1))
        fr = FleetRouter(cfg, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=4))
        free = base.route_stream(batch, region, t_hours)
        res = fr.route_stream(batch, region, t_hours)
        np.testing.assert_array_equal(np.asarray(res.target),
                                      np.asarray(free.target))
        np.testing.assert_array_equal(np.asarray(res.counts),
                                      np.asarray(free.counts))
        assert int(res.shed_count) == 0
        np.testing.assert_allclose(float(res.total_carbon_g),
                                   float(free.total_carbon_g), rtol=1e-5)

    def test_factorized_placement_matches_legacy_sweep(self, cfg, base,
                                                       xgrid):
        """The factorized einsum scorer and the legacy per-region Table-1
        sweep (the verbatim PR-3 program) agree on every uncapped placement
        decision — fp32-tolerance scores, identical argmins. (Capped
        streams go through different-but-equivalent admission programs —
        fixed-round march vs skip-full attempts — so decision parity is
        only exact where capacity does not bind.)"""
        n = 3000
        batch, region, t_hours = multi_region_stream(n, N_REGIONS, seed=1)
        caps = np.full((N_REGIONS, 3), np.inf)
        legacy = FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps, factorized=False))
        fact = FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps))
        rl, sl = legacy.route_stream_with_state(batch, region, t_hours)
        rf, sf = fact.route_stream_with_state(batch, region, t_hours)
        assert int(rl.shed_count) == int(rf.shed_count) == 0
        np.testing.assert_array_equal(np.asarray(rl.target),
                                      np.asarray(rf.target))
        np.testing.assert_array_equal(np.asarray(rl.exec_region),
                                      np.asarray(rf.exec_region))
        np.testing.assert_array_equal(np.asarray(rl.counts),
                                      np.asarray(rf.counts))
        np.testing.assert_allclose(np.asarray(rl.carbon_g),
                                   np.asarray(rf.carbon_g), rtol=1e-5)

    def test_pair_scores_factorized_matches_sweep(self, cfg, base, xgrid):
        """Raw (N, R, 3) candidate scores: einsum vs per-region sweep."""
        import jax.numpy as jnp

        n = 512
        batch, region, t_hours = _stream(n, seed=7)
        caps = np.full((N_REGIONS, 3), np.inf)
        pol = PlacementPolicy(OraclePolicy(base.infra), caps, grid=xgrid)
        w = batch.workload(cfg)
        hour = jnp.asarray(np.floor(t_hours).astype(np.int32) % 24)
        home = jnp.asarray(region.astype(np.int32))
        fr = FleetRouter(cfg, grid=xgrid)
        env = carbon_model.Environment(
            ci=fr.grid.table[home, hour],
            interference=jnp.ones(3, jnp.float32),
            net_slowdown=jnp.ones(2, jnp.float32))
        factors = carbon_model.energy_factors_batch(
            w, base.infra, env.interference, env.net_slowdown)
        sweep = pol.pair_scores(w, env, batch.avail, home, hour)
        fact = pol.pair_scores_from_factors(factors, w, env, batch.avail,
                                            home, hour)
        a, b = np.asarray(sweep), np.asarray(fact)
        np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b))
        mask = np.isfinite(a)
        np.testing.assert_allclose(a[mask], b[mask], rtol=1e-5)


class TestDeferralWins:
    """ISSUE-4 acceptance: with slack > 0 the joint (region, tier, hour)
    decision reduces routed gCO2 by >= 10% vs PR-3 cross-region spill on
    ``deferrable_stream`` while violating zero deadlines."""

    def test_uncapped_joint_beats_spatial_by_10pct(self, cfg, base, xgrid):
        n = 3000
        batch, region, t_hours = deferrable_stream(n, N_REGIONS, seed=0)
        caps = np.full((N_REGIONS, 3), np.inf)
        place = FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps))
        temp = FleetRouter(cfg, grid=xgrid, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=12))
        rp = place.route_stream(batch, region, t_hours)
        rt, st_ = temp.route_stream_with_state(batch, region, t_hours)
        assert int(rp.shed_count) == int(rt.shed_count) == 0
        reduction = 1.0 - float(rt.routed_carbon_g) / float(
            rp.routed_carbon_g)
        assert reduction >= 0.10, reduction
        assert int(rt.deferred_count) > 0
        assert float(rt.mean_defer_hours) > 0.0
        # zero deadline violations: defer within [0, slack] for every row
        defer = np.asarray(st_.defer_hours)
        assert (defer >= 0).all()
        assert (defer <= batch.slack_h).all()
        # interactive (zero-slack) rows never defer
        assert (defer[batch.slack_h == 0] == 0).all()

    def test_capped_joint_beats_spatial_and_sheds_no_more(self, cfg, base,
                                                          xgrid2):
        """Moderate cap pressure: deferral drains the evening peak into
        later windows, so the joint policy both routes greener and sheds
        less than space-only spill. Runs on a 2-day repeated-diurnal grid:
        evening arrivals defer across midnight into day two's (identical)
        morning CI — under the non-wrapping tail, candidates past the
        horizon are refused, so the grid must carry those hours."""
        n = 3000
        batch, region, t_hours = deferrable_stream(n, N_REGIONS, seed=0)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = max(1.0, 0.6 * n / (N_REGIONS * 24))
        place = FleetRouter(cfg, grid=xgrid2, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps))
        temp = FleetRouter(cfg, grid=xgrid2, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=12))
        rp = place.route_stream(batch, region, t_hours)
        rt = temp.route_stream(batch, region, t_hours)
        assert float(rt.total_carbon_g) < float(rp.total_carbon_g)
        assert int(rt.shed_count) <= int(rp.shed_count)
        assert int(rt.deferred_count) > 0

    def test_defer_only_mode_defers_at_home(self, cfg, base):
        """Identity adjacency: deferral without spatial spill — every
        request executes in its home region, some in a later hour."""
        n = 2000
        batch, region, t_hours = deferrable_stream(n, N_REGIONS, seed=1)
        caps = np.full((N_REGIONS, 3), np.inf)
        fr = FleetRouter(cfg, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=12))
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        np.testing.assert_array_equal(np.asarray(res.exec_region), region)
        assert int(res.spilled_count) == 0
        assert int(res.deferred_count) > 0
        defer = np.asarray(state.defer_hours)
        assert (defer <= batch.slack_h).all()
        # deferral never hurts: same stream, same caps, no deferral
        zero = FleetRouter(cfg, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=0))
        rz = zero.route_stream(batch, region, t_hours)
        assert float(res.total_carbon_g) <= float(rz.total_carbon_g) + 1e-6


class TestSingleEvaluation:
    """Satellite regression: the factorized hot path runs Table 1 exactly
    ONCE per batch — no per-candidate-region sweeps, no out_exec
    re-evaluation after admission (probed by counting trace-time calls of
    ``carbon_model.evaluate``)."""

    @staticmethod
    def _count_evaluates(monkeypatch, make_router, batch, region, t_hours):
        calls = {"n": 0}
        real = carbon_model.evaluate

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(carbon_model, "evaluate", counting)
        fr = make_router()  # construct AFTER the patch: jit traces lazily
        fr.route_stream(batch, region, t_hours)
        return calls["n"]

    def test_factorized_placement_evaluates_once(self, cfg, base, xgrid,
                                                 monkeypatch):
        batch, region, t_hours = _stream(256, seed=3)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = 4.0
        n = self._count_evaluates(
            monkeypatch,
            lambda: FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
                OraclePolicy(base.infra), caps)),
            batch, region, t_hours)
        assert n == 1

    def test_temporal_evaluates_once(self, cfg, base, xgrid, monkeypatch):
        batch, region, t_hours = _stream(256, seed=4)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = 4.0
        n = self._count_evaluates(
            monkeypatch,
            lambda: FleetRouter(cfg, grid=xgrid, policy=TemporalPolicy(
                OraclePolicy(base.infra), caps, max_defer_h=4)),
            batch, region, t_hours)
        assert n == 1

    def test_legacy_sweep_evaluates_many_times(self, cfg, base, xgrid,
                                               monkeypatch):
        """The probe itself is live: the PR-3 program re-evaluates Table 1
        per candidate region plus the out_exec pass."""
        batch, region, t_hours = _stream(256, seed=5)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = 4.0
        n = self._count_evaluates(
            monkeypatch,
            lambda: FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
                OraclePolicy(base.infra), caps, factorized=False)),
            batch, region, t_hours)
        assert n > 4


class TestWanHop:
    """Satellite: the (R, R) rtt_s matrix enters the QoS latency check —
    tight-budget requests refuse remote placement outright."""

    def test_default_grid_has_zero_rtt(self, xgrid):
        np.testing.assert_array_equal(np.asarray(xgrid.rtt_s),
                                      np.zeros((N_REGIONS, N_REGIONS)))

    def test_rtt_validation(self):
        bad = np.full((N_REGIONS, N_REGIONS), 0.1, np.float32)
        with pytest.raises(ValueError, match="diagonal"):
            CarbonGrid.from_regions(DEFAULT_REGIONS, rtt_s=bad)
        neg = np.zeros((N_REGIONS, N_REGIONS), np.float32)
        neg[0, 1] = -1.0
        with pytest.raises(ValueError, match="non-negative"):
            CarbonGrid.from_regions(DEFAULT_REGIONS, rtt_s=neg)
        with pytest.raises(ValueError, match="rtt_s must be"):
            CarbonGrid.from_regions(DEFAULT_REGIONS,
                                    rtt_s=np.zeros((2, 2), np.float32))

    def test_scalar_rtt_has_zero_diagonal(self):
        grid = CarbonGrid.fully_connected(DEFAULT_REGIONS, rtt_s=0.08)
        rtt = np.asarray(grid.rtt_s)
        np.testing.assert_array_equal(np.diag(rtt), np.zeros(N_REGIONS))
        assert (rtt[~np.eye(N_REGIONS, dtype=bool)] == np.float32(0.08)).all()

    def test_zero_rtt_is_bit_for_bit_noop(self, cfg, base):
        """Explicit zero rtt_s reproduces the default-grid placement
        decisions bit-for-bit (the PR-3 parity satellite)."""
        n = 2000
        batch, region, t_hours = multi_region_stream(n, N_REGIONS, seed=3)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = max(1.0, 0.25 * n / (N_REGIONS * 24))
        g0 = CarbonGrid.fully_connected(DEFAULT_REGIONS)
        g1 = CarbonGrid.fully_connected(DEFAULT_REGIONS, rtt_s=0.0)
        a, sa = FleetRouter(cfg, grid=g0, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps)).route_stream_with_state(
            batch, region, t_hours)
        b, sb = FleetRouter(cfg, grid=g1, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps)).route_stream_with_state(
            batch, region, t_hours)
        np.testing.assert_array_equal(np.asarray(a.target),
                                      np.asarray(b.target))
        np.testing.assert_array_equal(np.asarray(sa.shed),
                                      np.asarray(sb.shed))
        np.testing.assert_array_equal(np.asarray(a.exec_region),
                                      np.asarray(b.exec_region))
        np.testing.assert_array_equal(np.asarray(a.carbon_g),
                                      np.asarray(b.carbon_g))

    def test_tight_budgets_refuse_remote_placement(self, cfg, base):
        """With a WAN hop bigger than the tight latency budgets, capacity
        overflow of tight-budget requests sheds (or stays home) instead of
        spilling; relaxed-budget requests still spill remotely."""
        n = 3000
        rng = np.random.default_rng(9)
        prompt = rng.integers(16, 2048, n).astype(np.float64)
        budget = rng.choice([0.6, 30.0], n)
        batch = RequestBatch(
            prompt_tokens=prompt,
            max_new_tokens=rng.integers(8, 128, n).astype(np.float64),
            latency_budget_s=budget,
            bytes_per_token=np.full(n, 4.0),
            available=np.ones((n, 3), bool))
        region = rng.integers(0, N_REGIONS, n)
        t_hours = rng.uniform(0.0, 24.0, n)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = 3.0  # starve DCs: heavy spill pressure
        grid = CarbonGrid.fully_connected(DEFAULT_REGIONS,
                                          latency_penalty=1.0, rtt_s=1.0)
        fr = FleetRouter(cfg, grid=grid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps))
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        ex = np.asarray(res.exec_region)
        shed = np.asarray(state.shed)
        moved = (ex != region) & ~shed
        # a 1s hop busts the 0.6s budgets outright
        assert not moved[budget < 1.0].any()
        assert moved[budget > 1.0].any()
        # same without the hop: tight-budget requests do spill
        free = FleetRouter(cfg, grid=CarbonGrid.fully_connected(
            DEFAULT_REGIONS, latency_penalty=1.0), policy=PlacementPolicy(
            OraclePolicy(base.infra), caps))
        r0, s0 = free.route_stream_with_state(batch, region, t_hours)
        moved0 = (np.asarray(r0.exec_region) != region) & ~np.asarray(s0.shed)
        assert moved0[budget < 1.0].any()

    def test_temporal_respects_rtt(self, cfg, base):
        """The WAN hop also gates the deferral engine's remote candidates."""
        n = 2000
        batch, region, t_hours = deferrable_stream(n, N_REGIONS, seed=5)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = 3.0
        grid = CarbonGrid.fully_connected(DEFAULT_REGIONS,
                                          latency_penalty=1.0, rtt_s=1.0)
        fr = FleetRouter(cfg, grid=grid, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=8))
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        ex = np.asarray(res.exec_region)
        shed = np.asarray(state.shed)
        moved = (ex != region) & ~shed
        tight = np.asarray(batch.latency_budget_s) < 1.0
        assert not moved[tight].any()


class TestConservation:
    """Tentpole property (hypothesis): every request executes within
    [arrival, arrival + slack]; routed + shed == total; no (region, tier,
    exec-hour) cell exceeds its cap; spill only along adjacency."""

    N = 140
    R = 2

    @hypothesis.settings(max_examples=6, deadline=None)
    @hypothesis.given(
        caps_flat=st.lists(
            st.one_of(st.integers(0, 4), st.just(np.inf)),
            min_size=6, max_size=6),
        link=st.tuples(st.booleans(), st.booleans()),
        max_slack=st.integers(0, 5),
        seed=st.integers(0, 3),
    )
    def test_deadlines_conservation_and_caps(self, caps_flat, link,
                                             max_slack, seed):
        cfg = get_config(ARCH)
        from repro.core.infrastructure import pack_infra, tpu_fleet

        caps = np.asarray(caps_flat, np.float64).reshape(self.R, 3)
        adjacency = np.eye(self.R, dtype=bool)
        adjacency[0, 1], adjacency[1, 0] = link
        grid = CarbonGrid.from_regions(DEFAULT_REGIONS[:2],
                                       adjacency=adjacency,
                                       latency_penalty=1.03)
        infra = pack_infra(tpu_fleet(), "act")
        fr = FleetRouter(cfg, regions=DEFAULT_REGIONS[:2], grid=grid,
                         policy=TemporalPolicy(OraclePolicy(infra), caps,
                                               max_defer_h=5))
        batch, region, t_hours = _stream(self.N, seed=seed,
                                         n_regions=self.R,
                                         max_slack=max_slack)
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        shed = np.asarray(state.shed)
        defer = np.asarray(state.defer_hours)
        eh = np.asarray(state.exec_hour)
        arr = np.floor(t_hours).astype(int) % 24
        # deadlines: execution within [arrival, arrival + slack] always
        assert (defer >= 0).all()
        assert (defer <= np.minimum(batch.slack_h, 5)).all()
        np.testing.assert_array_equal(eh, (arr + defer) % 24)
        # conservation: every request is either capacity-routed or shed
        assert int(np.asarray(res.counts).sum()) + int(shed.sum()) == self.N
        # no (region, tier, exec-hour) cell exceeds its cap
        tgt = np.asarray(res.target)
        ex = np.asarray(state.exec_region)
        for h in range(24):
            for r in range(self.R):
                for t in range(3):
                    got = int(((eh == h) & (ex == r) & (tgt == t)
                               & ~shed).sum())
                    assert got <= caps[r, t], (h, r, t, got)
        # spill only along adjacency edges
        assert adjacency[region[~shed], ex[~shed]].all()


class TestMultiDayHorizon:
    """ISSUE-5 tentpole: the rolling multi-day CarbonGrid horizon.

    A repeated-diurnal multi-day grid reproduces the single-day decisions
    bit-for-bit wherever no deadline window crosses midnight, and deferral
    past midnight charges DAY TWO's capacity cells instead of aliasing
    modulo 24 into day one's spent budgets (the bug this PR fixes)."""

    def test_repeated_diurnal_parity_bit_for_bit(self, cfg, base):
        """Day-one-confined stream (arrival + slack < 24): 2-day repeated
        grid == single-day grid on every decision and carbon gram."""
        n = 2500
        batch, region, t_hours = deferrable_stream(n, N_REGIONS, seed=0,
                                                   slack_range_h=(2, 5))
        t_hours = np.clip(t_hours, 0.0, 18.0)  # deadline windows < 24h
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = max(1.0, 0.3 * n / (N_REGIONS * 24))
        g1 = CarbonGrid.fully_connected(DEFAULT_REGIONS)
        g2 = CarbonGrid.fully_connected(DEFAULT_REGIONS, n_days=2)
        f1 = FleetRouter(cfg, grid=g1, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=5))
        f2 = FleetRouter(cfg, grid=g2, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=5))
        r1, s1 = f1.route_stream_with_state(batch, region, t_hours)
        r2, s2 = f2.route_stream_with_state(batch, region, t_hours)
        np.testing.assert_array_equal(np.asarray(r1.target),
                                      np.asarray(r2.target))
        np.testing.assert_array_equal(np.asarray(s1.shed),
                                      np.asarray(s2.shed))
        np.testing.assert_array_equal(np.asarray(s1.exec_hour),
                                      np.asarray(s2.exec_hour))
        np.testing.assert_array_equal(np.asarray(r1.exec_region),
                                      np.asarray(r2.exec_region))
        np.testing.assert_array_equal(np.asarray(r1.carbon_g),
                                      np.asarray(r2.carbon_g))
        assert int(r1.shed_count) == int(r2.shed_count) > 0

    @staticmethod
    def _midnight_scenario():
        """One region, one open tier: A fills day-one hours 0-1, C fills
        hour 23, B arrives at 23.5 with 2h slack — every candidate of B
        (hours 23, 24, 25) is full under a modulo-24 wrap, but only hour
        23 is genuinely full on the true time axis."""
        cap = 10.0
        caps = np.array([[np.inf, cap, np.inf]])

        def mk(n, slack):
            return RequestBatch(
                prompt_tokens=np.full(n, 4096.0),  # never fits on-device
                max_new_tokens=np.full(n, 64.0),
                latency_budget_s=np.full(n, 120.0),
                bytes_per_token=np.full(n, 4.0),
                available=np.tile([False, True, False], (n, 1)),
                slack_hours=np.full(n, float(slack)))

        nA, nB, nC = 20, 8, 10
        groups = [mk(nA, 0), mk(nB, 2), mk(nC, 0)]
        batch = RequestBatch(*[
            np.concatenate([getattr(g, f.name) for g in groups])
            for f in dataclasses.fields(RequestBatch)])
        t = np.concatenate([np.repeat([0.5, 1.5], nA // 2),
                            np.full(nB, 23.5), np.full(nC, 23.5)])
        region = np.zeros(nA + nB + nC, np.int64)
        b_rows = slice(nA, nA + nB)
        return caps, batch, region, t, b_rows

    def test_day_boundary_aliasing_regression(self, cfg, base):
        """The horizon tail, non-wrapping: on a single-day grid B's
        past-midnight candidate hours (24, 25) are REFUSED — never aliased
        into day one's spent (or empty) hour-0/1 cells — so B and C's 18
        contenders share only hour 23's cap of 10 and exactly 8 shed, all
        executing/shedding at their arrival hour. On the 2-day grid the
        same deferral lands in day-two cells (fresh budgets) and routes."""
        caps, batch, region, t, b_rows = self._midnight_scenario()
        regions = DEFAULT_REGIONS[:1]

        def route(grid):
            fr = FleetRouter(cfg, regions=regions, grid=grid,
                             policy=TemporalPolicy(OraclePolicy(base.infra),
                                                   caps, max_defer_h=2))
            return fr.route_stream_with_state(batch, region, t)

        r1, s1 = route(CarbonGrid.from_regions(regions))
        shed1 = np.asarray(s1.shed)
        eh1 = np.asarray(s1.exec_hour)
        assert int(shed1.sum()) == len(batch) - 30 == 8
        # tail arrivals never wrap into hour 0: every hour-23 arrival
        # (B and C alike) executes or sheds at hour 23, and day-one's
        # early cells hold exactly A's 20 admissions
        assert (eh1[20:] == 23).all()
        assert (np.asarray(s1.defer_hours) == 0).all()

        r2, s2 = route(CarbonGrid.from_regions(regions, n_days=2))
        shed_b = np.asarray(s2.shed)[b_rows]
        eh_b = np.asarray(s2.exec_hour)[b_rows]
        assert not shed_b.any()
        assert (eh_b >= 24).all()  # executed in day-two cells
        # day-one cells must NOT be over cap: A kept its 20 slots, C its 10
        assert int(r2.shed_count) == 0
        counts = np.asarray(r2.counts)
        assert counts.sum() == len(batch)

    def test_cleaner_day_two_attracts_deferral(self, cfg, base):
        """day_scale makes tomorrow greener: uncapped joint deferral on the
        scaled grid defers at least as much carbon away as the repeated
        grid, and midnight-crossing deferrals exist."""
        n = 2000
        batch, region, t_hours = deferrable_stream_multiday(
            n, N_REGIONS, n_days=2, seed=3)
        caps = np.full((N_REGIONS, 3), np.inf)
        g_flat = CarbonGrid.fully_connected(DEFAULT_REGIONS, n_days=2)
        g_clean = CarbonGrid.fully_connected(DEFAULT_REGIONS, n_days=2,
                                             day_scale=(1.0, 0.8))
        out = {}
        for name, g in (("flat", g_flat), ("clean", g_clean)):
            fr = FleetRouter(cfg, grid=g, policy=TemporalPolicy(
                OraclePolicy(base.infra), caps, max_defer_h=16))
            out[name] = fr.route_stream_with_state(batch, region, t_hours)
        res, state = out["clean"]
        arr = np.floor(t_hours).astype(int) % 48
        eh = np.asarray(state.exec_hour)
        crossed = ((arr < 24) & (eh >= 24) & ~np.asarray(state.shed)).sum()
        assert int(crossed) > 0
        assert float(res.routed_carbon_g) < float(
            out["flat"][0].routed_carbon_g)

    @hypothesis.settings(max_examples=5, deadline=None)
    @hypothesis.given(
        caps_flat=st.lists(
            st.one_of(st.integers(0, 4), st.just(np.inf)),
            min_size=6, max_size=6),
        link=st.tuples(st.booleans(), st.booleans()),
        max_slack=st.integers(0, 5),
        seed=st.integers(0, 3),
    )
    def test_multiday_conservation_and_caps(self, caps_flat, link,
                                            max_slack, seed):
        """The PR-4 conservation property, lifted onto a rolling 2-day
        horizon: capacity cells are per ABSOLUTE (region, tier, hour 0..47)
        — so the cap check runs over 48 distinct hours — and deadlines
        hold on the absolute time axis."""
        cfg = get_config(ARCH)
        from repro.core.infrastructure import pack_infra, tpu_fleet

        R, N = 2, 120
        caps = np.asarray(caps_flat, np.float64).reshape(R, 3)
        adjacency = np.eye(R, dtype=bool)
        adjacency[0, 1], adjacency[1, 0] = link
        grid = CarbonGrid.from_regions(DEFAULT_REGIONS[:2],
                                       adjacency=adjacency,
                                       latency_penalty=1.03,
                                       n_days=2, day_scale=(1.0, 0.9))
        infra = pack_infra(tpu_fleet(), "act")
        fr = FleetRouter(cfg, regions=DEFAULT_REGIONS[:2], grid=grid,
                         policy=TemporalPolicy(OraclePolicy(infra), caps,
                                               max_defer_h=5))
        batch, region, t_hours = _stream(N, seed=seed, n_regions=R,
                                         max_slack=max_slack)
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        shed = np.asarray(state.shed)
        defer = np.asarray(state.defer_hours)
        eh = np.asarray(state.exec_hour)
        arr = np.floor(t_hours).astype(int) % 48
        assert (defer >= 0).all()
        assert (defer <= np.minimum(batch.slack_h, 5)).all()
        np.testing.assert_array_equal(eh, (arr + defer) % 48)
        assert int(np.asarray(res.counts).sum()) + int(shed.sum()) == N
        tgt = np.asarray(res.target)
        ex = np.asarray(state.exec_region)
        for h in range(48):
            for r in range(R):
                for t in range(3):
                    got = int(((eh == h) & (ex == r) & (tgt == t)
                               & ~shed).sum())
                    assert got <= caps[r, t], (h, r, t, got)
        assert adjacency[region[~shed], ex[~shed]].all()


DAY_AHEAD = r"""
import json, os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np
from repro.configs import get_config
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.serve import FleetRouter, OraclePolicy, TemporalPolicy, data_mesh
from repro.serve.streams import deferrable_stream

cfg = get_config("h2o-danube-1.8b")
r, n = len(DEFAULT_REGIONS), 3000
caps = np.full((r, 3), np.inf)
caps[:, 1:] = n / (r * 24)
grid = CarbonGrid.fully_connected(DEFAULT_REGIONS,
                                  latency_penalty=1.05).repeat(2)
fr = FleetRouter(cfg, grid=grid, policy=TemporalPolicy(
    OraclePolicy(FleetRouter(cfg).infra), caps, max_defer_h=23))
batch, region, t = deferrable_stream(n, r, seed=7, slack_range_h=(23, 23))
out = {}
for d in (None, 4):
    res, st = fr.route_stream_with_state(
        batch, region, t, mesh=None if d is None else data_mesh(d))
    out[str(d)] = {k: np.asarray(v).tolist() for k, v in dict(
        target=res.target, exec_region=st.exec_region,
        exec_hour=st.exec_hour, shed=st.shed).items()}
print("DECISIONS", json.dumps(dict(out, arrival=t.tolist(),
                                   slack=batch.slack_h.tolist())))
"""


def test_day_ahead_batch_window_on_two_day_horizon():
    """A 24-h completion window (slack 23) on a 2-day repeated grid: every
    row executes at an absolute hour within [arrival, arrival + slack],
    rows arriving late in day one run in day two, and the decisions of
    the one-device program and of the sharded one on 4 fake devices are
    bitwise equal (a fresh process: the only place the device-count
    override may exist)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", DAY_AHEAD],
                          capture_output=True, text=True, timeout=600,
                          cwd=root, env={**os.environ, "PYTHONPATH": "src",
                                         "JAX_PLATFORMS": "cpu"})
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("DECISIONS")]
    assert line, proc.stderr[-2000:]
    got = json.loads(line[0].split(" ", 1)[1])
    assert got["None"] == got["4"]
    eh = np.asarray(got["None"]["exec_hour"])
    arr = np.floor(got["arrival"]).astype(int)
    slack = np.asarray(got["slack"])
    assert ((eh >= arr) & (eh <= arr + slack)).all()
    late = (arr >= 18) & (slack == 23) & ~np.asarray(got["None"]["shed"])
    assert late.any() and (eh[late] >= 24).any()
    assert (eh > 23).any() and eh.max() <= 46


class TestLearnedFactorized:
    """ISSUE-5 tentpole: LearnedPolicy rides the factorized engines."""

    def test_scores_from_factors_matches_sweep(self, cfg, base):
        """With no WAN hop the factorized hook IS the sweep scorer — same
        features, same fitted model — for both the CI-linear and the
        generic schedulers."""
        import jax.numpy as jnp
        from repro.core.schedulers import RegressionScheduler

        n = 512
        batch, region, t_hours = _stream(n, seed=11)
        w = batch.workload(cfg)
        hour = jnp.asarray(np.floor(t_hours).astype(np.int32) % 24)
        home = jnp.asarray(region.astype(np.int32))
        env = carbon_model.Environment(
            ci=base.grid.table[home, hour],
            interference=jnp.ones(3, jnp.float32),
            net_slowdown=jnp.ones(2, jnp.float32))
        factors = carbon_model.energy_factors_batch(
            w, base.infra, env.interference, env.net_slowdown)
        for sched_cls in (ClassificationScheduler, RegressionScheduler):
            lp = _learned_policy(sched_cls)
            sweep = lp.scores(w, env, batch.avail, hour=hour)
            fact = lp.scores_from_factors(
                factors, w, env.ci, batch.avail, hour=hour,
                interference=env.interference,
                net_slowdown=env.net_slowdown)
            np.testing.assert_allclose(np.asarray(sweep), np.asarray(fact),
                                       rtol=1e-5)

    def test_ci_linear_einsum_matches_generic_inference(self, cfg, base,
                                                        xgrid):
        """The probed-sensitivity einsum path (ci_sens) and the generic
        per-candidate re-featurization agree on every (region, tier) pair
        score — the learned analogue of einsum-vs-sweep parity."""
        import jax.numpy as jnp

        n = 512
        batch, region, t_hours = _stream(n, seed=12)
        w = batch.workload(cfg)
        hour = jnp.asarray(np.floor(t_hours).astype(np.int32) % 24)
        home = jnp.asarray(region.astype(np.int32))
        home_ci = xgrid.table[home, hour]
        cand = xgrid.table[..., 2:][:, hour, :]  # (R, N, 3)
        factors = carbon_model.energy_factors_batch(
            w, base.infra, jnp.ones(3, jnp.float32),
            jnp.ones(2, jnp.float32))
        lp = _learned_policy()
        assert lp.ci_sens is not None  # classification is CI-linear
        generic = dataclasses.replace(lp, ci_sens=None)
        a = lp.pair_scores_from_factors(factors, w, home_ci, cand,
                                        batch.avail, hour=hour)
        b = generic.pair_scores_from_factors(factors, w, home_ci, cand,
                                             batch.avail, hour=hour)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)

    def test_learned_joint_deferral_conserves(self, cfg, base):
        """A learned scheduler on the joint (region, tier, hour) engine:
        decisions respect deadlines, caps, and conservation exactly like
        the oracle (the admission machinery is shared)."""
        n = 1500
        batch, region, t_hours = deferrable_stream_multiday(
            n, N_REGIONS, n_days=2, seed=4)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = max(1.0, 0.4 * n / (N_REGIONS * 48))
        grid = CarbonGrid.fully_connected(DEFAULT_REGIONS, n_days=2)
        fr = FleetRouter(cfg, grid=grid, policy=TemporalPolicy(
            _learned_policy(), caps, max_defer_h=16))
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        shed = np.asarray(state.shed)
        defer = np.asarray(state.defer_hours)
        assert (defer >= 0).all()
        assert (defer <= np.minimum(batch.slack_h, 16)).all()
        assert (defer[batch.slack_h == 0] == 0).all()
        assert int(np.asarray(res.counts).sum()) + int(shed.sum()) == n
        assert int(res.deferred_count) > 0

    def test_factorless_decide_on_rtt_grid_raises(self, cfg, base):
        """A LearnedPolicy fit without infra has no way to compute the
        EnergyFactors the WAN-hop gate needs: a direct decide() on an
        rtt_s grid must refuse loudly instead of silently degrading to the
        hop-blind legacy sweep."""
        import jax.numpy as jnp

        n = 64
        batch, region, t_hours = _stream(n, seed=14)
        caps = np.full((N_REGIONS, 3), np.inf)
        grid = CarbonGrid.fully_connected(DEFAULT_REGIONS, rtt_s=1.0)
        pol = PlacementPolicy(_learned_policy(), caps, grid=grid)
        w = batch.workload(cfg)
        hour = jnp.asarray(np.floor(t_hours).astype(np.int32) % 24)
        home = jnp.asarray(region.astype(np.int32))
        env = carbon_model.Environment(
            ci=grid.table[home, hour],
            interference=jnp.ones(3, jnp.float32),
            net_slowdown=jnp.ones(2, jnp.float32))
        with pytest.raises(ValueError, match="rtt_s"):
            pol.decide(w, env, batch.avail,
                       pol.initial_state(N_REGIONS, n),
                       region=home, hour=hour)

    def test_learned_rtt_gate_refuses_hop_broken_remotes(self, cfg, base):
        """The WAN-hop QoS gate applies to learned candidates too: with a
        1s hop, tight-budget requests never execute remotely."""
        n = 2000
        rng = np.random.default_rng(13)
        batch = RequestBatch(
            prompt_tokens=rng.integers(16, 2048, n).astype(np.float64),
            max_new_tokens=rng.integers(8, 128, n).astype(np.float64),
            latency_budget_s=rng.choice([0.6, 30.0], n),
            bytes_per_token=np.full(n, 4.0),
            available=np.ones((n, 3), bool))
        region = rng.integers(0, N_REGIONS, n)
        t_hours = rng.uniform(0.0, 24.0, n)
        caps = np.full((N_REGIONS, 3), np.inf)
        caps[:, 1] = caps[:, 2] = 3.0
        grid = CarbonGrid.fully_connected(DEFAULT_REGIONS,
                                          latency_penalty=1.0, rtt_s=1.0)
        fr = FleetRouter(cfg, grid=grid, policy=PlacementPolicy(
            _learned_policy(), caps))
        res, state = fr.route_stream_with_state(batch, region, t_hours)
        moved = (np.asarray(res.exec_region) != region) \
            & ~np.asarray(state.shed)
        tight = np.asarray(batch.latency_budget_s) < 1.0
        assert not moved[tight].any()
