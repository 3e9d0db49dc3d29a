"""Unit + property tests for the Table-1 carbon model (repro.core)."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Environment,
    Target,
    carbon_model,
    pack_infra,
    paper_fleet,
    tpu_fleet,
)
from repro.core.carbon_model import evaluate, evaluate_energy, feasible
from repro.core.workloads import ALL_PAPER_WORKLOADS, Workload, by_name

INFRA = pack_infra(paper_fleet(), "act")
INFRA_LCA = pack_infra(paper_fleet(), "lca")
ENV = Environment.make(300.0, 350.0, 280.0, 320.0)


def _w(flops=1e9, mem=1e7, din=1e5, dout=1e4, lat=0.1, cont=0.0, fps=0.0):
    return Workload.make(flops, mem, din, dout, lat, cont, fps)


class TestTable1Structure:
    def test_shapes(self):
        b = evaluate(_w(), INFRA, ENV)
        assert b.op_cf.shape == (3, 5)
        assert b.emb_cf.shape == (3, 5)
        assert b.latency.shape == (3,)

    def test_nonnegative(self):
        b = evaluate(_w(), INFRA, ENV)
        assert bool((b.op_cf >= 0).all()) and bool((b.emb_cf >= 0).all())

    def test_uninvolved_components_are_zero(self):
        """Table 1: '-' cells. Mobile target involves no network carbon;
        Edge-DC target involves no core-network carbon."""
        b = evaluate(_w(), INFRA, ENV)
        M, E, H = Target.MOBILE, Target.EDGE_DC, Target.HYPERSCALE_DC
        EN, CN = 1, 3  # Component.EDGE_NETWORK, CORE_NETWORK
        assert b.op_cf[M, EN] == 0 and b.op_cf[M, CN] == 0
        assert b.emb_cf[M, EN] == 0 and b.emb_cf[M, CN] == 0
        assert b.op_cf[E, CN] == 0 and b.emb_cf[E, CN] == 0
        # Hyperscale target touches everything
        assert bool((b.op_cf[H] > 0).all())

    def test_latency_ordering_structure(self):
        """Offload latency = comm + compute: DC latency includes both hops."""
        b = evaluate(_w(), INFRA, ENV)
        assert b.latency[2] >= b.t_comm[0] + b.t_comm[1]
        assert b.latency[1] >= b.t_comm[0]


class TestCarbonProperties:
    @hypothesis.given(
        flops=st.floats(1e6, 1e12), din=st.floats(1e2, 1e7),
        ci_scale=st.floats(0.1, 3.0))
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_operational_cf_linear_in_ci(self, flops, din, ci_scale):
        """Operational CF is linear in carbon intensity (Table 1)."""
        w = _w(flops=flops, din=din)
        b1 = evaluate(w, INFRA, ENV)
        env2 = Environment(ci=ENV.ci * ci_scale, interference=ENV.interference,
                           net_slowdown=ENV.net_slowdown)
        b2 = evaluate(w, INFRA, env2)
        np.testing.assert_allclose(np.asarray(b2.op_cf),
                                   np.asarray(b1.op_cf) * ci_scale,
                                   rtol=1e-5)
        # embodied CF does not depend on CI
        np.testing.assert_allclose(np.asarray(b2.emb_cf),
                                   np.asarray(b1.emb_cf), rtol=1e-6)

    @hypothesis.given(flops=st.floats(1e6, 1e13))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_cf_monotone_in_flops(self, flops):
        """More compute never reduces carbon (fixed everything else)."""
        b1 = evaluate(_w(flops=flops), INFRA, ENV)
        b2 = evaluate(_w(flops=flops * 2), INFRA, ENV)
        assert bool((b2.total_cf >= b1.total_cf - 1e-9).all())

    @hypothesis.given(n_user=st.floats(2.0, 1e4))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_sharing_amortizes_edge_dc(self, n_user):
        """More users co-sharing the edge DC -> lower per-user edge CF."""
        w = _w()
        few = evaluate(w, INFRA, ENV)
        many = evaluate(w, INFRA.replace(
            n_user_edge=jnp.asarray(float(INFRA.n_user_edge) * n_user)), ENV)
        assert float(many.total_cf[1]) <= float(few.total_cf[1]) + 1e-9

    @hypothesis.given(interf=st.floats(1.0, 8.0))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_interference_slows_and_dirties(self, interf):
        """Co-located interference scales T_comp -> latency and CF rise."""
        env = Environment.make(300.0, 350.0, 280.0, 320.0,
                               interference=(interf, 1.0, 1.0))
        b0 = evaluate(_w(), INFRA, ENV)
        b1 = evaluate(_w(), INFRA, env)
        assert float(b1.latency[0]) >= float(b0.latency[0])
        assert float(b1.total_cf[0]) >= float(b0.total_cf[0]) - 1e-9

    def test_energy_is_ci_independent(self):
        w = _w()
        e1 = evaluate_energy(w, INFRA, ENV)
        env2 = Environment(ci=ENV.ci * 7.0, interference=ENV.interference,
                           net_slowdown=ENV.net_slowdown)
        e2 = evaluate_energy(w, INFRA, env2)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-6)


class TestFeasibility:
    def test_impossible_latency(self):
        w = _w(flops=1e15, lat=1e-4)
        b = evaluate(w, INFRA, ENV)
        assert not bool(feasible(b, w).any())

    def test_streaming_needs_fps(self):
        """A stream whose per-frame payload exceeds frame-time bandwidth is
        infeasible on offload targets but fine locally."""
        w = by_name("fortnite").workload
        b = evaluate(w, INFRA, ENV)
        ok = feasible(b, w)
        assert bool(ok[0])  # local play always feasible

    def test_pick_target_falls_back(self):
        """When nothing is feasible the pick is still a valid target
        (paper Fig 10c behaviour)."""
        w = _w(flops=1e16, lat=1e-5)
        b = evaluate(w, INFRA, ENV)
        t = carbon_model.optimal_target(b, w)
        assert 0 <= int(t) <= 2

    def test_pick_target_all_unavailable_resolves_to_mobile(self):
        """Pinned degenerate behaviour (documented on pick_target): with an
        all-False availability mask every masked score is +inf and argmin
        resolves to index 0 — the request falls back to Target.MOBILE, the
        only tier that always physically exists — regardless of which tier
        the scores or the fallback would otherwise prefer."""
        score = jnp.asarray([9.0, 1.0, 5.0])  # would pick EDGE_DC
        fallback = jnp.asarray([7.0, 3.0, 1.0])  # would pick HYPERSCALE_DC
        none_avail = jnp.zeros(3, bool)
        for ok in (jnp.ones(3, bool), jnp.zeros(3, bool)):
            t = carbon_model.pick_target(score, ok, fallback,
                                         avail=none_avail)
            assert int(t) == int(Target.MOBILE)


class TestEmbodiedModels:
    def test_act_below_lca(self):
        """Paper §4.3: ACT estimates ~28% below the LCA reports."""
        w = _w()
        b_act = evaluate(w, INFRA, ENV)
        b_lca = evaluate(w, INFRA_LCA, ENV)
        act_emb = float(b_act.emb_cf[0].sum())
        lca_emb = float(b_lca.emb_cf[0].sum())
        assert act_emb < lca_emb

    def test_act_model_bottom_up(self):
        from repro.core.embodied import act_fleet_embodied_g
        est = act_fleet_embodied_g()
        # sanity: phone O(10kg), servers O(100kg-1t)
        assert 5e3 < est["pixel3"] < 1e5
        assert 1e5 < est["p3.2xlarge-v100"] < 1e7


class TestTpuFleet:
    def test_router_fleet_packs(self):
        infra = pack_infra(tpu_fleet(), "act")
        b = evaluate(_w(flops=1e12), infra, ENV)
        assert bool(jnp.isfinite(b.total_cf).all())


def test_all_paper_workloads_evaluate():
    for info in ALL_PAPER_WORKLOADS:
        b = evaluate(info.workload, INFRA, ENV)
        assert bool(jnp.isfinite(b.total_cf).all()), info.name


class TestFactorizedEvaluator:
    """ISSUE-4 acceptance: operational carbon is linear in CI, so one
    Table-1 evaluation at unit CI + an einsum against arbitrary CI rows
    must match the sweep-based evaluation to fp32 tolerance."""

    def _stream(self, n=256, seed=0):
        rng = np.random.default_rng(seed)
        from repro.core.workloads import batch_workloads

        w = batch_workloads(
            flops=rng.uniform(1e8, 1e13, n),
            mem_bytes=rng.uniform(1e6, 1e10, n),
            data_in=rng.uniform(1e3, 1e7, n),
            data_out=rng.uniform(1e3, 1e6, n),
            latency_req=rng.choice([0.05, 0.5, 2.0, 30.0], n),
        )
        ci = rng.uniform(20.0, 700.0, (n, 5)).astype(np.float32)
        avail = rng.random((n, 3)) < 0.9
        avail[~avail.any(axis=1)] = True
        return w, jnp.asarray(ci), jnp.asarray(avail)

    def test_total_cf_matches_sweep_to_fp32_tolerance(self):
        w, ci, avail = self._stream()
        interference = jnp.ones((3,), jnp.float32)
        net_slowdown = jnp.ones((2,), jnp.float32)
        f = carbon_model.energy_factors_batch(w, INFRA, interference,
                                              net_slowdown)
        got = carbon_model.total_cf_from_factors(f, ci)
        env = Environment(ci=ci, interference=interference,
                          net_slowdown=net_slowdown)
        ref = carbon_model.route_many_envs(w, INFRA, env, avail)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref.total_cf),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(f.latency),
                                   np.asarray(ref.latency), rtol=1e-6)

    def test_route_outputs_from_factors_match_sweep(self):
        """Same picks (carbon/latency/energy), same feasibility mask."""
        w, ci, avail = self._stream(seed=3)
        interference = jnp.asarray([1.1, 1.0, 1.3], jnp.float32)
        net_slowdown = jnp.asarray([1.2, 1.0], jnp.float32)
        env = Environment(ci=ci, interference=interference,
                          net_slowdown=net_slowdown)
        f = carbon_model.energy_factors_batch(w, INFRA, interference,
                                              net_slowdown)
        got = carbon_model.route_many_from_factors(f, w, ci, avail)
        ref = carbon_model.route_many_envs(w, INFRA, env, avail)
        np.testing.assert_array_equal(np.asarray(got.ok), np.asarray(ref.ok))
        np.testing.assert_array_equal(np.asarray(got.target),
                                      np.asarray(ref.target))
        np.testing.assert_array_equal(np.asarray(got.target_latency),
                                      np.asarray(ref.target_latency))
        np.testing.assert_array_equal(np.asarray(got.target_energy),
                                      np.asarray(ref.target_energy))
        np.testing.assert_allclose(np.asarray(got.total_cf),
                                   np.asarray(ref.total_cf), rtol=1e-5)

    def test_energy_j_matches_evaluate_energy(self):
        w, _, _ = self._stream(seed=5)
        interference = jnp.ones((3,), jnp.float32)
        net_slowdown = jnp.ones((2,), jnp.float32)
        f = carbon_model.energy_factors_batch(w, INFRA, interference,
                                              net_slowdown)
        env = Environment.make(300.0, 350.0, 280.0, 320.0)
        ref = jax.vmap(evaluate_energy, in_axes=(0, None, None))(w, INFRA,
                                                                 env)
        np.testing.assert_allclose(np.asarray(f.energy_j), np.asarray(ref),
                                   rtol=1e-6)

    def test_qos_feasible_with_wan_hop(self):
        """The extra-latency seam: zero hop reproduces ``feasible`` exactly,
        and a hop bigger than every budget kills every target (the hop
        applies uniformly per-target; remote-MOBILE exclusion is structural
        in the placement layer, not here)."""
        w = _w(lat=0.1)
        b = evaluate(w, INFRA, ENV)
        base = carbon_model.qos_feasible(b.latency, b.t_comm, w)
        np.testing.assert_array_equal(
            np.asarray(carbon_model.qos_feasible(b.latency, b.t_comm, w,
                                                 0.0)),
            np.asarray(base))
        hop = carbon_model.qos_feasible(b.latency, b.t_comm, w, 1e9)
        assert not bool(np.asarray(hop).any())


class TestBatchedBuild:
    """The Table-1 arrays are built whole: under ``vmap`` a per-entry
    ``.at[t, c].set`` write would become one dynamic-update-slice over the
    whole batched (N, 3, 5) array, a full pass over it per entry."""

    @staticmethod
    def _rows(n, seed=11):
        rng = np.random.default_rng(seed)
        from repro.core.workloads import batch_workloads

        stream = rng.random(n) < 0.25  # cloud-gaming style streams too
        return batch_workloads(
            flops=rng.uniform(1e8, 1e13, n),
            mem_bytes=rng.uniform(1e6, 1e10, n),
            data_in=rng.uniform(1e3, 1e7, n),
            data_out=rng.uniform(1e3, 1e6, n),
            latency_req=rng.choice([0.05, 0.5, 2.0, 30.0], n),
            continuous=stream.astype(np.float32),
            fps_req=np.where(stream, rng.choice([30.0, 60.0], n), 0.0),
            mobile_eff_scale=rng.uniform(0.5, 2.0, n),
        )

    @pytest.mark.parametrize("entry", ["energy_factors_batch",
                                       "evaluate_batch"])
    def test_batched_build_has_no_per_entry_writes(self, entry):
        w = self._rows(4096)
        interference = jnp.asarray([1.1, 1.0, 1.3], jnp.float32)
        net_slowdown = jnp.asarray([1.2, 1.0], jnp.float32)
        if entry == "energy_factors_batch":
            args = (w, INFRA, interference, net_slowdown)
        else:
            args = (w, INFRA, Environment.make(300.0, 350.0, 280.0, 320.0,
                                               interference, net_slowdown))
        hlo = jax.jit(getattr(carbon_model, entry)).lower(*args).compile()
        text = hlo.as_text()
        assert "dynamic-update-slice" not in text
        assert "scatter" not in text

    @pytest.mark.parametrize("fleet", [tpu_fleet, paper_fleet],
                             ids=["tpu_fleet", "paper_fleet"])
    def test_batched_matches_row_by_row(self, fleet):
        """``vmap(evaluate)`` against ``evaluate`` row by row, both op by op.
        The jitted batch is held to the Table-1 arrays' tolerance only: the
        compiler contracts multiply-adds in fused loops (about 1 ulp)."""
        n = 256
        w = self._rows(n, seed=5)
        infra = pack_infra(fleet(), "act")
        env = Environment.make(300.0, 350.0, 280.0, 320.0,
                               interference=(1.1, 1.0, 1.3),
                               net_slowdown=(1.2, 1.4))
        batched = jax.vmap(evaluate, in_axes=(0, None, None))
        got = batched(w, infra, env)
        fused = jax.jit(batched)(w, infra, env)
        rows = [evaluate(jax.tree.map(lambda a, i=i: a[i], w), infra, env)
                for i in range(n)]
        ref = jax.tree.map(lambda *xs: np.stack(xs), *rows)
        M, E = Target.MOBILE, Target.EDGE_DC
        EN, CN = 1, 3  # Component.EDGE_NETWORK, CORE_NETWORK
        for out in (got, fused):
            for name in ("op_cf", "emb_cf"):
                b = np.asarray(getattr(out, name))
                assert b.shape == (n, 3, 5) and b.dtype == np.float32
                np.testing.assert_allclose(b, getattr(ref, name), rtol=1e-6,
                                           err_msg=name)
                assert (b[:, M, EN] == 0).all() and (b[:, M, CN] == 0).all()
                assert (b[:, E, CN] == 0).all()
        for name in ("latency", "t_comp", "t_comm"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          getattr(ref, name), err_msg=name)
