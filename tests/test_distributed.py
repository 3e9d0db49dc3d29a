"""Device-sharded routing hot path tests (ISSUE-8): shard-count invariance
(the ``shard_map`` admission with psum/all_gather reconciliation is
bit-identical to the single-device program — in-process on a 1-device mesh,
and at 1/2/4/8 fake devices in a subprocess, the only place the XLA
device-count override may exist), property-based conservation/caps
invariants lifted onto the sharded path, the mesh-side aggregation against
the host formulas, buffer-donation probes for the routing and settle jits,
mesh-aware ``BatchFormer`` padding, and the ``CapacityLimiter`` refusal."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import carbon_model
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.serve.distributed import DEFAULT_COMPILE_CACHE
from repro.serve import (
    BatchFormer,
    CapacityLimiter,
    FleetRouter,
    OraclePolicy,
    PlacementPolicy,
    RequestBatch,
    RequestQueue,
    TemporalPolicy,
    data_mesh,
    enable_compile_cache,
    serve_stream,
)

ARCH = "h2o-danube-1.8b"
N_REGIONS = len(DEFAULT_REGIONS)


def _stream(n: int, seed: int = 0, n_regions: int = N_REGIONS,
            slack: bool = False):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(16, 4096, n).astype(np.float64)
    new = rng.integers(8, 512, n).astype(np.float64)
    avail = np.ones((n, 3), bool)
    avail[:, 0] = prompt < 2048
    batch = RequestBatch(
        prompt_tokens=prompt, max_new_tokens=new,
        latency_budget_s=rng.choice([0.5, 2.0, 10.0], n),
        bytes_per_token=np.full(n, 4.0), available=avail,
        slack_hours=(rng.integers(0, 6, n).astype(np.float64)
                     if slack else None))
    return batch, rng.integers(0, n_regions, n), rng.uniform(0.0, 24.0, n)


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


@pytest.fixture(scope="module")
def base(cfg):
    return FleetRouter(cfg)


@pytest.fixture(scope="module")
def mesh1():
    return data_mesh(1)


def _routers(cfg, base, cap: float = 30.0):
    """The parity matrix: every admission mode the reconciliation covers,
    each DC and mobile tier admitting ``cap`` requests a window."""
    caps = np.full((N_REGIONS, 3), cap)
    xgrid = CarbonGrid.fully_connected(DEFAULT_REGIONS)
    mk = lambda **kw: FleetRouter(cfg, **kw)
    return {
        "oracle": mk(),
        "placement-diag": mk(policy=PlacementPolicy(
            OraclePolicy(base.infra), caps)),
        "placement-cross": mk(grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps)),
        "placement-uncapped": mk(grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), np.full((N_REGIONS, 3), np.inf))),
        "temporal-joint": mk(grid=xgrid, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=6)),
        "temporal-diag": mk(policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=6)),
        # decide on a forecast, charge at actuals
        "placement-forecast": mk(
            grid=xgrid.forecast_from_actual(sigma_h=0.03),
            policy=PlacementPolicy(OraclePolicy(base.infra), caps)),
        # the Table-1 sweep per candidate region, not the factors
        "placement-legacy": mk(grid=xgrid, policy=PlacementPolicy(
            OraclePolicy(base.infra), caps, factorized=False)),
    }


def _assert_parity(ref, ref_state, res, state):
    """Decisions bit-exact; carbon per-row allclose (the sharded program is
    a different XLA fusion of the same accounting einsum — last-ulp f32
    differences, identical at every device count); aggregates consistent."""
    for k in ("target", "feasible", "exec_region"):
        np.testing.assert_array_equal(np.asarray(getattr(res, k)),
                                      np.asarray(getattr(ref, k)), err_msg=k)
    np.testing.assert_allclose(np.asarray(res.carbon_g),
                               np.asarray(ref.carbon_g), rtol=1e-5)
    np.testing.assert_allclose(float(res.routed_carbon_g),
                               float(ref.routed_carbon_g), rtol=1e-5)
    assert int(res.shed_count) == int(ref.shed_count)
    assert int(res.spilled_count) == int(ref.spilled_count)
    assert int(res.deferred_count) == int(ref.deferred_count)
    ref_shed = getattr(ref_state, "shed", None)
    if ref_shed is not None:
        np.testing.assert_array_equal(np.asarray(state.shed),
                                      np.asarray(ref_shed))
        np.testing.assert_array_equal(np.asarray(state.counts),
                                      np.asarray(ref_state.counts))
    eh = getattr(ref_state, "exec_hour", None)
    if eh is not None:
        np.testing.assert_array_equal(np.asarray(state.exec_hour),
                                      np.asarray(eh))
        np.testing.assert_array_equal(np.asarray(state.defer_hours),
                                      np.asarray(ref_state.defer_hours))


class TestShardedParity:
    """In-process half of the invariance suite: the sharded program (with
    its collectives live — axis size 1) against the single-device program,
    for every admission mode. Multi-device runs in the subprocess test."""

    @pytest.mark.parametrize("name", ["oracle", "placement-diag",
                                      "placement-cross",
                                      "placement-uncapped",
                                      "temporal-joint", "temporal-diag",
                                      "placement-forecast",
                                      "placement-legacy"])
    def test_mesh_matches_single_device(self, cfg, base, mesh1, name):
        fr = _routers(cfg, base)[name]
        batch, region, t = _stream(257, seed=3, slack=True)  # non-pow2 n
        ref, ref_state = fr.route_stream_with_state(batch, region, t)
        res, state = fr.route_stream_with_state(batch, region, t, mesh=mesh1)
        _assert_parity(ref, ref_state, res, state)

    def test_router_mesh_field_is_the_default(self, cfg, base, mesh1):
        caps = np.full((N_REGIONS, 3), 30.0)
        policy = lambda: PlacementPolicy(OraclePolicy(base.infra), caps)
        batch, region, t = _stream(130, seed=7)
        ref = FleetRouter(cfg, policy=policy()).route_stream(batch, region, t)
        res = FleetRouter(cfg, policy=policy(),
                          mesh=mesh1).route_stream(batch, region, t)
        np.testing.assert_array_equal(np.asarray(res.target),
                                      np.asarray(ref.target))
        np.testing.assert_array_equal(np.asarray(res.counts),
                                      np.asarray(ref.counts))

    def test_serve_stream_rides_the_mesh(self, cfg, base, mesh1):
        caps = np.full((N_REGIONS, 3), 20.0)
        batch, region, t = _stream(180, seed=11, slack=True)
        mk = lambda mesh: FleetRouter(cfg, mesh=mesh, policy=TemporalPolicy(
            OraclePolicy(base.infra), caps, max_defer_h=4))
        ref = serve_stream(mk(None), batch, region, t)
        res = serve_stream(mk(mesh1), batch, region, t)
        np.testing.assert_array_equal(res.target, ref.target)
        np.testing.assert_array_equal(res.shed, ref.shed)
        np.testing.assert_array_equal(res.exec_hour, ref.exec_hour)
        np.testing.assert_allclose(res.carbon_g, ref.carbon_g, rtol=1e-5)

    def test_empty_stream_falls_back(self, cfg, mesh1):
        batch, region, t = _stream(0)
        res = FleetRouter(cfg, mesh=mesh1).route_stream(batch, region, t)
        assert int(res.target.shape[0]) == 0

    def test_capacity_limiter_refused(self, cfg, base, mesh1):
        fr = FleetRouter(cfg, policy=CapacityLimiter(
            OraclePolicy(base.infra), np.full((N_REGIONS, 3), 8.0)))
        batch, region, t = _stream(64, seed=1)
        with pytest.raises(NotImplementedError, match="PlacementPolicy"):
            fr.route_stream(batch, region, t, mesh=mesh1)

    def test_mesh_must_be_1d(self, cfg):
        from jax.sharding import Mesh
        mesh2 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                     ("data", "model"))
        batch, region, t = _stream(32, seed=2)
        with pytest.raises(ValueError, match="ONE data axis"):
            FleetRouter(cfg).route_stream(batch, region, t, mesh=mesh2)


def _host_aggregates(res, state, region, n_regions=N_REGIONS):
    """The aggregates as the host formulas give them from the returned
    per-row columns: counts by bincount over rows that were not shed,
    float32 sums, and the shed/spill/defer counts."""
    target = np.asarray(res.target)
    carbon = np.asarray(res.carbon_g)
    exec_region = np.asarray(res.exec_region)
    shed_a = getattr(state, "shed", None)
    shed = np.zeros(len(target), bool) if shed_a is None else np.asarray(
        shed_a)
    defer_a = getattr(state, "defer_hours", None)
    out = dict(
        counts=np.bincount((exec_region * 3 + target)[~shed],
                           minlength=n_regions * 3).reshape(n_regions, 3),
        total_carbon_g=carbon.sum(dtype=np.float32),
        routed_carbon_g=carbon[~shed].sum(dtype=np.float32),
        infeasible_count=int((~np.asarray(res.feasible)).sum()),
        shed_count=int(shed.sum()),
        spilled_count=int(((exec_region != region) & ~shed).sum()),
        deferred_count=0, mean_defer_hours=0.0)
    if defer_a is not None:
        defer = np.asarray(defer_a)
        dmask = (defer > 0) & ~shed
        out["deferred_count"] = int(dmask.sum())
        out["mean_defer_hours"] = defer[dmask].sum() / max(dmask.sum(), 1)
    return out


def _assert_host_aggregates(res, state, region, n_regions=N_REGIONS):
    """Integer aggregates exactly the host formulas', float ones to
    rtol 1e-5."""
    want = _host_aggregates(res, state, region, n_regions)
    for k in ("counts", "infeasible_count", "shed_count", "spilled_count",
              "deferred_count"):
        np.testing.assert_array_equal(np.asarray(getattr(res, k)), want[k],
                                      err_msg=k)
    for k in ("total_carbon_g", "routed_carbon_g", "mean_defer_hours"):
        np.testing.assert_allclose(float(getattr(res, k)), float(want[k]),
                                   rtol=1e-5, err_msg=k)


class TestDeviceAggregation:
    """The sharded call keeps its per-row results on the mesh and sums the
    aggregates there: what it returns is what the host formulas give from
    the same rows, and the reference sums are the single-device
    program's."""

    @pytest.mark.parametrize("name", ["placement-cross", "temporal-joint"])
    def test_per_row_results_stay_on_the_mesh(self, cfg, base, mesh1, name):
        fr = _routers(cfg, base)[name]
        batch, region, t = _stream(257, seed=3, slack=True)
        res, state = fr.route_stream_with_state(batch, region, t, mesh=mesh1)
        fields = dict(target=res.target, carbon_g=res.carbon_g,
                      shed=state.shed, exec_region=res.exec_region)
        if name.startswith("temporal"):
            fields["exec_hour"] = state.exec_hour
        for k, a in fields.items():
            assert isinstance(a, jax.Array), k
            assert a.sharding == NamedSharding(mesh1, P("data")), (
                k, a.sharding)

    @pytest.mark.parametrize("name", ["oracle", "placement-diag",
                                      "placement-cross",
                                      "placement-uncapped",
                                      "temporal-joint", "temporal-diag"])
    def test_device_aggregates_match_host_formulas(self, cfg, base, mesh1,
                                                   name):
        fr = _routers(cfg, base, 1.0)[name]  # sheds, spills and defers
        batch, region, t = _stream(257, seed=3, slack=True)
        ref, _ = fr.route_stream_with_state(batch, region, t)
        res, state = fr.route_stream_with_state(batch, region, t, mesh=mesh1)
        _assert_host_aggregates(res, state, region)
        for k in ("latency_opt_carbon_g", "energy_opt_carbon_g",
                  "oracle_carbon_g"):
            np.testing.assert_allclose(float(getattr(res, k)),
                                       float(getattr(ref, k)), rtol=1e-5,
                                       err_msg=k)


MESH_AGGREGATES = r"""
import json, os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np, jax
from jax.sharding import Mesh
from repro.configs import get_config
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.serve import (FleetRouter, OraclePolicy, PlacementPolicy,
                         RequestBatch, TemporalPolicy)

cfg = get_config("h2o-danube-1.8b", smoke=True)
R = len(DEFAULT_REGIONS)
caps = np.full((R, 3), 1.0)  # sheds, spills and defers
xgrid = CarbonGrid.fully_connected(DEFAULT_REGIONS)
infra = FleetRouter(cfg).infra
routers = {
    "placement": FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
        OraclePolicy(infra), caps)),
    "temporal": FleetRouter(cfg, grid=xgrid, policy=TemporalPolicy(
        OraclePolicy(infra), caps, max_defer_h=6)),
}
out = []
for n in (515, 512):
    rng = np.random.default_rng(0)
    batch = RequestBatch(
        prompt_tokens=rng.integers(16, 512, n).astype(np.float64),
        max_new_tokens=rng.integers(16, 256, n).astype(np.float64),
        latency_budget_s=rng.uniform(0.3, 4.0, n),
        bytes_per_token=np.full(n, 4.0),
        available=rng.random((n, 3)) > 0.1,
        slack_hours=rng.integers(0, 6, n).astype(np.float64))
    region = rng.integers(0, R, n)
    t = rng.uniform(0, 24, n)
    for tag, fr in routers.items():
        ref, _ = fr.route_stream_with_state(batch, region, t)
        for d in (2, 4, 8):
            mesh = Mesh(np.asarray(jax.devices()[:d]), ("data",))
            res, state = fr.route_stream_with_state(batch, region, t,
                                                    mesh=mesh)
            rows = dict(target=res.target, carbon_g=res.carbon_g,
                        shed=state.shed,
                        exec_hour=getattr(state, "exec_hour", None))
            row = dict(
                tag=tag, n=n, d=d, region=region.tolist(),
                placement={k: dict(
                    devices=len(a.sharding.device_set),
                    mesh=a.sharding.mesh == mesh,
                    shards=sorted({s.data.shape[0]
                                   for s in a.addressable_shards}))
                    for k, a in rows.items() if a is not None},
                ref={k: float(getattr(ref, k)) for k in (
                    "latency_opt_carbon_g", "energy_opt_carbon_g",
                    "oracle_carbon_g")})
            for k in ("target", "carbon_g", "feasible", "exec_region",
                      "counts", "total_carbon_g", "routed_carbon_g",
                      "latency_opt_carbon_g", "energy_opt_carbon_g",
                      "oracle_carbon_g", "infeasible_count", "shed_count",
                      "spilled_count", "deferred_count",
                      "mean_defer_hours"):
                row[k] = np.asarray(getattr(res, k)).tolist()
            for k in ("shed", "defer_hours"):
                a = getattr(state, k, None)
                row["state_" + k] = None if a is None else np.asarray(
                    a).tolist()
            out.append(row)
print("MESH_AGGREGATES " + json.dumps(out))
"""


def test_mesh_aggregates_match_host_formulas_subprocess():
    """At 2/4/8 fake devices, for capped cross-region placement and joint
    temporal admission: per-row results stay split over the router's mesh
    where the length is a device multiple (n = 512) and are replicated
    over the mesh where it is not (n = 515), never held by one chip; the
    aggregates summed on the device are the host formulas' from the
    returned rows, and the reference sums the single-device program's."""
    proc = subprocess.run([sys.executable, "-c", MESH_AGGREGATES],
                          capture_output=True, text=True, timeout=560,
                          env={**os.environ, "PYTHONPATH": "src",
                               "JAX_PLATFORMS": "cpu"},
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("MESH_AGGREGATES")]
    assert line, proc.stderr[-2000:]
    got = json.loads(line[0].split(" ", 1)[1])
    assert len(got) == 2 * 2 * 3
    assert all(row["shed_count"] and row["spilled_count"] for row in got)
    for row in got:
        case = (row["tag"], row["n"], row["d"])
        even = row["n"] % row["d"] == 0
        for k, p in row["placement"].items():
            assert p["mesh"] and p["devices"] == row["d"], (case, k, p)
            assert p["shards"] == [row["n"] // row["d"] if even
                                   else row["n"]], (case, k, p)
        res = SimpleNamespace(**{k: np.asarray(v) for k, v in row.items()
                                 if not k.startswith("state_")})
        state = SimpleNamespace(shed=np.asarray(row["state_shed"]),
                                defer_hours=(
                                    None if row["state_defer_hours"] is None
                                    else np.asarray(row["state_defer_hours"])))
        _assert_host_aggregates(res, state, np.asarray(row["region"]))
        for k, v in row["ref"].items():
            np.testing.assert_allclose(row[k], v, rtol=1e-5,
                                       err_msg=f"{case} {k}")


class TestShardedInvariants:
    """Property: the capacity invariants that pin the single-device
    admission hold verbatim on the sharded path — the reconciled ledger is
    the same ledger."""

    N = 160
    R = 2

    @hypothesis.settings(max_examples=6, deadline=None)
    @hypothesis.given(
        caps_flat=st.lists(
            st.one_of(st.integers(0, 4), st.just(np.inf)),
            min_size=6, max_size=6),
        link=st.tuples(st.booleans(), st.booleans()),
        seed=st.integers(0, 3),
    )
    def test_conservation_and_caps_on_sharded_path(self, caps_flat, link,
                                                   seed):
        cfg = get_config(ARCH)
        caps = np.asarray(caps_flat, np.float64).reshape(self.R, 3)
        adjacency = np.eye(self.R, dtype=bool)
        adjacency[0, 1], adjacency[1, 0] = link
        grid = CarbonGrid.from_regions(DEFAULT_REGIONS[:2],
                                       adjacency=adjacency,
                                       latency_penalty=1.03)
        fr = FleetRouter(cfg, regions=DEFAULT_REGIONS[:2], grid=grid,
                         policy=PlacementPolicy(
                             OraclePolicy(FleetRouter(cfg).infra), caps))
        batch, region, t_hours = _stream(self.N, seed=seed,
                                         n_regions=self.R)
        res, state = fr.route_stream_with_state(batch, region, t_hours,
                                                mesh=data_mesh(1))
        shed = np.asarray(state.shed)
        # conservation: every request is either capacity-routed or shed
        assert int(np.asarray(res.counts).sum()) + int(shed.sum()) == self.N
        # the replicated device ledger == the host bincount of the rows
        tgt = np.asarray(res.target)
        ex = (region if state.exec_region is None
              else np.asarray(state.exec_region))
        hour = np.floor(t_hours).astype(int) % 24
        for h in range(24):
            for r in range(self.R):
                for k in range(3):
                    got = int(((hour == h) & (ex == r) & (tgt == k)
                               & ~shed).sum())
                    assert got <= caps[r, k], (h, r, k, got)
        # spill only along adjacency edges
        assert adjacency[region[~shed], ex[~shed]].all()


class TestDonation:
    """Satellite probes: the routing and settle jits consume their per-row
    buffers in place (donation deletes the caller's handle), and the
    sharded program compiles once per (router, mesh, shape) — re-routing
    the same shapes neither retraces nor re-evaluates Table 1."""

    def test_fleet_route_donates_stream_buffers(self, cfg, base):
        fr = FleetRouter(cfg, policy=PlacementPolicy(
            OraclePolicy(base.infra), np.full((N_REGIONS, 3), 30.0)))
        batch, region_np, t = _stream(96, seed=5)
        hour_np = (np.floor(t).astype(np.int32) % fr._horizon_h)
        key = (hour_np % 24) * N_REGIONS + region_np
        order_np = np.argsort(key, kind="stable").astype(np.int32)
        inv_np = np.empty_like(order_np)
        inv_np[order_np] = np.arange(len(order_np), dtype=np.int32)
        w = batch.workload(fr.cfg)
        region = jnp.asarray(region_np, jnp.int32)
        hour = jnp.asarray(hour_np)
        order, inv = jnp.asarray(order_np), jnp.asarray(inv_np)
        slack = jnp.asarray(batch.slack_h)
        state = fr.policy.initial_state(N_REGIONS, len(batch))
        fr._fleet_route(w, batch.avail, region, hour, fr._ci_table,
                        fr._ci_fc, state, order, inv, slack, None, None)
        # int32 stream tags alias the int32 outputs — donated AND consumed,
        # so the caller's handle is gone (no second resident copy); leaves
        # XLA cannot alias (the f32 workload columns) stay alive, which is
        # exactly what the partial-donation advisory says
        assert region.is_deleted() and hour.is_deleted()
        # the shared CI table must survive for the next call
        assert not fr._ci_table.is_deleted()

    def test_settle_carbon_donates_row_buffers(self, cfg, base):
        from repro.serve.queue import _settle_carbon
        batch, region_np, t = _stream(64, seed=6)
        n = len(batch)
        home = jnp.asarray(region_np, jnp.int32)
        er = jnp.asarray(region_np, jnp.int32)
        eh = jnp.asarray(np.floor(t).astype(np.int32) % 24)
        tgt = jnp.asarray(np.zeros(n, np.int32))
        w = batch.workload(cfg)
        out = _settle_carbon(w, base.infra,
                             base._interference, base._net_slowdown,
                             base._ci_table, home, er, eh, tgt)
        assert out.shape == (n,)
        # the (N,) f32 output aliases one of the donated f32 workload
        # columns — that column's caller handle is consumed in place
        assert any(leaf.is_deleted() for leaf in jax.tree.leaves(w))
        assert not base._ci_table.is_deleted()

    def test_sharded_program_compiles_once(self, cfg, base, mesh1,
                                           monkeypatch):
        calls = {"n": 0}
        real = carbon_model.evaluate

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(carbon_model, "evaluate", counting)
        fr = FleetRouter(cfg, mesh=mesh1, policy=PlacementPolicy(
            OraclePolicy(FleetRouter(cfg).infra),
            np.full((N_REGIONS, 3), 30.0)))
        batch, region, t = _stream(128, seed=8)
        fr.route_stream(batch, region, t)
        traced = calls["n"]
        # factorized: ONE Table-1 evaluation per trace of the local body
        # (shard_map traces it twice: abstract eval, then lowering)
        assert traced <= 2
        fr.route_stream(batch, region, t)  # same shapes: cached program
        assert calls["n"] == traced


class TestBatchFormerMesh:
    def test_meshless_padding_unchanged(self):
        from repro.serve.forecast import pad_pow2
        bf = BatchFormer()
        for k in (1, 5, 16, 17, 100):
            assert bf._pad_to(k) == pad_pow2(k, bf.min_pad)

    def test_mesh_padding_is_device_multiple_pow2(self, mesh1):
        class FakeMesh:
            class devices:
                size = 4

        bf = BatchFormer(mesh=FakeMesh(), min_pad=16)
        assert bf._pad_to(1) == 64        # 4 * pad_pow2(1)
        assert bf._pad_to(64) == 64
        assert bf._pad_to(65) == 128      # 4 * pad_pow2(17)
        # a real 1-device mesh degenerates to the meshless buckets
        assert BatchFormer(mesh=mesh1)._pad_to(17) == 32

    def test_draft_shapes_divide_the_mesh(self, cfg):
        class FakeMesh:
            class devices:
                size = 4

        batch, region, t = _stream(37, seed=9)
        queue = RequestQueue.from_stream(batch, region,
                                         np.floor(t).astype(np.int32))
        former = BatchFormer(mesh=FakeMesh(), min_pad=16)
        drafts = former.draft(queue, queue.ready(24, 0), 0)
        assert drafts and all(fb.pad_to % 4 == 0 for fb in drafts)


@pytest.fixture
def restore_cache_config():
    """Put jax's persistent-cache settings back after a test moved them."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_enable_compile_cache_configures_jax(tmp_path, monkeypatch,
                                             restore_cache_config):
    """``JAX_COMPILATION_CACHE_DIR`` wins: the cache is configured there
    and a fresh compile writes its entry there."""
    want = str(tmp_path / "jit-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    d = enable_compile_cache()
    assert d == want and os.path.isdir(d)
    assert jax.config.jax_compilation_cache_dir == d
    jax.block_until_ready(jax.jit(lambda x: x * 3.0 + 0.25)(jnp.ones(7)))
    assert os.listdir(d), "the compile wrote no cache entry"


def test_enable_compile_cache_default_in_checkout(monkeypatch,
                                                  restore_cache_config):
    """Without the variable the cache goes to ONE fixed, git-ignored
    directory at the root of the checkout."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert d == DEFAULT_COMPILE_CACHE
    assert os.path.dirname(d) == root and os.path.isdir(d)
    assert jax.config.jax_compilation_cache_dir == d
    with open(os.path.join(root, ".gitignore")) as f:
        assert os.path.basename(d) + "/" in f.read().split()


@pytest.mark.slow
def test_shard_count_invariance_subprocess():
    """The headline invariance matrix: decisions bit-identical at 1/2/4/8
    fake devices for capped cross-region placement AND joint temporal
    admission (the two reconciliation-heavy modes), in a fresh process
    (the only place the XLA device-count override may exist)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np, jax
from jax.sharding import Mesh
from repro.configs import get_config
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.serve import (FleetRouter, OraclePolicy, PlacementPolicy,
                         RequestBatch, TemporalPolicy)

cfg = get_config("h2o-danube-1.8b", smoke=True)
R = len(DEFAULT_REGIONS)
rng = np.random.default_rng(0)
n = 515  # deliberately not a device multiple
batch = RequestBatch(
    prompt_tokens=rng.integers(16, 512, n).astype(np.float64),
    max_new_tokens=rng.integers(16, 256, n).astype(np.float64),
    latency_budget_s=rng.uniform(0.3, 4.0, n),
    bytes_per_token=np.full(n, 4.0),
    available=rng.random((n, 3)) > 0.1,
    slack_hours=rng.integers(0, 6, n).astype(np.float64))
region = rng.integers(0, R, n)
t = rng.uniform(0, 24, n)
caps = np.full((R, 3), 25.0)
xgrid = CarbonGrid.fully_connected(DEFAULT_REGIONS)
routers = {
    "placement": FleetRouter(cfg, grid=xgrid, policy=PlacementPolicy(
        OraclePolicy(FleetRouter(cfg).infra), caps)),
    "temporal": FleetRouter(cfg, grid=xgrid, policy=TemporalPolicy(
        OraclePolicy(FleetRouter(cfg).infra), caps, max_defer_h=6)),
}
for tag, fr in routers.items():
    ref, ref_state = fr.route_stream_with_state(batch, region, t)
    for d in (1, 2, 4, 8):
        mesh = Mesh(np.asarray(jax.devices()[:d]), ("data",))
        res, state = fr.route_stream_with_state(batch, region, t, mesh=mesh)
        for k in ("target", "feasible", "exec_region"):
            assert np.array_equal(np.asarray(getattr(res, k)),
                                  np.asarray(getattr(ref, k))), (tag, d, k)
        assert np.array_equal(np.asarray(state.shed),
                              np.asarray(ref_state.shed)), (tag, d)
        assert np.array_equal(np.asarray(state.counts),
                              np.asarray(ref_state.counts)), (tag, d)
        np.testing.assert_allclose(np.asarray(res.carbon_g),
                                   np.asarray(ref.carbon_g), rtol=1e-5)
print("SHARD_INVARIANCE_OK")
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=560,
                          env={**os.environ, "PYTHONPATH": "src",
                               "JAX_PLATFORMS": "cpu"},
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SHARD_INVARIANCE_OK" in proc.stdout, proc.stderr[-2000:]
