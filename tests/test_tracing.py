"""The program's own tracing: ``gs.*`` host spans on the profiler's clock
(nesting, one step span per serve step, one fetch and one commit span per
draft), the ``admit_rounds`` counter of the admission programs (its value
on uncapped, zero-cap and capped streams; identical on 1, 2 and 4 fake
devices; one entry per serve draft), and decisions pinned to the values
they had before the spans, scopes and counter went in."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.serve import (
    BatchFormer,
    FleetRouter,
    OraclePolicy,
    PlacementPolicy,
    RequestBatch,
    TemporalPolicy,
    WorkerPool,
    data_mesh,
    serve_stream,
)

ARCH = "h2o-danube-1.8b"
R = len(DEFAULT_REGIONS)
XGRID = CarbonGrid.fully_connected(DEFAULT_REGIONS)


def _stream(n: int, seed: int = 0, slack: bool = False):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(16, 4096, n).astype(np.float64)
    avail = np.ones((n, 3), bool)
    avail[:, 0] = prompt < 2048
    batch = RequestBatch(
        prompt_tokens=prompt,
        max_new_tokens=rng.integers(8, 512, n).astype(np.float64),
        latency_budget_s=rng.choice([0.5, 2.0, 10.0], n),
        bytes_per_token=np.full(n, 4.0), available=avail,
        slack_hours=(rng.integers(0, 6, n).astype(np.float64)
                     if slack else None))
    return batch, rng.integers(0, R, n), rng.uniform(0.0, 24.0, n)


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


@pytest.fixture(scope="module")
def infra(cfg):
    return FleetRouter(cfg).infra


def _router(cfg, infra, kind: str, caps) -> FleetRouter:
    caps = np.full((R, 3), caps, np.float64)
    inner = OraclePolicy(infra)
    if kind == "cross":
        return FleetRouter(cfg, grid=XGRID,
                           policy=PlacementPolicy(inner, caps))
    if kind == "diag":
        return FleetRouter(cfg, policy=PlacementPolicy(inner, caps))
    if kind == "legacy":
        return FleetRouter(cfg, grid=XGRID, policy=PlacementPolicy(
            inner, caps, factorized=False))
    return FleetRouter(cfg, grid=XGRID, policy=TemporalPolicy(
        inner, caps, max_defer_h=6))


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; its result and the ``gs.*`` host
    spans as (name, start, end, stats), in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    spans = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("gs."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    return out, sorted(spans, key=lambda s: s[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


class TestSpans:
    def test_route_stream_spans(self, cfg, infra, tmp_path):
        fr = _router(cfg, infra, "cross", 20.0)
        batch, region, t = _stream(600)
        fr.route_stream(batch, region, t)  # compile outside the trace
        _, spans = _traced(tmp_path, lambda: jax.block_until_ready(
            fr.route_stream(batch, region, t)))
        assert {s[0] for s in spans} == {"gs.route.prepare",
                                         "gs.route.dispatch"}
        # arrival hours in route_stream_with_state, then _route_args
        prep, (disp,) = (_named(spans, "gs.route.prepare"),
                         _named(spans, "gs.route.dispatch"))
        assert len(prep) == 2 and prep[1][2] <= disp[1]

    def test_sharded_route_spans(self, cfg, infra, tmp_path):
        fr = _router(cfg, infra, "cross", 20.0)
        batch, region, t = _stream(600)
        mesh = data_mesh(1)
        fr.route_stream(batch, region, t, mesh=mesh)
        _, spans = _traced(tmp_path, lambda: fr.route_stream(
            batch, region, t, mesh=mesh))
        names = [s[0] for s in spans]
        assert names == ["gs.route.prepare", "gs.route.prepare",
                         "gs.route.dispatch", "gs.route.aggregate"]

    def test_serve_stream_spans(self, cfg, infra, tmp_path):
        fr = _router(cfg, infra, "cross", 1.0)
        batch, region, t = _stream(3000, seed=1)

        def run():
            pool = WorkerPool(R, slots_per_worker=40.0)
            for r in range(R):
                for tier in (1, 2):
                    pool.launch(r, tier, n=2)
            return serve_stream(fr, batch, region, t, pool=pool,
                                former=BatchFormer(max_batch=128))

        run()
        res, spans = _traced(tmp_path, run)
        steps = _named(spans, "gs.serve.step")
        assert [s[3]["step_num"] for s in steps] == list(range(24))
        n_drafts = sum(s.n_batches for s in res.steps)
        assert n_drafts > 24  # peak hours take more than one draft
        for name in ("gs.serve.pool", "gs.serve.draft", "gs.serve.refit"):
            assert len(_named(spans, name)) == 24, name
        for name in ("gs.serve.fetch", "gs.serve.commit",
                     "gs.route.prepare", "gs.route.dispatch"):
            assert len(_named(spans, name)) == n_drafts, name
        # every span but the set-up and the settle sits in exactly one
        # step, and one draft's fetch and commit share its (step, draft)
        # identifier
        children = [s for s in spans if s[0] not in (
            "gs.serve.setup", "gs.serve.step", "gs.serve.settle")]
        for c in children:
            around = [s for s in steps if _inside(c, s)]
            assert len(around) == 1, c[0]
            if "step" in c[3]:
                assert c[3]["step"] == around[0][3]["step_num"]
        ids = lambda name: [(s[3]["step"], s[3]["draft"])
                            for s in _named(spans, name)]
        assert ids("gs.serve.fetch") == ids("gs.serve.commit")
        per_step = [s.n_batches for s in res.steps]
        assert ids("gs.serve.fetch") == [
            (now, j) for now, k in enumerate(per_step) for j in range(k)]
        (setup,), (settle,) = (_named(spans, "gs.serve.setup"),
                               _named(spans, "gs.serve.settle"))
        assert setup[2] <= steps[0][1] and settle[1] >= steps[-1][2]


class TestAdmitRounds:
    @pytest.mark.parametrize("kind", ["cross", "temporal"])
    @pytest.mark.parametrize("caps,rounds", [(np.inf, 1), (0.0, 0)])
    def test_uncapped_one_round_zero_caps_none(self, cfg, infra, kind, caps,
                                               rounds):
        fr = _router(cfg, infra, kind, caps)
        _, state = fr.route_stream_with_state(*_stream(500, slack=True))
        assert int(state.admit_rounds) == rounds

    @pytest.mark.parametrize("kind,rounds", [("diag", 3), ("legacy", 12)])
    def test_unrolled_programs_report_their_rounds(self, cfg, infra, kind,
                                                   rounds):
        fr = _router(cfg, infra, kind, 20.0)
        _, state = fr.route_stream_with_state(*_stream(500))
        assert int(state.admit_rounds) == rounds

    def test_serve_stream_one_count_per_draft(self, cfg, infra):
        fr = _router(cfg, infra, "cross", 4.0)
        batch, region, t = _stream(3000, seed=2)
        res = serve_stream(fr, batch, region, t,
                           former=BatchFormer(max_batch=64))
        assert res.admit_rounds.shape == (sum(s.n_batches
                                              for s in res.steps),)
        # a draft that finds every cell full runs no round and sheds
        assert res.admit_rounds.min() >= 0 and res.admit_rounds.max() > 1
        assert (res.admit_rounds < 24 * R * 3 + 1).all()


def test_admit_rounds_identical_on_1_2_4_fake_devices():
    """The counter of a capped stream, below the loop's limit, on the
    single-device program and the sharded one at 1, 2 and 4 devices (a
    fresh process: the only place the device-count override may exist)."""
    code = r"""
import json, os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np, jax
from test_tracing import _router, _stream
from repro.configs import get_config
from repro.serve import FleetRouter, data_mesh
cfg = get_config("h2o-danube-1.8b")
infra = FleetRouter(cfg).infra
out = {}
for kind in ("cross", "temporal"):
    fr = _router(cfg, infra, kind, 6.0)
    args = _stream(515, seed=3, slack=True)
    out[kind] = [int(fr.route_stream_with_state(*args)[1].admit_rounds)]
    for d in (1, 2, 4):
        _, state = fr.route_stream_with_state(*args, mesh=data_mesh(d))
        out[kind].append(int(state.admit_rounds))
print("ROUNDS", json.dumps(out))
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=root,
                          env={**os.environ, "PYTHONPATH": f"src:{root}/tests",
                               "JAX_PLATFORMS": "cpu"})
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("ROUNDS")]
    assert line, proc.stderr[-2000:]
    for kind, rounds in json.loads(line[0].split(" ", 1)[1]).items():
        assert len(set(rounds)) == 1, (kind, rounds)
        assert 1 < rounds[0] < 24 * R * 3 + 1, (kind, rounds)


def test_reconcile_scope_only_on_the_sharded_program():
    """The cross-device step of sharded admission (the per-round
    ``all_gather`` of contender counts and the any-device loop test)
    carries the device scope ``reconcile`` on 4 fake devices, inside
    ``admit``; the one-device program has no such step and no op under
    the scope, and the sharded decisions are the one-device program's
    bit for bit."""
    code = r"""
import json, os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np
from test_tracing import _router, _stream
from repro.configs import get_config
from repro.serve import FleetRouter, data_mesh, distributed
cfg = get_config("h2o-danube-1.8b")
fr = _router(cfg, FleetRouter(cfg).infra, "temporal", 6.0)
batch, region, t = _stream(515, seed=3, slack=True)
hour = (np.floor(t) % fr._horizon_h).astype(np.int32)
region = np.asarray(region, np.int32)
mesh = data_mesh(4)
rows, _ = distributed.shard_stream(fr, batch, region, hour, mesh)
sharded = distributed._build_sharded_route(fr, mesh, distributed.DATA_AXIS)
text = sharded.lower(*rows, fr._ci_table, fr._ci_fc, None,
                     None).compile().as_text()
one = fr._fleet_route.lower(*fr._route_args(batch, region,
                                            hour)).compile().as_text()
names = lambda text: [ln.split('op_name="')[1].split('"')[0].split("/")
                      for ln in text.splitlines() if 'op_name="' in ln]
digest = lambda res, st: [np.asarray(a).tolist() for a in (
    res.target, res.exec_region, st.exec_hour, st.shed)]
print("SCOPES", json.dumps(dict(
    sharded=[n for n in names(text) if "reconcile" in n],
    one=[n for n in names(one) if "reconcile" in n],
    one_admit=sum("admit" in n for n in names(one)),
    same=digest(*fr.route_stream_with_state(batch, region, t))
    == digest(*fr.route_stream_with_state(batch, region, t, mesh=mesh)))))
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=root,
                          env={**os.environ, "PYTHONPATH": f"src:{root}/tests",
                               "JAX_PLATFORMS": "cpu"})
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("SCOPES")]
    assert line, proc.stderr[-2000:]
    got = json.loads(line[0].split(" ", 1)[1])
    assert got["sharded"] and all("admit" in n for n in got["sharded"])
    assert any("all_gather" in n for n in got["sharded"])
    assert got["one"] == [] and got["one_admit"] > 0
    assert got["same"]

def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


#: decisions (tier, executing region and hour, shed) of the parity streams,
#: as the program gave them before it carried spans, scopes or the counter
PINNED = {
    "cross": "0de8990092ceb9f9",
    "diag": "d323a0f8808a67ef",
    "serve": "e7031be3aa7ed9b3",
    "temporal": "3a861f9c6483f622",
}


@pytest.mark.parametrize("kind", sorted(PINNED))
def test_decisions_unchanged(cfg, infra, kind):
    batch, region, t = _stream(2000, seed=4, slack=True)
    if kind == "serve":
        res = serve_stream(_router(cfg, infra, "cross", 2.0), batch, region,
                           t, former=BatchFormer(max_batch=256))
        got = _digest(res.target, res.exec_region, res.exec_hour, res.shed)
    else:
        res, state = _router(cfg, infra, kind, 12.0).route_stream_with_state(
            batch, region, t)
        got = _digest(res.target, res.exec_region, state.shed,
                      getattr(state, "exec_hour", np.zeros(0)))
    assert got == PINNED[kind]
