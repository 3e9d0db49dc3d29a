"""The deferral deployment, ``dense4.defer.mesh4``, at a tiny size on the
CPU: a whole run on four fake devices comes out correct; its reference
agrees with the placement reference where nothing may wait, breaks exact
ties as the program does, and fails the program's faults and the control;
its grid is the program's repeated day."""

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import run
from calibrate import as_output
from harness import cells, check, grids, program, reference, traffic
from repro.core.carbon_intensity import DEFAULT_REGIONS, CarbonGrid
from repro.serve import TemporalPolicy
from repro.serve.router import FleetRouter

BENCH = Path(cells.__file__).resolve().parents[1]
NAME = "dense4.defer.mesh4"

FOUR_CHIPS = """
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import numpy as np
import run
from conftest import shrink
from harness import cells, program

cell = shrink(cells.load({name!r}), 4000)
g, caps, streams, entry = run.build(cell, 2**33 + 91, program.Spans(False))
devices = [d.id for d in entry.fr.mesh.devices.flat]
out = entry.once(0)[1]
s = streams[0]
d = out["exec_hour"] - np.floor(s.t_hours).astype(np.int64)
del entry
res = run.run_cell(cell, 2**33 + 91, 0.2, False, time.perf_counter())
print(json.dumps(dict(
    devices=devices, correct=res["correct"], checks=res["checks"],
    count=res["device"]["count"], in_window=bool(
        ((d >= 0) & (d <= s.slack_hours)).all()),
    deferred=int((d > 0).sum()), day_two=int((out["exec_hour"] >= 24).sum()),
    batch=int((s.slack_hours > 0).sum()))))
"""


def test_four_chip_defer_cell_runs_sharded_and_correct():
    code = FOUR_CHIPS.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                             tests=str(BENCH / "tests"), name=NAME)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == [0, 1, 2, 3] and res["count"] == 4
    assert res["correct"], res["checks"]
    assert res["in_window"]
    # the deferral does the work: most batch rows wait, some past midnight
    assert res["deferred"] > res["batch"] // 2 and res["day_two"] > 0


def _stream(cell, seed=3, **cols):
    n_regions = len(cell.config["grid"]["regions"])
    s = traffic.generate(cell.traffic, n_regions, traffic.stream_rng(seed, 0))
    return dataclasses.replace(s, **cols)


def test_zero_slack_reference_equals_placement_reference(small_cell):
    cell = small_cell(NAME, 6000)
    g = cell.grid()
    caps = cell.entry().caps(cell, 4)
    s = _stream(cell)
    s = dataclasses.replace(s, slack_hours=np.zeros(len(s)))
    ours = cell.reference().problem(cell, g, caps, s).solve()
    place = reference.Problem(s, cell.config, g,
                              np.asarray(caps, np.float32).astype(np.float64),
                              reference.n_active_params(cell.config["model"]))
    theirs = place.solve()
    assert theirs.shed.any() or (theirs.exec_region != s.region).any()
    for f in ("target", "exec_region", "exec_hour", "shed", "carbon_g"):
        np.testing.assert_array_equal(getattr(ours, f), getattr(theirs, f),
                                      err_msg=f)


def test_admission_is_the_harness_admission(small_cell):
    """The reference's admission reads open cells from a per-window table;
    ``reference.admit`` over the same columns, each standing for its
    (absolute hour, pair) cell, makes the same decisions, follows the same
    near-ties and leaves the same ones untaken, with and without a flip."""
    cell = small_cell(NAME, 6000)
    g = cell.grid()
    caps = cell.entry().caps(cell, 4)
    s = _stream(cell)
    ref = cell.reference().problem(cell, g, caps, s)
    fr = program.build_router(dataclasses.replace(cell, chips=1), g, caps)
    res, state = fr.route_stream_with_state(program.request_batch(s),
                                            s.region, s.t_hours)
    out = dict(target=res.target, exec_region=res.exec_region,
               exec_hour=state.exec_hour, shed=state.shed)
    k = ref.caps.size
    col = np.arange(ref.s.shape[1])
    harness = lambda follow, flip: reference.admit(
        reference.Scored(ref.s, np.broadcast_to(col, ref.s.shape)),
        np.arange(len(s)), ref.win, np.tile(ref.caps, ref.horizon),
        np.zeros(ref.horizon * k), k, follow, flip)
    follow = ref._follow(out)[0]
    untaken = ref._admit(follow)[3]
    for follow, flip in ((None, None), (follow, None),
                         (follow, dict(list(untaken.items())[:1]))):
        ours, theirs = ref._admit(follow, flip), harness(follow, flip)
        for a, b in zip(ours[:2], theirs[:2]):
            np.testing.assert_array_equal(a, b)
        assert ours[2:] == theirs[2:]


def _flat(g: dict) -> dict:
    """``g`` with every region's CI and PUE the same in every hour."""
    g = dict(g)
    for k in ("ci_hourly", "pue"):
        g[k] = np.broadcast_to(g[k].mean(axis=1, keepdims=True),
                               g[k].shape).astype(np.float32)
    return g


def test_exact_ties_go_to_the_lowest_column_defer_major(small_cell):
    """On a grid that is the same in every hour every defer of a candidate
    ties exactly: a row waits only when every earlier hour of its best
    pair is full, and the program and the reference pick the same cell
    without following any near-tie."""
    cell = small_cell(NAME, 6000)
    g = _flat(cell.grid())
    caps = cell.entry().caps(cell, 4)
    s = _stream(cell)
    ref = cell.reference().problem(cell, g, caps, s)
    sc = ref.s.reshape(len(s), -1, ref.caps.size)
    batch = np.flatnonzero(s.slack_hours[ref.order] > 0)
    np.testing.assert_array_equal(sc[batch, 1:], np.broadcast_to(
        sc[batch, :1], sc[batch, 1:].shape))
    batch = np.flatnonzero(s.slack_hours > 0)
    fr = program.build_router(dataclasses.replace(cell, chips=1), g, caps)
    res, state = fr.route_stream_with_state(program.request_batch(s),
                                            s.region, s.t_hours)
    out = dict(target=np.asarray(res.target),
               exec_region=np.asarray(res.exec_region),
               exec_hour=np.asarray(state.exec_hour),
               shed=np.asarray(state.shed), carbon_g=np.asarray(res.carbon_g))
    own = ref.solve()
    for f in check.FIELDS:
        np.testing.assert_array_equal(out[f], getattr(own, f), err_msg=f)
    d = out["exec_hour"] - np.floor(s.t_hours).astype(np.int64)
    assert (d[batch] > 0).any() and (d[batch] == 0).any()


def _run(cell):
    return run.run_cell(cell, 2**33 + 5, 0.2, False, time.perf_counter())


def _exec_hour_off_by_one(monkeypatch):
    """One deferred row runs an hour later than the program decided."""
    real = FleetRouter._route_arrays

    def late(self, *a, **kw):
        res, state = real(self, *a, **kw)
        i = int(jnp.argmax(state.defer_hours))
        return res, dataclasses.replace(
            state, exec_hour=state.exec_hour.at[i].add(1))

    monkeypatch.setattr(FleetRouter, "_route_arrays", late)


def _defer_past_slack(monkeypatch):
    """The policy ignores each request's slack: every request may wait
    the whole horizon."""
    real = TemporalPolicy.decide

    def decide(self, *a, slack=None, **kw):
        return real(self, *a, slack=jnp.full_like(slack, self.max_defer_h),
                    **kw)

    monkeypatch.setattr(TemporalPolicy, "decide", decide)


def _cell_over_full(monkeypatch):
    """Each DC cell admits one request more than its capacity."""
    policy = cells.module(BENCH / "policies" / "temporal.py")
    real = policy.build
    monkeypatch.setattr(policy, "build", lambda cfg, fleet, g, caps: real(
        cfg, fleet, g, np.asarray(caps) + 1.0))


@pytest.mark.parametrize("fault", [_exec_hour_off_by_one, _defer_past_slack,
                                   _cell_over_full],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_not_correct(small_cell, monkeypatch, fault):
    cell = dataclasses.replace(small_cell(NAME), chips=1)
    assert _run(cell)["correct"]
    fault(monkeypatch)
    res = _run(cell)
    assert not res["correct"], res["checks"]


def test_control_fails_the_check(small_cell):
    cell = small_cell(NAME, 20000)
    g = cell.grid()
    caps = cell.entry().caps(cell, 4)
    s = _stream(cell)
    ref = cell.reference().problem(cell, g, caps, s)
    ctrl = as_output(cell.reference().problem(cell, g, caps, s,
                                              precision="high").solve())
    limits = cells.load(NAME).limits
    assert check.judge(check.worst(run.compare([as_output(ref.solve())],
                                               ref)), limits)[0]
    ok, table = check.judge(check.worst(run.compare([ctrl], ref)), limits)
    assert not ok, table


def test_horizon_grid_is_the_programs_repeated_day():
    ours = cells.load(NAME).grid()
    theirs = CarbonGrid.fully_connected(DEFAULT_REGIONS,
                                        latency_penalty=1.05).repeat(2)
    assert ours["ci_hourly"].shape == (4, 48)
    for f in ("ci_hourly", "ci_mobile", "ci_core", "pue", "latency_penalty",
              "rtt_s"):
        np.testing.assert_allclose(ours[f], np.asarray(getattr(theirs, f)),
                                   rtol=1e-6, err_msg=f)
    np.testing.assert_array_equal(ours["adjacency"],
                                  np.asarray(theirs.adjacency))
    np.testing.assert_allclose(grids.component_table(ours),
                               np.asarray(theirs.table), rtol=1e-6)
    one_day = cells.load("dense4.place").grid()
    np.testing.assert_array_equal(ours["ci_hourly"][:, 24:],
                                  one_day["ci_hourly"])
