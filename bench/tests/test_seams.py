"""The parts of a deployment are files found by name (``cells.Cell.part``):
entry points, policies, references and grid kinds. Moving the two existing
cells onto those files changed nothing they do: both mixes give the same
streams as before, both entries the same decisions and readings as the
program built and driven the way the harness did before, a one-chip cell's
router carries no mesh and a four-chip cell's a mesh over four devices, and
no reference imports the program."""

import ast
import hashlib
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import run
from harness import cells, check, program, traffic

BENCH = Path(cells.__file__).resolve().parents[1]
SEED = 2**33 + 7

#: sha256 of every stream column, as generated before mixes could carry
#: ``slack_h``: (cell, seed, stream, requests; None = the mix's own)
STREAMS = {
    ("dense4.place", 3, 0, 20000):
        "8d4dd5a574c063592bd0329ac323f747123f78784c89121bad4b08f78cfcc5ec",
    ("dense4.place", 424242, 1, 4000):
        "3a0385dbdc8e419eb66993d5a7c83ef581feaffb071dbf62919f19aa7b7dd92e",
    ("dense4.place", SEED, 0, 4000):
        "327e4725b07deb072fda9cab2e1deef60f21b7be3bf277423fadb3d75d524b96",
    ("dense4.place", SEED, 1, 4000):
        "f14f10e3dfa1082baf6cae971cf6f99901763d769bde46f671a546544c1b799f",
    ("dense4.place", 2**31 + 12345, 0, None):
        "c398522a06acfd8ed3c27afb6f007b909f8daeecf0af962a63d9ae66cd77c6e5",
    ("dense4.serve", 3, 0, 20000):
        "0492885d6cc0aeebf6c13ad0eab0667e8b8f72276ea68ccb7762f1b3410ca0b9",
    ("dense4.serve", 424242, 1, 4000):
        "25fa5f4895bc979d378987c647bd01a167f18d089a339239ee4f5dfe8118cbc8",
    ("dense4.serve", SEED, 0, 4000):
        "a06923422a8cca2b2feefb1a7c37dd654f0d61b7b922b5bde4f541f56a93441e",
    ("dense4.serve", SEED, 1, 4000):
        "8b8fd1c3ab4ee2621242e26093d3893d82f3f09f94016cda0bf57264845215e9",
    ("dense4.serve", 2**31 + 12345, 0, None):
        "7978fca4d0f7863c83b7bcbd7bcbf49cc440d5d0c1d42dc735faec7b694ccbbd",
}
COLUMNS = ("prompt_tokens", "max_new_tokens", "latency_budget_s",
           "bytes_per_token", "available", "region", "t_hours")

#: sha256 of the decision columns (``check.FIELDS``) of the two calls of a
#: run seeded ``SEED`` at 4,000 requests a stream, from the harness before
#: the parts became files
DECISIONS = {
    "dense4.place": (
        "c0da505232b89a1e5420f9d2c4845395ed1527094c7a7521ef912c50cfcfe05f",
        "25f956d79df505c03f2310675e916e01e7d8c18e18f757ede8cb9394214c90aa"),
    "dense4.serve": (
        "aafde16bf669d5e1386757ae4a28a1274db61e810ba6999270346a7b6bc5e857",
        "05a68072ff91551811869819667214d4701735f2cc57a00357e3a601728381e0"),
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(STREAMS, key=str), ids=str)
def test_existing_mixes_give_the_same_streams(key):
    name, seed, k, n = key
    s = traffic.generate(cells.load(name).traffic, 4,
                         traffic.stream_rng(seed, k), n)
    assert s.slack_hours is None
    assert _digest([getattr(s, c) for c in COLUMNS]) == STREAMS[key]


def test_slack_class_yields_slack_hours():
    mix = cells.load("dense4.place").traffic
    classes = [dict(c) for c in mix["mix"]["classes"]]
    classes[1]["slack_h"] = 6
    slack_mix = dict(mix, mix=dict(mix["mix"], classes=classes))
    plain = traffic.generate(mix, 4, traffic.stream_rng(9, 0), 5000)
    s = traffic.generate(slack_mix, 4, traffic.stream_rng(9, 0), 5000)
    for c in COLUMNS:  # slack draws nothing
        np.testing.assert_array_equal(getattr(s, c), getattr(plain, c))
    # the two classes' budgets never meet at a whole output length
    second = np.isclose(s.latency_budget_s, classes[1]["ttft_s"]
                        + classes[1]["tpot_s"] * s.max_new_tokens)
    assert 0.4 < second.mean() < 0.6
    np.testing.assert_array_equal(s.slack_hours, np.where(second, 6.0, 0.0))
    batch = program.request_batch(s)
    np.testing.assert_array_equal(batch.slack_hours, s.slack_hours)
    assert program.request_batch(plain).slack_hours is None


def _earlier_harness_outputs(cell, g, caps, streams):
    """The calls as the harness made them before the parts became files:
    ``PlacementPolicy(OraclePolicy)`` built in place, the day plan's
    execution hour the arrival hour, a fresh pool for each serve day."""
    from repro.configs import get_config
    from repro.core.infrastructure import pack_infra
    from repro.serve import (
        FleetRouter,
        OraclePolicy,
        PlacementPolicy,
        RequestBatch,
        WorkerPool,
        serve_stream,
    )
    from repro.serve.queue import BatchFormer

    cfg, tr = cell.config, cell.traffic
    fleet = program.fleet_of(cfg)
    inner = OraclePolicy(pack_infra(fleet, cfg["embodied_model"]))
    fr = FleetRouter(get_config(cfg["model"]["name"]), fleet=fleet,
                     embodied_model=cfg["embodied_model"],
                     grid=program.carbon_grid(g),
                     policy=PlacementPolicy(inner, caps))
    outs = []
    for s in streams:
        batch = RequestBatch(
            prompt_tokens=s.prompt_tokens, max_new_tokens=s.max_new_tokens,
            latency_budget_s=s.latency_budget_s,
            bytes_per_token=s.bytes_per_token, available=s.available)
        region = np.asarray(s.region, np.int32)
        if tr["entry"] == "route":
            res, state = fr.route_stream_with_state(batch, region,
                                                    np.asarray(s.t_hours))
            outs.append(dict(
                target=np.asarray(res.target),
                exec_region=np.asarray(res.exec_region),
                shed=np.asarray(state.shed),
                carbon_g=np.asarray(res.carbon_g),
                exec_hour=np.floor(s.t_hours).astype(np.int64) % 24))
            continue
        c = cfg["capacity"]
        pool = WorkerPool(4, slots_per_worker=cells.slots_per_worker(
            cfg, tr["requests"], 4),
            launch_delay_steps=c["pool"]["launch_delay_steps"])
        for r in range(4):
            for tier in c["dc_tiers"]:
                pool.launch(r, tier, n=c["pool"]["workers"])
        res = serve_stream(fr, batch, region, np.asarray(s.t_hours),
                           step_h=int(tr["step_h"]), pool=pool,
                           former=BatchFormer(max_batch=tr["max_batch"]))
        outs.append(dict(target=res.target, exec_region=res.exec_region,
                         exec_hour=res.exec_hour, shed=res.shed,
                         carbon_g=res.carbon_g))
    return outs


@pytest.mark.parametrize("name", sorted(DECISIONS))
def test_entries_give_the_earlier_outputs_and_readings(small_cell, name):
    cell = small_cell(name)
    g, caps, streams, entry = run.build(cell, SEED, program.Spans(False))
    assert entry.fr.mesh is None  # one chip: the one-device program
    if cell.traffic["entry"] == "route":
        want = np.full((4, 3), np.inf)
        want[:, 1:] = cells.dc_capacity(cell.config, 4000, 4)
        np.testing.assert_array_equal(caps, want)
    else:
        np.testing.assert_array_equal(caps, np.ones((4, 3)))
    ours = [entry.once(k)[1] for k in range(len(streams))]
    earlier = _earlier_harness_outputs(cell, g, caps, streams)
    ref = cell.reference()
    for k, (a, b) in enumerate(zip(ours, earlier)):
        assert _digest([a[f] for f in check.FIELDS]) == DECISIONS[name][k]
        for f in (*check.FIELDS, "carbon_g"):
            np.testing.assert_array_equal(a[f], b[f], err_msg=f)
            assert np.asarray(a[f]).dtype == np.asarray(b[f]).dtype, f
        problem = ref.problem(cell, g, caps, streams[k])
        got, was = run.compare([a], problem)[0], run.compare([b], problem)[0]
        assert got == was and got["rows_differ"] == 0


def _imports(path: Path) -> set:
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module.split(".")[0])
    return names


REFERENCES = sorted((BENCH / "references").glob("*.py"))


@pytest.mark.parametrize("path", REFERENCES, ids=lambda p: p.stem)
def test_reference_imports_nothing_of_the_program(path):
    assert "repro" not in _imports(path)
    # nor through what it imports: load it where the program is importable
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / 'src')!r}]
        from pathlib import Path
        from harness import cells
        cells.module(Path({str(path)!r}))
        print(sorted(m for m in sys.modules if m.split('.')[0] == 'repro'))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True)
    assert out.stdout.strip() == "[]", out.stdout


FOUR_CHIPS = """
import dataclasses, json, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import run
from conftest import shrink
from harness import cells, program

cell = dataclasses.replace(shrink(cells.load("dense4.place"), 4000), chips=4)
g, caps, streams, entry = run.build(cell, 77, program.Spans(False))
mesh = entry.fr.mesh
devices = [d.id for d in mesh.devices.flat]
del entry
res = run.run_cell(cell, 77, 0.2, False, time.perf_counter())
print(json.dumps(dict(devices=devices, axes=list(mesh.axis_names),
                      correct=res["correct"], checks=res["checks"],
                      count=res["device"]["count"])))
"""


def test_four_chip_route_cell_runs_sharded_and_correct():
    code = FOUR_CHIPS.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                             tests=str(BENCH / "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == [0, 1, 2, 3] and len(res["axes"]) == 1
    assert res["count"] == 4
    assert res["correct"], res["checks"]

