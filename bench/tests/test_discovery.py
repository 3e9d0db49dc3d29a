"""A new cell, configuration, traffic mix and per-layer metric, and a new
entry point, policy, reference and grid kind, are picked up by name from
files of their own: nothing existing is edited."""

import hashlib
import json
import shutil
import time
from pathlib import Path

import numpy as np

import run
from harness import cells, program

BENCH = Path(cells.__file__).resolve().parents[1]


def _digest(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()
        and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    before = _digest(BENCH)
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics", "entries",
                "policies", "references", "grids"):
        shutil.copytree(BENCH / sub, bench / sub)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())

    cfg = json.loads((BENCH / "configs" / "regions4_dense.json").read_text())
    cfg["name"] = "regions4_tight"
    cfg["grid"]["latency_penalty"] = 1.2
    (bench / "configs" / "regions4_tight.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "azure_day.json").read_text())
    mix["requests"] = 3000
    mix["arrivals"]["weights"] = [5.0, 1.0]
    (bench / "traffic" / "day_steep.json").write_text(json.dumps(mix))
    (bench / "limits" / "tight4.steep.json").write_text(json.dumps(
        {"limits": {"rows_differ": 0, "carbon_row_gap": 3e-6}}))
    (bench / "metrics" / "calls_in_window.py").write_text(
        "def read(o):\n    return o.calls\n")
    spec["configs"].append({"name": "regions4_tight", "source": "x",
                            "file": "bench/configs/regions4_tight.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tight4.steep",
                              "config": "regions4_tight",
                              "traffic": "day_steep", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                              "better": "higher",
                              "source": "program_counter", "layer": "x",
                              "moves": "decisions_per_s",
                              "workloads": ["tight4.steep"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load("tight4.steep", tmp_path / "BENCHMARK.json", bench)
    assert cell.config["grid"]["latency_penalty"] == 1.2
    assert cell.traffic["requests"] == 3000
    assert "calls_in_window" in {m["name"] for m in cell.per_layer}
    res = run.run_cell(cell, 5, 0.2, True, time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["metrics"]["calls_in_window"]["value"] >= 1
    assert _digest(BENCH) == before


#: a deployment whose every part is new code: a grid kind that joins each
#: region to its two neighbours only, a policy kind that places without
#: caps with the reference of that kind, and an entry point that times each
#: day-plan call
NEW_PARTS = {
    "grids/regions_ring.py": '''
from pathlib import Path

import numpy as np

from harness import cells

regions = cells.module(Path(__file__).parent / "regions.py")


def build(spec, source_ci):
    g = regions.build(spec, source_ci)
    r = np.arange(len(g["adjacency"]))
    adj = np.zeros_like(g["adjacency"])
    adj[r, r] = adj[r, (r + 1) % len(r)] = adj[r, (r - 1) % len(r)] = True
    g["adjacency"] = adj
    return g
''',
    "policies/placement_uncapped.py": '''
import numpy as np

from repro.core.infrastructure import pack_infra
from repro.serve import OraclePolicy, PlacementPolicy


def build(cfg, fleet, g, caps):
    inner = OraclePolicy(pack_infra(fleet, cfg["embodied_model"]))
    return PlacementPolicy(inner, np.full(np.shape(caps), np.inf))
''',
    "references/placement_uncapped.py": '''
import numpy as np

from harness import reference


def problem(cell, g, caps, stream, precision="highest"):
    return reference.Problem(
        stream, cell.config, g, np.full(np.shape(caps), np.inf),
        reference.n_active_params(cell.config["model"]), precision)
''',
    "entries/route_timed.py": '''
import time
from pathlib import Path

from harness import cells

route = cells.module(Path(__file__).parent / "route.py")


class Entry(route.Entry):
    def once(self, k):
        t = time.perf_counter()
        n, out = super().once(k)
        self.step_s.append(time.perf_counter() - t)
        return n, out
''',
}


def test_new_policy_reference_grid_and_entry_are_found_by_name(tmp_path):
    before = _digest(BENCH)
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for rel, text in NEW_PARTS.items():
        (bench / rel).write_text(text.lstrip())
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())

    cfg = json.loads((BENCH / "configs" / "regions4_dense.json").read_text())
    cfg.update(name="ring4_uncapped", policy_kind="placement_uncapped")
    cfg["grid"]["kind"] = "regions_ring"
    (bench / "configs" / "ring4_uncapped.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "azure_day.json").read_text())
    mix.update(requests=3000, entry="route_timed")
    (bench / "traffic" / "day_timed.json").write_text(json.dumps(mix))
    (bench / "limits" / "ring4.timed.json").write_text(json.dumps(
        {"limits": {"rows_differ": 0, "carbon_row_gap": 4e-6}}))
    spec["configs"].append({"name": "ring4_uncapped", "source": "x",
                            "file": "bench/configs/ring4_uncapped.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "ring4.timed",
                              "config": "ring4_uncapped",
                              "traffic": "day_timed", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] in ("decisions_per_s", "step_p95_ms"):
            m["workloads"].append("ring4.timed")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load("ring4.timed", tmp_path / "BENCHMARK.json", bench)
    g, caps, streams, entry = run.build(cell, 8, program.Spans(False))
    assert type(entry).__name__ == "Entry" and type(entry).__bases__[0] is (
        cells.module(bench / "entries" / "route.py").Entry)
    adj = np.asarray(entry.fr.grid.adjacency)
    assert adj.sum() == 12 and not adj[0, 2] and not adj[1, 3]
    assert np.isinf(np.asarray(entry.fr.policy.caps)).all()
    outs = [entry.once(k)[1] for k in range(len(streams))]
    del entry
    # caps would bind at these peaks: only the uncapped reference agrees
    place = cells.load("dense4.place")
    capped, hourly = place.reference(), place.entry().caps(cell, 4)
    for out, s in zip(outs, streams):
        own = cell.reference().problem(cell, g, caps, s)
        assert run.compare([out], own)[0]["rows_differ"] == 0
        assert not out["shed"].any()
        assert ((out["exec_region"] - s.region) % 4 != 2).all()  # ring
        bound = capped.problem(cell, g, hourly, s)
        assert run.compare([out], bound)[0]["rows_differ"] > 0

    res = run.run_cell(cell, 8, 0.2, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"decisions_per_s", "step_p95_ms",
                                   "setup_s"}
    assert _digest(BENCH) == before

