"""A new cell, configuration, traffic mix and per-layer metric are picked
up by name from files of their own: nothing existing is edited."""

import hashlib
import json
import shutil
import time
from pathlib import Path

import run
from harness import cells

BENCH = Path(cells.__file__).resolve().parents[1]


def _digest(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()
        and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    before = _digest(BENCH)
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
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
