"""BENCHMARK.json keeps to the benchmark's static rules, and every cell's
pieces exist: configuration, traffic mix, limits, metric readers, and the
entry point, policy, reference and grid kind its files name."""

import json
import re
from pathlib import Path

from harness import cells

ROOT = Path(cells.__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all(_line(w) for w in SPEC["command"])
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()


def test_configs_and_cells():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(
            c["source"])
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        for part in ("policies", "references"):
            assert (ROOT / "bench" / part
                    / f"{cfg['policy_kind']}.py").is_file()
        assert (ROOT / "bench" / "grids"
                / f"{cfg['grid']['kind']}.py").is_file()
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and _line(w["why"])
        assert w["chips"] in (1, 4) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        entry = json.loads(mix.read_text())["entry"]
        assert (ROOT / "bench" / "entries" / f"{entry}.py").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cell_names = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and _line(m["layer"])
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cell_names
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for w in cell_names:
        cell = cells.load(w)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        assert {m["moves"] for m in cell.per_layer} <= reported
