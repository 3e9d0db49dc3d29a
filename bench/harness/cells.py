"""Finding a cell's pieces by name: its entry in ``BENCHMARK.json``, the
configuration and traffic files it names, its limits, the metrics it
reports, and the code of each part of its deployment. Adding a cell,
configuration, mix, per-layer metric, entry point, policy, reference or
grid kind is adding files; nothing here lists them.

A deployment's code is found under the cell's bench directory by the name
its files give:

``entries/<traffic["entry"]>.py``    class ``Entry``: the timed entry point
``policies/<config["policy_kind"]>.py``  ``build(cfg, fleet, g, caps)``: the
                                     program's routing policy
``references/<config["policy_kind"]>.py``  ``problem(cell, g, caps, stream,
                                     precision)``: the plain reference
``grids/<config["grid"]["kind"]>.py``  ``build(spec, source_ci)``: the grid
                                     tables
``metrics/<metric>.py``              ``read(observed)``: a per-layer metric
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list
    bench_dir: Path = BENCH  # where its code and metric readers live

    def part(self, kind: str, name: str):
        """The module ``<bench_dir>/<kind>/<name>.py``."""
        return module(self.bench_dir / kind / f"{name}.py")

    def entry(self):
        """The class of the entry point the cell's traffic drives."""
        return self.part("entries", self.traffic["entry"]).Entry

    def policy(self):
        """The module that builds the program's policy."""
        return self.part("policies", self.config["policy_kind"])

    def reference(self):
        """The module of the plain reference of the cell's policy."""
        return self.part("references", self.config["policy_kind"])

    def grid(self) -> dict:
        """The deployment's grid tables (``harness.grids``)."""
        spec = self.config["grid"]
        return self.part("grids", spec["kind"]).build(
            spec, self.config["source_ci"])


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict, cell: str, end_to_end_names: set) -> bool:
    """Does ``cell`` report ``metric``? Listed cells when the metric names
    its ``workloads``; otherwise every cell that reports what it moves
    (for a per-layer metric) or every cell (for an end-to-end one)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in end_to_end_names


def load(name: str, bench_json: Path | None = None,
         bench_dir: Path = BENCH) -> Cell:
    spec = _json(bench_json or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"] if reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(bench_dir.parent / conf["file"]),
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench_dir / "limits" / f"{name}.json")["limits"],
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def dc_capacity(config: dict, requests: int, n_regions: int) -> int:
    """Requests each DC tier of each region admits per hour: the day's mean
    offered load spread over the DC tiers at the configured mean
    utilization (``capacity.rule`` in the configuration file)."""
    c = config["capacity"]
    per_hour = requests / (n_regions * 24)
    return max(1, math.floor(per_hour / (len(c["dc_tiers"])
                                         * c["mean_utilization"])))


def slots_per_worker(config: dict, requests: int, n_regions: int) -> int:
    """The online loop's slots per worker: a DC tier's hourly capacity
    split over its workers."""
    workers = config["capacity"]["pool"]["workers"]
    return max(1, dc_capacity(config, requests, n_regions) // workers)


@functools.cache
def module(path: Path):
    """The Python file at ``path``, loaded once per process."""
    rel = path.with_suffix("").parts[-2:]
    name = "bench_" + "_".join(rel).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH):
    """The ``read(observed)`` function of ``bench/metrics/<name>.py``."""
    return module(bench_dir / "metrics" / f"{name}.py").read
