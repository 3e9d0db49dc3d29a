"""The one request-stream generator every traffic mix goes through.

A mix is a data file (``bench/traffic/<mix>.json``) of parameters: the
request classes with their shares, length distributions and latency
objectives, the arrival shape, the stream size and the entry point that
drives it. This module turns such a file plus a seeded ``numpy``
generator into columns.

A class draws its prompt and output lengths from log-normal distributions
around published medians, and its end-to-end latency budget from a
time-to-first-token objective plus a per-output-token one. A class may
carry ``slack_h``, the whole hours past arrival within which its requests
may run; it draws nothing, so a mix's streams do not depend on whether a
class sets it. Arrival times follow the program's canonical diurnal curve
(``repro.serve.streams.diurnal_hours``), copied rather than imported
because the yardstick must not move when the program does.
"""

from __future__ import annotations

import dataclasses

import numpy as np

HOURS = 24


@dataclasses.dataclass(frozen=True)
class Stream:
    """Columnar request stream: one row per request."""

    prompt_tokens: np.ndarray  # (N,) float64
    max_new_tokens: np.ndarray  # (N,) float64
    latency_budget_s: np.ndarray  # (N,) float64
    bytes_per_token: np.ndarray  # (N,) float64
    available: np.ndarray  # (N, 3) bool: [mobile, edge DC, hyperscale DC]
    region: np.ndarray  # (N,) int64 home region
    t_hours: np.ndarray  # (N,) float64 arrival time in [0, 24)
    #: (N,) float64 whole hours past arrival a request may run; None when
    #: no class of the mix sets ``slack_h``
    slack_hours: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.prompt_tokens)


def diurnal_hours(rng: np.random.Generator, n: int,
                  peak: float) -> np.ndarray:
    """Arrival times (hours): sinusoidal daily load peaking at ``peak``."""
    hours = np.arange(HOURS)
    rate = 1.0 + 0.8 * np.cos((hours - peak) / 24.0 * 2 * np.pi)
    p = rate / rate.sum()
    return rng.choice(HOURS, n, p=p) + rng.uniform(0.0, 1.0, n)


def lengths(rng: np.random.Generator, n: int, median: float, sigma: float,
            hi: np.ndarray | int) -> np.ndarray:
    """Whole token counts, log-normal around ``median``, in [1, ``hi``]."""
    x = np.rint(median * np.exp(sigma * rng.standard_normal(n)))
    return np.clip(x, 1, hi)


def request_mix(rng: np.random.Generator, n: int, mix: dict) -> dict:
    """Token counts, budgets and tier availability of ``n`` requests drawn
    from the mix's classes (each class: ``share``; ``prompt_median`` and
    ``new_median`` with log-normal ``sigma``; ``ttft_s`` and ``tpot_s``,
    the budget being ``ttft_s + tpot_s * new tokens``; optional
    ``slack_h``, 0 where unset). Prompt and output together fit the
    model's ``context`` positions; every tier can serve every request."""
    classes = mix["classes"]
    ctx = int(mix["context"])
    cls = rng.choice(len(classes), n, p=[c["share"] for c in classes])
    conds = [cls == i for i in range(len(classes))]
    new = np.select(conds, [lengths(rng, n, c["new_median"], c["sigma"],
                                    ctx - 1) for c in classes])
    prompt = np.select(conds, [lengths(rng, n, c["prompt_median"],
                                       c["sigma"], ctx - new)
                               for c in classes])
    budget = np.select(conds, [c["ttft_s"] + c["tpot_s"] * new
                               for c in classes])
    slack = None
    if any("slack_h" in c for c in classes):
        slack = np.select(conds, [float(c.get("slack_h", 0))
                                  for c in classes])
    return dict(prompt_tokens=prompt.astype(np.float64),
                max_new_tokens=new.astype(np.float64),
                latency_budget_s=budget.astype(np.float64),
                bytes_per_token=np.full(n, float(mix["bytes_per_token"])),
                available=np.ones((n, 3), bool), slack_hours=slack)


def arrivals(rng: np.random.Generator, n: int, n_regions: int,
             spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """(region, t_hours) of ``n`` requests.

    ``uniform``: homes uniform over the regions, one diurnal curve peaking
    at ``peak_h``. ``skewed``: region shares ramp linearly from
    ``weights[0]`` to ``weights[1]`` and each region peaks at its own local
    evening, ``peak_h`` plus an even stagger of 24 / n_regions hours."""
    if spec["kind"] == "uniform":
        region = rng.integers(0, n_regions, n)
        return region, diurnal_hours(rng, n, float(spec["peak_h"]))
    if spec["kind"] != "skewed":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    w = np.linspace(*spec["weights"], n_regions)
    peaks = (spec["peak_h"] + np.arange(n_regions) * 24.0 / n_regions) % 24.0
    region = rng.choice(n_regions, n, p=w / w.sum())
    t_hours = np.empty(n)
    for r in range(n_regions):
        idx = region == r
        t_hours[idx] = diurnal_hours(rng, int(idx.sum()), float(peaks[r]))
    return region, t_hours


def generate(traffic: dict, n_regions: int, rng: np.random.Generator,
             n: int | None = None) -> Stream:
    """One stream of the mix (``n`` overrides its size)."""
    n = int(traffic["requests"] if n is None else n)
    cols = request_mix(rng, n, traffic["mix"])
    region, t_hours = arrivals(rng, n, n_regions, traffic["arrivals"])
    return Stream(**cols, region=region, t_hours=t_hours)


def stream_rng(seed: int, k: int) -> np.random.Generator:
    """The generator of stream ``k`` of a run seeded ``seed`` (any
    non-negative integer; seeds past 32 bits are fine)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), k]))
