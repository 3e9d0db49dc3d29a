"""Carbon-intensity grids of the benchmark's deployments, as plain arrays.

The benchmark builds each deployment's grid tables itself, from the
configuration file, and hands the same arrays both to the program (through
the public ``CarbonGrid`` constructor) and to the plain reference, so the
reference takes no table the program has made. Each grid kind is a file of
its own, ``bench/grids/<kind>.py`` (``cells.Cell.grid``); this module holds
what they and the references share: the regional generation profiles, the
per-region tables and the Table-1 component view. The regional generation
mixes follow the program's own synthesis in ``repro.core.carbon_intensity``
(``grid_trace``, ``CarbonGrid.fully_connected``);
``bench/tests/test_grids.py`` pins that they describe the same deployment.

Tables (R regions, 24 hours):
``ci_hourly`` (R, 24) float32 grid CI, gCO2/kWh; ``ci_mobile`` / ``ci_core``
(R,) float32 device-battery and core-path CI; ``pue`` (R, 24) float32;
``adjacency`` (R, R) bool; ``latency_penalty`` / ``rtt_s`` (R, R) float32.
"""

from __future__ import annotations

import numpy as np

HOURS = 24
# generation sources, in the order of the configuration's ``source_ci``
WIND, SOLAR, WATER, OIL, GAS, COAL, NUCLEAR, OTHER = range(8)


def _solar(h: np.ndarray) -> np.ndarray:
    x = np.clip(np.cos((h - 13.0) / 7.0 * np.pi / 2.0), 0.0, None)
    return x**1.5


def _normalise(cols: dict[int, np.ndarray]) -> np.ndarray:
    mix = np.zeros((HOURS, 8))
    for src, v in cols.items():
        mix[:, src] = v
    return mix / mix.sum(axis=1, keepdims=True)


def _ciso(h):
    solar = 0.70 * _solar(h)
    wind = 0.08 + 0.04 * np.sin((h - 2.0) / 24.0 * 2 * np.pi)
    hydro, nuclear, other = (np.full_like(h, v) for v in (0.07, 0.07, 0.03))
    coal = 0.08 * ((h >= 21) | (h < 6)).astype(np.float64)
    gas = np.clip(1.0 - (solar + wind + hydro + nuclear + other + coal),
                  0.05, None)
    return _normalise({COAL: coal, SOLAR: solar, WIND: wind, WATER: hydro,
                       NUCLEAR: nuclear, OTHER: other, GAS: gas})


def _nyiso(h):
    wind = np.clip(0.12 + 0.10 * np.sin(h / 24.0 * 6 * np.pi)
                   + 0.05 * np.sin(h / 24.0 * 2 * np.pi), 0.02, None)
    hydro, nuclear, other = (np.full_like(h, v) for v in (0.18, 0.22, 0.05))
    gas = np.clip(1.0 - (wind + hydro + nuclear + other), 0.05, None)
    return _normalise({WIND: wind, WATER: hydro, NUCLEAR: nuclear,
                       OTHER: other, GAS: gas})


def _urban(h):
    solar = 0.06 * _solar(h)
    wind, nuclear, coal, other = (np.full_like(h, v)
                                  for v in (0.03, 0.15, 0.12, 0.06))
    gas = np.clip(1.0 - (solar + wind + nuclear + coal + other), 0.05, None)
    return _normalise({SOLAR: solar, WIND: wind, NUCLEAR: nuclear,
                       COAL: coal, OTHER: other, GAS: gas})


def _rural(h):
    solar = 0.40 * _solar(h)
    wind = 0.35 + 0.10 * np.sin(h / 24.0 * 4 * np.pi)
    hydro, other = np.full_like(h, 0.12), np.full_like(h, 0.03)
    gas = np.clip(1.0 - (solar + wind + hydro + other), 0.03, None)
    return _normalise({SOLAR: solar, WIND: wind, WATER: hydro,
                       OTHER: other, GAS: gas})


#: the four regional generation-mix profiles, by the name configs use
PROFILES = {"ciso": _ciso, "nyiso": _nyiso, "urban": _urban,
            "rural": _rural}


def profile_ci(name: str, source_ci) -> np.ndarray:
    """(24,) float64 hourly CI of one profile."""
    h = np.arange(HOURS, dtype=np.float64)
    return PROFILES[name](h) @ np.asarray(source_ci, np.float64)


def dense_tables(ci: np.ndarray, mobile: np.ndarray, core: np.ndarray,
                 pue: float) -> dict:
    """The per-region tables of a grid: hourly CI ``ci`` (R, 24), battery
    and core-path CI (R,), one PUE for every region and hour."""
    r = len(ci)
    return dict(ci_hourly=ci.astype(np.float32),
                ci_mobile=mobile.astype(np.float32),
                ci_core=core.astype(np.float32),
                pue=np.full((r, HOURS), pue, np.float32))


def component_table(g: dict) -> np.ndarray:
    """(R, 24, 5) float32 CI per Table-1 component [mobile, edge network,
    edge DC, core network, hyperscale DC]: edge network and edge DC share
    the grid CI, PUE scales the two DC components."""
    ci, pue = g["ci_hourly"], g["pue"]
    day = lambda a: np.broadcast_to(a[:, None], ci.shape)
    return np.stack([day(g["ci_mobile"]), ci, ci * pue, day(g["ci_core"]),
                     ci * pue], axis=-1).astype(np.float32)
