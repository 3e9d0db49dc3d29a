"""Policy kind ``temporal``: joint (defer, region, tier) placement over the
Table-1 carbon oracle, ``TemporalPolicy(OraclePolicy)``. A request may run
in any hour from its arrival through arrival + its slack (at most the
configuration's ``temporal.max_defer_h``), with per-(region, tier) caps in
every hourly window of the grid's horizon and spill first in time, then in
space."""

from __future__ import annotations

from repro.core.infrastructure import pack_infra
from repro.serve import OraclePolicy, TemporalPolicy


def build(cfg: dict, fleet, g: dict, caps):
    inner = OraclePolicy(pack_infra(fleet, cfg["embodied_model"]))
    return TemporalPolicy(inner, caps,
                          max_defer_h=int(cfg["temporal"]["max_defer_h"]))
