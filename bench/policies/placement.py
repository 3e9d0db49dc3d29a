"""Policy kind ``placement``: cross-region placement over the Table-1
carbon oracle, ``PlacementPolicy(OraclePolicy)``, with per-(region, tier)
caps in every hourly window and spill to the next-best open pair."""

from __future__ import annotations

from repro.core.infrastructure import pack_infra
from repro.serve import OraclePolicy, PlacementPolicy


def build(cfg: dict, fleet, g: dict, caps):
    inner = OraclePolicy(pack_infra(fleet, cfg["embodied_model"]))
    return PlacementPolicy(inner, caps)
