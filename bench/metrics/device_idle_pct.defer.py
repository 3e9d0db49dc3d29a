"""Idle share of the busiest chip over the deferral day plan's traced
window: 100 x (1 - union of its op intervals / window), as
``device_idle_pct`` reads it."""

from pathlib import Path

from harness import cells

read = cells.module(Path(__file__).parent / "device_idle_pct.py").read
