"""Admission rounds per deferral day-plan call: the program's own
``admit_rounds`` counter of each call (``TemporalState``), read once the
window has closed, averaged over the window's calls."""

from pathlib import Path

from harness import cells

read = cells.module(Path(__file__).parent / "admit_rounds.route.py").read
