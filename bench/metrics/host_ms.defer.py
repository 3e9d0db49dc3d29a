"""Host time per deferral day-plan call: milliseconds of each
``route_call`` span in which no chip runs an op (router entry, the sort
and sharding of the stream, host aggregation, copy-back), averaged over
the window's calls, as ``host_ms.route`` reads it."""

from pathlib import Path

from harness import cells

read = cells.module(Path(__file__).parent / "host_ms.route.py").read
