"""Device time per deferral day-plan call: milliseconds in which the
busiest chip runs an op inside each ``route_call`` span, averaged over
calls, as ``route_device_ms.route`` reads it."""

from pathlib import Path

from harness import cells

read = cells.module(Path(__file__).parent / "route_device_ms.route.py").read
