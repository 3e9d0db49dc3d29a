"""Device time of candidate scoring per deferral day-plan call:
milliseconds in which the busiest chip runs an op of the program's
``score`` scope (every (defer, region, tier) candidate at its own hour's
CI) inside each ``route_call`` span, averaged over calls."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_call(o, "score")
