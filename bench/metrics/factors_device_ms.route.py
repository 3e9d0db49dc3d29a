"""Device time of the Table-1 factor build per routing call: milliseconds
in which the busiest chip runs an op of the program's ``factors`` scope
inside each ``route_call`` span, averaged over calls."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_call(o, "factors")
