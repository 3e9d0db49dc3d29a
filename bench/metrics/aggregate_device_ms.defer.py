"""Device time of the sharded call's aggregation per deferral day-plan
call: milliseconds in which the busiest chip runs an op of the program's
``aggregate`` scope (per-row results unsorted into stream order, the
call's aggregates summed on the mesh) inside each ``route_call`` span,
averaged over calls. None where the program has no such scope."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_call(o, "aggregate")
