"""Host time of the sharded call's aggregation per deferral day-plan
call: milliseconds of the program's ``gs.route.aggregate`` spans (unpad,
unsort and sum the per-row outputs on the host) in which no chip runs an
op, summed over the window and divided by its ``route_call`` spans."""

from harness import program_trace


def read(o):
    return program_trace.host_ms_per(o, "gs.route.aggregate", "route_call")
