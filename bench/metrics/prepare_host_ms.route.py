"""Host time of the router's own preparation per routing call:
milliseconds of the program's ``gs.route.prepare`` spans (arrival hours,
window sort, inverse order, uploads, initial state) in which no chip runs
an op, summed over the window and divided by its ``route_call`` spans."""

from harness import program_trace


def read(o):
    return program_trace.host_ms_per(o, "gs.route.prepare", "route_call")
