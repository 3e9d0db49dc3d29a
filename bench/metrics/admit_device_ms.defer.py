"""Device time of cross-window admission per deferral day-plan call:
milliseconds in which the busiest chip runs an op of the program's
``admit`` scope (the admission ``while_loop``, its reconciliation across
chips and its tail) inside each ``route_call`` span, averaged over
calls."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_call(o, "admit")
