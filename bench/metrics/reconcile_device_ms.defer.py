"""Device time of the cross-chip step of sharded admission per deferral
day-plan call: milliseconds in which the busiest chip runs an op of the
program's ``reconcile`` scope (each round's ``all_gather`` and exclusive
prefix of the per-cell contender counts, and the any-device test that
ends the loop) inside each ``route_call`` span, averaged over calls. Part
of ``admit_device_ms.defer``. None for a program without the scope."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_call(o, "reconcile")
